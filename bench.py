"""Headline benchmark: flagship train-step throughput through the framework
vs the identical step written in plain JAX (no framework layer).

Runs on a TPU only, in one process, and prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}
On any other platform, or on a TPU whose ``device_kind`` has no published
peak in ``PEAK_BF16_FLOPS``, it raises: no number is ever printed from a
CPU or against an assumed peak.

vs_baseline semantics: the reference publishes no numbers (BASELINE.md), so
the baseline is the strongest available stand-in — the same training step
with every framework collective replaced by a raw lax.psum
(:func:`make_plain_step`).  A value >= 1.0 means the MPI-model layer
(communicators, comm_select dispatch, tuned decisions, f/g AD wrappers)
costs nothing over hand-written JAX; that is the claim being benchmarked.
On multi-device hosts the collectives are real; on one chip they lower to
no-ops but the full dispatch path still runs.

Timing discipline: every timing window ends with a forced host fetch of
the final loss — the step chain is sequentially dependent, so fetching
the last loss bounds the whole window.  A physics check rejects any
throughput implying more FLOP/s than the chip's peak, so a broken sync can
never ship a bogus number.

vs_baseline > 1 explained and eliminated (round-3 item 7):
``benchmarks/hlo_diff.py`` proves the optimized HLO of both steps is
IDENTICAL on this chip (after stripping source-location metadata and
argument names), so the true ratio is 1.00 and any deviation is
measurement procedure.  ``benchmarks/order_probe.py`` then located the
round-2 +10%: the chip runs ~10% faster for one brief window after first
dispatch.  The fix: one discarded burn-in window per path, median (not
best) over the remaining windows, and vs_baseline = median of
adjacent-pair ratios — drift-robust and centered at 1.00.

MFU levers (round 4): ``ops/fused_norm.py`` is a one-pass Pallas
layernorm and ``ops/fused_ce.py`` computes the identical loss with an
online-lse scan over vocab chunks, so no (B,S,V) f32 array reaches HBM.
vs_baseline compares against plain JAX running the SAME levers (one cfg,
both steps), so the ratio stays a pure framework-overhead measurement.
"""

import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))

# Peak dense bf16 matmul FLOP/s per chip, keyed by jax's device_kind.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16).
# A device_kind missing here is an error, never a default.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}


def chip_peak(dev) -> float:
    """Per-chip bf16 peak of ``dev`` from :data:`PEAK_BF16_FLOPS`."""
    kind = dev.device_kind
    if kind not in PEAK_BF16_FLOPS:
        raise RuntimeError(
            f"no published bf16 peak for device_kind {kind!r}; add it "
            f"with its source to bench.PEAK_BF16_FLOPS")
    return PEAK_BF16_FLOPS[kind]


def use_compile_cache() -> None:
    """JAX's persistent compile cache: the directory in
    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself), else a
    fixed ``<repo>/.jax_cache`` — the path is part of the cache key, so
    it must not move between runs."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_REPO, ".jax_cache"))


def chip_config(seq: int, **overrides):
    """The flagship transformer at the repo's chip width: vocab 8192,
    d_model 1024, 16 heads, d_ff 4096, 4 layers, bf16, remat, vocab-
    chunked CE; flash and fused layernorm on their auto (TPU) path."""
    import jax.numpy as jnp

    from zhpe_ompi_tpu.models import transformer as tfm

    return tfm.Config(
        vocab=8192, d_model=1024, n_heads=16, d_ff=4096, n_layers=4,
        seq=seq, dtype=jnp.bfloat16, remat=True, ce_chunk=1024,
        **overrides,
    )


def _train_flops_per_step(cfg, batch):
    """Approximate training FLOPs per step: 6 * n_matmul_params * tokens
    (fwd 2x + bwd 4x) plus the attention quadratic term
    12 * L * B * S^2 * D (QK^T and PV matmuls, fwd+bwd)."""
    d, f, v, L, s = cfg.d_model, cfg.d_ff, cfg.vocab, cfg.n_layers, cfg.seq
    matmul_params = L * (4 * d * d + 2 * d * f) + v * d  # qkv+o, ffn, unembed
    tokens = batch * s
    return 6 * matmul_params * tokens + 12 * L * batch * s * s * d


def make_plain_step(cfg, mesh, specs, lr: float = 1e-2):
    """The baseline: ``make_train_step``'s math on the same ("dp", "tp")
    mesh with every framework collective replaced by a raw lax.psum."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from zhpe_ompi_tpu.models import transformer as tfm

    dp, tp = mesh.shape["dp"], mesh.shape["tp"]

    class RawComm:
        axis = "tp"

        def allreduce(self, x, op):
            return lax.psum(x, self.axis)

    raw_tp = RawComm() if tp > 1 else None
    replicated = {"embed", "lnf", "ln1", "ln2"}

    def spmd_step(p, tok, tgt):
        loss, grads = jax.value_and_grad(
            lambda pp: tfm.loss_fn(pp, tok, tgt, cfg, raw_tp))(p)
        synced = {}
        for name, g in grads.items():
            g = lax.psum(g, "dp") / dp
            if name in replicated and raw_tp is not None:
                g = lax.psum(g, "tp") / tp
            synced[name] = g
        loss = lax.psum(loss, "dp") / dp
        if raw_tp is not None:
            loss = lax.psum(loss, "tp") / tp
        new_p = jax.tree.map(
            lambda a, g: (a - lr * g).astype(a.dtype), p, synced)
        return new_p, loss

    return jax.jit(jax.shard_map(
        spmd_step, mesh=mesh, in_specs=(specs, P("dp"), P("dp")),
        out_specs=(specs, P()), check_vma=False,
    ))


def dp_tp_mesh(devs):
    """("dp", "tp") mesh over ``devs``: tp 2 when the count is even."""
    from jax.sharding import Mesh

    n = len(devs)
    tp = 2 if n % 2 == 0 else 1
    return Mesh(np.asarray(devs).reshape(n // tp, tp), ("dp", "tp"))


def main():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import zhpe_ompi_tpu as zmpi
    from zhpe_ompi_tpu.models import transformer as tfm

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; JAX found {devs[0].platform!r}")
    peak_chip = chip_peak(devs[0])
    use_compile_cache()
    mesh = dp_tp_mesh(devs)
    dp, tp = mesh.shape["dp"], mesh.shape["tp"]
    peak = peak_chip * dp * tp
    dp_comm = zmpi.Communicator(mesh, "dp", name="bench_dp")
    tp_comm = zmpi.Communicator(mesh, "tp", name="bench_tp") if tp > 1 else None

    # batch 16 + remat: the measured MFU optimum of the round-3
    # batch/remat sweep, with fused layernorm (auto) and chunked CE
    cfg = chip_config(512)
    batch = 16 * dp
    iters = 12

    r = np.random.default_rng(0)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    tokens = r.integers(0, cfg.vocab, (batch, cfg.seq)).astype(np.int32)
    targets = r.integers(0, cfg.vocab, (batch, cfg.seq)).astype(np.int32)
    flops_step = _train_flops_per_step(cfg, batch)
    dspec = NamedSharding(mesh, P("dp"))

    def prep(step, specs):
        sharded = {
            k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in params.items()
        }
        tok = jax.device_put(tokens, dspec)
        tgt = jax.device_put(targets, dspec)
        ps, loss = step(sharded, tok, tgt)  # compile
        for _ in range(3):  # warm caches/threads
            ps, loss = step(ps, tok, tgt)
        float(loss)  # forced host fetch: drains the queue for real
        return {"step": step, "ps": ps, "tok": tok, "tgt": tgt,
                "times": []}

    def window(st):
        step, tok, tgt = st["step"], st["tok"], st["tgt"]
        ps = st["ps"]
        t0 = time.perf_counter()
        for _ in range(iters):
            ps, loss = step(ps, tok, tgt)
        # The steps form a dependency chain (params thread through), so
        # fetching the final loss to the host bounds the whole window.
        lval = float(loss)
        st["times"].append((time.perf_counter() - t0) / iters)
        st["ps"] = ps
        # raise (not assert): must survive python -O; checked per window
        # so a discarded window can't hide a NaN
        if not np.isfinite(lval):
            raise RuntimeError(f"non-finite loss {lval}")

    def check_physics(best):
        implied = flops_step / best
        if implied >= peak:
            raise RuntimeError(
                f"implied {implied/1e12:.1f} TFLOP/s exceeds chip peak "
                f"{peak/1e12:.1f} — timing sync is broken"
            )
        return best

    step_fw, specs = tfm.make_train_step(cfg, mesh, dp_comm, tp_comm)

    # Interleave the timing windows of the two steps: benching one path to
    # completion before compiling the other biases whichever runs in the
    # warmer device state (measured ~2 ms/step order bias on v5e).
    st_fw = prep(step_fw, specs)
    st_pl = prep(make_plain_step(cfg, mesh, specs), specs)
    # burn-in: the chip's very first timed window after dispatch runs ~10%
    # fast (order_probe.py); discard one window per path so the measured
    # windows are steady-state
    window(st_fw)
    window(st_pl)
    st_fw["times"].clear()
    st_pl["times"].clear()
    ratios = []
    for i in range(4):
        # alternate which path is timed first within each adjacent pair;
        # the pair ratio cancels any residual slow drift
        first, second = (st_fw, st_pl) if i % 2 == 0 else (st_pl, st_fw)
        window(first)
        window(second)
        ratios.append(st_pl["times"][-1] / st_fw["times"][-1])
    # physics-check the FASTEST window of each path (not just the median):
    # a sync that breaks in a minority of windows must still trip the guard
    check_physics(min(st_fw["times"]))
    check_physics(min(st_pl["times"]))
    fw_s = check_physics(float(np.median(st_fw["times"])))
    check_physics(float(np.median(st_pl["times"])))
    vs_baseline = float(np.median(ratios))

    result = {
        "metric": "train_step_throughput",
        "value": round(batch * cfg.seq / fw_s, 1),
        "unit": "tokens/s",
        "vs_baseline": round(vs_baseline, 4),
        "step_ms": round(fw_s * 1e3, 2),
        "mfu": round((flops_step / fw_s) / peak, 4),
        "flops_per_step": flops_step,
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)},
    }

    # Long-context configuration (round-3 item 6): seq 4096 with the
    # Pallas flash kernels + remat — the regime the flash backward was
    # built for (naive attention OOMs here).  Reported as extra fields on
    # the same line (the driver's one-JSON-line contract).
    lc_cfg = chip_config(4096)
    lc_batch, lc_iters = 2 * dp, 8
    lc_flops = _train_flops_per_step(lc_cfg, lc_batch)
    step_lc, lc_specs = tfm.make_train_step(lc_cfg, mesh, dp_comm, tp_comm)
    ps = {
        k: jax.device_put(v, NamedSharding(mesh, lc_specs[k]))
        for k, v in tfm.init_params(lc_cfg, jax.random.PRNGKey(1)).items()
    }
    lc_tok, lc_tgt = (
        jax.device_put(r.integers(0, lc_cfg.vocab, (lc_batch, lc_cfg.seq))
                       .astype(np.int32), dspec)
        for _ in range(2))
    ps, loss = step_lc(ps, lc_tok, lc_tgt)  # compile
    ps, loss = step_lc(ps, lc_tok, lc_tgt)
    float(loss)
    lc_times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(lc_iters):
            ps, loss = step_lc(ps, lc_tok, lc_tgt)
        lval = float(loss)
        lc_times.append((time.perf_counter() - t0) / lc_iters)
        if not np.isfinite(lval):
            raise RuntimeError(f"long-context non-finite loss {lval}")
    best = float(np.median(lc_times))  # steady-state by now; median
    if lc_flops / min(lc_times) >= peak:  # guard every window
        raise RuntimeError("long-context timing sync broken")
    result.update({
        "long_ctx_seq": lc_cfg.seq,
        "long_ctx_tokens_per_s": round(lc_batch * lc_cfg.seq / best, 1),
        "long_ctx_step_ms": round(best * 1e3, 2),
        "long_ctx_mfu": round((lc_flops / best) / peak, 4),
    })

    print(json.dumps(result))


if __name__ == "__main__":
    sys.exit(main())
