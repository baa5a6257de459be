"""Chip smoke: drive the device plane and the flagship train step once on
a TPU, through the entry points a user calls, and check what comes out.

    python3 chip_smoke.py             # one chip: device, collectives, train
    python3 chip_smoke.py --chips 4   # four chips: the dp2 x tp2 train step
                                      # and every forced tuned algorithm

One process; nothing here starts a child.  Each phase prints one JSON
line; the last line is ``{"ok": true, "device": {...}}``.  Any failed
check raises, so the script exits nonzero with no ``ok`` line — also when
JAX finds no TPU.  There is no CPU branch.

Phases:

- device: platform, device_kind and count as JAX reports them.
- collectives: allreduce (SUM, MAX), bcast, allgather, alltoall and
  reduce_scatter through the world ``Communicator`` (``comm_select`` ->
  coll/tuned) at 4 KiB and 4 MiB per rank, against numpy.  With
  ``--chips 4`` every algorithm of coll/tuned's table is forced in turn
  (the ``coll_tuned_<op>_algorithm`` MCA variable, which
  ``ZMPI_MCA_coll_tuned_<op>_algorithm`` sets from the environment), at
  sizes below and above ``coll_tuned_large_msg``.
- train: ``make_train_step`` at the chip width (``bench.chip_config``)
  against the plain-lax step (``bench.make_plain_step``) run with the
  jnp reference attention and layernorm.  The framework program must hold
  ``tpu_custom_call`` (flash and fused layernorm in effect), every loss
  must be finite, and the first two losses must match the reference
  within ``LOSS_RTOL``.  Compile seconds and steady step ms are printed
  as information, not as a metric.
"""

import argparse
import dataclasses
import json
import time

import numpy as np

# Losses of the kernel step vs the jnp-reference step: two bf16 ulps
# (bf16 keeps 8 significant bits) relative to the loss.
LOSS_RTOL = 2.0 ** -6
# Collectives vs numpy, f32: only summation order differs.
COLL_RTOL, COLL_ATOL = 1e-5, 1e-5
COLL_BYTES = (4 << 10, 4 << 20)  # below and above coll_tuned_large_msg
COLL_OPS = ("allreduce", "bcast", "allgather", "alltoall",
            "reduce_scatter")


def emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


def _fail(msg: str):
    raise RuntimeError(f"chip_smoke: {msg}")


def device_phase(chips: int):
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        _fail(f"JAX found platform {d0.platform!r}, not a TPU")
    if len(devs) < chips:
        _fail(f"--chips {chips} but JAX sees {len(devs)} device(s)")
    emit(phase="device", platform=d0.platform, kind=d0.device_kind,
         count=len(devs))
    return devs


def _expected(opname, xs, op, root):
    """numpy reference: per-rank outputs, stacked on axis 0."""
    n = xs.shape[0]
    if opname == "allreduce":
        red = xs.sum(0) if op == "SUM" else xs.max(0)
        return np.broadcast_to(red, xs.shape)
    if opname == "bcast":
        return np.broadcast_to(xs[root], xs.shape)
    if opname == "allgather":
        return np.broadcast_to(xs.reshape(-1), (n, xs.size))
    blocks = xs.reshape(n, n, -1)  # [src rank, dest block]
    if opname == "alltoall":
        return blocks.swapaxes(0, 1).reshape(n, -1)
    return blocks.sum(0)  # reduce_scatter SUM: rank r keeps block r


def _run_collective(world, opname, xs, op, root):
    import jax.numpy as jnp

    import zhpe_ompi_tpu as zmpi

    calls = {
        "allreduce": lambda s: world.allreduce(s, getattr(zmpi, op)),
        "bcast": lambda s: world.bcast(s, root),
        "allgather": world.allgather,
        "alltoall": world.alltoall,
        "reduce_scatter": lambda s: world.reduce_scatter(s, zmpi.SUM),
    }
    fn = calls[opname]
    out = world.run(lambda s: fn(s[0])[None],
                    world.device_put_sharded(jnp.asarray(xs)))
    return np.asarray(out)


def collectives_phase(devs, force_algorithms: bool) -> int:
    """Every COLL_OPS op through the world communicator, compared with
    numpy; returns the number of checks."""
    import zhpe_ompi_tpu as zmpi
    from zhpe_ompi_tpu.coll import tuned

    world = zmpi.init(devices=devs)
    n = world.size
    if world.mesh.devices.size != len(devs):
        _fail(f"world mesh spans {world.mesh.devices.size} devices")
    rng = np.random.default_rng(0)
    root = n - 1
    checks = 0
    for opname in COLL_OPS:
        component = world.coll[opname][1]
        if component != "tuned":
            _fail(f"{opname} selected {component!r}, not coll/tuned")
        var = f"coll_tuned_{opname}_algorithm"
        algs = list(tuned._ALG_TABLES[opname]) if force_algorithms \
            else ["auto"]
        for alg in algs:
            zmpi.mca_var.set_var(var, alg)
            try:
                for nbytes in COLL_BYTES:
                    xs = rng.standard_normal(
                        (n, nbytes // 4)).astype(np.float32)
                    for op in (("SUM", "MAX") if opname == "allreduce"
                               else (None,)):
                        got = _run_collective(world, opname, xs, op, root)
                        want = _expected(opname, xs, op, root)
                        if not np.allclose(got, want, rtol=COLL_RTOL,
                                           atol=COLL_ATOL):
                            _fail(f"{opname} {op or ''} alg={alg} "
                                  f"{nbytes} B differs from numpy: max "
                                  f"|err| {np.abs(got - want).max()}")
                        checks += 1
            finally:
                zmpi.mca_var.unset(var)
    emit(phase="collectives", world=n, forced_algorithms=force_algorithms,
         bytes=list(COLL_BYTES), checks=checks)
    return checks


def require_kernels(hlo_text: str, what: str) -> None:
    """Flash attention and fused layernorm lower to Mosaic custom calls:
    their absence means the step runs the jnp reference."""
    if "tpu_custom_call" not in hlo_text:
        _fail(f"{what}: no tpu_custom_call in the compiled program — "
              "the Pallas kernels are not in effect")


def _on_devices(tree, devs, what: str) -> None:
    want = set(devs)
    for k, leaf in tree.items():
        got = {sh.device for sh in leaf.addressable_shards}
        if got != want or leaf.sharding.device_set != want:
            _fail(f"{what}[{k!r}] lives on {len(got)} of {len(want)} "
                  "devices")


def train_phase(devs, seq: int, batch_per_dp: int, steps: int,
                seed: int = 0) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import bench
    import zhpe_ompi_tpu as zmpi
    from zhpe_ompi_tpu.models import transformer as tfm

    cfg = bench.chip_config(seq)
    mesh = bench.dp_tp_mesh(devs)
    dp, tp = mesh.shape["dp"], mesh.shape["tp"]
    dp_comm = zmpi.Communicator(mesh, "dp", name="smoke_dp")
    tp_comm = zmpi.Communicator(mesh, "tp", name="smoke_tp") \
        if tp > 1 else None
    step, specs = tfm.make_train_step(cfg, mesh, dp_comm, tp_comm)
    ref_step = bench.make_plain_step(
        dataclasses.replace(cfg, flash=False, fused_ln=False), mesh, specs)

    rng = np.random.default_rng(seed)
    params = {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in tfm.init_params(cfg, jax.random.PRNGKey(seed)).items()
    }
    _on_devices(params, devs, "params")
    dspec = NamedSharding(mesh, P("dp"))
    tok, tgt = (
        jax.device_put(rng.integers(0, cfg.vocab, (batch_per_dp * dp, seq))
                       .astype(np.int32), dspec)
        for _ in range(2))

    t0 = time.perf_counter()
    compiled = step.lower(params, tok, tgt).compile()
    compile_s = time.perf_counter() - t0
    require_kernels(compiled.as_text(), f"train step seq {seq}")
    t0 = time.perf_counter()
    ref = ref_step.lower(params, tok, tgt).compile()
    ref_compile_s = time.perf_counter() - t0

    ps, ref_losses = params, []
    for _ in range(2):
        ps, loss = ref(ps, tok, tgt)
        ref_losses.append(float(loss))

    ps, losses = params, []
    for _ in range(2):
        ps, loss = compiled(ps, tok, tgt)
        losses.append(float(loss))
    t0 = time.perf_counter()
    timed = []
    for _ in range(steps - 2):
        ps, loss = compiled(ps, tok, tgt)
        timed.append(loss)
    losses += [float(x) for x in timed]  # the fetch ends the window
    step_ms = (time.perf_counter() - t0) / max(1, steps - 2) * 1e3
    _on_devices(ps, devs, "updated params")

    emit(phase="train", seq=seq, batch=batch_per_dp * dp, dp=dp, tp=tp,
         losses=losses, ref_losses=ref_losses, compile_s=compile_s,
         ref_compile_s=ref_compile_s, step_ms=step_ms)
    if not np.isfinite(losses).all() or not np.isfinite(ref_losses).all():
        _fail(f"seq {seq}: non-finite loss {losses} / {ref_losses}")
    for i, (got, want) in enumerate(zip(losses, ref_losses)):
        if abs(got - want) > LOSS_RTOL * abs(want):
            _fail(f"seq {seq} step {i + 1}: loss {got} vs plain-lax "
                  f"reference {want} (rtol {LOSS_RTOL})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import bench

    devs_all = device_phase(args.chips)
    bench.use_compile_cache()
    devs = devs_all[:args.chips]
    if args.chips == 4:
        train_phase(devs, seq=512, batch_per_dp=16, steps=5)
        collectives_phase(devs, force_algorithms=True)
    else:
        collectives_phase(devs, force_algorithms=False)
        train_phase(devs, seq=512, batch_per_dp=16, steps=5)
        train_phase(devs, seq=4096, batch_per_dp=2, steps=3)
    d0 = devs_all[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs_all)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
