"""Probe why vs_baseline reads ~1.09 when the HLO is identical.

Builds THREE timed states: the framework step (fw), the plain-JAX step
(pl), and a second, independently-jitted instance of the framework step
(fw2).  If fw2 tracks fw and not pl, the delta is in the program (HLO
diff missed something); if fw2 tracks pl, the delta follows build order
(allocation/compilation state), i.e. measurement procedure.

Run from repo root: python benchmarks/order_probe.py
"""

import sys
import time

sys.path.insert(0, ".")

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import bench
    import zhpe_ompi_tpu as zmpi
    from zhpe_ompi_tpu.models import transformer as tfm

    mesh = bench.dp_tp_mesh(jax.devices())
    dp, tp = mesh.shape["dp"], mesh.shape["tp"]
    dp_comm = zmpi.Communicator(mesh, "dp", name="probe_dp")
    tp_comm = zmpi.Communicator(mesh, "tp", name="probe_tp") if tp > 1 else None

    if jax.devices()[0].platform == "tpu":
        cfg = tfm.Config(vocab=8192, d_model=1024, n_heads=16, d_ff=4096,
                         n_layers=4, seq=512, dtype=jnp.bfloat16)
        batch, iters = 8 * dp, 20
    else:
        cfg = tfm.Config(vocab=256, d_model=128, n_heads=8, d_ff=512,
                         n_layers=2, seq=128, dtype=jnp.float32)
        batch, iters = 2 * dp, 5

    r = np.random.default_rng(0)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(r.integers(0, cfg.vocab, (batch, cfg.seq)))
    targets = jnp.asarray(r.integers(0, cfg.vocab, (batch, cfg.seq)))

    step_fw, specs = tfm.make_train_step(cfg, mesh, dp_comm, tp_comm)
    step_fw2, _ = tfm.make_train_step(cfg, mesh, dp_comm, tp_comm)
    step_pl = bench.make_plain_step(cfg, mesh, specs)

    def prep(step):
        sharded = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
                   for k, v in params.items()}
        dspec = NamedSharding(mesh, P("dp"))
        tok = jax.device_put(tokens, dspec)
        tgt = jax.device_put(targets, dspec)
        ps, loss = step(sharded, tok, tgt)
        for _ in range(3):
            ps, loss = step(ps, tok, tgt)
        float(loss)
        return {"step": step, "ps": ps, "tok": tok, "tgt": tgt,
                "best": float("inf"), "times": []}

    def window(st):
        step, tok, tgt, ps = st["step"], st["tok"], st["tgt"], st["ps"]
        t0 = time.perf_counter()
        for _ in range(iters):
            ps, loss = step(ps, tok, tgt)
        lval = float(loss)
        dt = (time.perf_counter() - t0) / iters
        st["times"].append(dt)
        st["best"] = min(st["best"], dt)
        st["ps"] = ps
        if not np.isfinite(lval):
            raise RuntimeError("non-finite")

    sts = {"fw": prep(step_fw), "pl": prep(step_pl), "fw2": prep(step_fw2)}
    order = ["fw", "pl", "fw2"]
    for i in range(6):
        rot = order[i % 3:] + order[:i % 3]
        for name in rot:
            window(sts[name])
    for name in order:
        st = sts[name]
        print(name, "best", round(st["best"] * 1e3, 3), "ms  all",
              [round(t * 1e3, 2) for t in st["times"]])


if __name__ == "__main__":
    main()
