"""Multi-slice training under the launcher: each OS process is a
"slice" running the optax train step on its own batch shard; gradients
sync across slices over the host plane (DCN) between the two jits.

    python -m zhpe_ompi_tpu.tools.mpirun -n 2 examples/zmpirun_multislice_train.py

On TPU pods each slice would own an ICI mesh (dp/tp/sp inside); here
each slice is one CPU device, which exercises the identical code path.
"""

import os
import sys


def main():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import zhpe_ompi_tpu as zmpi
    from zhpe_ompi_tpu.models import transformer as tfm

    proc = zmpi.host_init()
    cfg = tfm.Config(vocab=128, d_model=32, n_heads=4, d_ff=64,
                     n_layers=2, seq=16, dtype=jnp.float32)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
    dp_comm = zmpi.Communicator(mesh, "dp")
    init_state, step, specs = tfm.make_train_step_optax(
        cfg, mesh, dp_comm, None, optimizer=optax.adam(1e-2),
        dcn_proc=proc,
    )
    params = {
        k: jax.device_put(np.asarray(v), NamedSharding(mesh, specs[k]))
        for k, v in tfm.init_params(cfg, jax.random.PRNGKey(0)).items()
    }
    st = init_state(params)
    r = np.random.default_rng(proc.rank)  # per-slice data shard
    ds = NamedSharding(mesh, P("dp"))
    tok = jax.device_put(jnp.asarray(r.integers(0, cfg.vocab, (4, cfg.seq))), ds)
    tgt = jax.device_put(jnp.asarray(r.integers(0, cfg.vocab, (4, cfg.seq))), ds)

    losses = []
    for s in range(5):
        params, st, loss = step(params, st, tok, tgt)
        losses.append(float(loss))
    # slices must agree bit-for-bit after DCN-synced updates
    digest = float(sum(np.abs(np.asarray(v)).sum() for v in params.values()))
    all_digests = proc.allgather(digest)
    if max(all_digests) - min(all_digests) > 1e-9:
        print(f"rank {proc.rank}: slices diverged: {all_digests}")
        sys.exit(1)
    ok = losses[-1] < losses[0]
    if proc.rank == 0:
        print(f"{proc.size} slices, losses {[round(x, 3) for x in losses]}")
        if ok:
            print("PASSED")
    zmpi.host_finalize()  # teardown first; exit code after
    if proc.rank == 0 and not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
