"""ZeRO-1 multi-slice training under the launcher: optimizer state is
PARTITIONED across slices (parallel/zero.py) — each process holds Adam
moments for 1/N of the flat parameter space, gradients reduce-scatter
so owners receive exactly their partition fully reduced, and updated
parameters allgather back.  Wire bytes match plain DDP; optimizer
memory drops by the slice count.

    python -m zhpe_ompi_tpu.tools.mpirun -n 2 examples/zmpirun_zero_train.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax

    import zhpe_ompi_tpu as zmpi
    from zhpe_ompi_tpu.models import transformer as tfm
    from zhpe_ompi_tpu.parallel.zero import ZeroOptimizer

    proc = zmpi.host_init()
    cfg = tfm.Config(vocab=128, d_model=32, n_heads=4, d_ff=64,
                     n_layers=2, seq=16, dtype=jnp.float32)
    params = {k: np.asarray(v) for k, v in
              tfm.init_params(cfg, jax.random.PRNGKey(0)).items()}

    zopt = ZeroOptimizer(proc, optax.adam(1e-2), params)
    total_param_bytes = sum(v.nbytes for v in params.values())
    print(f"slice {proc.rank}: params {total_param_bytes}B, "
          f"my optimizer state {zopt.state_bytes()}B "
          f"(~1/{proc.size} of adam's 2x)")

    r = np.random.default_rng(proc.rank)  # each slice's own batch shard
    tok = jnp.asarray(r.integers(0, cfg.vocab, (4, cfg.seq)))
    tgt = jnp.asarray(r.integers(0, cfg.vocab, (4, cfg.seq)))
    losses = []
    for step_i in range(8):  # memorize one fixed batch per slice
        loss, grads = jax.value_and_grad(
            lambda p: tfm.loss_fn(p, tok, tgt, cfg)
        )({k: jnp.asarray(v) for k, v in params.items()})
        params = zopt.step(params, grads)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses  # it learns
    print(f"slice {proc.rank}: loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"over 8 ZeRO steps — PASSED")
    proc.barrier()
    zmpi.host_finalize()


if __name__ == "__main__":
    main()
