"""ZeRO-1 optimizer-state sharding over the DCN plane
(``parallel/zero.py``): 2 launcher slices with half batches each must
reproduce the single-process full-batch Adam trajectory exactly, while
each slice holds only half the optimizer state."""

import io
import os
import textwrap

import numpy as np

from zhpe_ompi_tpu.tools import mpirun

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_single_rank_matches_plain_adam():
    """size-1 degenerate: ZeroOptimizer == plain optax adam (with f32
    master arithmetic)."""
    import jax
    import jax.numpy as jnp
    import optax

    from zhpe_ompi_tpu.parallel.zero import ZeroOptimizer

    class OneProc:
        rank, size = 0, 1

    params = {"a": np.asarray([1.0, 2.0, 3.0], np.float32),
              "b": np.asarray([[4.0, 5.0]], np.float32)}
    grads = {"a": np.asarray([0.1, -0.2, 0.3], np.float32),
             "b": np.asarray([[0.5, -0.5]], np.float32)}
    z = ZeroOptimizer(OneProc(), optax.adam(1e-2), params)
    got = z.step(params, grads)

    opt = optax.adam(1e-2)
    st = opt.init(params)
    upd, st = opt.update(grads, st, params)
    want = optax.apply_updates(params, upd)
    for k in params:
        np.testing.assert_allclose(np.asarray(got[k]),
                                   np.asarray(want[k]), rtol=1e-6)


def test_two_slice_zero_matches_replicated_adam(tmp_path):
    prog = tmp_path / "zero.py"
    prog.write_text(textwrap.dedent(f"""
        import os, sys
        sys.path.insert(0, {_REPO!r})
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        import numpy as np
        import jax
        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp
        import optax

        import zhpe_ompi_tpu as zmpi
        from zhpe_ompi_tpu.models import transformer as tfm
        from zhpe_ompi_tpu.parallel.zero import ZeroOptimizer

        proc = zmpi.host_init()
        cfg = tfm.Config(vocab=64, d_model=16, n_heads=2, d_ff=32,
                         n_layers=2, seq=8, dtype=jnp.float32)
        params = {{k: np.asarray(v) for k, v in
                  tfm.init_params(cfg, jax.random.PRNGKey(0)).items()}}
        r = np.random.default_rng(0)
        tok = r.integers(0, cfg.vocab, (8, cfg.seq))
        tgt = r.integers(0, cfg.vocab, (8, cfg.seq))
        lo, hi = proc.rank * 4, proc.rank * 4 + 4

        zopt = ZeroOptimizer(proc, optax.adam(1e-2), params)
        total = sum(v.size * 4 for v in params.values())
        # Adam state (mu + nu) for HALF the params on each slice
        sb = zopt.state_bytes()
        assert sb <= 2 * (total // 2 + 512), (sb, total)

        for _ in range(3):
            loss = lambda p: tfm.loss_fn(
                p, jnp.asarray(tok[lo:hi]), jnp.asarray(tgt[lo:hi]), cfg)
            grads = jax.grad(loss)(
                {{k: jnp.asarray(v) for k, v in params.items()}})
            params = zopt.step(params, grads)
        if proc.rank == 0:
            np.savez(os.path.join({str(tmp_path)!r}, "zero.npz"),
                     **{{k: np.asarray(v) for k, v in params.items()}})
            print("ZERO-DONE")
        proc.barrier()
        zmpi.host_finalize()
    """))
    out, err = io.StringIO(), io.StringIO()
    rc = mpirun.launch(2, [str(prog)], stdout=out, stderr=err,
                       timeout=180.0)
    assert rc == 0, err.getvalue()
    assert "ZERO-DONE" in out.getvalue()

    # single-process full-batch reference with replicated adam (f32
    # master arithmetic like the zero path)
    import jax
    import jax.numpy as jnp
    import optax

    from zhpe_ompi_tpu.models import transformer as tfm

    cfg = tfm.Config(vocab=64, d_model=16, n_heads=2, d_ff=32,
                     n_layers=2, seq=8, dtype=jnp.float32)
    params = {k: np.asarray(v, np.float32) for k, v in
              tfm.init_params(cfg, jax.random.PRNGKey(0)).items()}
    r = np.random.default_rng(0)
    tok = r.integers(0, cfg.vocab, (8, cfg.seq))
    tgt = r.integers(0, cfg.vocab, (8, cfg.seq))
    opt = optax.adam(1e-2)
    st = opt.init(params)
    for _ in range(3):
        grads = jax.grad(lambda p: tfm.loss_fn(
            p, jnp.asarray(tok), jnp.asarray(tgt), cfg))(
            {k: jnp.asarray(v) for k, v in params.items()})
        grads = {k: np.asarray(v, np.float32) for k, v in grads.items()}
        upd, st = opt.update(grads, st, params)
        params = optax.apply_updates(params, upd)

    got = np.load(os.path.join(str(tmp_path), "zero.npz"))
    for k, v in params.items():
        np.testing.assert_allclose(got[k], np.asarray(v), rtol=3e-4,
                                   atol=3e-6)


def test_odd_bucket_partition_two_ranks():
    """Regression (round-4 review): a bucket whose size does not divide
    the world size must still update correctly — init and step share
    the padded chunk geometry."""
    import optax

    from test_tcp import run_tcp
    from zhpe_ompi_tpu.parallel.zero import ZeroOptimizer

    params = {"w": np.arange(5, dtype=np.float32)}
    g = np.full(5, 0.5, np.float32)

    def prog(p):
        z = ZeroOptimizer(p, optax.sgd(0.1), params)
        out = z.step(params, {"w": g})
        return np.asarray(out["w"])

    res = run_tcp(2, prog)
    want = params["w"] - 0.1 * 0.5  # mean of equal grads
    for r in range(2):
        np.testing.assert_allclose(res[r], want, rtol=1e-6)


def test_mismatched_tree_rejected():
    import optax
    import pytest

    from zhpe_ompi_tpu.core import errors
    from zhpe_ompi_tpu.parallel.zero import ZeroOptimizer

    class OneProc:
        rank, size = 0, 1

    z = ZeroOptimizer(OneProc(), optax.sgd(0.1),
                      {"w": np.zeros(8, np.float32)})
    with pytest.raises(errors.ArgError, match="sizes"):
        z.step({"w": np.zeros(8, np.float32)},
               {"w": np.zeros(4, np.float32)})
