"""Hierarchical data parallelism (parallel/hybrid.py): host-plane (DCN)
gradient sync across launcher processes composes with in-process compute
to the exact full-batch gradient, and parameter bcast repairs slice
divergence.  The ICI-inside/DCN-outside shape of multi-slice scaling."""

import io
import os
import textwrap

import numpy as np

from zhpe_ompi_tpu.tools import mpirun

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_pack_unpack_roundtrip_mixed_dtypes():
    import jax

    from zhpe_ompi_tpu.parallel import hybrid

    tree = {
        "a": np.arange(6, dtype=np.float32).reshape(2, 3),
        "b": {"c": np.ones(4, np.float64), "d": np.zeros((), np.float32)},
    }
    bufs, treedef, meta = hybrid.pack_tree(tree)
    assert set(bufs) == {"float32", "float64"}
    out = hybrid.unpack_tree(bufs, treedef, meta)
    flat_in = jax.tree_util.tree_leaves(tree)
    flat_out = jax.tree_util.tree_leaves(out)
    for a, b in zip(flat_in, flat_out):
        np.testing.assert_array_equal(np.asarray(a), b)
        assert np.asarray(a).shape == b.shape


def test_two_slice_grad_sync_matches_full_batch(tmp_path):
    """2 launcher processes each grad a half batch; dcn_grad_sync must
    reproduce the single-process full-batch gradient exactly."""
    prog = tmp_path / "slice.py"
    prog.write_text(textwrap.dedent(f"""
        import os, sys
        sys.path.insert(0, {_REPO!r})
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        import numpy as np
        import jax
        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp

        import zhpe_ompi_tpu as zmpi
        from zhpe_ompi_tpu.models import transformer as tfm
        from zhpe_ompi_tpu.parallel import hybrid

        proc = zmpi.host_init()
        cfg = tfm.Config(vocab=64, d_model=16, n_heads=2, d_ff=32,
                         n_layers=2, seq=8, dtype=jnp.float32)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        r = np.random.default_rng(0)
        tok = r.integers(0, cfg.vocab, (8, cfg.seq))
        tgt = r.integers(0, cfg.vocab, (8, cfg.seq))
        lo, hi = proc.rank * 4, proc.rank * 4 + 4
        loss = lambda p: tfm.loss_fn(
            p, jnp.asarray(tok[lo:hi]), jnp.asarray(tgt[lo:hi]), cfg)
        grads = jax.grad(loss)(params)
        synced = hybrid.dcn_grad_sync(proc, grads)
        if proc.rank == 0:
            np.savez(os.path.join({str(tmp_path)!r}, "synced.npz"),
                     **{{k: np.asarray(v) for k, v in synced.items()}})
            print("SYNC-DONE")
        proc.barrier()
        zmpi.host_finalize()
    """))
    out, err = io.StringIO(), io.StringIO()
    rc = mpirun.launch(2, [str(prog)], stdout=out, stderr=err,
                       timeout=180.0)
    assert rc == 0, err.getvalue()
    assert "SYNC-DONE" in out.getvalue()

    # single-process full-batch reference
    import jax
    import jax.numpy as jnp

    from zhpe_ompi_tpu.models import transformer as tfm

    cfg = tfm.Config(vocab=64, d_model=16, n_heads=2, d_ff=32,
                     n_layers=2, seq=8, dtype=jnp.float32)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    r = np.random.default_rng(0)
    tok = r.integers(0, cfg.vocab, (8, cfg.seq))
    tgt = r.integers(0, cfg.vocab, (8, cfg.seq))
    ref = jax.grad(lambda p: tfm.loss_fn(
        p, jnp.asarray(tok), jnp.asarray(tgt), cfg))(params)

    got = np.load(os.path.join(str(tmp_path), "synced.npz"))
    for k, v in ref.items():
        np.testing.assert_allclose(
            got[k], np.asarray(v), rtol=2e-5, atol=2e-6,
        )


def test_param_bcast_repairs_divergence(tmp_path):
    prog = tmp_path / "bc.py"
    prog.write_text(textwrap.dedent(f"""
        import os, sys
        sys.path.insert(0, {_REPO!r})
        os.environ["JAX_PLATFORMS"] = "cpu"
        import numpy as np
        import zhpe_ompi_tpu as zmpi
        from zhpe_ompi_tpu.parallel import hybrid

        proc = zmpi.host_init()
        params = {{"w": np.full((64,), float(proc.rank), np.float32),
                   "b": np.arange(8, dtype=np.float64) * (proc.rank + 1)}}
        fixed = hybrid.dcn_bcast_params(proc, params, root=1)
        w = np.asarray(fixed["w"]) if not isinstance(fixed["w"], np.ndarray) else fixed["w"]
        assert (w == 1.0).all(), w[:4]
        assert np.allclose(np.asarray(fixed["b"]),
                           np.arange(8, dtype=np.float64) * 2)
        proc.barrier()
        if proc.rank == 0:
            print("BCAST-OK")
        zmpi.host_finalize()
    """))
    out, err = io.StringIO(), io.StringIO()
    rc = mpirun.launch(3, [str(prog)], stdout=out, stderr=err,
                       timeout=120.0)
    assert rc == 0, err.getvalue()
    assert "BCAST-OK" in out.getvalue()


def test_bfloat16_grads_sync_and_bcast(tmp_path):
    """bfloat16 — the TPU training dtype — must survive the DCN sync
    (transport as lossless f32 upcast) and bit-exact param bcast."""
    prog = tmp_path / "bf16.py"
    prog.write_text(textwrap.dedent(f"""
        import os, sys
        sys.path.insert(0, {_REPO!r})
        os.environ["JAX_PLATFORMS"] = "cpu"
        import numpy as np
        import ml_dtypes
        import zhpe_ompi_tpu as zmpi
        from zhpe_ompi_tpu.parallel import hybrid

        proc = zmpi.host_init()
        bf = ml_dtypes.bfloat16
        grads = {{"w": np.full(16, proc.rank + 1, bf),
                  "b": np.ones(4, np.float32) * proc.rank}}
        synced = hybrid.dcn_grad_sync(proc, grads)
        assert synced["w"].dtype == np.dtype("bfloat16"), synced["w"].dtype
        assert np.allclose(synced["w"].astype(np.float32), 1.5)  # mean 1,2
        assert np.allclose(synced["b"], 0.5)
        fixed = hybrid.dcn_bcast_params(
            proc, {{"w": (np.arange(8, dtype=np.float32)
                          * (proc.rank + 1)).astype(bf)}}, root=0)
        assert fixed["w"].dtype == np.dtype("bfloat16")
        assert (fixed["w"].astype(np.float32)
                == np.arange(8, dtype=np.float32)).all()
        proc.barrier()
        if proc.rank == 0:
            print("BF16-OK")
        zmpi.host_finalize()
    """))
    out, err = io.StringIO(), io.StringIO()
    rc = mpirun.launch(2, [str(prog)], stdout=out, stderr=err,
                       timeout=120.0)
    assert rc == 0, err.getvalue()
    assert "BF16-OK" in out.getvalue()


def test_single_slice_returns_numpy_leaves():
    import types

    from zhpe_ompi_tpu.parallel import hybrid

    proc = types.SimpleNamespace(size=1, rank=0)
    import jax.numpy as jnp

    got = hybrid.dcn_grad_sync(proc, {"w": jnp.ones(3, jnp.float32)})
    assert isinstance(got["w"], np.ndarray)


def test_multislice_adam_matches_full_batch(tmp_path):
    """The full composition: 2 launcher slices each run the optax train
    step with dcn_proc set; after 2 steps their params must match a
    single-process full-batch Adam run."""
    prog = tmp_path / "adam_slice.py"
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
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        import zhpe_ompi_tpu as zmpi
        from zhpe_ompi_tpu.models import transformer as tfm

        proc = zmpi.host_init()
        cfg = tfm.Config(vocab=64, d_model=16, n_heads=2, d_ff=32,
                         n_layers=2, seq=8, dtype=jnp.float32)
        mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                    ("dp", "tp"))
        dpc = zmpi.Communicator(mesh, "dp")
        init_state, step, specs = tfm.make_train_step_optax(
            cfg, mesh, dpc, None, optimizer=optax.adam(1e-2),
            dcn_proc=proc)
        params = {{k: jax.device_put(np.asarray(v),
                                     NamedSharding(mesh, specs[k]))
                   for k, v in tfm.init_params(
                       cfg, jax.random.PRNGKey(0)).items()}}
        st = init_state(params)
        r = np.random.default_rng(0)
        tok = r.integers(0, cfg.vocab, (8, cfg.seq))
        tgt = r.integers(0, cfg.vocab, (8, cfg.seq))
        lo = proc.rank * 4
        ds = NamedSharding(mesh, P("dp"))
        mtok = jax.device_put(jnp.asarray(tok[lo:lo+4]), ds)
        mtgt = jax.device_put(jnp.asarray(tgt[lo:lo+4]), ds)
        for _ in range(2):
            params, st, loss = step(params, st, mtok, mtgt)
        if proc.rank == 0:
            np.savez(os.path.join({str(tmp_path)!r}, "slice_params.npz"),
                     **{{k: np.asarray(v) for k, v in params.items()}})
            print("ADAM-SLICES-DONE")
        proc.barrier()
        zmpi.host_finalize()
    """))
    out, err = io.StringIO(), io.StringIO()
    rc = mpirun.launch(2, [str(prog)], stdout=out, stderr=err,
                       timeout=240.0)
    assert rc == 0, err.getvalue()
    assert "ADAM-SLICES-DONE" in out.getvalue()

    # single-process full-batch reference
    import jax
    import jax.numpy as jnp
    import optax

    from zhpe_ompi_tpu.models import transformer as tfm

    cfg = tfm.Config(vocab=64, d_model=16, n_heads=2, d_ff=32,
                     n_layers=2, seq=8, dtype=jnp.float32)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    opt = optax.adam(1e-2)
    st = opt.init(params)
    r = np.random.default_rng(0)
    tok = jnp.asarray(r.integers(0, cfg.vocab, (8, cfg.seq)))
    tgt = jnp.asarray(r.integers(0, cfg.vocab, (8, cfg.seq)))
    for _ in range(2):
        grads = jax.grad(lambda p: tfm.loss_fn(p, tok, tgt, cfg))(params)
        upd, st = opt.update(grads, st, params)
        params = optax.apply_updates(params, upd)

    got = np.load(os.path.join(str(tmp_path), "slice_params.npz"))
    for k, v in params.items():
        np.testing.assert_allclose(got[k], np.asarray(v),
                                   rtol=5e-5, atol=5e-6, err_msg=k)


def test_two_slice_sharded_sync_matches_full_gather(tmp_path):
    """Per-shard DCN sync (round 4, the memory-cliff scaling path):
    2 slices x 4 virtual devices with tp-sharded gradients — the
    shard-wise reduction must reproduce dcn_grad_sync's full-gather
    result exactly, with every output shard on its original device."""
    prog = tmp_path / "shardsync.py"
    prog.write_text(textwrap.dedent(f"""
        import os, sys
        sys.path.insert(0, {_REPO!r})
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np
        import jax
        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        import zhpe_ompi_tpu as zmpi
        from zhpe_ompi_tpu.parallel import hybrid

        proc = zmpi.host_init()
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("tp",))
        r = np.random.default_rng(proc.rank)
        tree = {{
            "w_sharded": jax.device_put(
                jnp.asarray(r.normal(size=(8, 6)), jnp.float32),
                NamedSharding(mesh, P("tp", None))),
            "w_repl": jax.device_put(
                jnp.asarray(r.normal(size=(5,)), jnp.float32),
                NamedSharding(mesh, P())),
            "w_bf16": jax.device_put(
                jnp.asarray(r.normal(size=(4, 4)), jnp.bfloat16),
                NamedSharding(mesh, P("tp"))),
            "scalar": np.float32(proc.rank + 1.0),
        }}
        synced = hybrid.dcn_grad_sync_sharded(proc, tree)
        full = hybrid.dcn_grad_sync(proc, tree)
        # shard-wise result == full-gather result, and shardings kept
        for k in tree:
            a = np.asarray(synced[k], np.float32)
            b = np.asarray(full[k], np.float32)
            assert np.allclose(a, b, rtol=1e-6), (k, a, b)
        assert synced["w_sharded"].sharding.is_equivalent_to(
            tree["w_sharded"].sharding, 2)
        assert synced["w_bf16"].dtype == jnp.bfloat16
        if proc.rank == 0:
            print("SHARD-SYNC-OK")
        proc.barrier()
        zmpi.host_finalize()
    """))
    out, err = io.StringIO(), io.StringIO()
    rc = mpirun.launch(2, [str(prog)], stdout=out, stderr=err,
                       timeout=180.0)
    assert rc == 0, err.getvalue()
    assert "SHARD-SYNC-OK" in out.getvalue()


def test_sharded_sync_dedups_replicas_and_checks_layout():
    """In-process unit checks on the per-shard sync: a dp-replicated,
    tp-sharded leaf reduces each DISTINCT shard once (not once per
    replica), and mismatched layouts across slices raise before any
    data moves."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from zhpe_ompi_tpu.core import errors
    from zhpe_ompi_tpu.parallel import hybrid

    devs = np.asarray(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("dp", "tp"))

    class FakeProc:
        """Two identical 'slices' collapsed into one process: allreduce
        doubles (sum of two equal contributions), allgather echoes."""

        size = 2

        def __init__(self):
            self.reduce_calls = 0
            self.peer_digest = None

        def allreduce(self, x, op):
            self.reduce_calls += 1
            return x * 2

        def allgather(self, x):
            return [x, self.peer_digest if self.peer_digest else x]

    proc = FakeProc()
    leaf = jax.device_put(
        jnp.arange(8, dtype=jnp.float32).reshape(2, 4),
        NamedSharding(mesh, P(None, "tp")),  # tp-sharded, dp-replicated
    )
    synced = hybrid.dcn_grad_sync_sharded(proc, {"w": leaf})
    # 4 devices hold 2 DISTINCT tp shards -> exactly 2 reduces
    assert proc.reduce_calls == 2, proc.reduce_calls
    # w = 1/size = 0.5, allreduce doubles: mean of two equal slices = x
    np.testing.assert_allclose(np.asarray(synced["w"]),
                               np.arange(8, dtype=np.float32).reshape(2, 4))
    assert synced["w"].sharding.is_equivalent_to(leaf.sharding, 2)

    # layout mismatch: peer reports a different fingerprint -> raise
    import pytest

    proc2 = FakeProc()
    proc2.peer_digest = "not-the-same"
    with pytest.raises(errors.ArgError, match="fingerprints differ"):
        hybrid.dcn_grad_sync_sharded(proc2, {"w": leaf})
