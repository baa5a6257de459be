"""Flagship transformer: multi-device (dp x tp) step must match single-device.

This is the numerical ground-truth test for the framework's gradient-sync
semantics (the examples/ acceptance-test analog of SURVEY.md §4).
"""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import zhpe_ompi_tpu as zmpi
from zhpe_ompi_tpu.models import transformer as tfm


@pytest.fixture(scope="module")
def cfg():
    return tfm.Config(
        vocab=61, d_model=16, n_heads=4, d_ff=32, n_layers=2, seq=8,
        dtype=jnp.float32,  # exact comparisons need f32
    )


def _data(cfg, batch=8):
    r = np.random.default_rng(0)
    tokens = r.integers(0, cfg.vocab, (batch, cfg.seq))
    targets = r.integers(0, cfg.vocab, (batch, cfg.seq))
    return jnp.asarray(tokens), jnp.asarray(targets)


def _single_device_step(cfg, params, tokens, targets, lr=1e-2):
    def loss(p):
        return tfm.loss_fn(p, tokens, targets, cfg, tp_comm=None)

    l, g = jax.value_and_grad(loss)(params)
    return jax.tree.map(lambda p, gg: p - lr * gg, params, g), l


def _sgd_step(cfg, mesh, dp_comm, tp_comm, sp_comm=None, lr=1e-2):
    """The train step with plain SGD, whose update ``p + (-lr * g)`` is
    the reference's ``p - lr * g`` in f32."""
    return tfm.make_train_step_optax(cfg, mesh, dp_comm, tp_comm, sp_comm,
                                     optimizer=optax.sgd(lr))


def _shard(params, mesh, specs):
    # Copy through numpy: device_put can alias the source buffer as one
    # replica shard, and the update's donation would then delete the
    # reference params
    return {k: jax.device_put(np.asarray(v), NamedSharding(mesh, specs[k]))
            for k, v in params.items()}


def test_dp_tp_step_matches_single_device(cfg):
    devs = np.asarray(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, axis_names=("dp", "tp"))
    dp_comm = zmpi.Communicator(mesh, "dp", name="dp")
    tp_comm = zmpi.Communicator(mesh, "tp", name="tp")

    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    tokens, targets = _data(cfg)

    ref_params, ref_loss = _single_device_step(cfg, params, tokens, targets)

    init_opt, step, specs = _sgd_step(cfg, mesh, dp_comm, tp_comm)
    sharded = _shard(params, mesh, specs)
    dspec = NamedSharding(mesh, P("dp"))
    new_params, _, loss = step(
        sharded, init_opt(sharded),
        jax.device_put(tokens, dspec), jax.device_put(targets, dspec)
    )

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(new_params[k]), np.asarray(ref_params[k]),
            rtol=2e-4, atol=2e-6, err_msg=f"param {k} diverged",
        )


def test_dp_tp_sp_step_matches_single_device(cfg):
    """Full 3-axis parallel step (dp=2, tp=2, sp=2 ring attention) must
    reproduce the single-device step."""
    devs = np.asarray(jax.devices()[:8]).reshape(2, 2, 2)
    mesh = Mesh(devs, axis_names=("dp", "tp", "sp"))
    dp_comm = zmpi.Communicator(mesh, "dp", name="dp3")
    tp_comm = zmpi.Communicator(mesh, "tp", name="tp3")
    sp_comm = zmpi.Communicator(mesh, "sp", name="sp3")

    params = tfm.init_params(cfg, jax.random.PRNGKey(2))
    tokens, targets = _data(cfg)
    ref_params, ref_loss = _single_device_step(cfg, params, tokens, targets)

    init_opt, step, specs = _sgd_step(cfg, mesh, dp_comm, tp_comm, sp_comm)
    sharded = _shard(params, mesh, specs)
    dspec = NamedSharding(mesh, P("dp", "sp"))
    new_params, _, loss = step(
        sharded, init_opt(sharded),
        jax.device_put(tokens, dspec), jax.device_put(targets, dspec)
    )
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(new_params[k]), np.asarray(ref_params[k]),
            rtol=2e-4, atol=2e-6, err_msg=f"param {k} diverged",
        )


def test_loss_decreases(cfg):
    devs = np.asarray(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devs, axis_names=("dp", "tp"))
    dp_comm = zmpi.Communicator(mesh, "dp", name="dp2")
    tp_comm = zmpi.Communicator(mesh, "tp", name="tp2")
    params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    tokens, targets = _data(cfg)
    init_opt, step, specs = _sgd_step(cfg, mesh, dp_comm, tp_comm, lr=0.05)
    sharded = _shard(params, mesh, specs)
    opt_state = init_opt(sharded)
    dspec = NamedSharding(mesh, P("dp"))
    tokens = jax.device_put(tokens, dspec)
    targets = jax.device_put(targets, dspec)
    losses = []
    for _ in range(5):
        sharded, opt_state, loss = step(sharded, opt_state, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_remat_matches_no_remat():
    """cfg.remat must not change the math — same loss and grads, only the
    backward's memory/recompute schedule differs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from zhpe_ompi_tpu.models import transformer as tfm

    r = np.random.default_rng(3)
    base = dict(vocab=64, d_model=32, n_heads=4, d_ff=64, n_layers=2,
                seq=16, dtype=jnp.float32, flash=False)
    cfg_a = tfm.Config(**base)
    cfg_b = tfm.Config(**base, remat=True)
    params = tfm.init_params(cfg_a, jax.random.PRNGKey(0))
    tok = jnp.asarray(r.integers(0, 64, (2, 16)))
    tgt = jnp.asarray(r.integers(0, 64, (2, 16)))

    def lossgrad(cfg):
        return jax.value_and_grad(
            lambda p: tfm.loss_fn(p, tok, tgt, cfg)
        )(params)

    la, ga = lossgrad(cfg_a)
    lb, gb = lossgrad(cfg_b)
    np.testing.assert_allclose(float(la), float(lb), rtol=1e-6)
    for k in ga:
        np.testing.assert_allclose(
            np.asarray(ga[k]), np.asarray(gb[k]), rtol=1e-5, atol=1e-6
        )


class TestOptaxStep:
    """Stateful optimizer through the framework: the dp2 x tp2 Adam run
    must match a single-device plain-optax run on the full batch."""

    def test_matches_single_device_adam(self):
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        import zhpe_ompi_tpu as zmpi

        cfg = tfm.Config(vocab=64, d_model=16, n_heads=2, d_ff=32,
                         n_layers=2, seq=8, dtype=jnp.float32)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        r = np.random.default_rng(0)
        tok = jnp.asarray(r.integers(0, cfg.vocab, (4, cfg.seq)))
        tgt = jnp.asarray(r.integers(0, cfg.vocab, (4, cfg.seq)))

        devs = jax.devices()[:4]
        mesh = Mesh(np.asarray(devs).reshape(2, 2), ("dp", "tp"))
        dp_comm = zmpi.Communicator(mesh, "dp", name="opx_dp")
        tp_comm = zmpi.Communicator(mesh, "tp", name="opx_tp")
        opt = optax.adam(1e-2)
        init_state, step, specs = tfm.make_train_step_optax(
            cfg, mesh, dp_comm, tp_comm, optimizer=opt
        )
        # device_put against the spec splits tp-sharded leaves across
        # ranks (the same layout the bench uses).  Copy through numpy:
        # device_put can alias the source buffer as one replica shard,
        # and apply()'s donation would then delete the reference params
        sharded = {
            k: jax.device_put(np.asarray(v), NamedSharding(mesh, specs[k]))
            for k, v in params.items()
        }
        st = init_state(sharded)
        dspec = NamedSharding(mesh, P("dp"))
        p2, st2, loss = step(sharded, st,
                             jax.device_put(tok, dspec),
                             jax.device_put(tgt, dspec))
        assert np.isfinite(float(loss))

        # single-device reference: same loss fn, same optimizer
        ref_loss, ref_grads = jax.value_and_grad(
            lambda p: tfm.loss_fn(p, tok, tgt, cfg))(params)
        ref_state = opt.init(params)
        upd, _ = opt.update(ref_grads, ref_state, params)
        ref_p2 = optax.apply_updates(params, upd)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=2e-5, atol=1e-6)
        for k in params:
            np.testing.assert_allclose(
                np.asarray(p2[k]), np.asarray(ref_p2[k]),
                rtol=3e-5, atol=3e-6, err_msg=k,
            )

        # second step exercises threaded optimizer state
        p3, st3, loss3 = step(p2, st2,
                              jax.device_put(tok, dspec),
                              jax.device_put(tgt, dspec))
        assert np.isfinite(float(loss3)) and float(loss3) < float(loss)


# ------------------------------------- the two-chunk tensor-parallel loop


def _sub_jaxprs(value):
    if hasattr(value, "eqns"):
        yield value
    elif hasattr(value, "jaxpr") and hasattr(value.jaxpr, "eqns"):
        yield value.jaxpr
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _eqns(sub)


def _layer_loop(cfg, mesh_shape, batch, sp=False):
    """The forward's layer scan in the traced ``loss_fn`` on a mesh of
    the given (dp, tp) shape, ``tp`` 0 meaning no tp communicator (``sp``
    adds an sp axis of 2): the shapes of its carry and the leading
    dimension of each flash forward call in its body."""
    dp, tp = mesh_shape
    axes = ("dp", "tp", "sp")[:3 if sp else 2]
    shape = (dp, max(tp, 1), 2)[:len(axes)]
    n = int(np.prod(shape))
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(shape), axes)
    name = f"ck{dp}{tp}{int(sp)}{batch}"
    tp_comm = zmpi.Communicator(mesh, "tp", name=name + "tp") if tp else None
    sp_comm = zmpi.Communicator(mesh, "sp", name=name + "sp") if sp else None
    specs = tfm._param_specs("tp" if tp else None)
    data = P("dp", "sp") if sp else P("dp")
    fn = jax.shard_map(
        lambda p, tok, tgt: tfm.loss_fn(p, tok, tgt, cfg, tp_comm, sp_comm),
        mesh=mesh, in_specs=(specs, data, data), out_specs=P(),
        check_vma=False)
    params = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.key(0)))
    tok = jax.ShapeDtypeStruct((batch, cfg.seq), jnp.int32)
    jaxpr = jax.make_jaxpr(fn)(params, tok, tok).jaxpr
    (loop,) = [e for e in _eqns(jaxpr) if e.primitive.name == "scan"
               and e.params["length"] == cfg.n_layers]
    k, c = loop.params["num_consts"], loop.params["num_carry"]
    carry = [v.aval.shape for v in loop.invars[k:k + c]]
    flash = [e.invars[0].aval.shape[0]
             for e in _eqns(loop.params["jaxpr"].jaxpr)
             if e.primitive.name == "pallas_call"
             and "flash_fwd" in str(e.params.get("name"))]
    return carry, flash


@pytest.mark.parametrize("mesh_shape,batch,sp,chunks", [
    ((2, 2), 8, False, 2),   # tp, even local batch: two chunks
    ((1, 2), 3, False, 1),   # odd local batch
    ((1, 2), 4, True, 1),    # sp (ring attention)
    ((1, 0), 4, False, 1),   # no tp
    ((1, 4), 4, False, 1),   # tp over four ranks: no swap to overlap
], ids=["tp_even", "tp_odd", "tp_sp", "no_tp", "tp4"])
def test_layer_loop_chunks(mesh_shape, batch, sp, chunks):
    """The layer loop carries two half-batch chunks only with a two-rank
    tp, no sp and an even local batch; otherwise one chunk at the full
    local batch, with one flash call per layer (the one-chip cells'
    program)."""
    cfg = tfm.Config(vocab=61, d_model=16, n_heads=4, d_ff=32, n_layers=3,
                     seq=8, dtype=jnp.float32, flash=None if sp else True)
    carry, flash = _layer_loop(cfg, mesh_shape, batch, sp)
    local = batch // mesh_shape[0]
    assert carry == [(local // chunks, cfg.seq // (2 if sp else 1),
                      cfg.d_model)] * chunks
    if not sp:
        heads = cfg.n_heads // max(mesh_shape[1], 1)
        assert flash == [local // chunks * heads] * chunks


@pytest.mark.parametrize("mesh_shape,swap", [
    ((2, 2), False), ((1, 2), False), ((2, 2), True),
], ids=["dp2tp2", "dp1tp2", "dp2tp2_swap"])
def test_two_chunk_tp_matches_one_chunk(cfg, monkeypatch, mesh_shape, swap):
    """The two-chunk tp loop gives the one-chunk loop's loss and every
    gradient leaf, to f32 rounding (only the order in which the weight
    gradients' chunk products are summed differs); with ``swap`` its
    all-reduces are the swaps a TPU gets, against the one-chunk psums."""
    from zhpe_ompi_tpu.coll import tuned

    dp, tp = mesh_shape
    mesh = Mesh(np.asarray(jax.devices()[:dp * tp]).reshape(dp, tp),
                ("dp", "tp"))
    dp_comm = zmpi.Communicator(mesh, "dp", name=f"two_dp{dp}{swap:d}")
    tp_comm = zmpi.Communicator(mesh, "tp", name=f"two_tp{dp}{swap:d}")
    specs = tfm._param_specs("tp")
    params = tfm.init_params(cfg, jax.random.PRNGKey(4))
    tokens, targets = _data(cfg)

    def loss_and_grads():
        def spmd(p, tok, tgt):
            loss, grads = jax.value_and_grad(
                lambda q: tfm.loss_fn(q, tok, tgt, cfg, tp_comm))(p)
            return tfm._sync_grads(grads, loss, dp_comm, tp_comm, None,
                                   dp, tp, 1)

        return jax.jit(jax.shard_map(
            spmd, mesh=mesh, in_specs=(specs, P("dp"), P("dp")),
            out_specs=(specs, P()), check_vma=False))(params, tokens,
                                                      targets)

    assert tfm._tp_chunks(tokens.shape[0] // dp, tp_comm, None) == 2
    with monkeypatch.context() as m:
        m.setattr(tuned, "_on_tpu", lambda comm: swap)
        assert tuned.decide("allreduce", tp_comm, tokens, zmpi.SUM) == (
            "recursive_doubling" if swap else "xla")
        grads2, loss2 = loss_and_grads()
    monkeypatch.setattr(tfm, "_tp_chunks", lambda *a: 1)
    grads1, loss1 = loss_and_grads()
    np.testing.assert_allclose(float(loss2), float(loss1), rtol=1e-6)
    for k in params:
        np.testing.assert_allclose(np.asarray(grads2[k]),
                                   np.asarray(grads1[k]), rtol=1e-5,
                                   atol=1e-7, err_msg=k)


def test_revoked_tp_comm_raises_in_loss_fn(cfg):
    """The tp all-reduces of the (two-chunk) layer loop go through the
    communicator's dispatch: a revoked tp communicator raises there,
    through its errhandler."""
    from zhpe_ompi_tpu.core import errhandler as errh
    from zhpe_ompi_tpu.core import errors

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    tp_comm = zmpi.Communicator(mesh, "tp", name="revoked_tp")
    tp_comm.set_errhandler(errh.ERRORS_RETURN)
    tp_comm.revoke()
    params = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.key(0)))
    tok = jax.ShapeDtypeStruct((8, cfg.seq), jnp.int32)
    fn = jax.shard_map(
        lambda p, t, g: tfm.loss_fn(p, t, g, cfg, tp_comm),
        mesh=mesh, in_specs=(tfm._param_specs("tp"), P("dp"), P("dp")),
        out_specs=P(), check_vma=False)
    assert tfm._tp_chunks(4, tp_comm, None) == 2
    with pytest.raises(errors.Revoked):
        jax.make_jaxpr(fn)(params, tok, tok)
