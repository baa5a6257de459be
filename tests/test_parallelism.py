"""sp/ep/pp parallelism built on framework primitives: exactness tests.

Each strategy's multi-device output is compared against a single-device
dense reference — the framework's answer to "long-context and distributed
are first-class".
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import zhpe_ompi_tpu as zmpi
from zhpe_ompi_tpu.models import moe, pipeline, ring_attention

N = 8


@pytest.fixture(scope="module")
def world():
    return zmpi.init()


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, world, causal):
        B, S, H, D = 2, 32, 4, 16  # S sharded into 8 blocks of 4
        r = np.random.default_rng(0)
        q = jnp.asarray(r.normal(size=(B, S, H, D)), jnp.float32)
        k = jnp.asarray(r.normal(size=(B, S, H, D)), jnp.float32)
        v = jnp.asarray(r.normal(size=(B, S, H, D)), jnp.float32)

        dense = ring_attention._block_attention_single(q, k, v, causal)

        spec = P(None, "world")
        sharding = NamedSharding(world.mesh, spec)
        qs, ks, vs = (jax.device_put(t, sharding) for t in (q, k, v))
        out = world.run(
            lambda a, b, c: ring_attention.ring_attention(
                world, a, b, c, causal=causal
            ),
            qs, ks, vs,
            in_specs=(spec, spec, spec), out_specs=spec,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(dense), rtol=2e-4, atol=2e-5
        )

    def test_long_sequence_jit(self, world):
        """Longer-than-memory-naive sequence: 8 x 64 = 512 under jit."""
        B, S, H, D = 1, 512, 2, 8
        r = np.random.default_rng(1)
        mk = lambda: jnp.asarray(r.normal(size=(B, S, H, D)), jnp.float32)
        q, k, v = mk(), mk(), mk()
        spec = P(None, "world")
        sharding = NamedSharding(world.mesh, spec)
        out = world.run(
            lambda a, b, c: ring_attention.ring_attention(world, a, b, c),
            *(jax.device_put(t, sharding) for t in (q, k, v)),
            in_specs=(spec, spec, spec), out_specs=spec,
        )
        dense = ring_attention._block_attention_single(q, k, v, True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(dense), rtol=2e-4, atol=2e-5
        )


class TestZigzagRingAttention:
    """Round 4: load-balanced causal ring attention — the zigzag
    chunk-pair layout where every (rank, step) computes exactly the
    live sub-blocks."""

    def _run_zigzag(self, world, q, k, v):
        n = world.size
        qz = ring_attention.zigzag_shard(q, n)
        kz = ring_attention.zigzag_shard(k, n)
        vz = ring_attention.zigzag_shard(v, n)
        # (n, B, Sc*2, H, D) sharded on dim 0 -> each rank's pair block
        spec = P("world")
        out = world.run(
            lambda a, b, c: ring_attention.ring_attention_zigzag(
                world, a[0], b[0], c[0])[None],
            *(world.device_put_sharded(t) for t in (qz, kz, vz)),
            in_specs=(spec, spec, spec), out_specs=spec,
        )
        return ring_attention.zigzag_unshard(out, n)

    def test_matches_dense_causal(self, world):
        B, S, H, D = 2, 64, 4, 16  # 2n = 16 chunks of 4
        r = np.random.default_rng(2)
        q = jnp.asarray(r.normal(size=(B, S, H, D)), jnp.float32)
        k = jnp.asarray(r.normal(size=(B, S, H, D)), jnp.float32)
        v = jnp.asarray(r.normal(size=(B, S, H, D)), jnp.float32)
        dense = ring_attention._block_attention_single(q, k, v, True)
        out = self._run_zigzag(world, q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(dense), rtol=2e-4, atol=2e-5
        )

    def test_shard_unshard_roundtrip(self, world):
        r = np.random.default_rng(3)
        x = jnp.asarray(r.normal(size=(2, 32, 3)), jnp.float32)
        z = ring_attention.zigzag_shard(x, world.size)
        back = ring_attention.zigzag_unshard(z, world.size)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))

    def test_grads_flow(self, world):
        """Differentiable through the switch + scan (training path)."""
        B, S, H, D = 1, 32, 2, 8
        r = np.random.default_rng(4)
        q = jnp.asarray(r.normal(size=(B, S, H, D)), jnp.float32)
        k = jnp.asarray(r.normal(size=(B, S, H, D)), jnp.float32)
        v = jnp.asarray(r.normal(size=(B, S, H, D)), jnp.float32)
        n = world.size
        spec = P("world")

        def loss_zig(q, k, v):
            qz = ring_attention.zigzag_shard(q, n)
            kz = ring_attention.zigzag_shard(k, n)
            vz = ring_attention.zigzag_shard(v, n)
            out = world.run(
                lambda a, b, c: ring_attention.ring_attention_zigzag(
                    world, a[0], b[0], c[0])[None],
                qz, kz, vz,
                in_specs=(spec, spec, spec), out_specs=spec,
            )
            return (ring_attention.zigzag_unshard(out, n) ** 2).sum()

        def loss_ref(q, k, v):
            return (ring_attention._block_attention_single(
                q, k, v, True) ** 2).sum()

        gz = jax.grad(loss_zig, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gz, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5)


class TestMoE:
    def test_matches_dense_reference(self, world):
        D, F, T_local = 16, 32, 8
        params = moe.init_moe_params(jax.random.PRNGKey(0), D, F, N)
        r = np.random.default_rng(2)
        x_all = jnp.asarray(r.normal(size=(N * T_local, D)), jnp.float32)

        # big capacity so nothing drops -> exact equivalence
        spec_x = P("world")
        px = jax.device_put(x_all, NamedSharding(world.mesh, spec_x))
        param_specs = {
            "router": P(),
            "w_in": P("world"),
            "w_out": P("world"),
        }
        pp = {
            k: jax.device_put(v, NamedSharding(world.mesh, param_specs[k]))
            for k, v in params.items()
        }

        def body(prm, xs):
            y, keep = moe.moe_ffn(world, prm, xs, capacity_factor=float(N))
            return y

        out = world.run(
            body, pp, px,
            in_specs=(param_specs, spec_x), out_specs=spec_x,
        )
        ref = moe.moe_reference_dense(params, x_all, N, capacity=10**9)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5
        )

    def test_capacity_drops_dont_crash(self, world):
        D, F, T_local = 8, 16, 4
        params = moe.init_moe_params(jax.random.PRNGKey(1), D, F, N)
        r = np.random.default_rng(3)
        x_all = jnp.asarray(r.normal(size=(N * T_local, D)), jnp.float32)
        spec_x = P("world")
        param_specs = {"router": P(), "w_in": P("world"), "w_out": P("world")}
        pp = {
            k: jax.device_put(v, NamedSharding(world.mesh, param_specs[k]))
            for k, v in params.items()
        }

        def body(prm, xs):
            y, keep = moe.moe_ffn(world, prm, xs, capacity_factor=0.5)
            return y

        out = world.run(
            body, pp,
            jax.device_put(x_all, NamedSharding(world.mesh, spec_x)),
            in_specs=(param_specs, spec_x), out_specs=spec_x,
        )
        assert np.isfinite(np.asarray(out)).all()
        # exact parity with the dense reference at the same binding capacity
        cap = max(1, int(0.5 * T_local / N))
        ref = moe.moe_reference_dense(
            params, x_all, N, capacity=cap, block_tokens=T_local
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5
        )


class TestPipeline:
    def test_matches_sequential(self, world):
        """8-stage pipeline of affine layers == sequential application."""
        M, mb, D = 6, 3, 8
        r = np.random.default_rng(4)
        # stage s applies x -> x @ W_s + 1  (W per stage, sharded over pp)
        Ws = jnp.asarray(r.normal(size=(N, D, D)) * 0.3, jnp.float32)
        xs = jnp.asarray(r.normal(size=(M, mb, D)), jnp.float32)

        def stage_fn(W, x):
            return x @ W[0] + 1.0

        spec_w = P("world")
        out = world.run(
            lambda W, x: pipeline.pipeline_apply(world, stage_fn, W, x),
            jax.device_put(Ws, NamedSharding(world.mesh, spec_w)),
            xs,
            in_specs=(spec_w, P()), out_specs=P("world"),
        )
        # sequential reference
        ref = xs
        for s in range(N):
            ref = ref @ Ws[s] + 1.0
        # per-stage outputs are stacked along dim 0; results live on the
        # LAST stage's block (other stages hold zeros)
        out = np.asarray(out).reshape(N, M, mb, D)[N - 1]
        np.testing.assert_allclose(out, np.asarray(ref), rtol=1e-4, atol=1e-5)


class TestZigzagTransformer:
    def test_sp_train_loss_matches_dense(self, world):
        """cfg.zigzag_sp end to end: the sp train step on zigzag-ordered
        tokens reproduces the dense single-device loss (the model has no
        positional encoding, so the token->rank assignment must not
        change the math — only the causal structure, which the zigzag
        ring preserves by global position)."""
        import optax

        import zhpe_ompi_tpu as zmpi
        from jax.sharding import Mesh, NamedSharding
        from zhpe_ompi_tpu.models import transformer as tfm

        n = 8
        devs = np.asarray(jax.devices()[:n]).reshape(1, 1, n)
        mesh = Mesh(devs, ("dp", "tp", "sp"))
        dp_comm = zmpi.Communicator(mesh, "dp", name="zz_dp")
        sp_comm = zmpi.Communicator(mesh, "sp", name="zz_sp")
        cfg = tfm.Config(vocab=64, d_model=16, n_heads=2, d_ff=32,
                         n_layers=2, seq=64, dtype=jnp.float32,
                         zigzag_sp=True)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        r = np.random.default_rng(7)
        tok = r.integers(0, cfg.vocab, (2, cfg.seq))
        tgt = r.integers(0, cfg.vocab, (2, cfg.seq))

        # dense reference on the ORIGINAL ordering (no sp)
        dense_cfg = tfm.Config(vocab=64, d_model=16, n_heads=2, d_ff=32,
                               n_layers=2, seq=64, dtype=jnp.float32)
        ref = float(tfm.loss_fn(params, jnp.asarray(tok),
                                jnp.asarray(tgt), dense_cfg))

        # zigzag column permutation: rank i's contiguous sp slice holds
        # global chunks (i, 2n-1-i)
        tz = np.concatenate(
            [np.asarray(ring_attention.zigzag_shard(
                jnp.asarray(tok)[..., None], n))[i, :, :, 0]
             for i in range(n)], axis=1)
        gz = np.concatenate(
            [np.asarray(ring_attention.zigzag_shard(
                jnp.asarray(tgt)[..., None], n))[i, :, :, 0]
             for i in range(n)], axis=1)

        init_opt, step, specs = tfm.make_train_step_optax(
            cfg, mesh, dp_comm, None, sp_comm, optimizer=optax.sgd(1e-2))
        # through numpy: the update donates its inputs
        sharded = {k: jax.device_put(np.asarray(v),
                                     NamedSharding(mesh, specs[k]))
                   for k, v in params.items()}
        dspec = NamedSharding(mesh, P("dp", "sp"))
        _, _, loss = step(sharded, init_opt(sharded),
                          jax.device_put(jnp.asarray(tz), dspec),
                          jax.device_put(jnp.asarray(gz), dspec))
        assert abs(float(loss) - ref) < 5e-4, (float(loss), ref)
