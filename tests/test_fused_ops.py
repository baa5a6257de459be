"""Round-4 MFU levers: the fused layernorm Pallas kernel
(``ops/fused_norm.py``) and the vocab-chunked cross-entropy
(``ops/fused_ce.py``) — numerics against their references, fwd and bwd,
plus end-to-end through the model.  Kernels run interpreted on CPU (the
flash-attention testing pattern, SURVEY.md §4)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from zhpe_ompi_tpu.ops import fused_ce as fce
from zhpe_ompi_tpu.ops import fused_norm as fnm


def _rel(a, b):
    af = np.asarray(a, np.float32)
    bf = np.asarray(b, np.float32)
    return np.abs(af - bf).max() / max(1e-9, np.abs(af).max())


class TestFusedLayerNorm:
    @pytest.mark.parametrize("dtype,tol", [
        (jnp.float32, 1e-6), (jnp.bfloat16, 2e-2),
    ])
    def test_forward_matches_reference(self, dtype, tol):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(2, 64, 256)), dtype)
        g = jnp.asarray(rng.normal(size=(256,)) + 1.0, jnp.float32)
        ref = fnm.ln_reference(x, g)
        out = fnm.layer_norm(x, g, block_rows=32, interpret=True,
                             force=True)
        assert _rel(ref, out) < tol

    @pytest.mark.parametrize("dtype,tol", [
        (jnp.float32, 1e-4), (jnp.bfloat16, 6e-2),
    ])
    def test_grads_match_reference(self, dtype, tol):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(4, 32, 128)), dtype)
        g = jnp.asarray(rng.normal(size=(128,)) + 1.0, jnp.float32)
        w = jnp.asarray(rng.normal(size=(4, 32, 128)), dtype)

        def loss(fn):
            return lambda xx, gg: (fn(xx, gg) * w).astype(
                jnp.float32).sum()

        gr = jax.grad(loss(fnm.ln_reference), argnums=(0, 1))(x, g)
        gk = jax.grad(
            loss(lambda xx, gg: fnm.layer_norm(
                xx, gg, block_rows=32, interpret=True, force=True)),
            argnums=(0, 1),
        )(x, g)
        assert _rel(gr[0], gk[0]) < tol  # dx
        assert _rel(gr[1], gk[1]) < tol  # dgamma

    def test_untileable_shapes_fall_back(self):
        """Rows/feature dims that don't tile route to the reference (the
        whole-tile rule flash also applies) — same numerics either way."""
        x = jnp.ones((3, 5, 96))  # 96 % 128 != 0
        g = jnp.ones((96,))
        out = fnm.layer_norm(x, g, force=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(fnm.ln_reference(x, g)))

    def test_model_end_to_end_forced_kernel(self):
        """The transformer with fused_ln forced (interpreted) matches
        fused_ln disabled — the dispatch seam is sound."""
        from zhpe_ompi_tpu.models import transformer as tfm

        rng = np.random.default_rng(2)
        base = dict(vocab=64, d_model=128, n_heads=4, d_ff=256,
                    n_layers=2, seq=32, dtype=jnp.float32)
        tok = jnp.asarray(rng.integers(0, 64, (2, 32)))
        tgt = jnp.asarray(rng.integers(0, 64, (2, 32)))
        params = tfm.init_params(tfm.Config(**base), jax.random.PRNGKey(0))
        l_off = tfm.loss_fn(params, tok, tgt,
                            tfm.Config(**base, fused_ln=False))
        l_on = tfm.loss_fn(params, tok, tgt,
                           tfm.Config(**base, fused_ln=True))
        assert abs(float(l_off) - float(l_on)) < 1e-4


class TestLayerNormDispatch:
    """The fused-norm dispatcher: kernel on TPU, a lowering failure
    raises, reference off-TPU and for shapes the kernel cannot tile."""

    def _spy(self, monkeypatch):
        calls = []
        real = fnm._ln_pallas

        def spy(x2, g, block, interpret):
            calls.append(interpret)
            return real(x2, g, block, True)  # runs on CPU

        monkeypatch.setattr(fnm, "_ln_pallas", spy)
        return calls

    @pytest.mark.parametrize("tpu,want", [(True, [False]), (False, [])])
    def test_platform_dispatch(self, monkeypatch, tpu, want):
        monkeypatch.setattr(fnm, "on_tpu", lambda: tpu)
        calls = self._spy(monkeypatch)
        x = jnp.ones((4, 64, 128))
        g = jnp.ones((128,))
        out = fnm.layer_norm(x, g)
        assert calls == want
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(fnm.ln_reference(x, g)),
                                   atol=1e-5)

    @pytest.mark.parametrize("shape", [(3, 5, 96), (3, 100, 128)],
                             ids=["d%128", "rows%block"])
    def test_untileable_shape_uses_reference_on_tpu(self, monkeypatch,
                                                    shape):
        monkeypatch.setattr(fnm, "on_tpu", lambda: True)
        calls = self._spy(monkeypatch)
        fnm.layer_norm(jnp.ones(shape), jnp.ones(shape[-1:]))
        assert calls == []

    def test_lowering_error_raises_on_tpu(self, monkeypatch):
        monkeypatch.setattr(fnm, "on_tpu", lambda: True)

        def boom(*a, **kw):
            raise RuntimeError("Mosaic lowering unsupported")

        monkeypatch.setattr(fnm, "_ln_pallas", boom)
        with pytest.raises(RuntimeError, match="Mosaic lowering"):
            fnm.layer_norm(jnp.ones((4, 64, 128)), jnp.ones((128,)))


class TestChunkedCE:
    @pytest.mark.parametrize("dtype,tol", [
        (jnp.float32, 1e-5), (jnp.bfloat16, 5e-2),
    ])
    def test_loss_and_grads_match_reference(self, dtype, tol):
        rng = np.random.default_rng(3)
        B, S, D, V = 2, 16, 64, 128
        x = jnp.asarray(rng.normal(size=(B, S, D)) * 0.5, dtype)
        emb = jnp.asarray(rng.normal(size=(V, D)) * 0.2, dtype)
        t = jnp.asarray(rng.integers(0, V, (B, S)))
        ref = fce.ce_reference(x, emb, t)
        ck = fce.chunked_ce(x, emb, t, 32)
        assert abs(float(ref) - float(ck)) < tol * max(1.0,
                                                       abs(float(ref)))
        gr = jax.grad(lambda a, e: fce.ce_reference(a, e, t),
                      argnums=(0, 1))(x, emb)
        gk = jax.grad(lambda a, e: fce.chunked_ce(a, e, t, 32),
                      argnums=(0, 1))(x, emb)
        assert _rel(gr[0], gk[0]) < tol
        assert _rel(gr[1], gk[1]) < tol

    def test_extreme_logits_stable(self):
        """The online-max recurrence keeps huge logits finite, exactly
        like one-shot logsumexp."""
        x = jnp.full((1, 4, 32), 40.0, jnp.float32)
        emb = jnp.full((64, 32), 40.0, jnp.float32)
        t = jnp.zeros((1, 4), jnp.int32)
        ref = fce.ce_reference(x, emb, t)
        ck = fce.chunked_ce(x, emb, t, 16)
        assert np.isfinite(float(ck))
        assert abs(float(ref) - float(ck)) < 1e-3

    def test_dispatcher_gates(self):
        """token_ce runs the reference for chunk None or one whole-vocab
        chunk, and refuses a chunk that does not divide the vocab."""
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(1, 8, 16)), jnp.float32)
        emb = jnp.asarray(rng.normal(size=(48, 16)), jnp.float32)
        t = jnp.asarray(rng.integers(0, 48, (1, 8)))
        ref = float(fce.ce_reference(x, emb, t))
        # 48 % 32 != 0 and 64 > 48: no silent fallback
        for bad in (32, 64):
            with pytest.raises(ValueError, match="does not divide"):
                fce.token_ce(x, emb, t, bad)
        assert abs(float(fce.token_ce(x, emb, t, None)) - ref) < 1e-6
        assert abs(float(fce.token_ce(x, emb, t, 48)) - ref) < 1e-6
        # 16 divides 48: genuinely chunked, same value
        assert abs(float(fce.token_ce(x, emb, t, 16)) - ref) < 1e-5

    def test_model_end_to_end_chunked(self):
        """loss_fn with ce_chunk set matches the unchunked loss, value
        AND gradients, through the full model."""
        from zhpe_ompi_tpu.models import transformer as tfm

        rng = np.random.default_rng(5)
        base = dict(vocab=128, d_model=64, n_heads=4, d_ff=128,
                    n_layers=2, seq=16, dtype=jnp.float32)
        tok = jnp.asarray(rng.integers(0, 128, (2, 16)))
        tgt = jnp.asarray(rng.integers(0, 128, (2, 16)))
        params = tfm.init_params(tfm.Config(**base), jax.random.PRNGKey(1))
        cfg_off = tfm.Config(**base)
        cfg_on = tfm.Config(**base, ce_chunk=32)
        l0, g0 = jax.value_and_grad(
            lambda p: tfm.loss_fn(p, tok, tgt, cfg_off))(params)
        l1, g1 = jax.value_and_grad(
            lambda p: tfm.loss_fn(p, tok, tgt, cfg_on))(params)
        assert abs(float(l0) - float(l1)) < 1e-5
        for k in g0:
            assert _rel(g0[k], g1[k]) < 1e-4, k
