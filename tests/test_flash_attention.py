"""Flash-attention kernel tests: Pallas interpret mode (CPU) against the
naive reference — the kernel analog of testing the datatype engine
without a network (SURVEY.md §4).  Both directions are kernels now, so
both are compared to the jnp reference's values/grads."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from zhpe_ompi_tpu.ops.flash_attention import (
    attn_reference,
    _flash_fwd,
    flash_attention,
)


def _qkv(B=2, S=128, h=2, hd=64, seed=0, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, S, h, hd)
    return (jax.random.normal(k1, shape, dtype),
            jax.random.normal(k2, shape, dtype),
            jax.random.normal(k3, shape, dtype))


def _ref_lse(q, k, causal):
    """Reference per-row logsumexp of the scaled (masked) scores."""
    B, S, h, hd = q.shape
    s = jnp.einsum("bshd,bthd->bhst", q * (hd ** -0.5), k)
    s = s.astype(jnp.float32)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -1e30)
    return jax.nn.logsumexp(s, axis=-1).reshape(B * h, S)


class TestForward:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        q, k, v = _qkv()
        ref = attn_reference(q, k, v, causal)
        out, lse = _flash_fwd(q, k, v, causal, 32, 32, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )
        np.testing.assert_allclose(
            np.asarray(lse[..., 0]), np.asarray(_ref_lse(q, k, causal)),
            atol=2e-5, rtol=2e-5,
        )

    def test_uneven_block_sizes(self):
        q, k, v = _qkv(S=96)
        ref = attn_reference(q, k, v, True)
        out, _ = _flash_fwd(q, k, v, True, 32, 48, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_indivisible_seq_falls_back(self):
        q, k, v = _qkv(S=100)
        out = flash_attention(q, k, v, block_q=32, block_k=32, force=True)
        ref = attn_reference(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_single_kv_block(self):
        q, k, v = _qkv(S=32)
        out, _ = _flash_fwd(q, k, v, True, 32, 32, interpret=True)
        ref = attn_reference(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestBackward:
    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match_reference(self, causal):
        q, k, v = _qkv(S=64)

        def loss_flash(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal=causal, block_q=32,
                                block_k=32, interpret=True) ** 2
            )

        def loss_ref(q, k, v):
            return jnp.sum(attn_reference(q, k, v, causal) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4
            )

    def test_grads_uneven_blocks(self):
        """block_q != block_k exercises the asymmetric tile masks in both
        backward kernels."""
        q, k, v = _qkv(S=96)

        def loss_flash(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal=True, block_q=32,
                                block_k=48, interpret=True) ** 2
            )

        def loss_ref(q, k, v):
            return jnp.sum(attn_reference(q, k, v, True) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4
            )

    def test_grads_nontrivial_cotangent(self):
        """A non-symmetric loss (weighted sum) catches transposition bugs
        that x**2 losses can miss."""
        q, k, v = _qkv(S=64, seed=3)
        w = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)

        def loss_flash(q, k, v):
            return jnp.sum(
                w * flash_attention(q, k, v, causal=True, block_q=32,
                                    block_k=32, interpret=True)
            )

        def loss_ref(q, k, v):
            return jnp.sum(w * attn_reference(q, k, v, True))

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4
            )


class TestDispatch:
    def test_cpu_defaults_to_reference(self):
        q, k, v = _qkv(S=32)
        out = flash_attention(q, k, v)  # no interpret, cpu platform
        ref = attn_reference(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)

    def test_force_runs_kernel_off_tpu(self):
        """force=True must genuinely exercise the kernel (interpreted on
        CPU), not silently fall back."""
        q, k, v = _qkv(S=64)
        out = flash_attention(q, k, v, block_q=32, block_k=32, force=True)
        ref = attn_reference(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_model_config_forces_kernel(self):
        """Config(flash=True) routes the transformer through the kernel."""
        import jax

        from zhpe_ompi_tpu.models import transformer as tfm

        cfg = tfm.Config(vocab=64, d_model=64, n_heads=2, d_ff=128,
                         n_layers=1, seq=32, dtype=jnp.float32, flash=True)
        cfg_naive = tfm.Config(vocab=64, d_model=64, n_heads=2, d_ff=128,
                               n_layers=1, seq=32, dtype=jnp.float32,
                               flash=False)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jnp.arange(2 * 32, dtype=jnp.int32).reshape(2, 32) % 64
        out_flash = tfm.forward(params, tokens, cfg, tp_comm=None)
        out_naive = tfm.forward(params, tokens, cfg_naive, tp_comm=None)
        np.testing.assert_allclose(
            np.asarray(out_flash), np.asarray(out_naive),
            atol=1e-4, rtol=1e-4,
        )


class TestDispatch:
    """The auto path dispatches on the platform and the input only: on
    TPU the kernels run and a lowering failure raises; nothing probes
    and nothing quietly reverts to the reference."""

    def _spy(self, monkeypatch, fa):
        calls = []
        real = fa._flash

        def spy(q, k, v, causal, bq, bk, interpret):
            calls.append(interpret)
            return real(q, k, v, causal, bq, bk, True)  # runs on CPU

        monkeypatch.setattr(fa, "_flash", spy)
        return calls

    def test_tpu_dispatch_picks_the_kernel(self, monkeypatch):
        from zhpe_ompi_tpu.ops import flash_attention as fa

        monkeypatch.setattr(fa, "on_tpu", lambda: True)
        calls = self._spy(monkeypatch, fa)
        q = fa.jnp.zeros((1, 128, 2, 8), fa.jnp.float32)
        fa.flash_attention(q, q, q, causal=True)
        assert calls == [False]  # the compiled kernel, not the interpreter

    def test_lowering_error_raises_on_tpu(self, monkeypatch):
        from zhpe_ompi_tpu.ops import flash_attention as fa

        monkeypatch.setattr(fa, "on_tpu", lambda: True)

        def boom(*a, **kw):
            raise RuntimeError("Mosaic lowering unsupported")

        monkeypatch.setattr(fa, "_flash", boom)
        q = fa.jnp.zeros((1, 128, 2, 8), fa.jnp.float32)
        with pytest.raises(RuntimeError, match="Mosaic lowering"):
            fa.flash_attention(q, q, q, causal=True)

    def test_off_tpu_auto_path_uses_reference(self, monkeypatch):
        from zhpe_ompi_tpu.ops import flash_attention as fa

        monkeypatch.setattr(fa, "on_tpu", lambda: False)
        calls = self._spy(monkeypatch, fa)
        q, k, v = _qkv(B=1, S=128, h=2, hd=8)
        out = fa.flash_attention(q, k, v, causal=True)
        assert calls == []
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(attn_reference(q, k, v)))

    def test_indivisible_seq_uses_reference_on_tpu(self, monkeypatch):
        """The shape rule: kernels need whole tiles, so an S the blocks
        do not divide takes the reference even on TPU."""
        from zhpe_ompi_tpu.ops import flash_attention as fa

        monkeypatch.setattr(fa, "on_tpu", lambda: True)
        calls = self._spy(monkeypatch, fa)
        q = fa.jnp.zeros((1, 96, 2, 8), fa.jnp.float32)
        fa.flash_attention(q, q, q, causal=True, block_q=64, block_k=64)
        assert calls == []

    @pytest.mark.parametrize("platform,kind,want", [
        ("tpu", "TPU v5 lite", True),
        ("cpu", "TPU v5 lite", False),  # no device_kind sniffing
        ("cpu", "cpu", False),
    ])
    def test_on_tpu_is_the_platform(self, monkeypatch, platform, kind,
                                    want):
        from zhpe_ompi_tpu.ops import flash_attention as fa

        class FakeDev:
            pass

        FakeDev.platform, FakeDev.device_kind = platform, kind
        monkeypatch.setattr(fa.jax, "devices", lambda: [FakeDev()])
        assert fa.on_tpu() is want
