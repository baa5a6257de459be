"""Fused attention (flash-attention) Pallas kernels for TPU — fwd AND bwd.

The reference has no accelerator kernels at all — its hot loops are C
(SURVEY.md §2) — so this is pure TPU-native ground: the transformer
models' attention is the FLOPs-dominant op after the matmuls, and the
naive form materializes the (S, S) score matrix in HBM.  The forward
kernel computes softmax(QKᵀ)V blockwise with the online-softmax
recurrence over a (batch·heads, q-blocks, k-blocks) grid: only
(block, d) tiles ever sit in VMEM (K/V stream one block per grid step —
whole-sequence staging would blow the ~16 MB VMEM budget at exactly the
long-context sizes the kernel targets), partial statistics live in VMEM
scratch across the k-grid, and fully-masked causal blocks skip their
compute.  It also emits the per-row logsumexp so the backward never
re-derives softmax statistics.

Backward pass (the flash-attention-2 scheme): a dq kernel over
(bh, q-blocks, k-blocks) and a dk/dv kernel over (bh, k-blocks,
q-blocks), each recomputing its (block_q, block_k) probability tile
in-kernel from Q, K and the saved logsumexp:

    p  = exp(q·kᵀ·scale − lse)
    dp = dO·Vᵀ           dv += pᵀ·dO
    ds = p·(dp − Δ)      with Δ = rowsum(dO ∘ O)
    dq += ds·K           dk += dsᵀ·(scale·Q)
    (dq scaled once when its accumulation ends)

Accumulators live in VMEM scratch across the streamed grid axis and
fully-masked causal tiles skip compute, so training-time memory stays
O(block·S) like the forward — the naive O(S²) rebuild would OOM
precisely the long-context runs this kernel exists for.

Precision: every matmul takes its operands in the inputs' dtype and
accumulates in f32 (``preferred_element_type``).  Q is scaled in its own
dtype, and the f32 p and ds tiles are cast to that dtype just before
they enter a matmul; the running max, the row sums, lse, Δ, the masking,
the exponentials and every accumulator stay f32.  These are the numerics
of ``attn_reference`` under autodiff (bf16 q·kᵀ, softmax weights cast to
v's dtype, the f32 score cotangent cast back before the dq and dk
products), so bf16 inputs get single-pass bf16 MXU work and f32 inputs
keep f32 operands throughout.  On a TPU v5e Mosaic's default precision
multiplies f32 operands in one bf16 pass as well, so up-casting the
tiles would give the same numbers at head width 64 and cost 0.4–1% of
the kernels' time, no more: the operands' dtype does not bound them.

Off-TPU the auto path uses the reference jnp implementation;
`interpret=True` runs the kernels on CPU for tests (the in-tree analog
of testing the datatype engine without a network, SURVEY.md §4), and
forcing the kernel off-TPU routes through the interpreter so "forced"
really does exercise the kernel path.  On TPU the kernels always run:
a lowering failure raises instead of quietly reverting to the O(S^2)
reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30


def attn_reference(q, k, v, causal=True):
    """Naive attention — the single semantic baseline (the models import
    this; keep numerics changes here only)."""
    B, S, h, hd = q.shape
    qs = q * (hd ** -0.5)
    scores = jnp.einsum("bshd,bthd->bhst", qs, k).astype(jnp.float32)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(mask, scores, _NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", w, v)


# ---------------------------------------------------------------- forward


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_sc, m_sc,
                      l_sc, *, block_q: int, block_k: int, n_kb: int,
                      causal: bool):
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)
    hd = q_ref.shape[-1]

    @pl.when(kj == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    def _compute():
        qs = q_ref[0] * hd ** -0.5                     # (block_q, hd)
        vb = v_ref[0]
        s = lax.dot_general(
            qs, k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            row = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            col = kj * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(col <= row, s, _NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * corr + lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_sc[...] = m_new

    if causal:
        # skip blocks entirely above the diagonal
        pl.when(kj * block_k <= (qi + 1) * block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(kj == n_kb - 1)
    def _finalize():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)
        # per-row logsumexp, saved for the backward's p-recompute
        lse_ref[0] = m_sc[...] + jnp.log(l)


def _flash_fwd(q, k, v, causal: bool, block_q: int, block_k: int,
               interpret: bool):
    """Returns (out (B,S,h,dv), lse (B*h, S, 1) float32), ``dv`` being
    v's head width.  Requires S divisible by both block sizes (the
    wrapper guarantees it)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, h, hd = q.shape
    dv = v.shape[-1]

    def fold(x):  # (B, S, h, d) -> (B*h, S, d)
        return x.transpose(0, 2, 1, 3).reshape(B * h, S, x.shape[-1])

    qf, kf, vf = fold(q), fold(k), fold(v)
    n_kb = S // block_k
    grid = (B * h, S // block_q, n_kb)
    # bh and q-block programs are independent; only the k-axis carries the
    # online-softmax recurrence
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )
    out, lse = pl.pallas_call(
        functools.partial(
            _flash_fwd_kernel, block_q=block_q, block_k=block_k,
            n_kb=n_kb, causal=causal,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, qi, kj: (bh, kj, 0)),
            pl.BlockSpec((1, block_k, dv), lambda bh, qi, kj: (bh, kj, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda bh, qi, kj: (bh, qi, 0)),
            # (bh, S, 1): the trailing unit dim satisfies the TPU tiling
            # rule (block dims must divide (8, 128) or equal the array's)
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, kj: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * h, S, dv), q.dtype),
            jax.ShapeDtypeStruct((B * h, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf)
    return out.reshape(B, h, S, dv).transpose(0, 2, 1, 3), lse


# ---------------------------------------------------------------- backward


def _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, kj,
                    block_q: int, block_k: int, causal: bool):
    """Shared backward-tile recompute (both backward kernels use exactly
    this math — keep it in one place so dq can never drift from dk/dv):

        s  = (scale·Q)·Kᵀ  (masked)     p  = exp(s − lse)
        dp = dO·Vᵀ                      ds = p·(dp − Δ)

    Q is scaled in its own dtype, as ``attn_reference`` scales it, and
    both matmuls take the tiles as loaded, accumulating in f32.

    Returns (qs, kb, dob, p, ds): the scaled Q, K and dO tiles in the
    inputs' dtype, p and ds in f32.
    """
    hd = q_ref.shape[-1]
    qs = q_ref[0] * hd ** -0.5                         # (bq, hd), pre-scaled
    kb = k_ref[0]                                      # (bk, hd)
    dob = do_ref[0]                                    # (bq, dv)
    s = lax.dot_general(                                # scaled scores
        qs, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if causal:
        row = qi * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        col = kj * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(col <= row, s, _NEG_INF)
    p = jnp.exp(s - lse_ref[0])                        # masked -> exp(-inf)=0
    dp = lax.dot_general(                               # dO · Vᵀ
        dob, v_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta_ref[0])
    return qs, kb, dob, p, ds


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_sc, *, block_q: int, block_k: int,
                         n_kb: int, causal: bool):
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)
    hd = q_ref.shape[-1]

    @pl.when(kj == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def _compute():
        _, kb, _, _, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, kj,
            block_q, block_k, causal,
        )
        dq_sc[...] += lax.dot_general(                  # ds · K
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(kj * block_k <= (qi + 1) * block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(kj == n_kb - 1)
    def _finalize():
        # dq = scale·(ds·K), scaled once here rather than on every tile
        dq_ref[0] = (dq_sc[...] * hd ** -0.5).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_sc, dv_sc, *, block_q: int,
                          block_k: int, n_qb: int, causal: bool):
    import jax.experimental.pallas as pl

    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def _compute():
        qs, _, dob, p, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, kj,
            block_q, block_k, causal,
        )
        dv_sc[...] += lax.dot_general(                  # pᵀ · dO
            p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dsᵀ · (scale·Q): qs is pre-scaled, so this IS scale·dsᵀ·Q
        dk_sc[...] += lax.dot_general(
            ds.astype(qs.dtype), qs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # same skip condition as dq — tiles entirely above the diagonal
        # contribute nothing to dk/dv either
        pl.when(kj * block_k <= (qi + 1) * block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(qi == n_qb - 1)
    def _finalize():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g, causal: bool, block_q: int, block_k: int,
               interpret: bool):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, h, hd = q.shape
    dv = v.shape[-1]

    def fold(x):  # (B, S, h, d) -> (B*h, S, d)
        return x.transpose(0, 2, 1, 3).reshape(B * h, S, x.shape[-1])

    qf, kf, vf, of, gf = fold(q), fold(k), fold(v), fold(o), fold(g)
    # Δ = rowsum(dO ∘ O): one fused elementwise+reduce, cheap in plain XLA;
    # kept (bh, S, 1) so its blocks satisfy the TPU tiling rule
    delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1, keepdims=True)

    n_qb = S // block_q
    n_kb = S // block_k

    q_spec = pl.BlockSpec((1, block_q, hd), lambda bh, qi, kj: (bh, qi, 0))
    k_spec = pl.BlockSpec((1, block_k, hd), lambda bh, qi, kj: (bh, kj, 0))
    v_spec = pl.BlockSpec((1, block_k, dv), lambda bh, qi, kj: (bh, kj, 0))
    do_spec = pl.BlockSpec((1, block_q, dv), lambda bh, qi, kj: (bh, qi, 0))
    row_spec = pl.BlockSpec((1, block_q, 1), lambda bh, qi, kj: (bh, qi, 0))

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, block_q=block_q, block_k=block_k,
            n_kb=n_kb, causal=causal,
        ),
        grid=(B * h, n_qb, n_kb),
        in_specs=[q_spec, k_spec, v_spec, do_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B * h, S, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qf, kf, vf, gf, lse, delta)

    # k-major grid: swap the roles of axes 1/2 in the index maps
    q_spec2 = pl.BlockSpec((1, block_q, hd), lambda bh, kj, qi: (bh, qi, 0))
    k_spec2 = pl.BlockSpec((1, block_k, hd), lambda bh, kj, qi: (bh, kj, 0))
    v_spec2 = pl.BlockSpec((1, block_k, dv), lambda bh, kj, qi: (bh, kj, 0))
    do_spec2 = pl.BlockSpec((1, block_q, dv), lambda bh, kj, qi: (bh, qi, 0))
    row_spec2 = pl.BlockSpec((1, block_q, 1), lambda bh, kj, qi: (bh, qi, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
            n_qb=n_qb, causal=causal,
        ),
        grid=(B * h, n_kb, n_qb),
        in_specs=[q_spec2, k_spec2, v_spec2, do_spec2, row_spec2, row_spec2],
        out_specs=[k_spec2, v_spec2],
        out_shape=[
            jax.ShapeDtypeStruct((B * h, S, hd), k.dtype),
            jax.ShapeDtypeStruct((B * h, S, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, hd), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qf, kf, vf, gf, lse, delta)

    def unfold(x):
        return x.reshape(B, h, S, x.shape[-1]).transpose(0, 2, 1, 3)

    return unfold(dq), unfold(dk), unfold(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return out


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, g, causal, block_q, block_k,
                      interpret)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def on_tpu() -> bool:
    """Whether the default backend is a TPU, the kernels' home."""
    return jax.devices()[0].platform == "tpu"


def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_k: int = 1024, interpret: bool = False,
                    force: bool = False):
    """Fused attention over (B, S, heads, head_dim) tensors.  v's head
    width may differ from q's and k's (latent attention's 128 against
    192): the output and dv take v's width, the scale q's.

    The kernels' matmuls take operands in the inputs' dtype and
    accumulate in f32, with the softmax statistics in f32: the numerics
    of ``attn_reference`` under autodiff.  bf16 inputs give bf16
    operands, f32 inputs f32 ones.

    Dispatch, on the input and the platform only:

    - S not divisible by the (clamped) block sizes: the jnp reference,
      since the kernels need whole tiles;
    - on TPU, or with ``force``/``interpret``: the Pallas kernels (fwd
      and bwd), through the Pallas interpreter off-TPU (slow; for tests
      and numerics comparison).  A kernel that fails to lower raises;
    - elsewhere: the jnp reference.

    Default blocks are large (512/1024, clamped to S): the kernels are
    per-program-overhead-bound on TPU at small tiles — measured on a v5e,
    128x128 blocks ran 2.4x slower than 512x1024 at S=2048."""
    S = q.shape[1]
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    if S % block_q or S % block_k:
        return attn_reference(q, k, v, causal)
    tpu = on_tpu()
    if not (tpu or force or interpret):
        return attn_reference(q, k, v, causal)
    return _flash(q, k, v, causal, block_q, block_k, interpret or not tpu)
