"""Fused layernorm Pallas kernel (fwd + bwd) — the round-4 MFU lever.

The round-3 cap analysis (bench.py docstring) measured the steady-state plateau at 38-39% MFU and named the HBM-bound
segments between matmuls: the f32 layernorms are pure bandwidth — XLA
computes the row statistics and the normalize as separate passes with an
f32 upcast materialized in between, so each LN costs ~3x the minimal
traffic.  This kernel does the whole thing in one pass: a row block is
read into VMEM once (bf16), statistics and the normalized, gain-scaled
output are produced in-register in f32, and one bf16 block is written
back — the same "one read, one write" discipline as the flash kernels
(``ops/flash_attention.py``), applied to the norm.

Backward is a second one-pass kernel over the same row blocks using the
saved per-row (mean, rstd): dx from the standard layernorm backward
formula, dgamma accumulated across the sequential TPU grid in VMEM
scratch and written at the last step.

The reference has no analog (its hot loops are C over the wire,
SURVEY.md §2); this is TPU-only ground.  Reference numerics live in
``ln_reference`` — the models import the dispatcher, which uses the
reference off-TPU exactly like flash attention does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import on_tpu

_EPS = 1e-5


def ln_reference(x, g):
    """The single semantic baseline (transformer._ln's historical body):
    f32 statistics and normalize, cast back to the input dtype."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    m = xf.mean(-1, keepdims=True)
    v = xf.var(-1, keepdims=True)
    return ((xf - m) * lax.rsqrt(v + _EPS) * g).astype(dt)


# ---------------------------------------------------------------- forward


def _ln_fwd_kernel(x_ref, g_ref, y_ref, m_ref, r_ref):
    xf = x_ref[...].astype(jnp.float32)          # (block_rows, D)
    gf = g_ref[...].astype(jnp.float32)          # (1, D)
    m = jnp.mean(xf, axis=-1, keepdims=True)
    c = xf - m
    v = jnp.mean(c * c, axis=-1, keepdims=True)
    r = lax.rsqrt(v + _EPS)
    y_ref[...] = (c * r * gf).astype(y_ref.dtype)
    m_ref[...] = m
    r_ref[...] = r


def _ln_fwd(x2, g, block_rows: int, interpret: bool):
    import jax.experimental.pallas as pl

    n, d = x2.shape
    grid = (n // block_rows,)
    y, m, r = pl.pallas_call(
        _ln_fwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x2.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2, g.reshape(1, d))
    return y, m, r


# ---------------------------------------------------------------- backward


def _ln_bwd_kernel(x_ref, g_ref, dy_ref, m_ref, r_ref, dx_ref, dg_ref,
                   dg_sc, *, n_blocks: int):
    import jax.experimental.pallas as pl

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dg_sc[...] = jnp.zeros_like(dg_sc)

    xf = x_ref[...].astype(jnp.float32)
    gf = g_ref[...].astype(jnp.float32)
    dyf = dy_ref[...].astype(jnp.float32)
    m = m_ref[...]
    r = r_ref[...]
    xhat = (xf - m) * r
    dxhat = dyf * gf
    # dx = r * (dxhat - mean(dxhat) - xhat * mean(dxhat * xhat))
    mean_dxhat = jnp.mean(dxhat, axis=-1, keepdims=True)
    mean_dxx = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (r * (dxhat - mean_dxhat - xhat * mean_dxx)
                   ).astype(dx_ref.dtype)
    # dgamma: cross-row reduction, accumulated across the sequential grid
    dg_sc[...] += jnp.sum(dyf * xhat, axis=0, keepdims=True)

    @pl.when(i == n_blocks - 1)
    def _emit():
        dg_ref[...] = dg_sc[...]


def _ln_bwd(x2, g, dy2, m, r, block_rows: int, interpret: bool):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = x2.shape
    n_blocks = n // block_rows
    dx, dg = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, n_blocks=n_blocks),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x2.dtype),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        interpret=interpret,
    )(x2, g.reshape(1, d), dy2, m, r)
    return dx, dg


# ------------------------------------------------------------- custom vjp


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _ln_pallas(x2, g, block_rows, interpret):
    y, _, _ = _ln_fwd(x2, g, block_rows, interpret)
    return y


def _ln_vjp_fwd(x2, g, block_rows, interpret):
    y, m, r = _ln_fwd(x2, g, block_rows, interpret)
    return y, (x2, g, m, r)


def _ln_vjp_bwd(block_rows, interpret, res, dy):
    x2, g, m, r = res
    dx, dg = _ln_bwd(x2, g, dy, m, r, block_rows, interpret)
    return dx, dg.reshape(g.shape).astype(g.dtype)


_ln_pallas.defvjp(_ln_vjp_fwd, _ln_vjp_bwd)


# ------------------------------------------------------------- dispatcher


def layer_norm(x, g, block_rows: int = 256, interpret: bool = False,
               force: bool = False):
    """Layernorm with gain over the last axis.

    Dispatch, on the input and the platform only: rows that do not tile
    the block, or a width ``d`` that is not a multiple of 128 (the TPU
    lane width), take the jnp reference — the kernels want whole tiles,
    as flash does.  Otherwise the one-pass Pallas kernel runs on TPU, or
    anywhere under ``force``/``interpret`` (interpreted off-TPU, for
    tests); a kernel that fails to lower raises.  Elsewhere the jnp
    reference runs."""
    d = x.shape[-1]
    n = 1
    for s in x.shape[:-1]:
        n *= s
    block = min(block_rows, n)
    if n % block or d % 128:
        return ln_reference(x, g)
    tpu = on_tpu()
    if not (tpu or force or interpret):
        return ln_reference(x, g)
    y = _ln_pallas(x.reshape(n, d), g, block, interpret or not tpu)
    return y.reshape(x.shape)
