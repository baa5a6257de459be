"""Chunked cross-entropy (fused logit/lse pass) — the round-4 MFU lever.

A round-3 analysis of the train step's cap measured the f32 (B, S, V)
logit/lse pass at ~13% of step FLOPs running at HBM-bandwidth rate: the
unembed matmul's f32 logits (16 x 512 x 8192 x 4B = 256 MB at a B 16,
S 512 step) are materialized to HBM, re-read for the logsumexp, and the
autodiff backward materializes the same-sized softmax.  This module
computes the identical loss WITHOUT ever materializing the full logits:

- **forward**: a ``lax.scan`` over vocabulary chunks runs the online
  logsumexp recurrence (the flash-attention trick applied along V); each
  chunk's (B, S, Vc) logits live only inside one fused scan step.
- **backward** (custom_vjp): re-runs the chunk scan using the saved lse,
  accumulating dx += p_c @ emb_c and demb_c = p_c^T x per chunk — all
  dense MXU matmuls, O(B*S*Vc) transient memory.

Everything is ``lax`` — no Pallas needed: the hot ops are matmuls XLA
already tiles onto the MXU; the win is eliminating the giant
intermediate, which is a dataflow property, not a kernel property.

Numerics: identical form to the unchunked loss (f32 lse from
model-dtype operands, target logit on the hidden side so no (B, S, V)
gather exists — transformer.loss_fn's measured-fast formulation); the
online-max recurrence makes the chunked lse exactly as stable as the
one-shot jax.nn.logsumexp.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_NEG = -1e30


def ce_reference(x, emb, targets):
    """Unchunked loss — the single semantic baseline (transformer's
    historical body): mean over tokens of lse(logits) - logits[target]."""
    logits = jnp.einsum(
        "bsd,vd->bsv", x, emb, preferred_element_type=jnp.float32
    )
    lse = jax.nn.logsumexp(logits, axis=-1)
    tl = jnp.einsum(
        "bsd,bsd->bs", x, emb[targets], preferred_element_type=jnp.float32
    )
    return jnp.mean(lse - tl)


def _chunks(emb, chunk):
    v, d = emb.shape
    return emb.reshape(v // chunk, chunk, d)


def _online_lse(x, emb_chunks):
    """Scan the online logsumexp recurrence over vocab chunks; returns
    the f32 (B, S) lse."""
    B, S, _ = x.shape

    def step(carry, emb_c):
        m, s = carry
        logits = jnp.einsum(
            "bsd,vd->bsv", x, emb_c, preferred_element_type=jnp.float32
        )
        cm = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, cm)
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[..., None]), axis=-1
        )
        return (m_new, s), None

    init = (jnp.full((B, S), _NEG, jnp.float32),
            jnp.zeros((B, S), jnp.float32))
    (m, s), _ = lax.scan(step, init, emb_chunks)
    return m + jnp.log(s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def chunked_ce(x, emb, targets, chunk: int):
    """Mean token cross-entropy, vocab-chunked; == ce_reference."""
    loss, _ = _ce_fwd(x, emb, targets, chunk)
    return loss


def _ce_fwd(x, emb, targets, chunk):
    emb_chunks = _chunks(emb, chunk)
    lse = _online_lse(x, emb_chunks)
    tl = jnp.einsum(
        "bsd,bsd->bs", x, emb[targets], preferred_element_type=jnp.float32
    )
    loss = jnp.mean(lse - tl)
    return loss, (x, emb, targets, lse)


def _ce_bwd(chunk, res, g):
    x, emb, targets, lse = res
    B, S, D = x.shape
    gt = (g / (B * S)).astype(jnp.float32)  # d mean
    emb_chunks = _chunks(emb, chunk)

    def step(dx_acc, emb_c):
        logits = jnp.einsum(
            "bsd,vd->bsv", x, emb_c, preferred_element_type=jnp.float32
        )
        p = jnp.exp(logits - lse[..., None])  # softmax rows for the chunk
        dx_acc = dx_acc + jnp.einsum(
            "bsv,vd->bsd", p, emb_c.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        demb_c = jnp.einsum(
            "bsv,bsd->vd", p, x.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return dx_acc, demb_c

    dx, demb = lax.scan(step, jnp.zeros((B, S, D), jnp.float32),
                        emb_chunks)
    demb = demb.reshape(emb.shape)
    # target-logit term: d(-logits[t])/dx = -emb[t]; /demb = scatter -x
    dx = (dx - emb[targets].astype(jnp.float32)) * gt
    demb = demb * gt - jnp.zeros_like(demb).at[targets].add(
        gt * x.astype(jnp.float32)
    )
    return dx.astype(x.dtype), demb.astype(emb.dtype), None


chunked_ce.defvjp(_ce_fwd, _ce_bwd)


def token_ce(x, emb, targets, chunk: int | None = None):
    """Dispatcher: the reference with ``chunk`` None (or one chunk of the
    whole vocab), chunked otherwise.  A ``chunk`` that does not divide the
    vocab raises: asking for chunks and quietly getting the (B, S, V)
    logits would move a step's memory peak with no sign."""
    v = emb.shape[0]
    if chunk is not None and v % chunk:
        raise ValueError(f"ce chunk {chunk} does not divide the vocab {v}")
    if chunk is None or v == chunk:
        return ce_reference(x, emb, targets)
    return chunked_ce(x, emb, targets, chunk)
