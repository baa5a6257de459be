"""Latent attention with sparse experts: the DeepSeek-V3 block, as
Moonlight-16B-A3B publishes it, trained through the framework's train step
with its experts spread over the data-parallel ranks.

- **MLA** (multi-head latent attention): queries from ``wq`` at
  ``qk_nope + qk_rope`` per head; keys and values from a ``kv_rank``-wide
  latent (RMS-normed, then expanded by ``wkv_b`` to ``qk_nope + v_dim`` per
  head) plus one ``qk_rope``-wide rotary key shared by every head; RoPE in
  DeepSeek's pair layout; causal attention at ``(qk_nope + qk_rope)^-0.5``
  through the flash kernels, whose v is narrower than q and k.
- **Feed-forward**: the first ``n_dense`` layers a SwiGLU; the rest an
  expert layer (:func:`..moe.expert_layer`): a sigmoid router in f32 over
  all ``n_experts``, top-``top_k`` chosen on the scores (``noaux_tc``
  with its correction bias held at zero, so no pick moves), gates
  normalised and scaled by ``routed_scale``, a grouped SwiGLU over the
  experts this rank holds (:func:`..moe.grouped_swiglu`), dispatch and
  combine through the ep communicator's ``alltoall``, plus ``n_shared``
  shared experts as one SwiGLU of ``n_shared * expert_ff``.
- Pre-norm RMSNorm, a final RMSNorm, an untied head, the vocabulary-chunked
  loss (:func:`..ops.fused_ce.token_ce`).

Experts are sharded over the dp axis: rank r holds experts
``[r*held, (r+1)*held)`` of each layer, ``held`` being the local expert
axis of ``e_gate``.  Everything else is replicated.  bf16 compute with f32
master weights; the router's logits and sigmoid run in f32 at full
precision, so that rounding flips as few picks as it can.

Device work is named for a profile: ``zmpi.mla``, ``zmpi.mlp`` (the dense
layers), ``zmpi.moe.route``, ``zmpi.moe.experts``, ``zmpi.moe.shared``,
``zmpi.loss``; the all-to-alls by the communicator
(``zmpi.coll.<axis>.<name>.alltoall``).  Three performance variables
(``tools/mpit``) report the last step: ``moe_dropped_assignments``,
``moe_routed_rows`` and ``moe_max_expert_rows``, computed on the device
and fetched only when read.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import moe

# Expert weights, sharded over dp on their expert axis (axis 1).
EXPERT_LEAVES = ("e_gate", "e_up", "e_down")
# Per-layer leaves of every layer's attention (leading axis n_layers).
ATTN_LEAVES = ("attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo",
               "mlp_norm")
DENSE_LEAVES = ("dense_gate", "dense_up", "dense_down")
MOE_LEAVES = ("router",) + EXPERT_LEAVES + ("s_gate", "s_up", "s_down")


@dataclass(frozen=True)
class Config:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    qk_nope: int = 16
    qk_rope: int = 8
    v_dim: int = 16
    kv_rank: int = 32
    dense_ff: int = 128
    expert_ff: int = 32
    n_experts: int = 16      # the router's width
    top_k: int = 4
    n_shared: int = 2
    routed_scale: float = 2.446
    n_layers: int = 3
    n_dense: int = 1
    rope_theta: float = 50000.0
    eps: float = 1e-5
    # rows one rank may send another, over the even share (moe.capacity)
    capacity_factor: float = 2.0
    dtype: Any = jnp.bfloat16
    flash: bool | None = None  # as transformer.Config.flash
    remat: bool = False
    ce_chunk: int | None = None


def init_params(cfg: Config, key, experts_held: int) -> dict:
    """Full parameters, N(0, 0.02) matrices and unit norms, in f32;
    ``experts_held`` experts per expert layer (over all ranks)."""
    D, H, V = cfg.d_model, cfg.n_heads, cfg.vocab
    L, Ld = cfg.n_layers, cfg.n_dense
    Lm, X, Fe = L - Ld, experts_held, cfg.expert_ff
    Fs = cfg.n_shared * Fe
    shapes = {
        "embed": (V, D), "head": (V, D),
        "wq": (L, D, H * (cfg.qk_nope + cfg.qk_rope)),
        "wkv_a": (L, D, cfg.kv_rank + cfg.qk_rope),
        "wkv_b": (L, cfg.kv_rank, H * (cfg.qk_nope + cfg.v_dim)),
        "wo": (L, H * cfg.v_dim, D),
        "dense_gate": (Ld, D, cfg.dense_ff), "dense_up": (Ld, D, cfg.dense_ff),
        "dense_down": (Ld, cfg.dense_ff, D),
        "router": (Lm, D, cfg.n_experts),
        "e_gate": (Lm, X, D, Fe), "e_up": (Lm, X, D, Fe),
        "e_down": (Lm, X, Fe, D),
        "s_gate": (Lm, D, Fs), "s_up": (Lm, D, Fs), "s_down": (Lm, Fs, D),
    }
    keys = jax.random.split(key, len(shapes))
    p = {k: jax.random.normal(kk, s, jnp.float32) * 0.02
         for kk, (k, s) in zip(keys, shapes.items())}
    p.update(attn_norm=jnp.ones((L, D)), kv_norm=jnp.ones((L, cfg.kv_rank)),
             mlp_norm=jnp.ones((L, D)), final_norm=jnp.ones((D,)))
    return p


def param_specs(dp_axis) -> dict:
    """Experts sharded over ``dp_axis`` on their expert axis; every other
    leaf replicated."""
    from jax.sharding import PartitionSpec as P

    names = ("embed", "head", "final_norm") + ATTN_LEAVES + DENSE_LEAVES \
        + MOE_LEAVES
    return {k: P(None, dp_axis, None, None) if k in EXPERT_LEAVES else P()
            for k in names}


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def rope_tables(seq: int, dim: int, theta: float):
    """cos, sin (seq, dim) of DeepSeek's rotary embedding: frequencies
    ``theta^(-2i/dim)``, each repeated for the two halves."""
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.arange(seq, dtype=np.float64)[:, None] * inv[None]
    emb = np.concatenate([ang, ang], -1)
    return (jnp.asarray(np.cos(emb), jnp.float32),
            jnp.asarray(np.sin(emb), jnp.float32))


def _rope(x, cos, sin):
    """RoPE on (B, S, h, d) in DeepSeek's pair layout: the interleaved
    pairs of the projection are first gathered into halves, then rotated
    half against half."""
    B, S, h, d = x.shape
    x = x.astype(jnp.float32).reshape(B, S, h, d // 2, 2)
    x = jnp.swapaxes(x, -1, -2).reshape(B, S, h, d)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos[:, None] + rot * sin[:, None]


def _mla(x, lp, cfg: Config, cos, sin):
    B, S, _ = x.shape
    H, nope, rope, dv = cfg.n_heads, cfg.qk_nope, cfg.qk_rope, cfg.v_dim
    dt = cfg.dtype
    h = _rms(x, lp["attn_norm"], cfg.eps)
    q = jnp.einsum("bsd,de->bse", h, lp["wq"].astype(dt))
    q = q.reshape(B, S, H, nope + rope)
    kv_a = jnp.einsum("bsd,de->bse", h, lp["wkv_a"].astype(dt))
    c = _rms(kv_a[..., :cfg.kv_rank], lp["kv_norm"], cfg.eps)
    kv = jnp.einsum("bsr,re->bse", c, lp["wkv_b"].astype(dt))
    kv = kv.reshape(B, S, H, nope + dv)
    q_pe = _rope(q[..., nope:], cos, sin).astype(dt)
    k_pe = _rope(kv_a[:, :, None, cfg.kv_rank:], cos, sin).astype(dt)
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (B, S, H, rope))], -1)
    v = kv[..., nope:]
    if cfg.flash is False:
        from ..ops.flash_attention import attn_reference

        o = attn_reference(q, k, v)
    else:
        from ..ops.flash_attention import flash_attention

        o = flash_attention(q, k, v, causal=True, force=cfg.flash is True)
    return jnp.einsum("bse,ed->bsd", o.reshape(B, S, H * dv),
                      lp["wo"].astype(dt))


def _swiglu(h, wg, wu, wd, dt):
    g = jnp.einsum("bsd,df->bsf", h, wg.astype(dt))
    u = jnp.einsum("bsd,df->bsf", h, wu.astype(dt))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, wd.astype(dt))


def _experts(x, lp, cfg: Config, ep_comm):
    """One expert layer's routed and shared experts on the normed ``x``."""
    B, S, D = x.shape
    dt = cfg.dtype
    t = x.reshape(B * S, D)
    with jax.named_scope("zmpi.moe.route"):
        logits = jnp.dot(t.astype(jnp.float32), lp["router"],
                         precision=lax.Precision.HIGHEST)
    y, st = moe.expert_layer(
        t, logits, cfg.top_k,
        lambda rows, eid: moe.grouped_swiglu(
            rows, eid, lp["e_gate"], lp["e_up"], lp["e_down"]),
        ep_comm, held=lp["e_gate"].shape[0],
        capacity_factor=cfg.capacity_factor, score="sigmoid", norm=True,
        scale=cfg.routed_scale)
    with jax.named_scope("zmpi.moe.shared"):
        sh = _swiglu(x, lp["s_gate"], lp["s_up"], lp["s_down"], dt)
    return y.reshape(B, S, D) + sh, st


def forward_hidden(params: dict, tokens, cfg: Config, ep_comm=None):
    """This rank's tokens through every layer to the final norm.  Returns
    ``(x, aux)``: aux holds the assignments dropped past capacity and the
    rows the held experts received (each summed over layers), and the most
    rows one held expert received (max over layers)."""
    dt = cfg.dtype
    x = params["embed"].astype(dt)[tokens]
    cos, sin = rope_tables(tokens.shape[1], cfg.qk_rope, cfg.rope_theta)
    Ld = cfg.n_dense

    def attend(x, lp):
        with jax.named_scope("zmpi.mla"):
            return x + _mla(x, lp, cfg, cos, sin)

    def dense_block(x, lp):
        x = attend(x, lp)
        with jax.named_scope("zmpi.mlp"):
            h = _rms(x, lp["mlp_norm"], cfg.eps)
            return x + _swiglu(h, lp["dense_gate"], lp["dense_up"],
                               lp["dense_down"], dt), None

    def moe_block(x, lp):
        x = attend(x, lp)
        y, st = _experts(_rms(x, lp["mlp_norm"], cfg.eps), lp, cfg, ep_comm)
        return x + y, (st["dropped"], st["rows"], st["max_rows"])

    if cfg.remat:
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        dense_block = jax.checkpoint(dense_block, policy=policy)
        moe_block = jax.checkpoint(moe_block, policy=policy)
    dense = {k: params[k][:Ld] for k in ATTN_LEAVES}
    dense.update({k: params[k] for k in DENSE_LEAVES})
    sparse = {k: params[k][Ld:] for k in ATTN_LEAVES}
    sparse.update({k: params[k] for k in MOE_LEAVES})
    x, _ = lax.scan(dense_block, x, dense)
    x, (dropped, rows, max_rows) = lax.scan(moe_block, x, sparse)
    x = _rms(x, params["final_norm"], cfg.eps)
    return x, {"dropped": jnp.sum(dropped), "rows": jnp.sum(rows),
               "max_rows": jnp.max(max_rows)}


def loss_fn(params, tokens, targets, cfg: Config, ep_comm=None):
    """Mean token cross-entropy over this rank's tokens through the untied
    head; returns ``(loss, aux)`` (see :func:`forward_hidden`)."""
    from ..ops.fused_ce import token_ce

    x, aux = forward_hidden(params, tokens, cfg, ep_comm)
    with jax.named_scope("zmpi.loss"):
        loss = token_ce(x, params["head"].astype(cfg.dtype), targets,
                        cfg.ce_chunk)
    return loss, aux


def _reader(last: dict, key, reduce):
    """A performance variable's reader: the last step's value of ``key``,
    fetched from the device only when read."""
    def read():
        a = last.get(key)
        return 0 if a is None else int(reduce(np.asarray(jax.device_get(a))))

    return read


def make_train_step(cfg: Config, mesh, dp_comm, optimizer=None):
    """The optax train step (:func:`.transformer.make_train_step_optax`)
    of this model over a ("dp",) mesh, experts over ``dp_comm``.  Returns
    ``(init_opt_state, step, param_specs)``; each step leaves its counters
    for the ``moe_*`` performance variables."""
    from ..tools import mpit
    from . import transformer as tfm

    last: dict = {}  # the last step's counters, on the device until read
    mpit.register_pvar(
        "moe_dropped_assignments", _reader(last, "dropped", np.sum),
        description="last step: assignments to held experts dropped past "
        "capacity, summed over layers and ranks")
    mpit.register_pvar(
        "moe_routed_rows", _reader(last, "rows", np.sum),
        description="last step: rows the held experts received, the rows "
        "their grouped matmuls compute, summed over layers and ranks")
    mpit.register_pvar(
        "moe_max_expert_rows", _reader(last, "max_rows", np.max),
        description="last step: the most rows one held expert received in "
        "one layer, over layers and ranks")
    return tfm.make_train_step_optax(
        cfg, mesh, dp_comm, None, optimizer=optimizer,
        loss=lambda p, t, y: loss_fn(p, t, y, cfg, dp_comm),
        param_specs=param_specs(dp_comm.axis), on_aux=last.update)
