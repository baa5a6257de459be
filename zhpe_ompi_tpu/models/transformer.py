"""Flagship model: a transformer LM parallelized *through the framework*.

This plays the role the reference's example programs play
(``examples/ring_c.c`` etc.): a real application whose every communication
goes through the framework's communicators — the way a Megatron-style trainer
drives MPI/NCCL:

- **tp** (tensor parallel): attention heads and MLP hidden are sharded over
  the 'tp' mesh axis; partial sums after the output/down projections are
  combined with ``tp_comm.allreduce`` (the MPI_Allreduce hot path of
  BASELINE.md, executed as XLA psum on ICI).
- **dp** (data parallel): gradients are averaged with ``dp_comm.allreduce``.
- **sp** (sequence parallel / long context): ring attention over the 'sp'
  axis using ``comm.ppermute`` ring steps (see ring_attention.py).

Everything is bfloat16 on the MXU path with float32 master params/reductions,
static shapes, and scan-over-layers for compile-time O(1) in depth.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .. import ops as zops


@dataclass(frozen=True)
class Config:
    vocab: int = 256
    d_model: int = 128
    n_heads: int = 8
    d_ff: int = 512
    n_layers: int = 2
    seq: int = 64
    dtype: Any = jnp.bfloat16
    # attention impl: None = auto (Pallas flash kernel on TPU, naive jnp
    # elsewhere); True/False forces
    flash: bool | None = None
    # rematerialize layer activations in the backward pass: saves
    # O(n_layers * B * S * (D + F)) HBM for ~1/3 more forward FLOPs,
    # buying batch (and therefore MFU) at long sequence lengths.  The
    # policy keeps matmul outputs (checkpoint_dots) so only the cheap
    # elementwise/norm intermediates are recomputed.
    remat: bool = False
    # round-4 MFU levers (bench.py's cap analysis named both):
    # fused layernorm Pallas kernel: None = auto (kernel on TPU,
    # reference jnp elsewhere), True/False forces
    fused_ln: bool | None = None
    # vocab-chunked cross-entropy (no (B,S,V) materialization): chunk
    # size, or None for the unchunked reference loss
    ce_chunk: int | None = None
    # zigzag sequence parallelism: tokens arrive zigzag-sharded (rank i
    # holds global chunks (i, 2n-1-i)) and causal ring attention skips
    # the dead half of the ring work, balanced across ranks
    # (models/ring_attention.py::ring_attention_zigzag)
    zigzag_sp: bool = False


def init_params(cfg: Config, key, tp: int = 1) -> dict:
    """Initialize host-side full parameters (unsharded)."""
    k = jax.random.split(key, 8)
    D, H, F, V = cfg.d_model, cfg.d_model, cfg.d_ff, cfg.vocab
    s = lambda *shape: (cfg.n_layers,) + shape

    def nrm(kk, shape, scale):
        return (jax.random.normal(kk, shape, jnp.float32) * scale)

    return {
        "embed": nrm(k[0], (V, D), 0.02),
        # (L, D, 3, H): the q/k/v axis is explicit so tp-sharding the head
        # dim (last axis) keeps each rank's slice = q,k,v of its own heads
        "wqkv": nrm(k[1], s(D, 3, H), D**-0.5),
        "wo": nrm(k[2], s(H, D), H**-0.5),
        "w1": nrm(k[3], s(D, F), D**-0.5),
        "w2": nrm(k[4], s(F, D), F**-0.5),
        "ln1": jnp.ones(s(D)),
        "ln2": jnp.ones(s(D)),
        "lnf": jnp.ones((D,)),
    }


def _ln(x, g, fused=None):
    """Layernorm: the fused Pallas one-pass kernel on TPU (round-4 MFU
    lever), reference jnp elsewhere; numerics live in one place
    (ops/fused_norm.ln_reference)."""
    from ..ops import fused_norm

    if fused is False:
        return fused_norm.ln_reference(x, g)
    return fused_norm.layer_norm(x, g, force=fused is True)


from ..ops.flash_attention import attn_reference as _attn  # noqa: E402
# single source of attention numerics: the naive reference lives with the
# flash kernel (ops/flash_attention.py) so fallback/backward can't diverge


def forward_hidden(params: dict, tokens, cfg: Config, tp_comm=None,
                   sp_comm=None):
    """Forward pass on one device's shard, up to the final layernorm
    (pre-unembed).  See ``forward`` for the communicator semantics.

    `tp_comm` is a framework communicator over the 'tp' axis (or None for no
    tensor parallelism).  Heads and ffn-hidden arrive pre-sharded: wqkv is
    (L, D, 3, H/tp), wo is (L, H/tp, D), w1 (L, D, F/tp), w2 (L, F/tp, D).
    After wo and w2 the partial products are summed with tp_comm.allreduce —
    the framework's MPI_Allreduce on the hot path.

    `sp_comm` (sequence parallel / long context): tokens arrive sequence-
    sharded over the 'sp' axis and attention runs as ring attention over
    the framework's ppermute ring (models/ring_attention.py).
    """
    dtype = cfg.dtype
    x = params["embed"].astype(dtype)[tokens]  # (B, S_local, D)
    B, S, D = x.shape
    hd = D // cfg.n_heads
    n_heads_local = params["wqkv"].shape[-1] // hd

    from ..parallel.grad import f_identity, g_allreduce
    from .ring_attention import ring_attention

    # flash dispatch: auto picks per-platform inside flash_attention;
    # flash=True forces the kernel (interpreted off-TPU), False forces naive
    use_flash = cfg.flash is not False

    def block(x, layer):
        wqkv, wo, w1, w2, g1, g2 = layer
        h = _ln(x, g1, cfg.fused_ln)
        if tp_comm is not None:
            h = f_identity(tp_comm, h)
        qkv = jnp.einsum("bsd,dce->bsce", h, wqkv.astype(dtype))
        q = qkv[:, :, 0].reshape(B, S, n_heads_local, hd)
        k = qkv[:, :, 1].reshape(B, S, n_heads_local, hd)
        v = qkv[:, :, 2].reshape(B, S, n_heads_local, hd)
        if sp_comm is not None:
            if cfg.zigzag_sp:
                from .ring_attention import ring_attention_zigzag

                o = ring_attention_zigzag(sp_comm, q, k, v)
            else:
                o = ring_attention(sp_comm, q, k, v, causal=True)
            o = o.reshape(B, S, -1)
        elif use_flash:
            from ..ops.flash_attention import flash_attention

            o = flash_attention(
                q, k, v, causal=True, force=cfg.flash is True
            ).reshape(B, S, -1)
        else:
            o = _attn(q, k, v).reshape(B, S, -1)
        o = jnp.einsum("bse,ed->bsd", o, wo.astype(dtype))
        if tp_comm is not None:
            o = g_allreduce(tp_comm, o)
        x = x + o
        h = _ln(x, g2, cfg.fused_ln)
        if tp_comm is not None:
            h = f_identity(tp_comm, h)
        u = jnp.einsum("bsd,df->bsf", h, w1.astype(dtype))
        u = jax.nn.gelu(u)
        d = jnp.einsum("bsf,fd->bsd", u, w2.astype(dtype))
        if tp_comm is not None:
            d = g_allreduce(tp_comm, d)
        return x + d, None

    layers = (
        params["wqkv"], params["wo"], params["w1"], params["w2"],
        params["ln1"], params["ln2"],
    )
    step_fn = block
    if cfg.remat:
        step_fn = jax.checkpoint(
            block,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    x, _ = lax.scan(
        lambda carry, layer: step_fn(carry, layer), x,
        layers,
    )
    return _ln(x, params["lnf"], cfg.fused_ln)


def forward(params: dict, tokens, cfg: Config, tp_comm=None, sp_comm=None):
    """Full forward pass: hidden states -> vocabulary logits (f32)."""
    x = forward_hidden(params, tokens, cfg, tp_comm, sp_comm)
    # model-dtype operands with f32 accumulation: a full-f32 matmul here
    # runs at a fraction of MXU rate
    return jnp.einsum(
        "bsd,vd->bsv", x, params["embed"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )


def loss_fn(params, tokens, targets, cfg: Config, tp_comm=None, sp_comm=None):
    """Mean token cross-entropy in the fused lse form.

    ``-logp[t] = lse(logits) - logits[t]``, with the target logit computed
    on the hidden side (``sum(x * embed[t])``) so no (B, S, V) gather or
    scatter ever materializes — the gather/scatter backward of the
    log_softmax + take_along_axis form measured 14.3 ms vs 3-5 ms for this
    form at (8, 512) x 8192 vocab on v5e.  Numerics are identical: both
    compute f32 lse and an f32 target logit from model-dtype operands.
    """
    x = forward_hidden(params, tokens, cfg, tp_comm, sp_comm)
    emb = params["embed"].astype(cfg.dtype)
    # round-4 lever: cfg.ce_chunk scans vocab chunks through the online
    # lse so no (B, S, V) f32 array ever reaches HBM; the unchunked
    # reference (ops/fused_ce.ce_reference) is this module's historical
    # loss body, bit-for-bit
    from ..ops.fused_ce import token_ce

    return token_ce(x, emb, targets, cfg.ce_chunk)


# Parameters replicated over tp (everything else is tp-sharded).
_TP_REPLICATED = frozenset({"embed", "lnf", "ln1", "ln2"})


def _param_specs(tp_ax):
    from jax.sharding import PartitionSpec as P

    return {
        "embed": P(), "lnf": P(),
        "wqkv": P(None, None, None, tp_ax),
        "wo": P(None, tp_ax, None),
        "w1": P(None, None, tp_ax),
        "w2": P(None, tp_ax, None),
        "ln1": P(), "ln2": P(),
    }


def _sync_grads(grads, loss, dp_comm, tp_comm, sp_comm, dp, tp, sp):
    """The gradient synchronization semantics (verified in tests against
    a single-device run) — ONE home for both train-step builders:
      - tp-sharded params (wqkv/wo/w1/w2): their grads are tp-local
        already; average over dp only.
      - replicated-over-tp params (embed/ln): with the f/g wrappers each
        tp rank holds the full tp-summed gradient; a tp-mean makes the
        update bitwise-identical across tp ranks.
      - sp: every rank sees only its sequence block, so EVERY param's
        grad is partial over sp — sp-mean them all (the global loss is a
        mean over tokens; dp-mean x sp-mean composes to the global mean).
    All syncs go through the framework's allreduce."""
    synced = {}
    for name, g in grads.items():
        g = dp_comm.allreduce(g, zops.SUM) / dp
        if sp_comm is not None:
            g = sp_comm.allreduce(g, zops.SUM) / sp
        if name in _TP_REPLICATED and tp_comm is not None:
            g = tp_comm.allreduce(g, zops.SUM) / tp
        synced[name] = g
    loss = dp_comm.allreduce(loss, zops.SUM) / dp
    if sp_comm is not None:
        loss = sp_comm.allreduce(loss, zops.SUM) / sp
    if tp_comm is not None:
        loss = tp_comm.allreduce(loss, zops.SUM) / tp
    return synced, loss


def make_train_step(cfg: Config, mesh, dp_comm, tp_comm, sp_comm=None,
                    lr: float = 1e-2):
    """Build the jitted SPMD training step over dp x tp (x sp): one
    fused shard_map program — grads, sync (see :func:`_sync_grads`),
    and the SGD update in a single jit (the structure bench.py's
    HLO-parity comparison against plain JAX relies on)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    dp = mesh.shape[dp_comm.axis]
    tp = mesh.shape[tp_comm.axis] if tp_comm is not None else 1
    sp = mesh.shape[sp_comm.axis] if sp_comm is not None else 1
    param_specs = _param_specs(tp_comm.axis if tp_comm is not None else None)

    def spmd_step(params, tokens, targets):
        def local_loss(p):
            return loss_fn(p, tokens, targets, cfg, tp_comm, sp_comm)

        loss, grads = jax.value_and_grad(local_loss)(params)
        synced, loss = _sync_grads(
            grads, loss, dp_comm, tp_comm, sp_comm, dp, tp, sp
        )
        new_params = jax.tree.map(
            lambda p, g: (p - lr * g).astype(p.dtype), params, synced
        )
        return new_params, loss

    sp_ax = sp_comm.axis if sp_comm is not None else None
    data_spec = P(dp_comm.axis, sp_ax)
    step = jax.jit(
        jax.shard_map(
            spmd_step,
            mesh=mesh,
            in_specs=(param_specs, data_spec, data_spec),
            out_specs=(param_specs, P()),
            check_vma=False,
        )
    )
    return step, param_specs


def make_train_step_optax(cfg: Config, mesh, dp_comm, tp_comm,
                          sp_comm=None, optimizer=None, dcn_proc=None,
                          dcn_weight: float | None = None,
                          dcn_sharded: bool = False):
    """Stateful-optimizer training step: the framework's SPMD grad
    computation composed with any optax GradientTransformation.

    The gradient pass is the same shard_map program ``make_train_step``
    builds (framework allreduces on the dp/tp/sp axes); the optimizer
    update runs in a second jit whose optimizer-state shardings follow
    from the gradient/parameter shardings by XLA propagation — Adam
    moments land sharded exactly like their parameters with no
    hand-written state specs.

    ``dcn_proc``: a host-plane endpoint (TcpProc from ``host_init``)
    makes this a MULTI-SLICE step — the in-mesh-synced gradients are
    additionally allreduce-meaned across launcher slices
    (:func:`zhpe_ompi_tpu.parallel.hybrid.dcn_grad_sync`) between the
    two jits, the ICI-inside/DCN-outside composition.  The loss scalar
    rides the same bucketed sync (no extra per-step DCN round trip).
    ``dcn_weight``: this slice's fraction of the global batch when
    slices carry unequal batches (default: equal, 1/size).

    Returns ``(init_opt_state, step, param_specs)``: ``step(params,
    opt_state, tokens, targets) -> (params, opt_state, loss)``."""
    import optax

    if optimizer is None:
        optimizer = optax.adam(1e-3)

    from jax.sharding import PartitionSpec as P

    dp = mesh.shape[dp_comm.axis]
    tp = mesh.shape[tp_comm.axis] if tp_comm is not None else 1
    sp = mesh.shape[sp_comm.axis] if sp_comm is not None else 1
    param_specs = _param_specs(tp_comm.axis if tp_comm is not None else None)

    def spmd_grads(params, tokens, targets):
        def local_loss(p):
            return loss_fn(p, tokens, targets, cfg, tp_comm, sp_comm)

        loss, grads = jax.value_and_grad(local_loss)(params)
        return _sync_grads(
            grads, loss, dp_comm, tp_comm, sp_comm, dp, tp, sp
        )

    sp_ax = sp_comm.axis if sp_comm is not None else None
    data_spec = P(dp_comm.axis, sp_ax)
    grad_step = jax.jit(
        jax.shard_map(
            spmd_grads, mesh=mesh,
            in_specs=(param_specs, data_spec, data_spec),
            out_specs=(param_specs, P()),
            check_vma=False,
        )
    )

    init_opt_state = jax.jit(optimizer.init)

    def _apply(params, opt_state, grads):
        updates, opt_state = optimizer.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        # preserve storage dtype (apply_updates upcasts mixed dtypes)
        new_params = jax.tree.map(
            lambda new, old: new.astype(old.dtype), new_params, params
        )
        return new_params, opt_state

    # donate the old params + optimizer state: callers thread both
    # through step() and never reuse them, so the update is in-place at
    # the XLA level instead of holding 2x params + both moment trees
    apply = jax.jit(_apply, donate_argnums=(0, 1))

    from jax.sharding import NamedSharding

    grad_shardings = {
        k: NamedSharding(mesh, spec) for k, spec in param_specs.items()
    }

    def step(params, opt_state, tokens, targets):
        grads, loss = grad_step(params, tokens, targets)
        if dcn_proc is not None and dcn_proc.size > 1:
            from ..parallel import hybrid

            if dcn_sharded:
                # scaling path (round 4): each distinct device shard
                # syncs with its same-index peer across slices — host
                # memory and DCN traffic are O(unique shard bytes),
                # shardings preserved with no reshard (identical meshes
                # on every slice, fingerprint-enforced).  The loss
                # scalar rides the same call's host-leaf bucket — no
                # extra DCN round trip.
                bundle = hybrid.dcn_grad_sync_sharded(
                    dcn_proc,
                    {"grads": grads,
                     "loss": np.asarray(loss, np.float32)},
                    weight=dcn_weight)
                grads = bundle["grads"]
                loss = jnp.asarray(bundle["loss"])
            else:
                # small-slice default: pack_tree gathers each gradient
                # fully to numpy and one bucketed allreduce syncs it —
                # fewer, larger messages, at the cost of full-tensor
                # host replication per step
                bundle = hybrid.dcn_grad_sync(
                    dcn_proc,
                    {"grads": grads,
                     "loss": np.asarray(loss, np.float32)},
                    weight=dcn_weight,
                )
                # Re-shard the synced host gradients explicitly before
                # the jitted apply: feeding unsharded numpy would force
                # XLA to re-infer layout from donated params and
                # materialize a replicated copy on every device first.
                grads = {
                    k: jax.device_put(v, grad_shardings[k])
                    for k, v in bundle["grads"].items()
                }
                # keep the return contract uniform across modes: loss
                # is always a jax scalar
                loss = jnp.asarray(bundle["loss"])
        new_params, opt_state = apply(params, opt_state, grads)
        return new_params, opt_state, loss

    return init_opt_state, step, param_specs
