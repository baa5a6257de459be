"""Flagship model: a transformer LM parallelized *through the framework*.

This plays the role the reference's example programs play
(``examples/ring_c.c`` etc.): a real application whose every communication
goes through the framework's communicators — the way a Megatron-style trainer
drives MPI/NCCL:

- **tp** (tensor parallel): attention heads and MLP hidden are sharded over
  the 'tp' mesh axis; partial sums after the output/down projections are
  combined with ``tp_comm.allreduce`` (the MPI_Allreduce hot path of
  BASELINE.md, executed as XLA psum on ICI, or with two ranks on a TPU
  as one asynchronous swap that the two-chunk layer loop of
  ``forward_hidden`` overlaps with the other chunk).
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
    # MFU levers (a profile of the step's cap named both):
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


# Independent batch chunks the tensor-parallel layer loop carries: two is
# the least count that gives each chunk's all-reduces the other chunk's
# matmuls to hide behind; more would shrink the matmuls and add per-call
# latency.  Fixed, not configurable.
TP_CHUNKS = 2


def _tp_chunks(batch: int, tp_comm, sp_comm) -> int:
    """Chunks the layer loop carries (see :func:`forward_hidden`)."""
    if tp_comm is None or sp_comm is not None or batch % TP_CHUNKS \
            or getattr(tp_comm, "uniform_size", None) != 2:
        return 1
    return TP_CHUNKS


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

    With a two-rank `tp_comm` and no `sp_comm`, an even local batch runs
    the layer loop as two independent half-batch chunks
    (:data:`TP_CHUNKS`), split after the embedding and joined before the
    final layernorm; each chunk runs the whole block.  Every tp all-reduce
    then has the other chunk's matmuls and attention beside it with no data
    dependency.  On a TPU the communicator issues a two-rank allreduce as
    one asynchronous swap (:func:`zhpe_ompi_tpu.coll.tuned.decide`), so the
    scheduler overlaps the two: chunk a's all-reduce with chunk b's
    matmuls, in the forward and, by autodiff, in the backward.  Per row the
    math is the block's; weight gradients sum the chunks' partial products.
    Without tp, with a wider tp (no swap to overlap), with sp (its ring
    would run twice) or with an odd local batch the loop runs one chunk,
    as before.
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
    n_chunks = _tp_chunks(B, tp_comm, sp_comm)

    def block(x, layer):
        wqkv, wo, w1, w2, g1, g2 = layer
        b = x.shape[0]
        # the scopes name each op's HLO metadata (forward, backward and
        # remat alike) so a profile splits the step by layer part; they
        # end before the tp all-reduce, which the communicator names
        with jax.named_scope("zmpi.attn"):
            h = _ln(x, g1, cfg.fused_ln)
            if tp_comm is not None:
                h = f_identity(tp_comm, h)
            qkv = jnp.einsum("bsd,dce->bsce", h, wqkv.astype(dtype))
            q = qkv[:, :, 0].reshape(b, S, n_heads_local, hd)
            k = qkv[:, :, 1].reshape(b, S, n_heads_local, hd)
            v = qkv[:, :, 2].reshape(b, S, n_heads_local, hd)
            if sp_comm is not None:
                if cfg.zigzag_sp:
                    from .ring_attention import ring_attention_zigzag

                    o = ring_attention_zigzag(sp_comm, q, k, v)
                else:
                    o = ring_attention(sp_comm, q, k, v, causal=True)
                o = o.reshape(b, S, -1)
            elif use_flash:
                from ..ops.flash_attention import flash_attention

                o = flash_attention(
                    q, k, v, causal=True, force=cfg.flash is True
                ).reshape(b, S, -1)
            else:
                o = _attn(q, k, v).reshape(b, S, -1)
            o = jnp.einsum("bse,ed->bsd", o, wo.astype(dtype))
        if tp_comm is not None:
            o = g_allreduce(tp_comm, o)
        x = x + o
        with jax.named_scope("zmpi.mlp"):
            h = _ln(x, g2, cfg.fused_ln)
            if tp_comm is not None:
                h = f_identity(tp_comm, h)
            u = jnp.einsum("bsd,df->bsf", h, w1.astype(dtype))
            u = jax.nn.gelu(u)
            d = jnp.einsum("bsf,fd->bsd", u, w2.astype(dtype))
        if tp_comm is not None:
            d = g_allreduce(tp_comm, d)
        return x + d

    layers = (
        params["wqkv"], params["wo"], params["w1"], params["w2"],
        params["ln1"], params["ln2"],
    )
    if n_chunks > 1:
        def step_fn(chunks, layer):
            return tuple(block(c, layer) for c in chunks), None

        carry = tuple(jnp.split(x, n_chunks))
    else:
        def step_fn(x, layer):
            return block(x, layer), None

        carry = x
    if cfg.remat:
        step_fn = jax.checkpoint(
            step_fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    carry, _ = lax.scan(step_fn, carry, layers)
    x = jnp.concatenate(carry) if n_chunks > 1 else carry
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

    with jax.named_scope("zmpi.loss"):
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


def _sync_grads(grads, loss, dp_comm, tp_comm, sp_comm, dp, tp, sp,
                dp_sharded=frozenset()):
    """The gradient synchronization semantics of
    :func:`make_train_step_optax` (verified in tests against a
    single-device run):
      - tp-sharded params (wqkv/wo/w1/w2): their grads are tp-local
        already; average over dp only.
      - dp-sharded params (``dp_sharded``: experts spread over the dp
        ranks): their grads are already whole, every rank's tokens having
        reached them through the all-to-all's transpose; no dp all-reduce,
        only the 1/dp of the dp mean.
      - replicated-over-tp params (embed/ln): with the f/g wrappers each
        tp rank holds the full tp-summed gradient; a tp-mean makes the
        update bitwise-identical across tp ranks.
      - sp: every rank sees only its sequence block, so EVERY param's
        grad is partial over sp — sp-mean them all (the global loss is a
        mean over tokens; dp-mean x sp-mean composes to the global mean).
    All syncs go through the framework's allreduce."""
    synced = {}
    for name, g in grads.items():
        if name in dp_sharded:
            g = g / dp
        else:
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


def _on_axis(spec, axis) -> bool:
    """Whether a PartitionSpec shards any dimension over ``axis``."""
    return any(a == axis or (isinstance(a, tuple) and axis in a)
               for a in spec)


def _init_like_params(optimizer, mesh, param_specs):
    """``optimizer.init`` jitted with each parameter-shaped part of the
    state placed as its parameter, the rest replicated.  (The moments are
    zeros tied to no input, so a plain ``jax.jit(optimizer.init)`` would
    leave them whole on one chip until the first update.)"""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    leaves = {k: NamedSharding(mesh, s) for k, s in param_specs.items()}
    jitted = []  # built at the first call, when the state's form is known

    def init(params):
        if not jitted:
            out = optax.tree_map_params(
                optimizer, lambda _, s: s,
                jax.eval_shape(optimizer.init, params), leaves,
                transform_non_params=lambda _: NamedSharding(mesh, P()))
            jitted.append(jax.jit(optimizer.init, out_shardings=out))
        return jitted[0](params)

    return init


def make_train_step_optax(cfg: Config, mesh, dp_comm, tp_comm,
                          sp_comm=None, optimizer=None, dcn_proc=None,
                          loss=None, param_specs=None, on_aux=None):
    """The training step: the framework's SPMD grad computation
    composed with any optax GradientTransformation (``optax.sgd(lr)``
    for plain SGD).

    The gradient pass is one shard_map program (the model's loss and
    its gradient, synced by the framework's allreduces on the dp/tp/sp
    axes, :func:`_sync_grads`); the optimizer update runs in a second
    jit, which donates the old parameters and state.  ``init_opt_state``
    places each parameter-shaped part of the state (Adam's moments) as
    its parameter (:func:`_init_like_params`), and the update keeps it
    there.

    ``dcn_proc``: a host-plane endpoint (TcpProc from ``host_init``)
    makes this a MULTI-SLICE step — the in-mesh-synced gradients are
    additionally allreduce-meaned across launcher slices
    (:func:`zhpe_ompi_tpu.parallel.hybrid.dcn_grad_sync`) between the
    two jits, the ICI-inside/DCN-outside composition.  The loss scalar
    rides the same bucketed sync (no extra per-step DCN round trip).

    ``loss``, ``param_specs``: another model's local loss
    ``loss(params, tokens, targets)`` and its parameter specs (default:
    this module's GPT-2 block, ``cfg``'s).  Leaves whose spec shards them
    over the dp axis get no dp all-reduce (see :func:`_sync_grads`).
    ``on_aux``: the loss returns ``(loss, aux)``; each step hands
    ``on_aux`` the aux pytree, each leaf one value per dp rank, still on
    the device.

    Returns ``(init_opt_state, step, param_specs)``: ``step(params,
    opt_state, tokens, targets) -> (params, opt_state, loss)``."""
    import optax

    if optimizer is None:
        optimizer = optax.adam(1e-3)

    from jax.sharding import PartitionSpec as P

    dp = mesh.shape[dp_comm.axis]
    tp = mesh.shape[tp_comm.axis] if tp_comm is not None else 1
    sp = mesh.shape[sp_comm.axis] if sp_comm is not None else 1
    if param_specs is None:
        param_specs = _param_specs(
            tp_comm.axis if tp_comm is not None else None)
    dp_sharded = frozenset(k for k, s in param_specs.items()
                           if _on_axis(s, dp_comm.axis))

    def spmd_grads(params, tokens, targets):
        def local_loss(p):
            if loss is not None:
                return loss(p, tokens, targets)
            return loss_fn(p, tokens, targets, cfg, tp_comm, sp_comm)

        if on_aux is None:
            value, grads = jax.value_and_grad(local_loss)(params)
            return _sync_grads(grads, value, dp_comm, tp_comm, sp_comm,
                               dp, tp, sp, dp_sharded)
        (value, aux), grads = jax.value_and_grad(
            local_loss, has_aux=True)(params)
        synced, value = _sync_grads(grads, value, dp_comm, tp_comm,
                                    sp_comm, dp, tp, sp, dp_sharded)
        return synced, value, jax.tree.map(lambda a: a.reshape(1), aux)

    sp_ax = sp_comm.axis if sp_comm is not None else None
    data_spec = P(dp_comm.axis, sp_ax)
    out_specs = (param_specs, P())
    if on_aux is not None:
        out_specs += (P(dp_comm.axis),)
    grad_step = jax.jit(
        jax.shard_map(
            spmd_grads, mesh=mesh,
            in_specs=(param_specs, data_spec, data_spec),
            out_specs=out_specs,
            check_vma=False,
        )
    )

    init_opt_state = _init_like_params(optimizer, mesh, param_specs)

    def _apply(params, opt_state, grads):
        with jax.named_scope("zmpi.optimizer"):
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

    # host spans on the profiler's clock around the two dispatches, so an
    # idle gap on the device can be told to belong to one or the other
    def step(params, opt_state, tokens, targets):
        with jax.profiler.TraceAnnotation("zmpi.train.grad"):
            if on_aux is None:
                grads, loss = grad_step(params, tokens, targets)
            else:
                grads, loss, aux = grad_step(params, tokens, targets)
                on_aux(aux)
        if dcn_proc is not None and dcn_proc.size > 1:
            from ..parallel import hybrid

            # pack_tree gathers each gradient fully to numpy and one
            # bucketed allreduce syncs it: fewer, larger messages, at
            # the cost of full-tensor host replication per step
            bundle = hybrid.dcn_grad_sync(
                dcn_proc,
                {"grads": grads,
                 "loss": np.asarray(loss, np.float32)},
            )
            # Re-shard the synced host gradients explicitly before the
            # jitted apply: feeding unsharded numpy would force XLA to
            # re-infer layout from donated params and materialize a
            # replicated copy on every device first.
            grads = {
                k: jax.device_put(v, grad_shardings[k])
                for k, v in bundle["grads"].items()
            }
            # keep the return contract uniform: loss is always a jax
            # scalar
            loss = jnp.asarray(bundle["loss"])
        with jax.profiler.TraceAnnotation("zmpi.train.apply"):
            new_params, opt_state = apply(params, opt_state, grads)
        return new_params, opt_state, loss

    return init_opt_state, step, param_specs
