"""Mixture-of-Experts layer — expert parallelism over framework alltoall.

Expert parallelism (ep) is the MPI_Alltoall workload par excellence: tokens
are routed to experts living on other devices, processed, and routed back.
Both transposes go through the framework's ``comm.alltoall`` (XLA
``all_to_all`` on ICI via the coll table, so `--mca coll` selection and
monitoring interposition apply to the model's hot path).

One routing-and-dispatch function, :func:`expert_layer`, serves every
layer here: top-k over the router's full width, each rank holding
``held`` consecutive experts (rank r holds ``[r*held, (r+1)*held)``), a
static capacity per (source rank, destination rank) pair, dispatch and
combine through the communicator's ``alltoall``.  Picks of experts that no
rank of the communicator holds (a deployment wider than the mesh) add
nothing; assignments past a destination's capacity are dropped in a
stated order (token order, then pick rank) and add nothing either; the
residual carries the token.  Shapes are static throughout.

:func:`moe_ffn` (top-1 switch routing, one GELU expert per device) and
:func:`moe_host_ffn` (the same over a host endpoint) are that function
with ``k`` 1.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax


def init_moe_params(key, d_model: int, d_ff: int, n_experts: int):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "router": jax.random.normal(k1, (d_model, n_experts), jnp.float32)
        * d_model**-0.5,
        # per-device expert slice (shard over 'ep' axis at dim 0)
        "w_in": jax.random.normal(k2, (n_experts, d_model, d_ff), jnp.float32)
        * d_model**-0.5,
        "w_out": jax.random.normal(k3, (n_experts, d_ff, d_model), jnp.float32)
        * d_ff**-0.5,
    }


class Routing(NamedTuple):
    expert: jax.Array   # (T, k) global expert ids, best first
    gate: jax.Array     # (T, k) f32 combine weights
    slot: jax.Array     # (T, k) row of the (ranks * cap) dispatch buffer,
                        # ranks * cap where the pick is not sent
    dropped: jax.Array  # () assignments to held experts past capacity


def capacity(tokens: int, k: int, held: int, n_experts: int,
             factor: float) -> int:
    """Rows one source rank may send one destination rank: ``factor``
    times the even share, ``tokens * k * held / n_experts``."""
    return max(1, int(factor * tokens * k * held / n_experts))


def route(logits, k: int, ranks: int, held: int, cap: int, *,
          score: str = "softmax", norm: bool = False,
          scale: float = 1.0) -> Routing:
    """Top-``k`` routing over all ``logits.shape[-1]`` experts.

    Experts are chosen on ``score(logits)`` (DeepSeek's ``noaux_tc`` with
    its correction bias at zero, which changes no pick); gates are the
    scores of the chosen, normalised over the ``k`` when ``norm``, times
    ``scale``.
    Expert ``e`` lives on rank ``e // held``; a pick of an expert past the
    last of ``ranks`` ranks is not sent.  Each destination takes the
    first ``cap`` of a source's assignments, in token order and then pick
    rank."""
    logits = logits.astype(jnp.float32)
    if score == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    elif score == "sigmoid":
        s = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown router score {score!r}")
    _, expert = lax.top_k(s, k)
    gate = jnp.take_along_axis(s, expert, axis=-1)
    if norm:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    gate = gate * scale
    dest = expert // held
    present = dest < ranks
    onehot = jax.nn.one_hot(jnp.where(present, dest, ranks).reshape(-1),
                            ranks, dtype=jnp.int32)
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
    pos = pos.reshape(expert.shape)
    keep = present & (pos < cap)
    slot = jnp.where(keep, dest * cap + pos, ranks * cap)
    dropped = jnp.sum(present & ~keep, dtype=jnp.int32)
    return Routing(expert, gate, slot.astype(jnp.int32), dropped)


def expert_layer(x, logits, k: int, experts, comm, *, held: int = 1,
                 capacity_factor: float = 1.25, score: str = "softmax",
                 norm: bool = False, scale: float = 1.0):
    """Route this rank's tokens ``x`` (T, D) by ``logits`` (T, E), send
    each kept assignment to the rank holding its expert through
    ``comm.alltoall``, run ``experts(rows, local_ids)`` there over the
    rows received (``local_ids`` in ``[0, held)``, ``held`` for an empty
    row; it returns one row per row), send the results back, and sum them
    weighted by their gates.  ``comm`` is anything with ``size`` and a
    row-blocked ``alltoall``; with one rank, or ``comm`` None, nothing is
    exchanged.

    Returns ``(y, stats)``: ``y`` (T, D) in ``x``'s dtype, and ``stats``
    with ``keep`` (T, k), ``dropped``, the rows the held experts received
    (``rows``) and the most rows any one of them received (``max_rows``;
    with one expert held, both are the buffer's rows, since no exchange
    names the empty ones)."""
    T, D = x.shape
    n = comm.size if comm is not None else 1
    cap = capacity(T, k, held, logits.shape[-1], capacity_factor)
    with jax.named_scope("zmpi.moe.route"):
        r = route(logits, k, n, held, cap, score=score, norm=norm,
                  scale=scale)
        slots = r.slot.reshape(-1)
        slot_tok = jnp.full((n * cap,), T, jnp.int32).at[slots].set(
            jnp.repeat(jnp.arange(T, dtype=jnp.int32), k), mode="drop")
        # one zero row past the tokens fills every empty slot
        buf = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])[slot_tok]

    def exchange(a):
        return a if n == 1 else comm.alltoall(a)

    # ep transpose #1: each destination rank's block of the buffer to it
    rows = exchange(buf)
    if held > 1:
        with jax.named_scope("zmpi.moe.route"):
            slot_eid = jnp.full((n * cap,), held, jnp.int32).at[slots].set(
                (r.expert % held).reshape(-1).astype(jnp.int32),
                mode="drop")
        eid = exchange(slot_eid)
    else:  # one expert: every row is its, empty rows included
        eid = jnp.zeros((n * cap,), jnp.int32)
    with jax.named_scope("zmpi.moe.experts"):
        out = experts(rows, eid).astype(x.dtype)
        per_expert = jnp.sum(jax.nn.one_hot(eid, held, dtype=jnp.int32), 0)
        got, max_rows = jnp.sum(per_expert), jnp.max(per_expert)
    # ep transpose #2: results back to their source ranks
    back = exchange(out)
    with jax.named_scope("zmpi.moe.route"):
        back = jnp.concatenate([back, jnp.zeros((1, D), back.dtype)])
        y = jnp.einsum("tkd,tk->td", back[r.slot], r.gate.astype(x.dtype))
    keep = r.slot < n * cap
    return y, {"keep": keep, "dropped": r.dropped, "rows": got,
               "max_rows": max_rows}


def grouped_swiglu(rows, eid, w_gate, w_up, w_down):
    """The held experts' SwiGLU over the rows received, ``eid`` naming
    each row's expert (``held`` for an empty row): rows sorted by expert,
    three grouped matmuls (``lax.ragged_dot``), unsorted.  The empty rows
    ride in the last expert's group and come out zero, so the matmuls
    cover the whole buffer whatever the routing: a step's work is the
    same for a balanced router and a collapsed one.  Operands and results
    in ``rows``' dtype, as the model's other matmuls."""
    held = w_gate.shape[0]
    dt = rows.dtype
    order = jnp.argsort(eid, stable=True)  # empty rows last
    sizes = jnp.sum(jax.nn.one_hot(jnp.minimum(eid, held - 1), held,
                                   dtype=jnp.int32), axis=0)
    xs = rows[order]

    def gmm(a, w):
        return lax.ragged_dot(a, w.astype(dt), sizes)

    h = jax.nn.silu(gmm(xs, w_gate)) * gmm(xs, w_up)
    o = gmm(h, w_down)
    o = jnp.where((eid[order] < held)[:, None], o, 0)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    return o[inv]


def _gelu_expert(params):
    """The switch layer's one GELU expert per device, in f32."""
    w_in, w_out = params["w_in"][0], params["w_out"][0]

    def run(rows, _eid):
        return jax.nn.gelu(rows.astype(jnp.float32) @ w_in) @ w_out

    return run


def moe_ffn(comm, params, x, capacity_factor: float = 1.25):
    """Expert-parallel FFN: x is (T_local, D) tokens on this device; the
    device holds expert weights w_in/w_out of shape (1, D, F)/(1, F, D)
    (its shard of the expert dim).  Returns ``(y, keep)``, (T_local, D)
    and (T_local,).
    """
    logits = x.astype(jnp.float32) @ params["router"]  # (T, E)
    y, st = expert_layer(x, logits, 1, _gelu_expert(params), comm,
                         capacity_factor=capacity_factor)
    return y, st["keep"][:, 0]


class _HostExchange:
    """A host endpoint's list-of-blocks ``alltoall`` as the row-blocked
    one :func:`expert_layer` calls."""

    def __init__(self, ep):
        self.ep, self.size = ep, ep.size

    def alltoall(self, a):
        blocks = np.asarray(a).reshape((self.size, -1) + a.shape[1:])
        got = self.ep.alltoall([np.array(b) for b in blocks])
        return jnp.concatenate([jnp.asarray(b) for b in got])


def moe_host_ffn(ep, params, x, capacity_factor: float = 1.25):
    """:func:`moe_ffn` on the HOST plane: the same top-1 routing and
    static-capacity math, but both ep transposes ride the host
    endpoint's ``alltoall`` — which the coll layer routes through the
    hierarchical han schedule when the topology qualifies (intra
    gather → one aggregated wire message per host pair → intra
    scatter), the serving plane's expert-dispatch path.  ``ep`` is any
    host endpoint carrying ``HostCollectives`` (a RankContext, a
    TcpProc, a shrunken live window); one expert per rank.  Returns
    ``(y, keep)`` exactly like :func:`moe_ffn`."""
    return moe_ffn(_HostExchange(ep), params, x, capacity_factor)


def moe_reference_dense(
    params, x_all, n_experts: int, capacity: int, block_tokens: int | None = None
):
    """Single-device reference for tests: same routing/capacity semantics as
    :func:`moe_ffn`, no communication.

    `capacity` is per (source block, expert), matching moe_ffn where each
    device owns `cap` dispatch slots per expert; `block_tokens` is the
    per-device token count T_local (default: all of x_all is one block).
    Dropped tokens produce zero output, as in moe_ffn.
    """
    T, D = x_all.shape
    bt = block_tokens or T
    logits = x_all.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
    # first-come-first-served capacity per (block, expert), as in moe_ffn
    onehot = jax.nn.one_hot(expert, n_experts, dtype=jnp.int32)
    blocks = onehot.reshape(T // bt, bt, n_experts)
    pos = jnp.sum((jnp.cumsum(blocks, axis=1) - 1) * blocks, axis=-1)
    keep = (pos < capacity).reshape(T)
    out = jnp.zeros((T, D), jnp.float32)
    for e in range(n_experts):
        w_in = params["w_in"][e]
        w_out = params["w_out"][e]
        h = jax.nn.gelu(x_all.astype(jnp.float32) @ w_in)
        y = h @ w_out
        out = jnp.where((expert == e)[:, None], y, out)
    return out * gate[:, None] * keep[:, None]
