"""Collective algorithm library — the heart of the framework.

TPU-native re-design of ``ompi/mca/coll/base`` (SURVEY.md §2.4).  Where the
reference implements each algorithm as a loop of blocking send/recv pairs
driven by the progress engine (e.g. recursive doubling at
``coll_base_allreduce.c:130``, ring at ``:341``, Rabenseifner at ``:970``;
binomial bcast at ``coll_base_bcast.c:329``; pairwise alltoall at
``coll_base_alltoall.c:132``; Bruck allgather at ``coll_base_allgather.c:85``),
here every algorithm is a *static communication schedule* traced once under
``jit``: rounds become ``lax.ppermute`` ops over the ICI mesh, per-rank
divergence becomes ``jnp.where`` masks on the traced rank, and XLA overlaps /
pipelines the rounds.  There is no matching, no fragmentation, no progress
loop — the compiler owns scheduling.

Conventions:

- all functions take ``(comm, x, ...)`` and must be called inside
  ``shard_map`` over the comm's mesh axis;
- ``x`` may be a pytree for the mask-based algorithms (MINLOC/MAXLOC pairs are
  (value, index) tuples); chunked algorithms (ring, Bruck, pairwise) require a
  single dense array;
- patterns are comm-relative and instantiated per sub-group by
  :func:`zhpe_ompi_tpu.pt2pt.spmd.global_pairs` — one XLA op carries every
  sub-communicator of a split;
- mask-based algorithms require a uniform partition (same size per group);
  the components route non-uniform comms to the XLA-native paths.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ..core import errors
from ..pt2pt import spmd


def _where(mask, a, b):
    """Pytree-aware jnp.where with a scalar traced mask."""
    return jax.tree.map(lambda u, v: jnp.where(mask, u, v), a, b)


def _require_uniform(comm) -> int:
    n = comm.uniform_size
    if n is None:
        raise errors.CommError(
            "algorithmic collectives require a uniform partition; "
            "use the xla component for non-uniform splits"
        )
    return n


def _pow2_floor(n: int) -> int:
    return 1 << (n.bit_length() - 1)


# ---------------------------------------------------------------------------
# Allreduce (cf. coll_base_allreduce.c)
# ---------------------------------------------------------------------------


def allreduce_recursive_doubling(comm, x, op):
    """Recursive doubling (reference: coll_base_allreduce.c:130): log2(p)
    exchange rounds; non-power-of-two handled by folding the tail into the
    leading block first (the reference's pow2 adjust at :175-185)."""
    n = _require_uniform(comm)
    if n == 1:
        return x
    rank = comm.rank()
    p2 = _pow2_floor(n)
    extra = n - p2
    if extra:
        recv = spmd.ppermute(comm, x, [(p2 + i, i) for i in range(extra)])
        x = _where(rank < extra, op(recv, x), x)
    k = 1
    while k < p2:
        recv = spmd.ppermute(
            comm, x, [(i, i ^ k) for i in range(p2)]
        )
        # every rank is below p2 when no tail was folded in
        x = _where(rank < p2, op(recv, x), x) if extra else op(recv, x)
        k <<= 1
    if extra:
        recv = spmd.ppermute(comm, x, [(i, p2 + i) for i in range(extra)])
        x = _where(rank >= p2, recv, x)
    return x


def _chunked(x, n):
    """Pad-and-view a dense array as (n, chunk) plus restore info."""
    flat = x.reshape(-1)
    length = flat.shape[0]
    chunk = -(-length // n)  # ceil
    pad = n * chunk - length
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(n, chunk), length


def allreduce_ring(comm, x, op):
    """Ring allreduce: reduce-scatter ring + allgather ring (reference:
    coll_base_allreduce.c:341).  Bandwidth-optimal — 2(p-1)/p of the data
    crosses each link; the shape XLA itself uses for large psums on ICI."""
    n = _require_uniform(comm)
    if n == 1:
        return x
    if not isinstance(x, jax.Array) and not hasattr(x, "shape"):
        raise errors.ArgError("ring allreduce requires a dense array")
    rank = comm.rank()
    buf, length = _chunked(x, n)

    def rs_round(k, b):
        send_idx = (rank - k) % n
        recv_idx = (rank - k - 1) % n
        sent = spmd.ppermute(
            comm, jnp.take(b, send_idx, axis=0),
            lambda m: [(i, (i + 1) % m) for i in range(m)],
        )
        return b.at[recv_idx].set(op(sent, jnp.take(b, recv_idx, axis=0)))

    buf = lax.fori_loop(0, n - 1, rs_round, buf)

    def ag_round(k, b):
        send_idx = (rank + 1 - k) % n
        recv_idx = (rank - k) % n
        sent = spmd.ppermute(
            comm, jnp.take(b, send_idx, axis=0),
            lambda m: [(i, (i + 1) % m) for i in range(m)],
        )
        return b.at[recv_idx].set(sent)

    buf = lax.fori_loop(0, n - 1, ag_round, buf)
    return buf.reshape(-1)[:length].reshape(x.shape)


def allreduce_rabenseifner(comm, x, op):
    """Rabenseifner: recursive-halving reduce-scatter + recursive-doubling
    allgather (reference: coll_base_allreduce.c:970).  Power-of-two ranks;
    falls back to ring otherwise — the same guard the reference's decision
    logic applies."""
    n = _require_uniform(comm)
    if n & (n - 1):
        return allreduce_ring(comm, x, op)
    if n == 1:
        return x
    rank = comm.rank()
    buf, length = _chunked(x, n)
    chunk = buf.shape[1]

    # reduce-scatter by recursive halving; rank ends owning chunk `rank`
    lo = jnp.zeros((), jnp.int32)
    bit = n >> 1
    while bit:
        pairs = [(i, i ^ bit) for i in range(n)]
        on_upper = (rank & bit) != 0
        send_lo = jnp.where(on_upper, lo, lo + bit)  # give away other half
        keep_lo = jnp.where(on_upper, lo + bit, lo)
        sent = spmd.ppermute(
            comm, lax.dynamic_slice(buf, (send_lo, 0), (bit, chunk)), pairs
        )
        kept = lax.dynamic_slice(buf, (keep_lo, 0), (bit, chunk))
        buf = lax.dynamic_update_slice(buf, op(sent, kept), (keep_lo, 0))
        lo = keep_lo
        bit >>= 1

    # allgather by recursive doubling
    w = 1
    while w < n:
        pairs = [(i, i ^ w) for i in range(n)]
        my_lo = rank & ~(w - 1)
        partner_lo = (rank ^ w) & ~(w - 1)
        sent = spmd.ppermute(
            comm, lax.dynamic_slice(buf, (my_lo, 0), (w, chunk)), pairs
        )
        buf = lax.dynamic_update_slice(buf, sent, (partner_lo, 0))
        w <<= 1
    return buf.reshape(-1)[:length].reshape(x.shape)


def allreduce_nonoverlapping(comm, x, op):
    """Reduce-then-bcast (reference: coll_base_allreduce.c:54): compose the
    two tree phases; on TPU the value is that the reduce tree and the bcast
    tree use disjoint link directions, so XLA overlaps the tail of one with
    the head of the other."""
    n = _require_uniform(comm)
    if n == 1:
        return x
    reduced = reduce_binomial(comm, x, op, root=0)
    return bcast_binomial(comm, reduced, root=0)


def allreduce_segmented_ring(comm, x, op, segments: int = 4):
    """Segmented ring (reference: coll_base_allreduce.c:618 with its
    ``segment_size`` knob): the message is cut into independent segments,
    each running its own ring.  The reference pipelines segments by hand to
    overlap wire and reduction; here the segment rings share no data
    dependencies, so XLA's scheduler interleaves their ppermutes across ICI
    for the same effect."""
    n = _require_uniform(comm)
    if n == 1:
        return x
    flat = x.reshape(-1)
    length = flat.shape[0]
    segments = max(1, min(segments, max(1, length // n)))
    seg = -(-length // segments)
    pad = segments * seg - length
    if pad:
        flat = jnp.pad(flat, (0, pad))
    parts = [
        allreduce_ring(comm, flat[i * seg : (i + 1) * seg], op)
        for i in range(segments)
    ]
    return jnp.concatenate(parts)[:length].reshape(x.shape)


def allreduce_linear(comm, x, op):
    """Basic linear (reference: coll_base_allreduce.c:881): gather everything
    everywhere, reduce locally in strict rank order — the only algorithm
    whose reduction order matches MPI's canonical order for non-commutative
    ops (rank 0's value ⊕ rank 1's ⊕ ...)."""
    n = _require_uniform(comm)
    if n == 1:
        return x
    # stack every rank's contribution: leaf shape (n, *leaf.shape)
    gathered = jax.tree.map(
        lambda a: allgather_ring(comm, jnp.asarray(a)[None]), x
    )

    def block(i):
        return jax.tree.map(lambda g: jnp.take(g, i, axis=0), gathered)

    acc = block(0)
    for i in range(1, n):
        acc = op(acc, block(i))
    return jax.tree.map(
        lambda o, xx: o.reshape(jnp.shape(xx)), acc, x
    )


# ---------------------------------------------------------------------------
# Bcast (cf. coll_base_bcast.c)
# ---------------------------------------------------------------------------


def bcast_binomial(comm, x, root=0):
    """Binomial tree (reference: coll_base_bcast.c:329): round k, virtual
    ranks < 2^k forward to vrank+2^k."""
    n = _require_uniform(comm)
    if n == 1:
        return x
    rank = comm.rank()
    vrank = (rank - root) % n
    k = 1
    while k < n:
        pairs = []
        for v in range(min(k, n - k)):
            pairs.append((( v + root) % n, (v + k + root) % n))
        recv = spmd.ppermute(comm, x, pairs)
        x = _where((vrank >= k) & (vrank < 2 * k), recv, x)
        k <<= 1
    return x


def bcast_chain(comm, x, root=0, segments: int = 4):
    """Chain/pipeline bcast (reference: coll_base_bcast.c:273,301): the
    message is cut into segments flowing down a rank chain; XLA overlaps the
    segment ppermutes.  `segments` plays the role of the reference's segsize
    MCA param."""
    n = _require_uniform(comm)
    if n == 1:
        return x
    rank = comm.rank()
    vrank = (rank - root) % n
    flat = x.reshape(-1)
    length = flat.shape[0]
    segments = max(1, min(segments, length))
    seg = -(-length // segments)
    pad = segments * seg - length
    if pad:
        flat = jnp.pad(flat, (0, pad))
    segs = flat.reshape(segments, seg)

    # chain pattern in vrank space: v -> v+1; segment s reaches chain
    # position v at step v-1+s, so at step t position v adopts segment
    # s = t - v + 1.  All rounds are static; XLA pipelines the hops.
    pairs = [((v + root) % n, (v + 1 + root) % n) for v in range(n - 1)]
    total_steps = (n - 1) + (segments - 1)

    def step(t, sg):
        sent = spmd.ppermute(comm, sg, pairs)
        s_idx = t - vrank + 1
        adopt = (vrank > 0) & (s_idx >= 0) & (s_idx < segments)
        mask = (jnp.arange(segments) == s_idx) & adopt
        return jnp.where(mask[:, None], sent, sg)

    segs = lax.fori_loop(0, total_steps, step, segs)
    return segs.reshape(-1)[:length].reshape(x.shape)


def bcast_linear(comm, x, root=0):
    """Basic linear bcast (reference: coll_base_bcast.c:624): root sends the
    whole message to each rank individually.  collective_permute patterns
    need unique sources, so the p-1 sends are p-1 independent permutes —
    sharing no data dependencies, XLA schedules them concurrently, which is
    the latency shape of the reference's p-1 non-blocking isends."""
    n = _require_uniform(comm)
    if n == 1:
        return x
    rank = comm.rank()
    out = x
    for i in range(n):
        if i == root:
            continue
        got = spmd.ppermute(comm, x, [(root, i)])
        out = _where(rank == i, got, out)
    return out


def bcast_binary(comm, x, root=0):
    """Binary-tree bcast (reference: coll_base_bcast.c:245): complete binary
    tree in virtual-rank space (vrank v forwards to 2v+1 and 2v+2), depth
    ceil(log2 p) rounds, two sends per interior node per round."""
    n = _require_uniform(comm)
    if n == 1:
        return x
    rank = comm.rank()
    vrank = (rank - root) % n

    # level boundaries: nodes [2^d - 1, 2^(d+1) - 1) are at depth d.
    # ppermute needs unique sources, so each level is two permutes — the
    # left-child arm and the right-child arm (independent; XLA overlaps).
    depth = 0
    x_have = x
    while (1 << depth) - 1 < n:
        lo, hi = (1 << depth) - 1, min((1 << (depth + 1)) - 1, n)
        any_pairs = False
        for side in (1, 2):
            pairs = [
                ((v + root) % n, (2 * v + side + root) % n)
                for v in range(lo, hi)
                if 2 * v + side < n
            ]
            if not pairs:
                continue
            any_pairs = True
            recv = spmd.ppermute(comm, x_have, pairs)
            is_child = ((vrank - side) % 2 == 0) & (
                (vrank - side) // 2 >= lo
            ) & ((vrank - side) // 2 < hi) & (vrank >= side)
            x_have = _where(is_child, recv, x_have)
        if not any_pairs:
            break
        depth += 1
    return x_have


def bcast_pipeline(comm, x, root=0, segments: int = 8):
    """Pipelined single-chain bcast (reference: coll_base_bcast.c:273 — the
    chain algorithm with fanout 1): segments stream down one chain, the
    classic latency-hiding shape for large messages.  Delegates to the
    segment-stepping machinery of :func:`bcast_chain`."""
    return bcast_chain(comm, x, root=root, segments=segments)


def bcast_split_binary(comm, x, root=0):
    """Split-binary bcast (reference: coll_base_bcast.c:357): the message is
    split in two halves broadcast down independent trees, followed by a
    pairing exchange.  TPU-native form: the two half-trees are two
    independent static schedules with mirrored round orderings (so they use
    opposing link directions), and XLA overlaps them; the final exchange is
    implicit because both trees span all ranks."""
    n = _require_uniform(comm)
    if n == 1:
        return x
    flat = x.reshape(-1)
    length = flat.shape[0]
    if length < 2:
        return bcast_binomial(comm, x, root)
    half = length // 2
    a = bcast_binomial(comm, flat[:half], root)
    b = bcast_binary(comm, flat[half:], root)
    return jnp.concatenate([a, b]).reshape(x.shape)


def bcast_knomial(comm, x, root=0, radix: int = 4):
    """K-nomial tree bcast (reference: coll_base_bcast.c:714): radix-k
    generalization of binomial — round d, every vrank that is a multiple of
    radix^(d+1) sends to vrank + j*radix^d for j in 1..radix-1.  Fewer
    rounds than binomial (log_k p) at k-1 sends per round; on ICI the k-1
    sends of a round ride one collective_permute."""
    n = _require_uniform(comm)
    if n == 1:
        return x
    if radix < 2:
        raise errors.ArgError(f"knomial radix must be >= 2, got {radix}")
    rank = comm.rank()
    vrank = (rank - root) % n
    # rounds from the top of the tree down: highest stride first
    strides = []
    s = 1
    while s < n:
        strides.append(s)
        s *= radix
    # one permute per child arm j (unique sources per permute); the k-1
    # arms of a round are independent and XLA overlaps them
    for stride in reversed(strides):
        for j in range(1, radix):
            pairs = [
                ((v + root) % n, (v + j * stride + root) % n)
                for v in range(0, n, stride * radix)
                if v + j * stride < n
            ]
            if not pairs:
                continue
            recv = spmd.ppermute(comm, x, pairs)
            is_child = vrank % (stride * radix) == j * stride
            x = _where(is_child, recv, x)
    return x


def bcast_scatter_allgather(comm, x, root=0):
    """Scatter + allgather bcast (reference: coll_base_bcast.c knomial/
    scatter_allgather): binomial scatter of chunks then ring allgather —
    bandwidth-optimal for large messages."""
    n = _require_uniform(comm)
    if n == 1:
        return x
    length = x.size
    # scatter: keep only own chunk (root's data is authoritative)
    own = scatter_linear(comm, x, root)
    gathered = allgather_ring(comm, own)
    return gathered.reshape(-1)[:length].reshape(x.shape)


# ---------------------------------------------------------------------------
# Reduce (cf. coll_base_reduce.c)
# ---------------------------------------------------------------------------


def reduce_binomial(comm, x, op, root=0):
    """Binomial-tree reduce (reference: coll_base_reduce.c:471).  Result is
    significant at root (SPMD: other ranks hold partials)."""
    n = _require_uniform(comm)
    if n == 1:
        return x
    rank = comm.rank()
    vrank = (rank - root) % n
    k = 1
    while k < n:
        pairs = []
        for v in range(0, n - k, 2 * k):
            pairs.append(((v + k + root) % n, (v + root) % n))
        recv = spmd.ppermute(comm, x, pairs)
        is_recv = (vrank % (2 * k) == 0) & (vrank + k < n)
        # op(x, recv): x holds [v, v+k), recv holds [v+k, v+2k) — keeps
        # the tree's reduction in ascending vrank order
        x = _where(is_recv, op(x, recv), x)
        k <<= 1
    return x


def reduce_linear(comm, x, op, root=0):
    """Linear reduce preserving strict rank order for non-commutative ops."""
    full = allreduce_linear(comm, x, op)
    return full  # every rank computes the rank-ordered result


def reduce_chain(comm, x, op, root=0, segments: int = 4):
    """Chain/pipelined reduce (reference: coll_base_reduce.c:379 chain, :409
    pipeline): partial sums flow down a single chain toward root, segmented
    so the hops of different segments overlap.  Segment chains share no data
    dependencies — XLA interleaves them, which is the pipelining the
    reference hand-schedules."""
    n = _require_uniform(comm)
    if n == 1:
        return x
    rank = comm.rank()
    vrank = (rank - root) % n
    flat = x.reshape(-1)
    length = flat.shape[0]
    segments = max(1, min(segments, length))
    seg = -(-length // segments)
    pad = segments * seg - length
    if pad:
        flat = jnp.pad(flat, (0, pad))
    # chain toward root in vrank space: v -> v-1, accumulated at each hop
    pairs = [((v + root) % n, (v - 1 + root) % n) for v in range(1, n)]

    def one_segment(sg):
        def hop(t, acc):
            recv = spmd.ppermute(comm, acc, pairs)
            # at hop t, vrank n-2-t absorbs the partial from vrank n-1-t;
            # op(acc, recv) keeps MPI's rank order x_v ⊕ (x_{v+1} ⊕ ...)
            absorbing = vrank == (n - 2 - t)
            return _where(absorbing, op(acc, recv), acc)

        return lax.fori_loop(0, n - 1, hop, sg)

    parts = [
        one_segment(flat[i * seg : (i + 1) * seg]) for i in range(segments)
    ]
    return jnp.concatenate(parts)[:length].reshape(x.shape)


def reduce_pipeline(comm, x, op, root=0, segments: int = 8):
    """Pipelined reduce (reference: coll_base_reduce.c:409): the chain
    algorithm at higher segment count."""
    return reduce_chain(comm, x, op, root=root, segments=segments)


def reduce_binary(comm, x, op, root=0):
    """Binary-tree reduce (reference: coll_base_reduce.c:440): leaves send
    up a complete binary tree, interior nodes absorb both children per
    round (one collective_permute per child side)."""
    n = _require_uniform(comm)
    if n == 1:
        return x
    rank = comm.rank()
    vrank = (rank - root) % n
    # deepest level d has nodes [2^d - 1, 2^(d+1) - 1) ∩ [0, n)
    max_depth = 0
    while (1 << (max_depth + 1)) - 1 < n:
        max_depth += 1
    for d in range(max_depth, 0, -1):
        lo, hi = (1 << d) - 1, min((1 << (d + 1)) - 1, n)
        for side in (1, 2):  # vrank 2p+1 is p's left child, 2p+2 its right
            pairs = [
                ((v + root) % n, ((v - side) // 2 + root) % n)
                for v in range(lo, hi)
                if (v - side) % 2 == 0
            ]
            if not pairs:
                continue
            recv = spmd.ppermute(comm, x, pairs)
            is_parent = (2 * vrank + side >= lo) & (2 * vrank + side < hi)
            x = _where(is_parent, op(x, recv), x)
    return x


def reduce_in_order_binary(comm, x, op, root=0):
    """In-order binary reduce (reference: coll_base_reduce.c:509): exists to
    give non-commutative ops a deterministic reduction order.  On SPMD the
    rank-ordered guarantee is provided by the linear algorithm (the only
    order MPI defines), so this delegates — the reference's in-order tree is
    an optimization of the same contract."""
    return reduce_linear(comm, x, op, root)


def reduce_rabenseifner(comm, x, op, root=0):
    """Rabenseifner reduce (reference: coll_base_reduce.c:797): recursive
    -halving reduce-scatter + binomial gather to root.  SPMD form: after the
    reduce-scatter each rank owns one reduced chunk; the gather is an
    allgather (result significant at root), which on ICI is the faster
    primitive anyway."""
    n = _require_uniform(comm)
    if n & (n - 1) or n == 1:
        return reduce_binomial(comm, x, op, root)
    buf, length = _chunked(x, n)
    own = reduce_scatter_recursive_halving(comm, buf.reshape(-1), op)
    gathered = allgather_ring(comm, own)
    return gathered.reshape(-1)[:length].reshape(x.shape)


# ---------------------------------------------------------------------------
# Allgather (cf. coll_base_allgather.c)
# ---------------------------------------------------------------------------


def _stack_shape(x):
    return x[None] if x.ndim == 0 else x


def allgather_ring(comm, x):
    """Ring allgather (reference: coll_base_allgather.c:358)."""
    n = _require_uniform(comm)
    x = _stack_shape(x)
    if n == 1:
        return x
    rank = comm.rank()
    buf = jnp.zeros((n,) + x.shape, x.dtype)
    buf = lax.dynamic_update_slice(buf, x[None], (rank,) + (0,) * x.ndim)

    def ag_round(k, b):
        send_idx = (rank - k) % n
        recv_idx = (rank - k - 1) % n
        sent = spmd.ppermute(
            comm, jnp.take(b, send_idx, axis=0),
            lambda m: [(i, (i + 1) % m) for i in range(m)],
        )
        return b.at[recv_idx].set(sent)

    buf = lax.fori_loop(0, n - 1, ag_round, buf)
    return buf.reshape((n * x.shape[0],) + x.shape[1:])


def allgather_bruck(comm, x):
    """Bruck allgather (reference: coll_base_allgather.c:85): ceil(log2 p)
    rounds of doubling block counts, then a rotation."""
    n = _require_uniform(comm)
    x = _stack_shape(x)
    if n == 1:
        return x
    rank = comm.rank()
    buf = jnp.zeros((n,) + x.shape, x.dtype)
    buf = buf.at[0].set(x)
    k = 1
    while k < n:
        cnt = min(k, n - k)
        send = buf[:cnt]  # static slice
        recv = spmd.ppermute(
            comm, send, lambda m, k=k: [(i, (i - k) % m) for i in range(m)]
        )
        buf = lax.dynamic_update_slice(
            buf, recv, (k,) + (0,) * (buf.ndim - 1)
        )
        k <<= 1
    # buf[j] holds the block of comm rank (rank + j) % n; rotate to rank order
    buf = jnp.roll(buf, shift=rank, axis=0)
    return buf.reshape((n * x.shape[0],) + x.shape[1:])


def allgather_recursive_doubling(comm, x):
    """Recursive-doubling allgather (pow2; reference pattern of
    coll_base_allgather.c). Falls back to Bruck otherwise."""
    n = _require_uniform(comm)
    if n & (n - 1):
        return allgather_bruck(comm, x)
    x = _stack_shape(x)
    if n == 1:
        return x
    rank = comm.rank()
    buf = jnp.zeros((n,) + x.shape, x.dtype)
    buf = lax.dynamic_update_slice(buf, x[None], (rank,) + (0,) * x.ndim)
    w = 1
    while w < n:
        pairs = [(i, i ^ w) for i in range(n)]
        my_lo = rank & ~(w - 1)
        partner_lo = (rank ^ w) & ~(w - 1)
        sent = spmd.ppermute(
            comm,
            lax.dynamic_slice(
                buf, (my_lo,) + (0,) * x.ndim, (w,) + x.shape
            ),
            pairs,
        )
        buf = lax.dynamic_update_slice(
            buf, sent, (partner_lo,) + (0,) * x.ndim
        )
        w <<= 1
    return buf.reshape((n * x.shape[0],) + x.shape[1:])


def _neighbor_exchange_plan(n: int):
    """Static per-step (pairs, sent_lo[rank], recv_lo[rank]) tables for the
    neighbor-exchange allgather — computed once in Python since n is static
    under jit."""
    sent = [r - (r % 2) for r in range(n)]  # pair window owned after step 0
    steps = []
    for s in range(1, n // 2):
        partner = []
        for r in range(n):
            if r % 2 == 0:
                p = (r - 1) % n if s % 2 == 1 else (r + 1) % n
            else:
                p = (r + 1) % n if s % 2 == 1 else (r - 1) % n
            partner.append(p)
        pairs = [(r, partner[r]) for r in range(n)]
        recv = [sent[partner[r]] for r in range(n)]
        steps.append((pairs, list(sent), list(recv)))
        sent = recv
    return steps


def allgather_neighbor_exchange(comm, x):
    """Neighbor-exchange allgather (reference: coll_base_allgather.c:484,
    the Chen et al. algorithm): even n only — n/2 rounds alternating
    exchanges with left/right neighbors, each carrying the pair-window
    received in the previous round.  Falls back to ring for odd n, as the
    reference's selection logic does."""
    n = _require_uniform(comm)
    x = _stack_shape(x)
    if n == 1:
        return x
    if n % 2:
        return allgather_ring(comm, x)
    rank = comm.rank()
    zero_idx = (0,) * x.ndim
    buf = jnp.zeros((n,) + x.shape, x.dtype)
    buf = lax.dynamic_update_slice(buf, x[None], (rank,) + zero_idx)
    # step 0: exchange own block within (even, odd) pairs
    recv0 = spmd.ppermute(comm, x, [(i, i ^ 1) for i in range(n)])
    buf = lax.dynamic_update_slice(buf, recv0[None], (rank ^ 1,) + zero_idx)
    for pairs, sent_lo, recv_lo in _neighbor_exchange_plan(n):
        s_lo = jnp.take(jnp.asarray(sent_lo), rank)
        r_lo = jnp.take(jnp.asarray(recv_lo), rank)
        win = lax.dynamic_slice(buf, (s_lo,) + zero_idx, (2,) + x.shape)
        got = spmd.ppermute(comm, win, pairs)
        buf = lax.dynamic_update_slice(buf, got, (r_lo,) + zero_idx)
    return buf.reshape((n * x.shape[0],) + x.shape[1:])


def allgather_two_proc(comm, x):
    """Two-process allgather (reference: coll_base_allgather.c:598): one
    exchange.  Requires comm size 2; falls back to ring otherwise."""
    n = _require_uniform(comm)
    if n != 2:
        return allgather_ring(comm, x)
    x = _stack_shape(x)
    rank = comm.rank()
    other = spmd.ppermute(comm, x, [(0, 1), (1, 0)])
    lo = _where(rank == 0, x, other)
    hi = _where(rank == 0, other, x)
    return jnp.concatenate([lo, hi], axis=0)


def allgather_linear(comm, x):
    """Basic linear allgather (reference: coll_base_allgather.c:681): every
    rank sends to every other.  The reference posts p(p-1) point-to-points;
    here it is p-1 independent shift permutes that XLA schedules
    concurrently — latency-optimal for tiny payloads on ICI."""
    n = _require_uniform(comm)
    x = _stack_shape(x)
    if n == 1:
        return x
    rank = comm.rank()
    buf = jnp.zeros((n,) + x.shape, x.dtype)
    buf = lax.dynamic_update_slice(buf, x[None], (rank,) + (0,) * x.ndim)
    for r in range(1, n):
        got = spmd.shift(comm, x, r, wrap=True)
        src = (rank - r) % n
        buf = lax.dynamic_update_slice(
            buf, got[None], (src,) + (0,) * x.ndim
        )
    return buf.reshape((n * x.shape[0],) + x.shape[1:])


# ---------------------------------------------------------------------------
# Alltoall (cf. coll_base_alltoall.c)
# ---------------------------------------------------------------------------


def _atoall_blocks(comm, x):
    n = _require_uniform(comm)
    if x.shape[0] % n:
        raise errors.CountError(
            f"alltoall needs dim0 divisible by comm size {n}, got {x.shape[0]}"
        )
    m = x.shape[0] // n
    return n, x.reshape((n, m) + x.shape[1:])


def alltoall_pairwise(comm, x):
    """Pairwise exchange (reference: coll_base_alltoall.c:132): p-1 rounds,
    round r exchanges with rank±r."""
    n, blocks = _atoall_blocks(comm, x)
    if n == 1:
        return x
    rank = comm.rank()
    out = jnp.zeros_like(blocks)
    out = out.at[rank].set(jnp.take(blocks, rank, axis=0))

    def round_r(r, o):
        sendto = (rank + r) % n
        recvfrom = (rank - r) % n
        sent = spmd.ppermute(
            comm, jnp.take(blocks, sendto, axis=0),
            lambda m, r=r: [(i, (i + r) % m) for i in range(m)],
        )
        return o.at[recvfrom].set(sent)

    # r is traced inside fori_loop but the ppermute pattern depends on it,
    # so unroll the (static-count) rounds instead.
    for r in range(1, n):
        out = round_r(r, out)
    return out.reshape(x.shape)


def alltoall_bruck(comm, x):
    """Bruck alltoall (reference: coll_base_alltoall.c:191): log2(p) rounds
    moving blocks whose index has bit k set; saves latency for small
    messages at the cost of local rotations."""
    n, blocks = _atoall_blocks(comm, x)
    if n == 1:
        return x
    rank = comm.rank()
    # phase 1: local rotation so block j targets rank (rank + j) % n
    blocks = jnp.roll(blocks, shift=-rank, axis=0)
    # phase 2: for each bit k, send blocks with bit k set to rank + 2^k
    k = 1
    while k < n:
        mask = (jnp.arange(n) & k) != 0
        sent = spmd.ppermute(
            comm, blocks, lambda m, k=k: [(i, (i + k) % m) for i in range(m)]
        )
        blocks = jnp.where(
            mask.reshape((n,) + (1,) * (blocks.ndim - 1)), sent, blocks
        )
        k <<= 1
    # phase 3: after phase 2, slot j at rank d holds data from source
    # (d - j) mod n; restoring source order is a flip then rotate by rank+1
    blocks = jnp.roll(jnp.flip(blocks, axis=0), shift=rank + 1, axis=0)
    return blocks.reshape(x.shape)


def alltoall_linear(comm, x):
    """Basic linear alltoall (reference: coll_base_alltoall.c:569): post
    everything at once.  On SPMD the posting-order distinction between
    linear and pairwise vanishes — both lower to the same p-1 static shift
    permutes, which XLA is free to schedule concurrently — so this shares
    pairwise's schedule."""
    return alltoall_pairwise(comm, x)


def alltoall_linear_sync(comm, x, window: int = 4):
    """Linear-sync alltoall (reference: coll_base_alltoall.c:333): like
    linear but with at most `window` transfers in flight.  The TPU analog of
    the in-flight cap is a data-dependency barrier between batches of
    `window` rounds, bounding concurrent ICI traffic (useful when the
    alltoall shares the mesh with other collectives)."""
    n, blocks = _atoall_blocks(comm, x)
    if n == 1:
        return x
    rank = comm.rank()
    out = jnp.zeros_like(blocks)
    out = out.at[rank].set(jnp.take(blocks, rank, axis=0))
    token = jnp.zeros((), blocks.dtype)
    for r in range(1, n):
        sendto = (rank + r) % n
        recvfrom = (rank - r) % n
        payload = jnp.take(blocks, sendto, axis=0) + token
        sent = spmd.ppermute(
            comm, payload,
            lambda m, r=r: [(i, (i + r) % m) for i in range(m)],
        )
        out = out.at[recvfrom].set(sent)
        if r % window == 0:
            # serialize the next batch behind this one; the zero tie-in is a
            # *float* mul-by-zero — integer x*0 would be constant-folded and
            # the window cap silently lost (see _barrier_token)
            token = (
                jnp.sum(sent).astype(jnp.float32) * 0.0
            ).astype(blocks.dtype)
    return out.reshape(x.shape)


def alltoall_two_proc(comm, x):
    """Two-process alltoall (reference: coll_base_alltoall.c:490): one
    exchange of the off-diagonal blocks."""
    n, blocks = _atoall_blocks(comm, x)
    if n != 2:
        return alltoall_pairwise(comm, x)
    rank = comm.rank()
    mine = jnp.take(blocks, rank, axis=0)
    theirs = spmd.ppermute(
        comm, jnp.take(blocks, 1 - rank, axis=0), [(0, 1), (1, 0)]
    )
    lo = _where(rank == 0, mine, theirs)
    hi = _where(rank == 0, theirs, mine)
    return jnp.stack([lo, hi]).reshape(x.shape)


# ---------------------------------------------------------------------------
# Alltoallv (cf. coll_base_alltoallv.c)
# ---------------------------------------------------------------------------


def alltoallv_prepare(comm, x, counts):
    """Shared front half of every alltoallv transport: validate the static
    ``counts[i][j]`` matrix, pad the send blocks to the global max count,
    and zero-mask rows beyond this rank's per-destination counts so
    padding can never leak into receive buffers.  Returns
    ``(blocks, max_recv)`` with blocks shaped ``(n, max_recv, ...)``."""
    n = _require_uniform(comm)
    if len(counts) != n or any(
        not hasattr(row, "__len__") or len(row) != n for row in counts
    ):
        raise errors.ArgError(f"counts must be {n}x{n}")
    if x.shape[0] != n:
        raise errors.CountError(
            f"alltoallv send buffer needs {n} blocks, got {x.shape[0]}"
        )
    rank = comm.rank()
    max_recv = max(max(row) for row in counts)
    blk = x.shape[1]
    if blk < max_recv:
        x = jnp.pad(
            x, ((0, 0), (0, max_recv - blk)) + ((0, 0),) * (x.ndim - 2)
        )
    else:
        x = x[:, :max_recv]
    sent_cnt = jnp.asarray(counts)[rank]  # (n,) rows sent to each dest
    mask = jnp.arange(max_recv)[None, :] < sent_cnt[:, None]
    x = jnp.where(
        mask.reshape((n, max_recv) + (1,) * (x.ndim - 2)), x,
        jnp.zeros_like(x),
    )
    return x, max_recv


def alltoallv_padded(comm, x, counts):
    """Pairwise alltoallv (reference: coll_base_alltoallv.c:125) with a
    static count matrix.  ``counts[i][j]`` is how many dim0 rows rank i
    sends to rank j (known to all ranks — the SPMD analog of every rank's
    sendcounts array).  ``x`` is this rank's send buffer laid out as
    ``(n, max_send, ...)`` padded blocks.  Returns ``(n, max_recv, ...)``
    padded receive blocks — entries beyond ``counts[src][rank]`` are zero.
    Static padding is the price of static shapes; the communicator layer
    offers the ragged reassembly."""
    blocks, max_recv = alltoallv_prepare(comm, x, counts)
    n = _require_uniform(comm)
    rank = comm.rank()
    out = jnp.zeros_like(blocks)
    out = lax.dynamic_update_slice(
        out, jnp.take(blocks, rank, axis=0)[None],
        (rank,) + (0,) * (out.ndim - 1),
    )
    for r in range(1, n):
        sendto = (rank + r) % n
        recvfrom = (rank - r) % n
        sent = spmd.ppermute(
            comm, jnp.take(blocks, sendto, axis=0),
            lambda m, r=r: [(i, (i + r) % m) for i in range(m)],
        )
        out = lax.dynamic_update_slice(
            out, sent[None], (recvfrom,) + (0,) * (out.ndim - 1)
        )
    return out


# ---------------------------------------------------------------------------
# Reduce_scatter (cf. coll_base_reduce_scatter.c)
# ---------------------------------------------------------------------------


def reduce_scatter_ring(comm, x, op):
    """Ring reduce-scatter (reference: coll_base_reduce_scatter.c:456)."""
    n = _require_uniform(comm)
    if n == 1:
        return x
    rank = comm.rank()
    n_, blocks = _atoall_blocks(comm, x)

    def rs_round(k, b):
        send_idx = (rank - k) % n
        recv_idx = (rank - k - 1) % n
        sent = spmd.ppermute(
            comm, jnp.take(b, send_idx, axis=0),
            lambda m: [(i, (i + 1) % m) for i in range(m)],
        )
        return b.at[recv_idx].set(op(sent, jnp.take(b, recv_idx, axis=0)))

    blocks = lax.fori_loop(0, n - 1, rs_round, blocks)
    # rank owns chunk (rank+1)%n; shift it home so rank r holds chunk r
    owned = jnp.take(blocks, (rank + 1) % n, axis=0)
    return spmd.shift(comm, owned, 1, wrap=True)


def reduce_scatter_recursive_halving(comm, x, op):
    """Recursive halving (reference: coll_base_reduce_scatter.c:132); pow2
    ranks, falls back to ring otherwise."""
    n = _require_uniform(comm)
    if n & (n - 1):
        return reduce_scatter_ring(comm, x, op)
    if n == 1:
        return x
    rank = comm.rank()
    _, blocks = _atoall_blocks(comm, x)
    shape_rest = blocks.shape[1:]
    lo = jnp.zeros((), jnp.int32)
    bit = n >> 1
    while bit:
        pairs = [(i, i ^ bit) for i in range(n)]
        on_upper = (rank & bit) != 0
        send_lo = jnp.where(on_upper, lo, lo + bit)
        keep_lo = jnp.where(on_upper, lo + bit, lo)
        sent = spmd.ppermute(
            comm,
            lax.dynamic_slice(
                blocks, (send_lo,) + (0,) * len(shape_rest), (bit,) + shape_rest
            ),
            pairs,
        )
        kept = lax.dynamic_slice(
            blocks, (keep_lo,) + (0,) * len(shape_rest), (bit,) + shape_rest
        )
        blocks = lax.dynamic_update_slice(
            blocks, op(sent, kept), (keep_lo,) + (0,) * len(shape_rest)
        )
        lo = keep_lo
        bit >>= 1
    return jnp.take(blocks, rank, axis=0)


def reduce_scatter_nonoverlapping(comm, x, op):
    """Reduce + scatter composition (reference:
    coll_base_reduce_scatter.c:47): binomial reduce to rank 0, then linear
    scatter of the chunks."""
    n = _require_uniform(comm)
    if n == 1:
        return x
    _atoall_blocks(comm, x)  # validate divisibility
    reduced = reduce_binomial(comm, x, op, root=0)
    chunk = x.shape[0] // n
    scattered = scatter_linear(comm, reduced.reshape(-1), 0)
    return scattered[: chunk * math.prod(x.shape[1:])].reshape(
        (chunk,) + x.shape[1:]
    )


def reduce_scatter_butterfly(comm, x, op):
    """Butterfly reduce-scatter (reference: coll_base_reduce_scatter.c:691).
    For power-of-two comms the butterfly's pairwise distance-halving
    exchange coincides with recursive halving; the reference's extra
    machinery exists to handle non-power-of-two ranks, which here (as in
    Rabenseifner) falls back to the ring."""
    n = _require_uniform(comm)
    if n & (n - 1):
        return reduce_scatter_ring(comm, x, op)
    return reduce_scatter_recursive_halving(comm, x, op)


# ---------------------------------------------------------------------------
# Reduce_scatter_block (cf. coll_base_reduce_scatter_block.c)
# ---------------------------------------------------------------------------
# MPI_Reduce_scatter_block: equal recvcounts — exactly the contract the
# chunked algorithms above already implement, so the block entry points are
# the canonical ones and MPI_Reduce_scatter with uniform counts delegates
# here.


def reduce_scatter_block_linear(comm, x, op):
    """Reduce-to-all then take own block (reference:
    coll_base_reduce_scatter_block.c:55 reduce+scatter via rank order)."""
    n = _require_uniform(comm)
    _, blocks = _atoall_blocks(comm, x)
    full = allreduce_linear(comm, x, op)
    return jnp.take(
        full.reshape((n,) + blocks.shape[1:]), comm.rank(), axis=0
    )


def reduce_scatter_block_recursive_doubling(comm, x, op):
    """Recursive-doubling variant (reference:
    coll_base_reduce_scatter_block.c:128): allreduce by recursive doubling,
    keep own block — latency-optimal for small payloads."""
    n = _require_uniform(comm)
    _, blocks = _atoall_blocks(comm, x)
    full = allreduce_recursive_doubling(comm, x, op)
    return jnp.take(
        full.reshape((n,) + blocks.shape[1:]), comm.rank(), axis=0
    )


def reduce_scatter_block_recursive_halving(comm, x, op):
    """Recursive-halving variant (reference:
    coll_base_reduce_scatter_block.c:326)."""
    return reduce_scatter_recursive_halving(comm, x, op)


def reduce_scatter_block_butterfly(comm, x, op):
    """Butterfly variant (reference: coll_base_reduce_scatter_block.c:567
    and the pow2 specialization at :810)."""
    return reduce_scatter_butterfly(comm, x, op)


# ---------------------------------------------------------------------------
# Scan / Exscan (cf. coll_base_scan.c, coll_base_exscan.c)
# ---------------------------------------------------------------------------


def scan_recursive_doubling(comm, x, op):
    """Inclusive prefix reduction, Hillis-Steele over ranks (reference:
    coll_base_scan.c:157).  Order-preserving: safe for non-commutative
    (associative) ops."""
    n = _require_uniform(comm)
    if n == 1:
        return x
    rank = comm.rank()
    k = 1
    while k < n:
        recv = spmd.ppermute(
            comm, x, [(i, i + k) for i in range(n - k)]
        )
        x = _where(rank >= k, op(recv, x), x)
        k <<= 1
    return x


def scan_linear(comm, x, op):
    """Linear scan (reference: coll_base_scan.c:35): the running prefix
    crawls up the rank chain one hop per round — n-1 rounds, each a single
    point-to-point.  Exists for forced selection and as the semantic
    baseline; recursive doubling is the performant choice."""
    n = _require_uniform(comm)
    if n == 1:
        return x
    rank = comm.rank()
    acc = x
    for r in range(1, n):
        recv = spmd.ppermute(comm, acc, [(r - 1, r)])
        acc = _where(rank == r, op(recv, acc), acc)
    return acc


def exscan_linear(comm, x, op):
    """Linear exscan (reference: coll_base_exscan.c:35): the inclusive
    prefix of rank r-1 arrives as rank r's exclusive result."""
    n = _require_uniform(comm)
    if n == 1:
        return jax.tree.map(jnp.zeros_like, x)
    rank = comm.rank()
    acc = x                     # inclusive prefix being built
    out = jax.tree.map(jnp.zeros_like, x)
    for r in range(1, n):
        recv = spmd.ppermute(comm, acc, [(r - 1, r)])
        out = _where(rank == r, recv, out)
        acc = _where(rank == r, op(recv, acc), acc)
    return out


def exscan_recursive_doubling(comm, x, op):
    """Exclusive scan (reference: coll_base_exscan.c:142): inclusive scan,
    then shift the RESULTS up one rank — correct for every associative op
    (shifting inputs instead would inject ppermute's zero-fill at rank 0
    into every prefix, which is only an identity for SUM).  Rank 0's result
    is undefined per MPI; here it holds zeros."""
    _require_uniform(comm)
    inclusive = scan_recursive_doubling(comm, x, op)
    return spmd.shift(comm, inclusive, 1, wrap=False)


# ---------------------------------------------------------------------------
# Barrier (cf. coll_base_barrier.c)
# ---------------------------------------------------------------------------


def _barrier_token(comm, token):
    """The scalar each barrier round actually permutes.

    Three elimination traps, all verified against the XLA CPU pipeline:
    integer ``sum(token) * 0`` is algebraically folded to a literal; a
    collective-permute whose operand is a provably-constant splat is folded
    (zeros in, zeros out), taking the whole barrier with it; and
    ``optimization_barrier`` does not help because JAX's jaxpr-level DCE
    prunes its unused outputs together with their operands.  So the wire
    payload is *float32* and runtime-variant — axis_index (partition id)
    plus the caller's token data — and :func:`_seal_token` turns the final
    value into zero with a float mul-by-zero, which XLA must keep (0*x is
    NaN for x=NaN/Inf, so floats never fold)."""
    t = comm.axis_index().astype(jnp.float32)
    if token is not None:
        t = t + jnp.sum(token).astype(jnp.float32)
    return t


def _seal_token(t):
    """An int32 zero whose value genuinely flows from the barrier rounds
    (see :func:`_barrier_token` for why this is a float multiply).  NaN in
    the caller's token would poison the zero — garbage in, garbage out, as
    with any data-dependent sequencing."""
    return (t.astype(jnp.float32) * 0.0).astype(jnp.int32)


def barrier_dissemination(comm, token=None):
    """Bruck/dissemination barrier (reference: coll_base_barrier.c:253):
    ceil(log2 p) rounds of shifted notifications.  Returns a data-dependent
    zero scalar usable as a sequencing token."""
    n = _require_uniform(comm)
    t = _barrier_token(comm, token)
    k = 1
    while k < n:
        t = t + spmd.ppermute(
            comm, t, lambda m, k=k: [(i, (i + k) % m) for i in range(m)]
        )
        k <<= 1
    return _seal_token(t)


def barrier_double_ring(comm, token=None):
    """Double-ring barrier (reference: coll_base_barrier.c:100): two full
    laps of a unit token around the ring — 2(p-1) hops, the simplest
    schedule that transitively orders every rank."""
    n = _require_uniform(comm)
    t = _barrier_token(comm, token)

    def hop(_, tok):
        # pass the token along (no accumulation: tok + shift(tok) doubles
        # per hop and overflows f32 to inf around 60 ranks, NaN-poisoning
        # the seal); each hop depends on the left neighbor's previous hop,
        # so n-1 laps transitively order every rank
        return spmd.shift(comm, tok, 1, wrap=True)

    return _seal_token(lax.fori_loop(0, 2 * (n - 1), hop, t))


def barrier_recursive_doubling(comm, token=None):
    """Recursive-doubling barrier (reference: coll_base_barrier.c:172):
    log2(p) pairwise xor-distance exchanges (pow2 comms; dissemination
    handles the rest and is what non-pow2 falls back to)."""
    n = _require_uniform(comm)
    if n & (n - 1):
        return barrier_dissemination(comm, token)
    t = _barrier_token(comm, token)
    k = 1
    while k < n:
        t = t + spmd.ppermute(comm, t, [(i, i ^ k) for i in range(n)])
        k <<= 1
    return _seal_token(t)


def barrier_two_proc(comm, token=None):
    """Two-process barrier (reference: coll_base_barrier.c:291): one
    exchange."""
    n = _require_uniform(comm)
    if n != 2:
        return barrier_dissemination(comm, token)
    t = _barrier_token(comm, token)
    return _seal_token(t + spmd.ppermute(comm, t, [(0, 1), (1, 0)]))


def barrier_tree(comm, token=None):
    """Tree barrier (reference: coll_base_barrier.c:404): binomial fan-in to
    rank 0 then binomial fan-out — the reduce/bcast trees applied to a unit
    token."""
    _require_uniform(comm)
    t = _barrier_token(comm, token)
    t = reduce_binomial(comm, t, lambda a, b: a + b, root=0)
    return _seal_token(bcast_binomial(comm, t, root=0))


def barrier_linear(comm, token=None):
    """Linear barrier (reference: coll_base_barrier.c:330): everyone
    reports to everyone.  The reference funnels through rank 0; the SPMD
    equivalent of "rank 0 heard from all, then told all" with static
    patterns is the all-to-all notification, p-1 concurrent permutes."""
    n = _require_uniform(comm)
    t = _barrier_token(comm, token)
    acc = t
    for r in range(1, n):
        acc = acc + spmd.shift(comm, t, r, wrap=True)
    return _seal_token(acc)


# ---------------------------------------------------------------------------
# Gather / Scatter (cf. coll_base_gather.c / coll_base_scatter.c)
# ---------------------------------------------------------------------------


def gather_ring(comm, x, root=0):
    """Gather via allgather.  SPMD note (documented semantic): on a
    single-program machine every device executes the same collective, so the
    "only root receives" optimization of the reference's binomial gather
    (coll_base_gather.c:41) buys nothing — the result is simply significant
    at root."""
    return allgather_ring(comm, x)


def scatter_linear(comm, x, root=0):
    """Linear scatter (reference: coll_base_scatter.c:63): root sends chunk i
    to rank i, one static ppermute per destination; XLA overlaps them."""
    n = _require_uniform(comm)
    buf, length = _chunked(x, n)
    chunk = buf.shape[1]
    rank = comm.rank()
    out = jnp.take(buf, rank, axis=0)  # root's own chunk (and garbage elsewhere)
    for i in range(n):
        if i == root:
            continue
        sent = spmd.ppermute(comm, buf[i], [(root, i)])
        out = _where(rank == i, sent, out)
    # non-root ranks' x may be garbage; out at rank i is root's chunk i
    return out


def gather_binomial(comm, x, root=0):
    """Binomial-tree gather (reference: coll_base_gather.c:41): round k,
    vranks with bit k set ship their accumulated window of k blocks to
    vrank−k; root ends holding all p blocks.  The windows are dynamic
    slices at traced offsets with static sizes — jit-compatible.  Result is
    the full (p·m, ...) buffer, significant at root."""
    n = _require_uniform(comm)
    x = _stack_shape(x)
    if n == 1:
        return x
    rank = comm.rank()
    vrank = (rank - root) % n
    zero_idx = (0,) * x.ndim
    # 2n rows so window reads/writes past n land in the zero pad instead of
    # being clamped by dynamic_slice (non-pow2 tails)
    buf = jnp.zeros((2 * n,) + x.shape, x.dtype)
    # each rank's accumulated window starts at its own vrank
    buf = lax.dynamic_update_slice(buf, x[None], (vrank,) + zero_idx)
    k = 1
    while k < n:
        pairs = [
            ((v + k + root) % n, (v + root) % n)
            for v in range(0, n - k, 2 * k)
        ]
        sent = spmd.ppermute(
            comm,
            lax.dynamic_slice(buf, (vrank,) + zero_idx, (k,) + x.shape),
            pairs,
        )
        is_recv = (vrank % (2 * k) == 0) & (vrank + k < n)
        merged = lax.dynamic_update_slice(
            buf, sent, (vrank + k,) + zero_idx
        )
        buf = _where(is_recv, merged, buf)
        k <<= 1
    # root's window is [0, n) in vrank order; rotate to rank order
    buf = jnp.roll(buf[:n], shift=root, axis=0)
    return buf.reshape((n * x.shape[0],) + x.shape[1:])


def gather_linear_sync(comm, x, root=0):
    """Linear-sync gather (reference: coll_base_gather.c:208): the
    reference rate-limits senders with an ack handshake; on a statically
    scheduled machine the collective_permutes already execute in schedule
    order, so this shares the ring-gather schedule."""
    return gather_ring(comm, x, root)


def scatter_binomial(comm, x, root=0):
    """Binomial-tree scatter (reference: coll_base_scatter.c:63, the
    binomial entry): the mirror of binomial gather — root starts with all p
    chunks, round k (descending) hands the upper half of each holder's
    window to vrank+k.  Dynamic windows at traced offsets, static sizes."""
    n = _require_uniform(comm)
    buf, length = _chunked(x, n)
    chunk = buf.shape[1]
    if n == 1:
        return buf.reshape(-1)[:length]
    rank = comm.rank()
    vrank = (rank - root) % n
    # rotate root's buffer into vrank order, then pad to 2n rows so window
    # reads past n hit the zero pad instead of dynamic_slice clamping
    buf = jnp.roll(buf, shift=-root, axis=0)
    buf = jnp.concatenate([buf, jnp.zeros_like(buf)], axis=0)
    k = _pow2_floor(n - 1) if n > 1 else 0
    while k >= 1:
        pairs = [
            ((v + root) % n, (v + k + root) % n)
            for v in range(0, n - k, 2 * k)
        ]
        sent = spmd.ppermute(
            comm,
            lax.dynamic_slice(buf, (vrank + k, 0), (k, chunk)),
            pairs,
        )
        is_recv = vrank % (2 * k) == k
        merged = lax.dynamic_update_slice(buf, sent, (vrank, 0))
        buf = _where(is_recv, merged, buf)
        k >>= 1
    return jnp.take(buf, vrank, axis=0)


def bcast_via_scatter(comm, x, root=0):
    return bcast_scatter_allgather(comm, x, root)


# ---------------------------------------------------------------------------
# Vector (v) variants
# ---------------------------------------------------------------------------


def allgatherv_concat(comm, x, counts: list[int]):
    """Allgatherv with static per-rank counts (cf. coll_base_allgatherv.c):
    pad to the max count, exchange, then statically re-concatenate.  `x` is
    this device's contribution, whose dim0 may be any value up to
    max(counts); entries beyond the device's count are ignored."""
    n = _require_uniform(comm)
    if len(counts) != n:
        raise errors.ArgError(f"need {n} counts, got {len(counts)}")
    mx = max(counts)
    pad = mx - x.shape[0]
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    gathered = allgather_ring(comm, x).reshape((n, mx) + x.shape[1:])
    parts = [gathered[i, : counts[i]] for i in range(n)]
    return jnp.concatenate(parts, axis=0)
