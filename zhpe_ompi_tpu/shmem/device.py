"""Device-plane PGAS: the symmetric heap resident in HBM.

The round-3 OSHMEM transports (direct/mmap/am) are all host-plane — the
symmetric heap lives in process or mapped memory.  This module is the
missing fast-fabric spml, inverted the way ``coll/tpu`` inverted
``coll/cuda``: the reference's spml/ucx
(``oshmem/mca/spml/ucx/spml_ucx.c:57``) reaches device memory through a
fabric's RDMA verbs; on this platform the "fabric" is ICI and the
idiomatic form is the compiled epoch — the same schedule-compilation
shape ``osc/spmd_window.py`` established for MPI RMA, here carrying
OpenSHMEM semantics:

- the **symmetric heap** is a set of per-dtype arenas, each a jax Array
  sharded one-shard-per-PE over the communicator's mesh axis (data
  lives in HBM and never leaves it);
- **symmetric allocation** is deterministic (every PE runs the same
  ``shmalloc`` sequence against the same first-fit allocator —
  ``memheap.py``'s property), so remote offsets are computed, never
  exchanged — exactly the reference's memheap contract;
- **put/get/AMO epochs** lower onto :class:`DeviceWindow` static
  schedules (ppermute + dynamic-update under one jit); ``barrier`` is
  the window fence, carried as a data dependency.

Like DeviceWindow, target PEs are *static per-rank schedules*: a
``pe_of`` argument is a list indexed by rank, or a callable
``f(rank, n_pes) -> target`` evaluated at trace time (the classic
OpenSHMEM neighbor patterns — shift, ring, halo — are all static).
``-1`` means "this rank does not participate".

Selected through the spml MCA framework at priority 100 ("device"):
``spml.shmem_pe(device_comm)`` hands back a :class:`DeviceHeap` when
the endpoint is a device communicator, the host backends otherwise —
one selection mechanism, two planes (SURVEY.md §5's backend map).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp

from ..core import errors
from ..osc.spmd_window import DeviceWindow
from .memheap import SymmetricHeapAllocator


@dataclass(frozen=True)
class DeviceSym:
    """A symmetric allocation: (arena key, element offset, shape).  The
    same descriptor is valid on every PE — offsets are deterministic."""

    arena: str
    offset: int  # in elements
    shape: tuple
    dtype: Any

    @property
    def elems(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


def _normalize_pe_of(pe_of, n: int) -> list[int]:
    if callable(pe_of):
        pe_of = [pe_of(r, n) for r in range(n)]
    elif isinstance(pe_of, int):
        pe_of = [pe_of] * n
    pe_of = list(pe_of)
    if len(pe_of) != n:
        raise errors.ArgError(f"pe_of needs {n} entries, got {len(pe_of)}")
    for t in pe_of:
        if not -1 <= t < n:
            raise errors.RankError(f"target PE {t} out of range")
    return pe_of


class DevicePE:
    """The in-epoch handle (valid inside shard_map): wraps the comm and
    this PE's arena shards.  Functional-update semantics like
    DeviceWindow — operations RETURN the updated handle."""

    def __init__(self, comm, arenas: dict):
        self.comm = comm
        self.arenas = arenas  # key -> (elems,) local shard

    def my_pe(self):
        return self.comm.rank()

    def n_pes(self) -> int:
        return self.comm.axis_size

    # -- local access ----------------------------------------------------

    def local(self, sym: DeviceSym):
        """This PE's view of the allocation (a traced value)."""
        from jax import lax

        flat = self.arenas[sym.arena]
        return lax.dynamic_slice(flat, (sym.offset,), (sym.elems,)
                                 ).reshape(sym.shape)

    def local_set(self, sym: DeviceSym, value) -> "DevicePE":
        from jax import lax

        flat = self.arenas[sym.arena]
        val = jnp.asarray(value, flat.dtype).reshape(-1)
        if val.size != sym.elems:
            val = jnp.broadcast_to(val, (sym.elems,))
        new = lax.dynamic_update_slice(flat, val, (sym.offset,))
        return self._with(sym.arena, new)

    def _with(self, key: str, new_arena) -> "DevicePE":
        arenas = dict(self.arenas)
        arenas[key] = new_arena
        return DevicePE(self.comm, arenas)

    def _window(self, sym: DeviceSym) -> DeviceWindow:
        return DeviceWindow(self.comm, self.arenas[sym.arena])

    # -- RMA epochs ------------------------------------------------------

    def put(self, sym: DeviceSym, value, pe_of) -> "DevicePE":
        """Every rank r puts `value` (its local traced array, sym-shaped)
        into PE ``pe_of[r]``'s allocation."""
        n = self.n_pes()
        targets = _normalize_pe_of(pe_of, n)
        val = jnp.asarray(value, self.arenas[sym.arena].dtype).reshape(-1)
        # bounds against the ALLOCATION, not the arena: the window spans
        # the whole arena, so without this check an oversized value would
        # silently overwrite the next symmetric allocation
        if val.size > sym.elems:
            raise errors.ArgError(
                f"put of {val.size} elems into allocation of {sym.elems}"
            )
        win = self._window(sym).put(val, targets, [sym.offset] * n)
        return self._with(sym.arena, win.shard)

    def get(self, sym: DeviceSym, pe_of, count: int | None = None,
            offset: int = 0):
        """Every rank r reads PE ``pe_of[r]``'s allocation (or a
        count-slice at element offset)."""
        n = self.n_pes()
        sources = _normalize_pe_of(pe_of, n)
        cnt = sym.elems if count is None else count
        if not 0 <= offset <= sym.elems or offset + cnt > sym.elems:
            raise errors.ArgError(
                f"get of {cnt} elems at offset {offset} overruns "
                f"allocation of {sym.elems}"
            )
        return self._window(sym).get(
            sources, [sym.offset + offset] * n, cnt)

    def add(self, sym: DeviceSym, value, pe_of, index: int = 0
            ) -> "DevicePE":
        """shmem_atomic_add as a schedule: rank r adds its `value` into
        element ``index`` of PE ``pe_of[r]``'s allocation.

        Unique targets lower onto DeviceWindow.accumulate (one ppermute,
        no collective).  When several PEs target the SAME PE — the
        canonical "everyone bumps one counter" shmem_atomic idiom
        (``oshmem/shmem/c/shmem_fadd.c``) — the epoch switches to the
        *combining* form: each rank scatters its contribution into a
        one-hot length-n vector, a single psum folds all contributions,
        and each PE deposits its own total.  Associativity of the psum
        is the serialization, so any writer multiplicity is exact."""
        n = self.n_pes()
        targets = _normalize_pe_of(pe_of, n)
        if not 0 <= index < sym.elems:
            raise errors.ArgError(
                f"AMO index {index} out of range for allocation of "
                f"{sym.elems} elements"
            )
        if self._has_collision(targets):
            return self._add_combining(sym, value, targets, index)
        val = jnp.asarray(value, self.arenas[sym.arena].dtype).reshape(1)
        win = self._window(sym).accumulate(
            val, targets, [sym.offset + index] * n)
        return self._with(sym.arena, win.shard)

    def fadd(self, sym: DeviceSym, value, pe_of, index: int = 0):
        """shmem_atomic_fetch_add: returns (old, updated pe).  Unique
        targets read-before-add in the same compiled epoch.  Colliding
        targets use the combining epoch with rank-order serialization:
        rank r's fetch is the pre-epoch value plus the exclusive prefix
        sum of lower-ranked contributions to the same target — every
        fetcher observes a distinct, complete intermediate value, exactly
        the linearization a hardware fetch-add in rank order produces."""
        n = self.n_pes()
        targets = _normalize_pe_of(pe_of, n)
        if self._has_collision(targets):
            old = self._prefix_fetch(sym, value, targets, index)
            return old, self.add(sym, value, targets, index)
        old = self.get(sym, targets, count=1, offset=index)
        return old, self.add(sym, value, targets, index)

    @staticmethod
    def _has_collision(targets: list[int]) -> bool:
        live = [t for t in targets if t >= 0]
        return len(live) != len(set(live))

    def _amo_vectors(self, sym: DeviceSym, value, targets: list[int]):
        """Per-rank (target, active, contribution) as traced values: the
        static schedule indexed by the executing PE's axis index."""
        dt = self.arenas[sym.arena].dtype
        my = self.comm.rank()
        t_arr = jnp.asarray([t if t >= 0 else 0 for t in targets])
        act_arr = jnp.asarray([1 if t >= 0 else 0 for t in targets])
        val = jnp.asarray(value, dt).reshape(())
        t = t_arr[my]
        active = act_arr[my]
        contrib = jnp.where(active == 1, val, jnp.zeros((), dt))
        return my, t, active, contrib

    def _add_combining(self, sym: DeviceSym, value, targets: list[int],
                       index: int) -> "DevicePE":
        from .. import ops as zops

        n = self.n_pes()
        dt = self.arenas[sym.arena].dtype
        my, t, _active, contrib = self._amo_vectors(sym, value, targets)
        onehot = jnp.zeros((n,), dt).at[t].add(contrib)
        totals = self.comm.allreduce(onehot, zops.SUM)
        flat = self.arenas[sym.arena]
        new = flat.at[sym.offset + index].add(totals[my])
        return self._with(sym.arena, new)

    def _prefix_fetch(self, sym: DeviceSym, value, targets: list[int],
                      index: int):
        """Old value rank r observes under rank-order combining: target's
        pre-epoch element + sum of contributions from ranks < r aimed at
        the same target.  Idle (-1) ranks fetch 0 — the same masking the
        unique-target ppermute path applies to non-destinations."""
        if not 0 <= index < sym.elems:
            raise errors.ArgError(
                f"AMO index {index} out of range for allocation of "
                f"{sym.elems} elements"
            )
        n = self.n_pes()
        my, t, active, contrib = self._amo_vectors(sym, value, targets)
        elem = self.arenas[sym.arena][sym.offset + index]
        both = self.comm.allgather(
            jnp.stack([elem.astype(contrib.dtype), contrib])[None])
        elems, vals = both.reshape(n, 2)[:, 0], both.reshape(n, 2)[:, 1]
        t_arr = jnp.asarray([tt if tt >= 0 else 0 for tt in targets])
        before_me = (t_arr == t) & (jnp.arange(n) < my)
        prefix = jnp.sum(jnp.where(before_me, vals, 0))
        old = jnp.where(active == 1, elems[t] + prefix,
                        jnp.zeros((), contrib.dtype))
        return old.reshape(1)

    # -- collectives (the scoll analog, on XLA collectives) --------------
    # The reference's scoll/basic runs linear/binomial trees over pt2pt;
    # on the device plane the idiomatic form is the framework's own
    # XLA-native collective components operating on the heap values
    # inside the same compiled epoch (scoll/mpi's reuse trick, executed
    # as psum/all_gather/all_to_all on ICI).

    def broadcast(self, sym: DeviceSym, root: int = 0) -> "DevicePE":
        """shmem_broadcast: root's instance overwrites every PE's."""
        if not 0 <= root < self.n_pes():
            # the masked-psum bcast would silently zero every PE's copy
            raise errors.RankError(f"root PE {root} out of range")
        data = self.comm.bcast(self.local(sym), root=root)
        return self.local_set(sym, data)

    def fcollect(self, dest: DeviceSym, src: DeviceSym) -> "DevicePE":
        """shmem_fcollect: concatenate every PE's src (equal sizes) into
        every PE's dest, PE order."""
        n = self.n_pes()
        if dest.elems != src.elems * n:
            raise errors.CountError(
                f"fcollect dest must hold n_pes * src "
                f"({dest.elems} != {n} * {src.elems})"
            )
        gathered = self.comm.allgather(self.local(src).reshape(-1))
        return self.local_set(dest, gathered.reshape(-1))

    def reduce_to_all(self, dest: DeviceSym, src: DeviceSym, op=None
                      ) -> "DevicePE":
        """shmem_<op>_to_all: elementwise reduction of every PE's src
        into every PE's dest (framework allreduce on the heap value)."""
        from .. import ops as zops

        if dest.elems != src.elems:
            raise errors.CountError("reduce dest/src size mismatch")
        red = self.comm.allreduce(self.local(src),
                                  op if op is not None else zops.SUM)
        return self.local_set(dest, red)

    def alltoall(self, dest: DeviceSym, src: DeviceSym) -> "DevicePE":
        """shmem_alltoall: PE i's block j lands in PE j's block i."""
        n = self.n_pes()
        if src.elems % n or dest.elems != src.elems:
            raise errors.CountError(
                f"alltoall needs equal dest/src with elems divisible "
                f"by {n}"
            )
        moved = self.comm.alltoall(
            self.local(src).reshape(n, src.elems // n))
        return self.local_set(dest, moved.reshape(-1))

    def barrier(self) -> "DevicePE":
        """shmem_barrier_all: fence every arena on the dissemination
        token via ``optimization_barrier`` — an O(1) control dependency
        per arena (XLA may not reorder or DCE across it), not an
        elementwise pass over the heap.  The returned arenas carry a
        data dependency on every PE's arrival at zero HBM traffic."""
        from jax import lax

        from ..coll import algorithms as alg

        token = alg.barrier_dissemination(self.comm)
        arenas = {}
        for k, a in self.arenas.items():
            fenced, _ = lax.optimization_barrier((a, token))
            arenas[k] = fenced
        return DevicePE(self.comm, arenas)


class DeviceHeap:
    """Host-side owner of the HBM symmetric heap: allocator + the
    sharded arena state + the epoch runner."""

    plane = "device"

    def __init__(self, comm, heap_bytes: int = 1 << 20):
        if getattr(comm, "is_partitioned", False):
            # group-relative ranks vs full-axis schedules would diverge;
            # the spml also refuses selection for partitioned comms
            raise errors.CommError(
                "device PGAS requires an unpartitioned communicator "
                "(one group spanning the axis)"
            )
        self.comm = comm
        self.heap_bytes = int(heap_bytes)
        self._allocators: dict[str, SymmetricHeapAllocator] = {}
        self._arenas: dict[str, Any] = {}  # key -> (n, elems) jax Array

    # -- symmetric allocation (deterministic; memheap contract) ----------

    def _arena_key(self, dtype) -> str:
        return np.dtype(dtype).str

    def shmalloc(self, shape, dtype, align: int | None = None
                 ) -> DeviceSym:
        """Deterministic symmetric allocation; ``align`` is the
        shmem_align contract shared with the host backends (one
        allocator surface across all four spml transports — the same
        request sequence yields the same offsets on every plane)."""
        from jax.sharding import PartitionSpec as P

        if isinstance(shape, int):
            shape = (shape,)
        dt = np.dtype(dtype)
        key = self._arena_key(dt)
        if key not in self._allocators:
            elems = self.heap_bytes // dt.itemsize
            self._allocators[key] = SymmetricHeapAllocator(self.heap_bytes)
            n = self.comm.axis_size
            self._arenas[key] = self.comm.device_put_sharded(
                jnp.zeros((n, elems), dtype=dt), P(self.comm.axis)
            )
        nbytes = int(np.prod(shape)) * dt.itemsize
        off_bytes = self._allocators[key].alloc(
            nbytes, align if align else 64)
        assert off_bytes % dt.itemsize == 0  # ALIGN=64 covers all dtypes
        return DeviceSym(key, off_bytes // dt.itemsize, tuple(shape), dt)

    def shfree(self, sym: DeviceSym) -> None:
        self._allocators[sym.arena].free(sym.offset * sym.dtype.itemsize)

    # -- epochs ----------------------------------------------------------

    def epoch(self, fn: Callable, *args):
        """Run ``fn(pe, *args) -> (pe, out)`` as ONE compiled program
        under shard_map over the heap's mesh axis; commits the updated
        arena state and returns ``out`` (axis-sharded, or None).  Extra
        ``args`` arrive axis-sharded along dim 0."""
        from jax.sharding import PartitionSpec as P

        keys = sorted(self._arenas)
        ax = self.comm.axis

        def body(arena_list, *xs):
            pe = DevicePE(self.comm,
                          {k: a[0] for k, a in zip(keys, arena_list)})
            pe, out = fn(pe, *xs)
            new = [pe.arenas[k][None] for k in keys]
            return new, (jnp.zeros((1, 1)) if out is None else out)

        in_specs = ([P(ax)] * len(keys),) + tuple(P(ax) for _ in args)
        mapped = jax.shard_map(
            body, mesh=self.comm.mesh,
            in_specs=in_specs,
            out_specs=([P(ax)] * len(keys), P(ax)),
            check_vma=False,
        )
        from ..runtime import spc

        spc.record("pgas_device_epochs")
        new_arenas, out = mapped([self._arenas[k] for k in keys], *args)
        self._arenas = dict(zip(keys, new_arenas))
        return out

    def read(self, sym: DeviceSym) -> np.ndarray:
        """Host view of every PE's copy of the allocation: (n,) + shape
        (debug/verification path — data stays device-resident otherwise)."""
        arena = np.asarray(self._arenas[sym.arena])
        return arena[:, sym.offset:sym.offset + sym.elems].reshape(
            (arena.shape[0],) + sym.shape)

    def finalize(self) -> None:
        self._arenas.clear()
        self._allocators.clear()
