"""Communicators — the SPMD re-design.

Re-design of ``ompi/communicator`` (``ompi_communicator_t``,
``ompi/communicator/communicator.h:134-191``) for a single-controller SPMD
machine.  Key semantic shift, documented here once:

- In the reference, every process holds its *own* communicator object and
  ``MPI_Comm_split`` is a collective over processes.  Under JAX's
  single-controller model one Python object describes the communicator for
  ALL devices; ``split(colors)`` takes the full color assignment (what the
  reference reconstructs via an allgather inside ``ompi_comm_split``) and
  returns ONE object representing every sub-communicator of the partition.
  Inside traced SPMD code each device then acts within its own group.
- A communicator is bound to one mesh axis.  Per-axis communicators of an
  N-D mesh are the cartesian sub-communicators of ``MPI_Cart_sub``.
- "rank" is a traced value (``lax.axis_index``) inside ``shard_map``; the
  host never has a rank — it is the controller of all of them.

The collective function table (``comm.coll``) is composed per-communicator,
per-operation from the coll framework's components by priority, exactly
mirroring ``mca_coll_base_comm_select.c:108-152``.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Any, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import attributes
from ..core import errhandler as errh
from ..core import errors
from ..core import info as info_mod
from ..mca import output as mca_output
from .group import Group


def _axis_devices(mesh: Mesh, axis: str) -> list:
    """One representative device per index of `axis` (index 0 of every
    other axis)."""
    k = mesh.axis_names.index(axis)
    arr = np.moveaxis(mesh.devices, k, 0)
    return [np.asarray(arr[i]).flat[0] for i in range(arr.shape[0])]

_stream = mca_output.open_stream("comm")

_cid_lock = threading.Lock()
_next_cid = [0]


def _alloc_cid() -> int:
    """CID allocation (cf. ompi_comm_nextcid) — trivial under one controller."""
    with _cid_lock:
        cid = _next_cid[0]
        _next_cid[0] += 1
        return cid


class Communicator(errh.HasErrhandler, attributes.AttrHost):
    """A communicator over one mesh axis, optionally partitioned into
    same-axis sub-groups (the result of ``split``).

    Carries an :class:`~zhpe_ompi_tpu.core.info.Info` of hints, an
    attachable :class:`~zhpe_ompi_tpu.core.errhandler.Errhandler`
    (default MPI_ERRORS_ARE_FATAL, the reference's communicator default),
    and keyval attribute caching (``core/attributes.py`` — copy callbacks
    run at dup, delete callbacks at free, per ompi/attribute)."""

    _default_errhandler = errh.ERRORS_ARE_FATAL

    def __init__(
        self,
        mesh: Mesh,
        axis: str,
        partition: list[Group] | None = None,
        name: str | None = None,
        info=None,
    ) -> None:
        if axis not in mesh.axis_names:
            raise errors.CommError(f"axis {axis!r} not in mesh {mesh.axis_names}")
        self.mesh = mesh
        self.axis = axis
        self.axis_size = mesh.shape[axis]
        if partition is None:
            partition = [Group(range(self.axis_size))]
        covered = sorted(r for g in partition for r in g.ranks)
        if covered != list(range(self.axis_size)):
            raise errors.CommError(
                "partition must cover every axis index exactly once"
            )
        self.partition = partition
        self.cid = _alloc_cid()
        self.name = name or f"comm{self.cid}"
        self.attributes: dict[Any, Any] = {}  # MPI attribute caching
        self.info = info_mod.coerce(info)  # MPI_Comm_set_info hints
        # Static lookup tables (device-constant arrays built lazily):
        #   axis index -> comm-relative rank, and -> its group's size
        self._rank_table = np.empty(self.axis_size, dtype=np.int32)
        self._size_table = np.empty(self.axis_size, dtype=np.int32)
        for g in partition:
            for i, glob in enumerate(g.ranks):
                self._rank_table[glob] = i
                self._size_table[glob] = g.size
        self._coll: dict[str, tuple] | None = None
        mca_output.verbose(
            5, _stream, "created %s over axis %s (%d groups)",
            self.name, axis, len(partition),
        )

    # -- shape/introspection --------------------------------------------

    @property
    def is_partitioned(self) -> bool:
        return len(self.partition) > 1

    @property
    def uniform_size(self) -> int | None:
        sizes = {g.size for g in self.partition}
        return sizes.pop() if len(sizes) == 1 else None

    @property
    def size(self) -> int:
        """Group size when every sub-group has the same size (the common
        case); raises otherwise — use ``size_traced()`` inside the program."""
        s = self.uniform_size
        if s is None:
            raise errors.CommError(
                f"{self.name} has non-uniform sub-group sizes; use size_traced()"
            )
        return s

    @property
    def group(self) -> Group:
        if self.is_partitioned:
            raise errors.CommError(
                f"{self.name} is partitioned; access .partition instead"
            )
        return self.partition[0]

    @property
    def index_groups(self) -> list[list[int]] | None:
        """axis_index_groups for XLA collectives (None for the whole axis)."""
        if not self.is_partitioned and self.partition[0].ranks == tuple(
            range(self.axis_size)
        ):
            return None
        return [list(g.ranks) for g in self.partition]

    # -- traced views (valid inside shard_map over self.mesh) ------------

    def axis_index(self):
        """Global index along the comm's mesh axis (traced)."""
        return jax.lax.axis_index(self.axis)

    def rank(self):
        """Comm-relative rank of the executing device (traced)."""
        if not self.is_partitioned:
            return self.axis_index()
        import jax.numpy as jnp

        return jnp.asarray(self._rank_table)[self.axis_index()]

    def size_traced(self):
        import jax.numpy as jnp

        return jnp.asarray(self._size_table)[self.axis_index()]

    # -- construction of new communicators ------------------------------

    def dup(self, name: str | None = None) -> "Communicator":
        """MPI_Comm_dup: same partition, fresh CID; attributes propagate
        through their keyvals' copy callbacks (MPI dup semantics)."""
        new = Communicator(self.mesh, self.axis, list(self.partition), name)
        self._copy_attrs_to(new)
        return new

    def free(self) -> None:
        """MPI_Comm_free: runs attribute delete callbacks.  The object
        itself is garbage-collected; collectives after free are a user
        error the dispatch layer surfaces naturally."""
        self._delete_all_attrs()

    def split_type(self, split_type: str = "shared",
                   keys: Sequence[int] | None = None,
                   name: str | None = None) -> "Communicator":
        """MPI_Comm_split_type: "shared" groups axis indices whose
        devices share a host (process_index) — the
        MPI_COMM_TYPE_SHARED/OMPI_COMM_TYPE_NODE semantics on a device
        mesh.  On a single-host mesh this is one group (== dup)."""
        if split_type != "shared":
            raise errors.ArgError(f"unknown split_type {split_type!r}")
        devs = _axis_devices(self.mesh, self.axis)
        colors = [int(getattr(d, "process_index", 0)) for d in devs]
        return self.split(colors, keys, name)

    def split(self, colors: Sequence[int], keys: Sequence[int] | None = None,
              name: str | None = None) -> "Communicator":
        """MPI_Comm_split, single-controller form: `colors[i]` is the color of
        axis index i (UNDEFINED/-1 for "not in any group" is not supported on
        an SPMD machine — every device executes the program; use a color).
        `keys` orders ranks within each new group (ties by old rank)."""
        if len(colors) != self.axis_size:
            raise errors.ArgError(
                f"need {self.axis_size} colors, got {len(colors)}"
            )
        keys = list(keys) if keys is not None else [0] * self.axis_size
        buckets: dict[int, list[int]] = {}
        for idx in range(self.axis_size):
            buckets.setdefault(int(colors[idx]), []).append(idx)
        groups = []
        for color in sorted(buckets):
            members = sorted(buckets[color], key=lambda i: (keys[i], i))
            groups.append(Group(members))
        return Communicator(self.mesh, self.axis, groups, name)

    def create_from_group(self, group: Group, name: str | None = None
                          ) -> "Communicator":
        """MPI_Comm_create_from_group-style: the given group plus the
        complement as a second group (every device must belong somewhere on
        an SPMD machine)."""
        rest = [r for r in range(self.axis_size) if group.rank_of_global(r) < 0]
        parts = [group] + ([Group(rest)] if rest else [])
        return Communicator(self.mesh, self.axis, parts, name)

    # -- ULFM (MPIX_Comm_revoke / _shrink / _agree / _failure_ack) --------

    def bind_failure_state(self, state) -> "Communicator":
        """Attach a host-plane :class:`~zhpe_ompi_tpu.ft.ulfm
        .FailureState` so shrink()/agree()/failure_ack() can consult the
        live failure view (the host plane is where processes die; the
        device mesh is static under the single controller)."""
        self._ft_state = state
        return self

    @property
    def ft_state(self):
        return getattr(self, "_ft_state", None)

    def revoke(self) -> None:
        """MPIX_Comm_revoke: poison this communicator's cid — every
        pending and future operation on it raises ``Revoked``.  Under
        the single controller every device-plane operation dispatches
        through this one object, so the process-global registry (comm
        cids are monotonic, never reused) is the complete revocation
        view; the host-plane endpoint cid space is a different
        numbering and is revoked through its own FailureState."""
        from ..ft import ulfm

        ulfm.revoke_cid(self.cid)
        mca_output.verbose(5, _stream, "revoked %s (cid=%d)",
                           self.name, self.cid)

    def is_revoked(self) -> bool:
        from ..ft import ulfm

        return ulfm.is_revoked(self.cid)

    def _failed_ranks(self, failed) -> set[int]:
        if failed is None:
            if self.ft_state is None:
                raise errors.ArgError(
                    "no failed ranks given and no failure state bound "
                    "(bind_failure_state)"
                )
            failed = self.ft_state.failed()
        return {int(r) for r in failed}

    def shrink(self, failed=None, name: str | None = None
               ) -> "Communicator":
        """MPIX_Comm_shrink: a fresh communicator (new, unrevoked cid)
        whose primary group is the survivors, ordered by old rank.
        `failed` defaults to the bound failure state's view."""
        dead = self._failed_ranks(failed)
        survivors = [r for r in range(self.axis_size) if r not in dead]
        if not survivors:
            raise errors.ProcFailed("no survivors to shrink onto",
                                    failed_ranks=dead)
        new = self.create_from_group(
            Group(survivors), name or f"{self.name}_shrunk"
        )
        if self.ft_state is not None:
            new.bind_failure_state(self.ft_state)
        return new

    def agree(self, flag: bool = True, contributions=None,
              failed=None) -> bool:
        """MPIX_Comm_agree, single-controller form: AND-reduce `flag`
        (and optional per-rank `contributions`, a dict or sequence) over
        the LIVE ranks — dead participants are excluded, so agreement
        completes despite their death."""
        if failed is None:
            failed = (self.ft_state.failed()
                      if self.ft_state is not None else ())
        dead = {int(r) for r in failed}
        acc = bool(flag)
        if contributions is not None:
            items = (contributions.items()
                     if isinstance(contributions, dict)
                     else enumerate(contributions))
            for rank, contrib in items:
                if int(rank) in dead:
                    continue
                acc = acc and bool(contrib)
        return acc

    def failure_ack(self) -> None:
        """MPIX_Comm_failure_ack on the bound failure state."""
        if self.ft_state is None:
            raise errors.ArgError("no failure state bound")
        self.ft_state.ack()

    def failure_get_acked(self) -> Group:
        """MPIX_Comm_failure_get_acked: acknowledged-failed ranks."""
        if self.ft_state is None:
            raise errors.ArgError("no failure state bound")
        return Group(sorted(self.ft_state.acked()))

    # -- collective dispatch --------------------------------------------

    @property
    def coll(self) -> dict:
        """Per-communicator collective table, composed on first use
        (mca_coll_base_comm_select semantics)."""
        if self._coll is None:
            from ..coll.framework import comm_select

            self._coll = comm_select(self)
        return self._coll

    def _coll_call(self, opname: str, *args, **kwargs):
        # errors at the dispatch boundary route through the attached
        # errhandler (OMPI_ERRHANDLER_INVOKE at the binding layer)
        return self._errhandler_guard(
            self._coll_call_inner, opname, *args, **kwargs
        )

    def _coll_call_inner(self, opname: str, *args, **kwargs):
        if self.is_revoked():
            raise errors.Revoked(
                f"{opname} on revoked communicator {self.name}",
                cid=self.cid,
            )
        entry = self.coll.get(opname)
        if entry is None:
            raise errors.UnsupportedError(
                f"no coll component provides {opname} for {self.name}"
            )
        fn, comp_name = entry
        # PMPI interposition point (the weak-symbol MPI_X = PMPI_X analog,
        # ompi/mpi/c/send.c:37-39): tools see the call before the MCA path
        from ..tools import pmpi

        with self._scope(opname):
            if pmpi.active():
                return pmpi.dispatch(opname, self, fn, args, kwargs)
            return fn(self, *args, **kwargs)

    def _scope(self, opname: str):
        """Names the device ops of one call in their HLO metadata,
        ``zmpi.coll.<axis>.<name>.<op>``, whichever component serves it,
        so a profile gives each collective to its communicator."""
        return jax.named_scope(f"zmpi.coll.{self.axis}.{self.name}.{opname}")

    def set_info(self, info) -> None:
        """MPI_Comm_set_info: replace the hint set."""
        self.info = info_mod.coerce(info)

    def allreduce(self, x, op=None, **kw):
        from .. import ops as _ops

        return self._coll_call("allreduce", x, op or _ops.SUM, **kw)

    def reduce(self, x, op=None, root: int = 0, **kw):
        from .. import ops as _ops

        return self._coll_call("reduce", x, op or _ops.SUM, root, **kw)

    def bcast(self, x, root: int = 0, **kw):
        return self._coll_call("bcast", x, root, **kw)

    def barrier(self, token=None):
        return self._coll_call("barrier", token)

    def allgather(self, x, **kw):
        return self._coll_call("allgather", x, **kw)

    def alltoall(self, x, **kw):
        return self._coll_call("alltoall", x, **kw)

    def reduce_scatter(self, x, op=None, **kw):
        from .. import ops as _ops

        return self._coll_call("reduce_scatter", x, op or _ops.SUM, **kw)

    def reduce_scatter_block(self, x, op=None, **kw):
        from .. import ops as _ops

        return self._coll_call(
            "reduce_scatter_block", x, op or _ops.SUM, **kw
        )

    def alltoallv(self, x, counts, **kw):
        """MPI_Alltoallv with a static count matrix: ``counts[i][j]`` rows
        go from rank i to rank j; ``x`` is (size, max_send, ...) padded
        blocks, result is (size, max_recv, ...) padded blocks."""
        return self._coll_call("alltoallv", x, counts, **kw)

    def scan(self, x, op=None, **kw):
        from .. import ops as _ops

        return self._coll_call("scan", x, op or _ops.SUM, **kw)

    def exscan(self, x, op=None, **kw):
        from .. import ops as _ops

        return self._coll_call("exscan", x, op or _ops.SUM, **kw)

    def gather(self, x, root: int = 0, **kw):
        return self._coll_call("gather", x, root, **kw)

    def scatter(self, x, root: int = 0, **kw):
        return self._coll_call("scatter", x, root, **kw)

    def allgatherv(self, x, counts, **kw):
        return self._coll_call("allgatherv", x, counts, **kw)

    # -- point-to-point (SPMD plane) -------------------------------------

    def shift(self, x, offset: int, wrap: bool = True):
        """Uniform-shift sendrecv (MPI_Sendrecv in a ring / MPI_Cart_shift):
        every rank sends its buffer to (rank+offset) and receives from
        (rank-offset).  With wrap=False the ends get zeros (MPI_PROC_NULL)."""
        from ..pt2pt import spmd as _spmd

        with self._scope("shift"):
            return _spmd.shift(self, x, offset, wrap=wrap)

    def permute(self, x, dest_of: list[int]):
        """General static sendrecv: dest_of[i] is where comm rank i's buffer
        goes (-1 = sends nowhere); ranks nobody targets receive zeros."""
        from ..pt2pt import spmd as _spmd

        with self._scope("permute"):
            return _spmd.sendrecv(self, x, dest_of)

    def ppermute(self, x, pairs: list[tuple[int, int]]):
        """Comm-relative collective permute (the BTL of the SPMD plane)."""
        from ..pt2pt import spmd as _spmd

        with self._scope("ppermute"):
            return _spmd.ppermute(self, x, pairs)

    # -- host-side execution helper --------------------------------------

    def run(self, fn, *args, in_specs=None, out_specs=None):
        """Run `fn(*args)` under shard_map over this comm's mesh with data
        sharded along the comm axis (dim 0 by default).  Convenience for
        tests/examples; real applications compose shard_map themselves."""
        if in_specs is None:
            in_specs = P(self.axis)
        if out_specs is None:
            out_specs = P(self.axis)
        mapped = jax.shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return mapped(*args)

    def device_put_sharded(self, x, spec=None):
        """Place a host array onto the mesh, sharded along the comm axis."""
        sharding = NamedSharding(self.mesh, spec or P(self.axis))
        return jax.device_put(x, sharding)

    def __repr__(self):  # pragma: no cover
        part = f", groups={len(self.partition)}" if self.is_partitioned else ""
        return f"Communicator({self.name}, axis={self.axis}{part})"
