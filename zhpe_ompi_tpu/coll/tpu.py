"""coll/tpu — XLA-native collectives over the ICI mesh.

The inversion of the reference's ``coll/cuda`` (SURVEY.md §2.4): where
``coll_cuda_allreduce.c:30-69`` stages device buffers to the host and
delegates to a CPU component, this component keeps data in HBM and lowers
every operation to the XLA collective the TPU executes natively on ICI —
``psum``/``pmax``/``pmin``, ``all_gather``, ``all_to_all``, ``psum_scatter``,
with ``axis_index_groups`` carrying split sub-communicators in one op.

Ops without a native XLA reduction (PROD, bitwise, MINLOC/MAXLOC, user ops)
fall back to the algorithmic layer's recursive doubling — the same shape the
reference uses when hardware collectives don't cover an op.  Logical ops are
re-expressed arithmetically (LAND = pmin(x≠0), LOR = pmax(x≠0),
LXOR = psum(x≠0) mod 2) so they still ride a single native collective.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .. import ops as _ops
from . import algorithms as alg
from .framework import CollComponent, CollModule


# -- device liveness probe (the killable-child half) ------------------------
#
# The tiny deadline-bounded psum the device-plane fault loop runs
# (parallel/mesh.py arms it through utils/deadline.run_probe, which
# prepends the internal-watchdog preamble): a wedged TPU participant
# surfaces as an indefinite XLA hang, so the probe must live where it
# can be killed — a subprocess — and die from the inside at its
# deadline even if the outer kill is delayed.  ``ZMPI_DEVICE_WEDGE=1``
# is the fault-injection hook (ft/inject.py's wedge_device exports it):
# the child wedges INSIDE the collective region, exactly where a real
# wedge holds the thread, so the whole classification ladder is
# drillable in CI without real hardware loss.

#: structured wedge-injection hook read by the probe child (and by the
#: armed guard's owning process — ft/inject.py documents the contract).
#: Value WEDGE_ALL wedges every probe child of the process (the
#: real-process drill); a rank number wedges only probes launched FOR
#: that rank (shared-process thread drills: the prober exports
#: PROBE_RANK_ENV, so a healthy survivor's probe never inherits the
#: victim's wedge).  The all-sentinel is deliberately NON-NUMERIC — a
#: rank-number value must never double as the process-wide switch
#: (wedging rank 1 must not wedge rank 0's probes)
WEDGE_ENV = "ZMPI_DEVICE_WEDGE"
WEDGE_ALL = "all"
PROBE_RANK_ENV = "ZMPI_PROBE_RANK"

PROBE_SRC = (
    "import json\n"
    "import jax\n"
    "import jax.numpy as jnp\n"
    "d=jax.devices()\n"
    f"_w=os.environ.get({WEDGE_ENV!r})\n"
    f"if _w is not None and _w in ({WEDGE_ALL!r}, os.environ.get("
    f"{PROBE_RANK_ENV!r}, '')):\n"
    "    time.sleep(3600)  # the injected wedge: hang mid-collective\n"
    "x=jnp.arange(float(len(d)))\n"
    "try:\n"
    "    s=jax.pmap(lambda v: jax.lax.psum(v,'i'),axis_name='i')(x)\n"
    "    total=float(jax.device_get(s)[0])\n"
    "except Exception:\n"
    "    # single-device/odd topology: a per-device round trip still\n"
    "    # proves the plane answers (the reduced claim, reported as is)\n"
    "    total=float(jax.device_get(jax.device_put(x[0],d[-1])))\n"
    "print(json.dumps({'n':len(d),'platform':d[0].platform,"
    "'psum':total}))\n"
)


def _groups(comm):
    return comm.index_groups


def _psum(comm, x):
    return lax.psum(x, comm.axis, axis_index_groups=_groups(comm))


def _pmax(comm, x):
    return lax.pmax(x, comm.axis, axis_index_groups=_groups(comm))


def _pmin(comm, x):
    return lax.pmin(x, comm.axis, axis_index_groups=_groups(comm))


def allreduce(comm, x, op):
    name = op.name
    if name == "MPI_SUM":
        return _psum(comm, x)
    if name == "MPI_MAX":
        return _pmax(comm, x)
    if name == "MPI_MIN":
        return _pmin(comm, x)
    if name == "MPI_LAND":
        return _pmin(comm, (x != 0).astype(jnp.int32)).astype(x.dtype)
    if name == "MPI_LOR":
        return _pmax(comm, (x != 0).astype(jnp.int32)).astype(x.dtype)
    if name == "MPI_LXOR":
        return (_psum(comm, (x != 0).astype(jnp.int32)) % 2).astype(x.dtype)
    # PROD / bitwise / MINLOC / MAXLOC / user ops: algorithmic path
    return alg.allreduce_recursive_doubling(comm, x, op)


def reduce(comm, x, op, root=0):
    # SPMD: computing the allreduce everywhere IS the fastest reduce on an
    # ICI mesh (result significant at root, per MPI semantics)
    return allreduce(comm, x, op)


def bcast(comm, x, root=0):
    # one native collective: zero every contribution but root's and all-reduce
    rank = comm.rank()
    contrib = jax.tree.map(
        lambda a: jnp.where(rank == root, a, jnp.zeros_like(a)), x
    )
    return jax.tree.map(lambda a: _psum(comm, a), contrib)


def barrier(comm, token=None):
    # alg._barrier_token ties the wire payload to the caller's token without
    # a foldable *0; _seal_token zeroes the psum result the same way
    return alg._seal_token(_psum(comm, alg._barrier_token(comm, token)))


def allgather(comm, x):
    x = alg._stack_shape(x)
    return lax.all_gather(
        x, comm.axis, axis_index_groups=_groups(comm), tiled=True
    )


def allgatherv(comm, x, counts):
    n = comm.size
    mx = max(counts)
    pad = mx - x.shape[0]
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    g = lax.all_gather(x, comm.axis, axis_index_groups=_groups(comm))
    parts = [g[i, : counts[i]] for i in range(n)]
    return jnp.concatenate(parts, axis=0)


def alltoall(comm, x):
    n = comm.size
    if x.shape[0] % n:
        from ..core import errors

        raise errors.CountError(
            f"alltoall needs dim0 divisible by comm size {n}"
        )
    return lax.all_to_all(
        x, comm.axis, split_axis=0, concat_axis=0,
        axis_index_groups=_groups(comm), tiled=True,
    )


def reduce_scatter(comm, x, op):
    if op.name == "MPI_SUM":
        return lax.psum_scatter(
            x, comm.axis, scatter_dimension=0,
            axis_index_groups=_groups(comm), tiled=True,
        )
    return alg.reduce_scatter_recursive_halving(comm, x, op)


def reduce_scatter_block(comm, x, op):
    # MPI_Reduce_scatter_block (equal counts) — the contract psum_scatter
    # implements natively
    return reduce_scatter(comm, x, op)


def alltoallv(comm, x, counts):
    """Padded alltoallv on the native all_to_all: x is (n, max_send, ...)
    blocks, counts the n x n static matrix; validation, padding and
    count-masking are shared with the algorithmic transport
    (alg.alltoallv_prepare — cf. coll_base_alltoallv.c:125)."""
    blocks, _ = alg.alltoallv_prepare(comm, x, counts)
    return lax.all_to_all(
        blocks, comm.axis, split_axis=0, concat_axis=0,
        axis_index_groups=_groups(comm), tiled=False,
    )


def scan(comm, x, op):
    return alg.scan_recursive_doubling(comm, x, op)


def exscan(comm, x, op):
    return alg.exscan_recursive_doubling(comm, x, op)


def gather(comm, x, root=0):
    return allgather(comm, x)


def scatter(comm, x, root=0):
    # take own block of root's buffer after a single-collective bcast
    n = comm.size
    full = bcast(comm, x, root)
    buf, _ = alg._chunked(full, n)
    return jnp.take(buf, comm.rank(), axis=0)


class TpuCollComponent(CollComponent):
    # Priority 40 < tuned's 50: the decision layer is the default entry point
    # (mirroring the reference, where tuned outranks basic/others) and its
    # "xla" algorithm delegates here for the cases where hardware collectives
    # win — which is most of them.  `--mca coll tpu` selects this component
    # directly, bypassing decisions.
    name = "tpu"
    default_priority = 40

    def available(self) -> bool:
        return True  # XLA collectives exist on every backend

    def comm_query(self, comm) -> CollModule:
        mod = CollModule(
            allreduce=allreduce,
            reduce=reduce,
            bcast=bcast,
            barrier=barrier,
            allgather=allgather,
            allgatherv=allgatherv,
            alltoall=alltoall,
            alltoallv=alltoallv,
            reduce_scatter=reduce_scatter,
            reduce_scatter_block=reduce_scatter_block,
            scan=scan,
            exscan=exscan,
            gather=gather,
            scatter=scatter,
        )
        if comm.uniform_size is None:
            # non-uniform partitions: only ops whose XLA form takes
            # axis_index_groups with unequal group sizes remain
            mod.scan = None
            mod.exscan = None
            mod.scatter = None
            mod.gather = None
            mod.allgather = None
            mod.allgatherv = None
            mod.alltoall = None
            mod.alltoallv = None
            mod.reduce_scatter = None
            mod.reduce_scatter_block = None
        return mod
