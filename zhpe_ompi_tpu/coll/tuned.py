"""coll/tuned — the decision layer.

Re-design of ``ompi/mca/coll/tuned`` (SURVEY.md §2.4): picks an algorithm per
(operation, message size, comm size).  Three differences from the reference,
all TPU-native:

- decisions happen at **trace time** (shapes are static under jit), so the
  decision tree costs zero at execution — the reference pays it per call
  (``coll_tuned_decision_fixed.c:45-85``);
- algorithm 0 ("xla") hands the op to the XLA-native component path — the
  normally-best choice, analogous to tuned delegating to hardware
  collectives;
- forced algorithms are MCA vars holding *names*, not magic integers:
  ``ZMPI_MCA_coll_tuned_allreduce_algorithm=ring`` (the reference's
  ``coll_tuned_allreduce_decision.c:37-46`` enum, readable).

Dynamic-rules files (``coll_tuned_dynamic_file.c``) are supported in a
simplified form: ``coll_tuned_dynamic_rules`` names a file of
``<op> <comm_size_min> <msg_bytes_min> <algorithm>`` lines; the most specific
matching line wins.  Since PR 19 the loader is :mod:`.ztable`, which adds
``[topology n_hosts n_domains ranks_per_domain]`` sections (headerless
files keep their PR 6 meaning) and a second table source ahead of the
file var: a ztune-swept table served from the DVM's PMIx store when
``ZMPI_PMIX`` is set.  The decide ladder is therefore store table ->
file table -> builtin fixed decisions.
"""

from __future__ import annotations

import os


from ..mca import output as mca_output
from ..mca import var as mca_var
from . import algorithms as alg
from . import tpu as xla_mod
from .framework import CollComponent, CollModule

_stream = mca_output.open_stream("coll_tuned")

ALLREDUCE_ALGS = {
    # forced-alg name surface mirrors coll_tuned_allreduce_decision.c:37-46
    "xla": None,  # delegate to the XLA-native path
    "linear": alg.allreduce_linear,
    "nonoverlapping": alg.allreduce_nonoverlapping,
    "recursive_doubling": alg.allreduce_recursive_doubling,
    "ring": alg.allreduce_ring,
    "segmented_ring": alg.allreduce_segmented_ring,
    "rabenseifner": alg.allreduce_rabenseifner,
}
BCAST_ALGS = {
    # cf. coll_tuned_bcast_decision.c:37-49
    "xla": None,
    "linear": alg.bcast_linear,
    "chain": alg.bcast_chain,
    "pipeline": alg.bcast_pipeline,
    "split_binary": alg.bcast_split_binary,
    "binary": alg.bcast_binary,
    "binomial": alg.bcast_binomial,
    "knomial": alg.bcast_knomial,
    "scatter_allgather": alg.bcast_scatter_allgather,
}
REDUCE_ALGS = {
    # cf. coll_tuned_reduce_decision.c
    "xla": None,
    "linear": alg.reduce_linear,
    "chain": alg.reduce_chain,
    "pipeline": alg.reduce_pipeline,
    "binary": alg.reduce_binary,
    "binomial": alg.reduce_binomial,
    "in_order_binary": alg.reduce_in_order_binary,
    "rabenseifner": alg.reduce_rabenseifner,
}
ALLGATHER_ALGS = {
    # cf. coll_tuned_allgather_decision.c
    "xla": None,
    "linear": alg.allgather_linear,
    "bruck": alg.allgather_bruck,
    "recursive_doubling": alg.allgather_recursive_doubling,
    "ring": alg.allgather_ring,
    "neighbor_exchange": alg.allgather_neighbor_exchange,
    "two_proc": alg.allgather_two_proc,
}
ALLTOALL_ALGS = {
    # cf. coll_tuned_alltoall_decision.c:35-43
    "xla": None,
    "linear": alg.alltoall_linear,
    "pairwise": alg.alltoall_pairwise,
    "bruck": alg.alltoall_bruck,
    "linear_sync": alg.alltoall_linear_sync,
    "two_proc": alg.alltoall_two_proc,
}
REDUCE_SCATTER_ALGS = {
    "xla": None,
    "nonoverlapping": alg.reduce_scatter_nonoverlapping,
    "recursive_halving": alg.reduce_scatter_recursive_halving,
    "ring": alg.reduce_scatter_ring,
    "butterfly": alg.reduce_scatter_butterfly,
    "linear": alg.reduce_scatter_block_linear,
}
REDUCE_SCATTER_BLOCK_ALGS = {
    # cf. coll_base_reduce_scatter_block.c:55,128,326,567
    "xla": None,
    "linear": alg.reduce_scatter_block_linear,
    "recursive_doubling": alg.reduce_scatter_block_recursive_doubling,
    "recursive_halving": alg.reduce_scatter_block_recursive_halving,
    "butterfly": alg.reduce_scatter_block_butterfly,
}
BARRIER_ALGS = {
    # cf. coll_base_barrier.c:100,172,253,291,330,404
    "xla": None,
    "linear": alg.barrier_linear,
    "double_ring": alg.barrier_double_ring,
    "recursive_doubling": alg.barrier_recursive_doubling,
    "bruck": alg.barrier_dissemination,
    "two_proc": alg.barrier_two_proc,
    "tree": alg.barrier_tree,
}
SCAN_ALGS = {
    "linear": alg.scan_linear,
    "recursive_doubling": alg.scan_recursive_doubling,
}
EXSCAN_ALGS = {
    "linear": alg.exscan_linear,
    "recursive_doubling": alg.exscan_recursive_doubling,
}
GATHER_ALGS = {
    # cf. coll_base_gather.c:41,208
    "xla": None,
    "binomial": alg.gather_binomial,
    "linear_sync": alg.gather_linear_sync,
    "ring": alg.gather_ring,
}
SCATTER_ALGS = {
    # cf. coll_base_scatter.c:63,285
    "xla": None,
    "binomial": alg.scatter_binomial,
    "linear": alg.scatter_linear,
}
ALLGATHERV_ALGS = {
    "xla": None,
    "concat": alg.allgatherv_concat,
}
ALLTOALLV_ALGS = {
    "xla": None,
    "pairwise": alg.alltoallv_padded,
}

_ALG_TABLES = {
    "allreduce": ALLREDUCE_ALGS,
    "bcast": BCAST_ALGS,
    "reduce": REDUCE_ALGS,
    "allgather": ALLGATHER_ALGS,
    "alltoall": ALLTOALL_ALGS,
    "reduce_scatter": REDUCE_SCATTER_ALGS,
    "reduce_scatter_block": REDUCE_SCATTER_BLOCK_ALGS,
    "barrier": BARRIER_ALGS,
    "scan": SCAN_ALGS,
    "exscan": EXSCAN_ALGS,
    "gather": GATHER_ALGS,
    "scatter": SCATTER_ALGS,
    "allgatherv": ALLGATHERV_ALGS,
    "alltoallv": ALLTOALLV_ALGS,
}

# ops whose first positional arg is the reduction op
_OPS_WITH_REDUCTION = (
    "allreduce", "reduce", "reduce_scatter", "reduce_scatter_block",
    "scan", "exscan",
)

# Decision thresholds (bytes); MCA-tunable.  Provenance (round 3): the
# committed sweep benchmarks/baseline_cpu8.json (8-virtual-CPU loopback
# mesh, benchmarks/capture_baseline.py) measures the algorithmic
# crossovers: allreduce recursive_doubling beats ring below ~256KB-1MB
# and ring wins from ~1MB up (16MB: ring 246ms vs rd 298ms); bcast
# binomial overtakes the latency-optimal k-nomial in the same band.
# These agree with the reference's historical 10KB/1MB switch points
# (coll_tuned_decision_fixed.c:53,73), so the defaults keep that order of
# magnitude.  On the loopback mesh the XLA-native path wins at EVERY
# size (no wire: its extra bytes are shared-memory copies), so the
# small/large routing primarily matters on real ICI, where the p-x-bytes
# forms (masked-psum bcast, bcast+slice scatter) pay for their traffic —
# re-measure there when a multi-chip slice is available (the bench chip
# this round is single-device, where every collective is degenerate).
_DEFAULT_SMALL = 16 * 1024
_DEFAULT_LARGE = 1 * 1024 * 1024


def _register_params():
    # category derivation (tools/mpit.py): coll_tuned_* is its own
    # component family, not a scatter across the coll bucket
    mca_var.register_family("coll_tuned", "tuned")
    for opname, table in _ALG_TABLES.items():
        mca_var.register(
            f"coll_tuned_{opname}_algorithm",
            "auto",
            f"Forced algorithm for {opname}: one of "
            + ", ".join(["auto"] + list(table)),
            enum=tuple(["auto"] + list(table)),
        )
    mca_var.register(
        "coll_tuned_small_msg", _DEFAULT_SMALL,
        "Message size (bytes) below which latency-optimal algorithms win",
        type=int,
    )
    mca_var.register(
        "coll_tuned_large_msg", _DEFAULT_LARGE,
        "Message size (bytes) above which bandwidth-optimal algorithms win",
        type=int,
    )
    mca_var.register(
        "coll_tuned_dynamic_rules", "",
        "Path to a dynamic decision-rules file "
        "(<op> <comm_size_min> <msg_bytes_min> <algorithm> per line; "
        "'han' as the algorithm selects the hierarchical host path for "
        + ", ".join(sorted(_HAN_RULE_OPS)) + "; optional "
        "[topology n_hosts n_domains ranks_per_domain] sections scope "
        "rules to a topology shape — see coll/ztable.py)",
    )
    # the topology key selecting [topology ...] sections; registered by
    # coll/ztable.py at import (same default) — re-register here so the
    # MPI_T/zmpi-info surface lists it with the decision layer's vars
    mca_var.register(
        "coll_tuned_topology", "",
        "Topology key 'n_hosts:n_domains:ranks_per_domain' for tuned "
        "decision-table section matching (see coll/ztable.py)",
    )
    # the hierarchical host component's enable knob lives with the host
    # collectives (coll/host.py registers it at import); re-register
    # here so the MPI_T/zmpi-info surface lists it with the decision
    # layer's other vars even in device-only processes
    mca_var.register(
        "coll_han_enable", "auto",
        "Hierarchical (han) host collectives: auto/on/off (see "
        "coll/host.py)",
        enum=("auto", "on", "off"),
    )
    mca_var.register(
        "coll_han_numa_level", "auto",
        "Third (NUMA) topology level of the hierarchical host "
        "collectives: auto/on/off (see coll/han.py)",
        enum=("auto", "on", "off"),
    )


from ..utils.payload import payload_nbytes as _nbytes  # noqa: E402


# host-plane ops the hierarchical (coll/han) component provides: "han"
# is a valid rule-line algorithm for exactly these — the rule then
# selects the two-level schedule through coll/host.py's dispatch seam
# (the DEVICE decision below never returns it; its tables are XLA-side).
# One source of truth: the seam's own set.
from .host import HAN_OPS as _HAN_RULE_OPS  # noqa: E402
from . import ztable  # noqa: E402

# The table cache, shared with (and owned by) coll/ztable.py: keyed
# path -> ((mtime_ns, size), sections), so a rules file rewritten in
# place — exactly what ztune re-emitting a table does — reloads on the
# next decide (the PR 19 fix of the PR 6 path-only cache).  The alias
# keeps the historical invalidation idiom working:
# ``tuned._rules_cache.pop(path, None)``.
_rules_cache = ztable._file_cache


def invalidate_rules_cache() -> None:
    """Drop every cached decision-table source — file stamps AND the
    once-per-process store-served table — so the next decide() re-reads
    them.  The hook ztune (or any operator retuning a live process)
    calls after republishing a table."""
    ztable.invalidate_cache()


def _valid_rule_alg(op: str, algname: str) -> bool:
    if algname == "builtin":
        # explicit band terminator: "keep the builtin decision here" —
        # ztune's distiller emits it so a rejected cell is never covered
        # by a neighboring winner's band (decide()'s ``dyn in table``
        # check makes it fall through naturally)
        return True
    table = _ALG_TABLES.get(op)
    if table is not None and algname in table:
        return True
    return algname == "han" and op in _HAN_RULE_OPS


# install the (op, alg)-pair validator on the table plane: ztable owns
# shape parsing; WHICH algorithm names exist is this module's knowledge
ztable.set_alg_validator(_valid_rule_alg)


def _load_rules(path: str) -> list[tuple[str, int, int, str]]:
    """Parse a dynamic-rules file into the historical FLAT rule list,
    degrading LOUDLY per line (malformed / unknown-op / unknown-
    algorithm lines are reported and skipped — never raising out of the
    decision layer into a collective call).  Sectioned tables flatten
    across sections; topology-aware resolution goes through
    :func:`ztable.resolve_rule` instead."""
    try:
        with open(path, "r", encoding="utf-8") as fh:
            text = fh.read()
    except OSError as e:
        mca_output.emit(
            _stream,
            "coll_tuned_dynamic_rules file %r unreadable (%s); "
            "falling back to fixed decisions", path, e,
        )
        return []
    return [
        rule
        for _key, rules, _geom in ztable.parse_table(text, origin=path)
        for rule in rules
    ]


def _dynamic_rule(opname: str, comm_size: int, nbytes: int) -> str | None:
    """Resolve through the PR 19 table ladder: the store-served ztune
    table (when ``ZMPI_PMIX`` is set) first, then the file named by
    ``coll_tuned_dynamic_rules``, else None (fixed decisions apply).
    Topology sections match against the ``coll_tuned_topology`` key."""
    return ztable.resolve_rule(
        opname, comm_size, nbytes, ztable.job_topology_key(),
    )


def profiles() -> dict[str, str]:
    """Shipped decision profiles (coll_tuned_dynamic_file.c analogs):
    name -> absolute path, loadable via the coll_tuned_dynamic_rules
    var.  ``v5e8_ici`` is a documented UNMEASURED placeholder for a
    v5e-8 ICI ring (round-4, VERDICT Missing #4) — topology-derived
    estimates so a multi-chip deployment never silently inherits
    loopback-calibrated crossovers; replace with an on-hardware sweep."""
    pdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "profiles")
    return {
        name.rsplit(".", 1)[0]: os.path.join(pdir, name)
        for name in sorted(os.listdir(pdir))
        if name.endswith(".rules")
    }


# Largest two-rank allreduce a TPU gets as a swap.  The swap holds the
# partner's copy in a buffer of its own until the combine, which the
# in-place all-reduce does not; at gradient sizes (hundreds of MB, several
# in flight at once) that copy is HBM a full chip does not have.
_SWAP_MAX_BYTES = 64 * 1024 * 1024


def _on_tpu(comm) -> bool:
    """Whether ``comm``'s mesh holds TPU devices (a described one too)."""
    devices = getattr(getattr(comm, "mesh", None), "devices", None)
    return devices is not None and devices.flat[0].platform == "tpu"


def decide(opname: str, comm, x, op=None) -> str:
    """Pick an algorithm name for this call — all inputs static at trace
    time, mirroring coll_tuned_decision_fixed.c but at zero runtime cost."""
    table = _ALG_TABLES[opname]
    forced = mca_var.get(f"coll_tuned_{opname}_algorithm", "auto")
    if forced != "auto" and forced in table:
        return forced
    n = comm.uniform_size or 0
    nbytes = _nbytes(x)
    # Non-commutative ops must reduce in rank order: only linear preserves
    # it.  Checked BEFORE dynamic rules — a tuning profile is a
    # performance hint and must never override correctness (forced
    # algorithms remain the user's explicit responsibility, as in the
    # reference).
    if op is not None and not op.commute and opname in (
        "allreduce", "reduce", "reduce_scatter", "reduce_scatter_block",
    ):
        return "linear"
    dyn = _dynamic_rule(opname, n, nbytes)
    if dyn in table:
        return dyn
    small = mca_var.get("coll_tuned_small_msg", _DEFAULT_SMALL)
    large = mca_var.get("coll_tuned_large_msg", _DEFAULT_LARGE)
    if opname == "allreduce":
        if n == 2 and nbytes <= _SWAP_MAX_BYTES and _on_tpu(comm):
            # one swap with the partner and a local combine moves what a
            # two-rank ring moves; a TPU runs the swap (a collective
            # permute) asynchronously, start and done with independent
            # work between, where its all-reduce is one blocking op
            return "recursive_doubling"
        if op is not None and op.xla_collective:
            return "xla"
        if nbytes < small:
            return "recursive_doubling"
        if n and n & (n - 1) == 0 and nbytes >= large:
            return "rabenseifner"
        return "ring"
    if opname == "bcast":
        if nbytes < small:
            return "xla"
        return "scatter_allgather" if nbytes >= large else "binomial"
    if opname == "reduce":
        if op is not None and op.xla_collective:
            return "xla"
        return "binomial"
    if opname in ("scatter", "gather"):
        # The XLA forms are single-collective but move p x the payload
        # (scatter = bcast+slice, gather = allgather): right at latency-
        # bound sizes, wrong shape for large tensors — route those to the
        # log(p) ppermute trees (round-3 fix of the masked-psum weakness).
        return "xla" if nbytes < large else "binomial"
    if opname in ("allgather", "alltoall", "barrier",
                  "allgatherv", "alltoallv"):
        # XLA's native collectives are optimal on ICI at every size; the
        # algorithmic variants exist for forced selection and benchmarking,
        # not the auto path.
        return "xla"
    if opname in ("reduce_scatter", "reduce_scatter_block"):
        if op is not None and op.xla_collective == "psum":
            return "xla"
        if n and n & (n - 1) == 0:
            return "recursive_halving"
        return "ring" if opname == "reduce_scatter" else "recursive_doubling"
    if opname in ("scan", "exscan"):
        return "recursive_doubling"
    return next(iter(table))


def _dispatch(opname):
    def fn(comm, *args, **kwargs):
        x = args[0] if args else kwargs.get("token")
        algname = decide(
            opname, comm, x,
            op=(args[1] if opname in _OPS_WITH_REDUCTION and len(args) > 1
                else None),
        )
        mca_output.verbose(
            9, _stream, "%s size=%s -> %s", opname,
            comm.uniform_size, algname,
        )
        impl = _ALG_TABLES[opname][algname]
        if impl is None:
            impl = getattr(xla_mod, opname)
        return impl(comm, *args, **kwargs)

    return fn


class TunedCollComponent(CollComponent):
    name = "tuned"
    default_priority = 50

    def register_params(self) -> None:
        _register_params()

    def comm_query(self, comm) -> CollModule | None:
        if comm.uniform_size is None:
            return None  # algorithmic layer needs uniform groups
        _register_params()
        return CollModule(
            **{opname: _dispatch(opname) for opname in _ALG_TABLES},
        )
