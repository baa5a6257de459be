"""Collective checkpoint I/O plane (``io/ckptio.py``): sharded
two-phase collective write, manifest/digest integrity, incremental
(delta) checkpoints, deadline-bounded writers, and the crash-seam
matrix — kill an aggregator mid-exchange, kill a writer mid-stream,
corrupt a shard on disk, restore under a concurrent rank failure —
over the thread plane here and over real DVM processes in the
slow-marked drill class (reference: the ompio/fcoll two-phase +
fbtl stack, re-shaped for recovery time as a first-class metric)."""

import os
import threading
import time

import numpy as np
import pytest

import jax
from zhpe_ompi_tpu.core import errhandler as errh
from zhpe_ompi_tpu.core import errors
from zhpe_ompi_tpu.ft import recovery, ulfm
from zhpe_ompi_tpu.ft.inject import FaultPlan, corrupt_ckpt_shard
from zhpe_ompi_tpu.io import ckptio
from zhpe_ompi_tpu.io.ckptio import (
    CheckpointWriteError,
    CollectiveCheckpointer,
)
from zhpe_ompi_tpu.mca import var as mca_var
from zhpe_ompi_tpu.runtime import flightrec, spc

from test_ulfm import run_tcp_ft


def _state(scale=1.0):
    """A small replicated SPMD pytree (dict flattens keys sorted:
    leaf 0 = 'b', leaf 1 = 'w')."""
    return {
        "b": (np.arange(16, dtype=np.float32) * scale),
        "w": (np.arange(64, dtype=np.float32) * scale + 1.0),
    }


def _assert_tree_equal(got, want):
    gl, gt = jax.tree_util.tree_flatten(got)
    wl, wt = jax.tree_util.tree_flatten(want)
    assert gt == wt
    for g, w in zip(gl, wl):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


class TestManifestAndDigest:
    """Single-writer mode: the manifest/digest/delta machinery with no
    exchange (ep=None — same code path the degenerate 1-rank job
    takes)."""

    def test_roundtrip_and_manifest_shape(self, tmp_path):
        ck = CollectiveCheckpointer(str(tmp_path))
        state = _state()
        ck.save(3, state, blocking=True)
        assert ck.all_steps() == [3]
        got, step = ck.restore()
        assert step == 3
        _assert_tree_equal(got, state)
        m = ckptio._read_manifest(str(tmp_path / "step_3"))
        assert m is not None and m["complete"]
        assert m["world"] == 1 and m["n_leaves"] == 2
        assert len(m["shards"]) == 2
        for e in m["shards"]:
            assert len(e["digest"]) == 32  # blake2b-128 hex
        # hygiene: nothing in flight, nothing torn, nothing orphaned
        assert not ck.in_flight
        assert ckptio.live_writer_threads() == []
        assert ckptio.orphaned_shard_temps() == []
        assert ckptio.incomplete_manifests() == []

    def test_async_save_overlaps_then_drains(self, tmp_path):
        """The snapshot-then-stream overlap: save() returns while the
        stream drains (in_flight), wait() joins it, and the begin/
        commit flightrec events bracket the stream."""
        ck = CollectiveCheckpointer(str(tmp_path))
        state = _state()
        release = threading.Event()

        def slow_write(seam, rank, **info):
            if seam == "write":
                release.wait(5.0)

        remove = ckptio.install_fault_hook(slow_write)
        flightrec.arm()
        try:
            ck.save(1, state, blocking=False)
            assert ck.in_flight  # the stream is parked on the hook
            release.set()
            ck.wait()
            assert not ck.in_flight
            kinds = [e["type"] for e in flightrec.window()]
        finally:
            flightrec.disarm()
            remove()
        assert flightrec.CKPT_BEGIN in kinds
        assert flightrec.CKPT_COMMIT in kinds
        _assert_tree_equal(ck.restore()[0], state)
        assert ckptio.live_writer_threads() == []

    def test_torn_shard_rejected_loudly_degrades(self, tmp_path):
        """corrupt-shard-on-disk seam: digest verification rejects the
        step BEFORE any unpickle (ckpt_integrity_rejects), the walk
        degrades to the previous complete step
        (ckpt_degraded_restores) — never a silent acceptance."""
        ck = CollectiveCheckpointer(str(tmp_path))
        ck.save(1, _state(1.0), blocking=True)
        ck.save(2, _state(2.0), blocking=True)
        corrupt_ckpt_shard(str(tmp_path), step=2, leaf=1, rank=0)
        rejects0 = spc.read("ckpt_integrity_rejects")
        degraded0 = spc.read("ckpt_degraded_restores")
        got, step = ck.restore()
        assert step == 1
        _assert_tree_equal(got, _state(1.0))
        assert spc.read("ckpt_integrity_rejects") > rejects0
        assert spc.read("ckpt_degraded_restores") == degraded0 + 1
        # naming the torn step explicitly is a typed failure, not a
        # silent fallback
        with pytest.raises(errors.ArgError):
            ck.restore(step=2)

    def test_delta_checkpoint_relinks_unchanged_shards(self, tmp_path):
        """Incremental checkpoints: a shard whose digest matches the
        previous complete manifest is skipped and its manifest entry
        re-links the previous step's bytes."""
        ck = CollectiveCheckpointer(str(tmp_path))
        s1 = _state(1.0)
        ck.save(1, s1, blocking=True)
        s2 = {"b": s1["b"], "w": s1["w"] + 5.0}  # only 'w' changes
        skips0 = spc.read("ckpt_delta_skips")
        ck.save(2, s2, blocking=True)
        assert spc.read("ckpt_delta_skips") == skips0 + 1
        m2 = ckptio._read_manifest(str(tmp_path / "step_2"))
        by_leaf = {e["leaf"]: e for e in m2["shards"]}
        assert by_leaf[0]["file"].startswith("step_1/")  # re-linked
        assert by_leaf[1]["file"].startswith("step_2/")  # re-written
        got, step = ck.restore()
        assert step == 2
        _assert_tree_equal(got, s2)

    def test_delta_descendant_of_torn_base_also_rejected(self, tmp_path):
        """A delta step SHARES bytes with its base: corrupting the
        referenced region must tear both, and restore degrades past
        the whole chain to an untainted step."""
        ck = CollectiveCheckpointer(str(tmp_path))
        ck.save(0, _state(3.0), blocking=True)  # untainted ancestor
        ck.save(1, _state(1.0), blocking=True)
        ck.save(2, _state(1.0), blocking=True)  # all-skip delta of 1
        corrupt_ckpt_shard(str(tmp_path), step=2, leaf=0, rank=0)
        got, step = ck.restore()
        assert step == 0
        _assert_tree_equal(got, _state(3.0))

    def test_delta_disabled_rewrites_everything(self, fresh_vars,
                                                tmp_path):
        mca_var.set_var("ckpt_delta", 0)
        ck = CollectiveCheckpointer(str(tmp_path))
        ck.save(1, _state(), blocking=True)
        skips0 = spc.read("ckpt_delta_skips")
        ck.save(2, _state(), blocking=True)  # identical bytes
        assert spc.read("ckpt_delta_skips") == skips0
        m2 = ckptio._read_manifest(str(tmp_path / "step_2"))
        assert all(e["file"].startswith("step_2/")
                   for e in m2["shards"])

    def test_retention_keeps_delta_referenced_steps(self, tmp_path):
        """Retention must not tear incremental descendants: a step a
        retained manifest still delta-references survives the keep
        window; an unreferenced one is reaped."""
        ck = CollectiveCheckpointer(str(tmp_path), keep=2)
        for step in (1, 2, 3, 4):
            ck.save(step, _state(), blocking=True)  # 2..4 delta-ref 1
        steps = ck.all_steps()
        assert 3 in steps and 4 in steps  # the keep window
        assert 1 in steps                 # still referenced
        assert 2 not in steps             # reaped
        _assert_tree_equal(ck.restore()[0], _state())

    def test_incomplete_step_is_invisible_and_healable(self, tmp_path):
        """A crash before the manifest rename leaves a step directory
        with no complete manifest: restore never sees it, the hygiene
        registry names it, heal() removes it."""
        ck = CollectiveCheckpointer(str(tmp_path))
        ck.save(1, _state(), blocking=True)

        def die_at_manifest(seam, rank, **info):
            if seam == "manifest":
                raise OSError("injected crash before the rename")

        remove = ckptio.install_fault_hook(die_at_manifest)
        try:
            with pytest.raises(errors.MpiError):
                ck.save(2, _state(2.0), blocking=True)
        finally:
            remove()
        assert ck.all_steps() == [1]  # step 2 never became complete
        torn = ckptio.incomplete_manifests()
        assert any(p.endswith("step_2") for p in torn)
        got, step = ck.restore()  # restore heals, then degrades
        assert step == 1
        assert ckptio.incomplete_manifests() == []
        _assert_tree_equal(got, _state())


class TestDeadlineBoundedWriter:
    """utils/deadline.Watchdog bounds every fbtl stream write: a wedge
    becomes a bounded retry, an exhausted budget becomes a typed
    CheckpointWriteError — never a hang."""

    def test_wedged_attempt_expires_then_retry_lands(self, fresh_vars,
                                                     tmp_path):
        mca_var.set_var("ckpt_write_deadline_s", 0.15)
        plan = FaultPlan(seed=5).ckpt_wedge_write(0, hold_s=0.8,
                                                  times=1)
        ck = CollectiveCheckpointer(str(tmp_path))
        retries0 = spc.read("ckpt_write_retries")
        fails0 = spc.read("ckpt_write_deadline_failures")
        with plan.arm_ckpt(0):
            ck.save(1, _state(), blocking=True)
        assert spc.read("ckpt_write_retries") == retries0 + 1
        assert spc.read("ckpt_write_deadline_failures") == fails0
        _assert_tree_equal(ck.restore()[0], _state())

    def test_wedge_exhausts_budget_typed_failure(self, fresh_vars,
                                                 tmp_path):
        mca_var.set_var("ckpt_write_deadline_s", 0.1)
        mca_var.set_var("ckpt_write_retries", 1)
        plan = FaultPlan(seed=6).ckpt_wedge_write(0, hold_s=0.5,
                                                  times=8)
        ck = CollectiveCheckpointer(str(tmp_path))
        fails0 = spc.read("ckpt_write_deadline_failures")
        with plan.arm_ckpt(0):
            with pytest.raises(CheckpointWriteError):
                ck.save(1, _state(), blocking=True)
        assert spc.read("ckpt_write_deadline_failures") == fails0 + 1
        # the failed step never committed; heal clears the partial
        assert ck.all_steps() == []
        ck.heal()
        assert ckptio.incomplete_manifests() == []
        with pytest.raises(errors.ArgError):
            ck.restore()
        # let the abandoned wedged attempts drain their sleeps so the
        # session-wide writer-thread gate sees a quiet plane
        deadline = time.monotonic() + 10.0
        while ckptio.live_writer_threads():
            assert time.monotonic() < deadline
            time.sleep(0.05)

    def test_transient_write_error_is_retried(self, fresh_vars,
                                              tmp_path):
        attempts = []

        def flaky(seam, rank, **info):
            if seam == "write":
                attempts.append(info.get("attempt"))
                if len(attempts) == 1:
                    raise OSError("injected transient EIO")

        ck = CollectiveCheckpointer(str(tmp_path))
        retries0 = spc.read("ckpt_write_retries")
        remove = ckptio.install_fault_hook(flaky)
        try:
            ck.save(1, _state(), blocking=True)
        finally:
            remove()
        assert len(attempts) == 2  # failed once, landed on the retry
        assert spc.read("ckpt_write_retries") == retries0 + 1
        _assert_tree_equal(ck.restore()[0], _state())


BOOTS = {0: {"sm_boot_id": "hosta"}, 1: {"sm_boot_id": "hosta"},
         2: {"sm_boot_id": "hostb"}, 3: {"sm_boot_id": "hostb"}}


class TestCollectiveTwoPhase:
    """4 thread-plane ranks on 2 emulated hosts: the gather rides the
    han locality hierarchy (every non-aggregator sends to exactly ONE
    destination — never the flat all-pairs O(n^2)), and the survivors
    of every crash seam degrade to the newest COMPLETE step."""

    def _ckpt(self, p, tmp_path):
        ck = CollectiveCheckpointer(str(tmp_path), ep=p,
                                    check_quiescent=False,
                                    drain_timeout=30.0)
        ck.bind(p)
        return ck

    def test_wire_shape_and_collective_roundtrip(self, fresh_vars,
                                                 tmp_path):
        state = _state()
        gb0 = spc.read("ckpt_gather_bytes")
        sw0 = spc.read("ckpt_shards_written")
        bw0 = spc.read("ckpt_bytes_written")

        def prog(p):
            p.set_errhandler(errh.ERRORS_RETURN)
            ck = self._ckpt(p, tmp_path)
            ck.save(1, state, blocking=True)
            stats = dict(ck.last_stats)
            got, step = ck.restore()
            gl = jax.tree_util.tree_flatten(got)[0]
            wl = jax.tree_util.tree_flatten(state)[0]
            same = all(np.array_equal(np.asarray(g), np.asarray(w))
                       for g, w in zip(gl, wl))
            return stats, step, same

        res = run_tcp_ft(4, prog, kwargs_by_rank=BOOTS)
        for stats, step, same in res:
            assert step == 1 and same
        # aggregators (group leaders 0 and 2) send nothing; members
        # send every live shard to exactly their own host's aggregator
        assert res[0][0]["gather_sends"] == 0
        assert res[2][0]["gather_sends"] == 0
        assert res[1][0]["gather_dests"] == {0}
        assert res[3][0]["gather_dests"] == {2}
        total_sends = sum(r[0]["gather_sends"] for r in res)
        n_leaves, size, n_groups = 2, 4, 2
        assert total_sends == (size - n_groups) * n_leaves  # O(n)
        # wire-delta gate: gather bytes = the two members' chunks of
        # each leaf (b: 64 B, w: 256 B -> 16+64 per rank), nothing more
        assert spc.read("ckpt_gather_bytes") - gb0 == 2 * (16 + 64)
        assert spc.read("ckpt_shards_written") - sw0 == size * n_leaves
        assert spc.read("ckpt_bytes_written") - bw0 == 64 + 256
        m = ckptio._read_manifest(str(tmp_path / "step_1"))
        assert m["world"] == 4 and len(m["shards"]) == 8

    def test_collective_delta_sends_nothing_new(self, fresh_vars,
                                                tmp_path):
        """Second collective save of identical state: phase one marks
        every shard skipped, phase two moves ZERO gather bytes, and
        the new manifest re-links the old step's bytes."""
        state = _state()

        def prog(p):
            p.set_errhandler(errh.ERRORS_RETURN)
            ck = self._ckpt(p, tmp_path)
            ck.save(1, state, blocking=True)
            # counters are process-global across the thread ranks:
            # fence so every rank's step-1 bytes landed before reading
            p.barrier()
            gb0 = spc.read("ckpt_gather_bytes")
            ck.save(2, state, blocking=True)
            p.barrier()
            gb1 = spc.read("ckpt_gather_bytes")
            got, step = ck.restore()
            return (ck.last_stats["gather_sends"],
                    ck.last_stats["delta_skips"], gb1 - gb0, step)

        res = run_tcp_ft(4, prog, kwargs_by_rank=BOOTS)
        for sends, skips, gb_delta, step in res:
            assert sends == 0 and skips == 2
            assert step == 2
        # counters are process-global across the 4 thread ranks: the
        # whole second exchange moved zero bytes
        assert all(r[2] == 0 for r in res)
        m = ckptio._read_manifest(str(tmp_path / "step_2"))
        assert all(e["file"].startswith("step_1/") for e in m["shards"])

    def _crash_seam_prog(self, plan, tmp_path, victim):
        state0, state1 = _state(1.0), _state(2.0)

        def prog(p):
            p.set_errhandler(errh.ERRORS_RETURN)
            ck = self._ckpt(p, tmp_path)
            ck.save(0, state0, blocking=True)  # the rollback point
            with plan.arm_ckpt(p.rank, ep=p, state=p.ft_state):
                ck.save(1, state1, blocking=True)
            # survivors only from here: the victim's RankKilled
            # unwound out of the armed save above
            assert p.ft_state.wait_failed(victim, timeout=15.0)
            p.failure_ack()
            got, step = ck.restore()  # heals the torn step 1
            gl = jax.tree_util.tree_flatten(got)[0]
            wl = jax.tree_util.tree_flatten(state0)[0]
            same = all(np.array_equal(np.asarray(g), np.asarray(w))
                       for g, w in zip(gl, wl))
            return step, same, ck.all_steps()

        return prog

    def test_kill_aggregator_mid_exchange(self, fresh_vars, tmp_path):
        """kill -9 shape at the aggregate seam: rank 2 (host B's
        aggregator) dies after collecting one shard — step 1 never
        commits, survivors restore step 0."""
        mca_var.set_var("ft_detector_period", 0.05)
        mca_var.set_var("ft_detector_timeout", 0.8)
        plan = FaultPlan(seed=21).ckpt_kill_aggregator(2,
                                                       after_shards=1)
        prog = self._crash_seam_prog(plan, tmp_path, victim=2)
        res = run_tcp_ft(4, prog, kwargs_by_rank=BOOTS, timeout=90.0)
        assert res[2] == "killed"
        for r in (0, 1, 3):
            step, same, steps = res[r]
            assert step == 0 and same and steps == [0]
        assert ckptio.incomplete_manifests() == []

    def test_kill_writer_mid_stream(self, fresh_vars, tmp_path):
        """The mid-stream crash: rank 0 — an aggregator AND the
        manifest committer — dies inside its first fbtl write attempt;
        no manifest can exist for the torn step, survivors degrade."""
        mca_var.set_var("ft_detector_period", 0.05)
        mca_var.set_var("ft_detector_timeout", 0.8)
        plan = FaultPlan(seed=22).ckpt_kill_writer(0, after_writes=0)
        prog = self._crash_seam_prog(plan, tmp_path, victim=0)
        res = run_tcp_ft(4, prog, kwargs_by_rank=BOOTS, timeout=90.0)
        assert res[0] == "killed"
        for r in (1, 2, 3):
            step, same, steps = res[r]
            assert step == 0 and same and steps == [0]
        assert ckptio.incomplete_manifests() == []

    def test_restore_under_concurrent_rank_failure(self, fresh_vars,
                                                   tmp_path):
        """The matrix's fourth leg: a COMPLETE-but-torn newest step
        (corrupt shard) plus a rank dying while the survivors restore
        — every survivor still lands on the untainted step."""
        mca_var.set_var("ft_detector_period", 0.05)
        mca_var.set_var("ft_detector_timeout", 0.8)
        plan = FaultPlan(seed=23).kill_rank(3, after_ops=1)
        state0, state1 = _state(1.0), _state(2.0)
        degraded0 = spc.read("ckpt_degraded_restores")

        def prog(p):
            p.set_errhandler(errh.ERRORS_RETURN)
            ck = self._ckpt(p, tmp_path)
            ck.save(0, state0, blocking=True)
            ck.save(1, state1, blocking=True)
            if p.rank == 0:
                corrupt_ckpt_shard(str(tmp_path), step=1, leaf=1,
                                   rank=2)
            p.barrier()
            inj = plan.arm(p)
            try:
                inj.send(p.rank, dest=(p.rank + 1) % 4, tag=1)
                inj.recv(source=(p.rank - 1) % 4, tag=1, timeout=10.0)
            except errors.ProcFailed:
                pass
            assert p.ft_state.wait_failed(3, timeout=15.0)
            p.failure_ack()
            got, step = ck.restore()  # concurrent with peers', local
            gl = jax.tree_util.tree_flatten(got)[0]
            wl = jax.tree_util.tree_flatten(state0)[0]
            return step, all(
                np.array_equal(np.asarray(g), np.asarray(w))
                for g, w in zip(gl, wl))

        res = run_tcp_ft(4, prog, kwargs_by_rank=BOOTS, timeout=90.0)
        assert res[3] == "killed"
        for r in (0, 1, 2):
            assert res[r] == (0, True)
        # every survivor degraded LOUDLY past the torn step
        assert spc.read("ckpt_degraded_restores") == degraded0 + 3


class TestRollbackLegInstrumentation:
    """The MTTR surface: the checkpoint-restore leg is a named,
    measured entry in postmortems — a ckpt_restore flightrec event
    with restore bytes, mapped by recovery.mttr_legs, and a rollback
    ztrace span merged by tools/ztrace into the critical path."""

    def test_mttr_legs_name_the_rollback(self, tmp_path):
        ck = CollectiveCheckpointer(str(tmp_path))
        ck.save(4, _state(), blocking=True)
        flightrec.arm()
        flightrec.clear()  # a process-global ring: drop other tests' events
        try:
            flightrec.record(flightrec.DAEMON_FAULT, job="j0",
                             cause="killed", deaths=[1])
            state, step = recovery.rollback(ck)
            window = flightrec.window()
            anchors = flightrec.anchors()
        finally:
            flightrec.disarm()
        assert step == 4
        legs = recovery.mttr_legs(window, anchors)
        assert len(legs) == 1
        rec = legs[0]
        assert "rollback" in rec["legs_ms"]
        assert rec["legs_ms"]["rollback"] >= 0.0
        assert rec["rollback_step"] == 4
        # restore bytes ride the event so reports derive a bandwidth:
        # exactly the shard payload (b: 64 B + w: 256 B), not treedef
        assert rec["rollback_bytes"] == 320

    def test_tools_ztrace_merges_rollback_into_critical_path(self):
        from zhpe_ompi_tpu.tools import ztrace as ztrace_tool

        spans = [
            {"kind": "ft_class", "ts": 1.0, "dur": 0.001, "tid": 0,
             "cause": "killed", "failed": 2},
            {"kind": "agree", "ts": 1.01, "dur": 0.02, "tid": 0},
            {"kind": "shrink", "ts": 1.04, "dur": 0.01, "tid": 0},
            {"kind": "rollback", "ts": 1.06, "dur": 0.5, "tid": 0,
             "bytes": 4096},
            {"kind": "respawn", "ts": 1.6, "dur": 0.1, "tid": 0},
        ]
        legs = ztrace_tool._recovery_legs(spans)
        assert len(legs) == 1
        kinds = [s["kind"] for s in legs[0]["legs"]]
        assert "rollback" in kinds
        # the longest leg IS the rollback here: the critical-path
        # entry the report names
        assert legs[0]["longest"]["kind"] == "rollback"


class TestFtLoopOverlap:
    """models/ftloop.py drives the collective plane: async saves
    overlap training steps (ckpt_async_overlapped), and the final
    wait() drains the last stream before the loop declares done."""

    def _proc_stub(self):
        class Stub:
            rank, size = 0, 1
            ft_state = ulfm.FailureState(1)
        return Stub()

    def test_async_overlap_counted_and_drained(self, tmp_path):
        from zhpe_ompi_tpu.models.ftloop import FtTrainLoop

        def step_fn(ep, state, i):
            w = state["w"]
            return {"w": w - 0.1 * (w - 1.0)}, float(np.mean(w))

        def slow_write(seam, rank, **info):
            if seam == "write":
                time.sleep(0.1)

        ck = CollectiveCheckpointer(str(tmp_path), keep=20,
                                    check_quiescent=False)
        assert ck.async_capable
        over0 = spc.read("ckpt_async_overlapped")
        remove = ckptio.install_fault_hook(slow_write)
        try:
            loop = FtTrainLoop(
                self._proc_stub(), step_fn=step_fn,
                state={"w": np.zeros(256, np.float32)},
                checkpointer=ck, ckpt_every=1)
            state, losses = loop.run(4)
        finally:
            remove()
        assert len(losses) == 4
        # at least one step committed while a stream was draining
        assert spc.read("ckpt_async_overlapped") > over0
        # the run-done contract drained the last stream
        assert not ck.in_flight
        assert ckptio.live_writer_threads() == []
        assert ck.latest_step() == 4

    def test_serial_cadence_contract_unchanged(self, tmp_path):
        """The collective checkpointer honors the exact cadence the
        serial one established (step-0 snapshot + every-k + final)."""
        from zhpe_ompi_tpu.models.ftloop import FtTrainLoop

        def step_fn(ep, state, i):
            return state, 0.0

        ck = CollectiveCheckpointer(str(tmp_path), keep=20,
                                    check_quiescent=False)
        loop = FtTrainLoop(self._proc_stub(), step_fn=step_fn,
                           state={"w": np.zeros(8, np.float32)},
                           checkpointer=ck, ckpt_every=2)
        loop.run(5)
        assert ck.all_steps() == [0, 2, 4, 5]


_DVM_CKPT_DRILL_PROG = '''
import os, sys, time
sys.path.insert(0, {repo!r})
import numpy as np
import zhpe_ompi_tpu as zmpi
from zhpe_ompi_tpu import ops
from zhpe_ompi_tpu.core import errhandler as errh
from zhpe_ompi_tpu.ft import inject, recovery
from zhpe_ompi_tpu.ft.inject import FaultPlan
from zhpe_ompi_tpu.io import ckptio
from zhpe_ompi_tpu.io.ckptio import CollectiveCheckpointer
from zhpe_ompi_tpu.models.ftloop import FtTrainLoop
from zhpe_ompi_tpu.runtime import flightrec, spc

DIM = 256
STEPS = 6
SEAM = os.environ.get("TEST_CKPT_SEAM", "")
VICTIM = int(os.environ.get("TEST_CKPT_VICTIM", "-1"))
AFTER = int(os.environ.get("TEST_CKPT_AFTER", "1"))
CORRUPT = os.environ.get("TEST_CKPT_CORRUPT") == "1"
CKPT_DIR = os.environ["TEST_CKPT"]

proc = zmpi.host_init()
proc.set_errhandler(errh.ERRORS_RETURN)
flightrec.arm()

rng = np.random.default_rng(7)  # same seed: replicated SPMD state
target = rng.normal(size=DIM).astype(np.float32)
first_life = os.environ.get("ZMPI_REJOIN") != "1"
did_corrupt = [False]


def step_fn(ep, state, i):
    if CORRUPT and i == 2 and proc.rank == 0 and first_life \
            and not did_corrupt[0]:
        # the torn-shard drill: drain the step-2 stream, then flip one
        # manifest-recorded shard on disk — the rollback below must
        # reject step 2 by digest and degrade to step 1 LOUDLY
        did_corrupt[0] = True
        ck.wait()
        inject.corrupt_ckpt_shard(CKPT_DIR, step=2, leaf=0, rank=2)
    w = np.asarray(state["w"], np.float32)
    grad = ((2.0 / w.size) * (w - target)).astype(np.float32)
    loss = float(np.mean((w - target) ** 2))
    # one collective per step: survivors discover faults typed here
    total = ep.allreduce(np.float64(loss), ops.SUM)
    return ({{"w": (w - 0.1 * grad).astype(np.float32)}},
            float(np.asarray(total)) / ep.size)


# slow the aggregator's stream (well under the deadline) so checkpoint
# drains genuinely overlap the next training step
if proc.rank == 0:
    def _slow(seam, rank, **info):
        if seam == "write":
            time.sleep(0.05)
    ckptio.install_fault_hook(_slow)

if SEAM and proc.rank == VICTIM and first_life:
    # first incarnation only: the respawned replacement must not
    # re-kill itself at the same seam forever
    plan = FaultPlan(seed=11).ckpt_fault(VICTIM, SEAM, after=AFTER,
                                         action="kill9")
    plan.arm_ckpt(proc.rank, ep=proc, state=proc.ft_state).__enter__()

ck = CollectiveCheckpointer(CKPT_DIR, keep=20, check_quiescent=False)
loop = FtTrainLoop(proc, step_fn=step_fn,
                   state={{"w": np.zeros(DIM, np.float32)}},
                   checkpointer=ck, ckpt_every=1,
                   respawner=recovery.daemon_respawn)
state, losses = loop.run(STEPS)

overlapped = spc.read("ckpt_async_overlapped")
degraded = spc.read("ckpt_degraded_restores")
window = flightrec.window()
restores = [e for e in window if e["type"] == flightrec.CKPT_RESTORE]
faults = [e for e in window if e["type"] == flightrec.FT_CLASS]
rb_ms = -1.0
rb_bytes = 0
if restores:
    rb_bytes = int(restores[-1].get("bytes", 0))
    if faults:
        rb_ms = (int(restores[-1]["t_ns"])
                 - int(faults[0]["t_ns"])) / 1e6
flightrec.disarm()
print(f"CKPT-OK rank={{proc.rank}} size={{proc.size}} "
      f"recoveries={{loop.recoveries}} steps={{len(losses)}} "
      f"final={{losses[-1]:.6f}} overlapped={{overlapped}} "
      f"degraded={{degraded}} restores={{len(restores)}} "
      f"rb_bytes={{rb_bytes}} rb_ms={{rb_ms:.2f}}", flush=True)
zmpi.host_finalize()
'''


@pytest.mark.slow
class TestCkptCrashDrillDvm:
    """THE acceptance drill: a 4-rank real-process training job with
    async collective checkpoints overlapping steps; kill -9 one rank
    mid-checkpoint (at a seam, first incarnation only) — survivors
    shrink to a 3-rank mesh, roll back onto it from the newest
    COMPLETE step (the rollback leg named + measured out of
    flightrec), respawn, resume at full size — and the post-recovery
    losses equal the fault-free run's."""

    N = 4
    VICTIM = 1

    def _launch(self, tmp_path, seam: str, victim: int | None = None,
                after: int = 1, corrupt: bool = False,
                extra_mca: list | None = None):
        import io
        import re

        from zhpe_ompi_tpu.runtime import dvm as dvm_mod

        repo = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        victim = self.VICTIM if victim is None else victim
        tag = (seam or "ref") + ("_corrupt" if corrupt else "") \
            + f"_v{victim}"
        prog = tmp_path / f"ckpt_drill_{tag}.py"
        prog.write_text(_DVM_CKPT_DRILL_PROG.format(repo=repo))
        env = {
            "TEST_CKPT": str(tmp_path / f"ckpt_{tag}"),
            "TEST_CKPT_SEAM": seam,
            "TEST_CKPT_VICTIM": str(victim) if seam else "-1",
            "TEST_CKPT_AFTER": str(after),
            "TEST_CKPT_CORRUPT": "1" if corrupt else "0",
        }
        old = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        d = dvm_mod.Dvm()
        try:
            cli = dvm_mod.DvmClient(d.address)
            out, err = io.StringIO(), io.StringIO()
            rc = cli.launch(
                self.N, [str(prog)], ft=True, timeout=240.0,
                # a big flightrec ring: the postmortem window must
                # still hold the mid-run ft_class + ckpt_restore
                # events after several more steps of traffic
                mca=[("ft_detector_period", "0.2"),
                     ("ft_detector_timeout", "5.0"),
                     ("flightrec_capacity", "16384")]
                    + list(extra_mca or []),
                stdout=out, stderr=err,
            )
            text = out.getvalue()
            assert rc == 0, (text, err.getvalue())
            rows = re.findall(
                r"CKPT-OK rank=(\d+) size=(\d+) recoveries=(\d+) "
                r"steps=(\d+) final=([\d.]+) overlapped=(\d+) "
                r"degraded=(\d+) restores=(\d+) rb_bytes=(\d+) "
                r"rb_ms=(-?[\d.]+)", text)
            cli.stop()
            cli.close()
            return rows
        finally:
            d.stop()
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    def test_kill9_mid_gather_recovers_and_matches(self, tmp_path):
        ref_rows = self._launch(tmp_path, seam="")
        assert len(ref_rows) == self.N
        ref_final = {int(r[0]): float(r[4]) for r in ref_rows}
        assert all(int(r[2]) == 0 for r in ref_rows)  # no recoveries
        # the overlap gate: async streams drained UNDER later steps
        assert sum(int(r[5]) for r in ref_rows) > 0

        rows = self._launch(tmp_path, seam="gather")
        assert len(rows) == self.N, rows
        by_rank = {int(r[0]): r for r in rows}
        assert sorted(by_rank) == list(range(self.N))
        for rank, row in by_rank.items():
            (_, size, recov, steps, final, _, _, restores,
             rb_bytes, rb_ms) = row
            assert int(size) == self.N  # finished at FULL size
            assert int(steps) == 6
            # deterministic resume: the faulted run's losses match the
            # fault-free run's, rank for rank
            assert abs(float(final) - ref_final[rank]) < 1e-5
            if rank != self.VICTIM:
                assert int(recov) >= 1  # survivors ran the pipeline
                # the rollback leg is named + measured from flightrec:
                # restore bytes (bandwidth) and ms-since-classification
                assert int(restores) >= 1
                assert int(rb_bytes) > 0
                assert float(rb_ms) >= 0.0
        # the replacement (fresh incarnation) restored on entry
        assert int(by_rank[self.VICTIM][7]) >= 1

    def test_kill9_with_torn_newest_step_degrades(self, tmp_path):
        """corrupt shard + kill -9 under one recovery: the newest
        complete step is TORN on disk when the fault lands — every
        restoring rank (survivors' rollback AND the replacement's
        entry restore) rejects it by digest and degrades LOUDLY to the
        previous complete step, and the job still finishes at full
        size with the fault-free trajectory."""
        ref_rows = self._launch(tmp_path, seam="")
        ref_final = {int(r[0]): float(r[4]) for r in ref_rows}

        # with delta off the single-leaf state costs the victim ONE
        # gather send per save (save(k) is send k+1), so after=3 fires
        # mid-save(3) — AFTER rank 0 tore the committed step 2 at
        # step_fn(i=2), and early enough that the next step's allreduce
        # observes the corpse in-loop: the rollback must walk
        # incomplete step 3 (healed), torn step 2 (digest-rejected),
        # and land on step 1
        rows = self._launch(tmp_path, seam="gather", after=3,
                            corrupt=True,
                            extra_mca=[("ckpt_delta", "0")])
        assert len(rows) == self.N, rows
        by_rank = {int(r[0]): r for r in rows}
        for rank, row in by_rank.items():
            (_, size, _, steps, final, _, degraded, restores,
             _, _) = row
            assert int(size) == self.N
            # every rank's trajectory ends on the fault-free step-5
            # loss; the replacement entered at the rolled-back step so
            # its loss LIST is shorter, never longer
            assert 1 <= int(steps) <= 6
            if rank != self.VICTIM:
                assert int(steps) == 6
                # zero silent torn-shard acceptance: every survivor's
                # rollback ran before any re-publication, so each one
                # rejected torn step 2 by digest and degraded
                if int(restores) >= 1:
                    assert int(degraded) >= 1
            assert abs(float(final) - ref_final[rank]) < 1e-5
        # ... and somebody actually took the degraded-restore path
        assert any(int(r[6]) >= 1 for r in rows)
        assert any(int(r[7]) >= 1 for r in rows)

    def test_kill9_mid_stream_writer(self, tmp_path):
        """The mid-stream real-process seam: SIGKILL inside an fbtl
        write attempt — the victim is rank 0, the single-host job's
        aggregator AND committer, so a torn stream can never become a
        complete manifest; the job recovers and finishes at full
        size."""
        rows = self._launch(tmp_path, seam="write", victim=0)
        assert len(rows) == self.N, rows
        for r in rows:
            assert int(r[1]) == self.N and int(r[3]) == 6
        # at least one survivor named + measured the rollback leg
        assert any(int(r[7]) >= 1 and int(r[8]) > 0 for r in rows
                   if int(r[0]) != 0)
