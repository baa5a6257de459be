"""Device-plane fault tolerance: the liveness probe (killable child +
armed guard), typed cause="device" classification into the SAME
FailureState the host plane feeds, the wedge-injection mode, the
survivor-mesh remesh, and the thread-plane recovery drill.

The host-plane FT pipeline watches PROCESSES; a TPU participant that
wedges mid-psum surfaces as an indefinite XLA hang.  These tests drive
the other half: probe → classify → flood → shrink → remesh → resume.
"""

import os
import threading
import time

import numpy as np
import pytest

from zhpe_ompi_tpu import ops
from zhpe_ompi_tpu.coll import tpu as coll_tpu
from zhpe_ompi_tpu.core import errhandler as errh
from zhpe_ompi_tpu.core import errors
from zhpe_ompi_tpu.ft import ulfm
from zhpe_ompi_tpu.ft.inject import FaultPlan, WedgedDevice
from zhpe_ompi_tpu.parallel import mesh as mesh_mod
from zhpe_ompi_tpu.runtime import flightrec, spc
from zhpe_ompi_tpu.runtime.checkpoint import Checkpointer
from zhpe_ompi_tpu.utils import deadline as deadline_mod

from test_ulfm import run_tcp_ft


def _stub_probe(kind="deadline", detail="stub", calls=None):
    """A probe_fn stub: the classification ladder without subprocess
    cost.  Counts the same SPC counters the real probe does, so the
    gated assertions hold either way."""

    def probe(timeout=None, deadline=None):
        if calls is not None:
            calls.append(kind)
        spc.record("device_probe_rounds")
        if kind in ("hung", "deadline"):
            spc.record("device_probe_misses")
        return kind, detail

    return probe


def _wedge_probe(wedge, miss_kind="deadline"):
    """A probe_fn stub keyed to ONE rank's wedge — the in-process model
    of reality: the killable child hangs only on the rank whose device
    wedged; every healthy rank's probe answers ok (its guard may expire
    while it waits out a PEER's wedge inside a collective — an ok probe
    must ride that out, never self-classify)."""

    def probe(timeout=None, deadline=None):
        spc.record("device_probe_rounds")
        if wedge.fired:
            spc.record("device_probe_misses")
            return miss_kind, "wedged participant"
        return "ok", '{"n": 1, "platform": "stub"}'

    return probe


class TestDeviceFaultType:
    def test_typed_class_and_family(self):
        e = errors.DeviceFault("wedged", failed_ranks=[2], kind="hung")
        assert e.errclass == errors.ERR_DEVICE_FAULT
        assert isinstance(e, errors.ProcFailed)  # recovery family
        assert e.failed_ranks == (2,) and e.kind == "hung"
        assert "DEVICE_FAULT" in errors.error_string(
            errors.ERR_DEVICE_FAULT)


class TestClassify:
    def test_miss_classifies_device_cause_into_failure_state(self):
        state = ulfm.FailureState(4)
        before = spc.read("device_faults")
        faults = []
        probe = mesh_mod.DeviceLivenessProbe(
            state=state, rank=2, on_fault=faults.append, enable=True)
        fault = probe.classify("deadline", "probe hit its deadline")
        assert isinstance(fault, errors.DeviceFault)
        assert state.is_failed(2)
        assert state.cause_of(2) == "device"
        assert faults == [fault]
        assert spc.read("device_faults") - before == 1
        # never a detector false positive: the cause is typed, not a
        # suspicion — the session gate proves the complement
        assert ulfm.false_positive_count() == 0

    def test_flightrec_event_is_typed(self):
        state = ulfm.FailureState(2)
        probe = mesh_mod.DeviceLivenessProbe(state=state, rank=1,
                                             enable=True)
        flightrec.arm()
        try:
            probe.classify("hung", "outer kill")
            window = flightrec.window()
        finally:
            flightrec.disarm()
        kinds = [e["type"] for e in window]
        assert flightrec.DEVICE_FAULT in kinds
        evt = [e for e in window
               if e["type"] == flightrec.DEVICE_FAULT][-1]
        assert evt["rank"] == 1 and evt["kind"] == "hung"
        # the FailureState classification event landed too (the same
        # FT_CLASS seam every other cause rides)
        assert flightrec.FT_CLASS in kinds


class TestGuard:
    def test_fast_region_no_probe_no_fault(self):
        calls = []
        probe = mesh_mod.DeviceLivenessProbe(
            state=ulfm.FailureState(2), rank=0, enable=True,
            probe_fn=_stub_probe(calls=calls), deadline=5.0)
        with probe.guard():
            pass
        assert calls == [] and probe.fault is None
        assert deadline_mod.live_watchdog_threads() == []

    def test_wedged_region_probes_and_classifies(self):
        state = ulfm.FailureState(2)
        release = threading.Event()
        probe = mesh_mod.DeviceLivenessProbe(
            state=state, rank=0, enable=True,
            probe_fn=_stub_probe("deadline"), deadline=0.05,
            on_fault=lambda f: release.set())
        with probe.guard():
            # the "wedged collective": parked until classification
            assert release.wait(10.0), "guard never classified"
        assert state.cause_of(0) == "device"
        assert probe.fault is not None and probe.fault.kind == "deadline"
        assert deadline_mod.live_watchdog_threads() == []

    def test_ok_probes_never_classify_a_slow_region(self):
        """A slow-but-alive local plane is a PEER's fault to classify:
        ok probes ride out the grace rounds and go quiet."""
        state = ulfm.FailureState(2)
        calls = []
        probe = mesh_mod.DeviceLivenessProbe(
            state=state, rank=0, enable=True,
            probe_fn=_stub_probe("ok", calls=calls), deadline=0.05,
            grace=2)
        hold = threading.Event()
        with probe.guard():
            deadline = time.monotonic() + 10.0
            while len(calls) < 2 and time.monotonic() < deadline:
                hold.wait(0.02)
        assert len(calls) >= 2
        assert probe.fault is None
        assert not state.is_failed(0)
        assert ulfm.false_positive_count() == 0

    def test_disabled_guard_is_a_noop(self):
        calls = []
        probe = mesh_mod.DeviceLivenessProbe(
            state=ulfm.FailureState(2), rank=0, enable=False,
            probe_fn=_stub_probe(calls=calls), deadline=0.01)
        with probe.guard():
            time.sleep(0.1)
        assert calls == [] and probe.fault is None

    def test_region_finishing_during_probe_is_not_classified(self):
        """The race the disarm re-check exists for: the collective
        completes while the probe child runs — no fault, no false
        positive."""
        state = ulfm.FailureState(2)
        probing = threading.Event()
        finish = threading.Event()

        def slow_probe(timeout=None, deadline=None):
            probing.set()
            finish.wait(10.0)  # the region exits while we "probe"
            return "deadline", "late miss"

        probe = mesh_mod.DeviceLivenessProbe(
            state=state, rank=0, enable=True, probe_fn=slow_probe,
            deadline=0.05)
        wd = probe.guard()
        wd.arm()
        assert probing.wait(10.0)
        # the region completes while the probe is still in flight:
        # signal the disarm first (white-box: avoid blocking this
        # thread on the watchdog's join while the probe still runs)
        wd._disarmed.set()
        finish.set()
        wd._thread.join(5.0)
        assert not wd._thread.is_alive()
        assert probe.fault is None
        assert not state.is_failed(0)


class TestProbeChild:
    """The REAL killable-child probe (one subprocess each — the
    moderately slow half; the ladder above is stubbed)."""

    def test_healthy_plane_answers_ok(self):
        kind, detail = mesh_mod.probe_device_plane(timeout=90.0,
                                                   deadline=60.0)
        assert kind == "ok", detail
        import json

        info = json.loads(detail)
        assert info["n"] >= 1
        assert info["platform"] == "cpu"
        assert deadline_mod.orphaned_probe_processes() == []

    def test_wedge_hook_is_scoped_to_the_wedged_rank(self, monkeypatch):
        """A shared-process job: rank 2's wedge must not hang a HEALTHY
        rank's probe child (the self-false-positive the rank-scoped
        hook exists to prevent) — rank 0's probe answers ok while the
        hook names rank 2; rank 2's own probe wedges."""
        monkeypatch.setenv(coll_tpu.WEDGE_ENV, "2")
        kind, detail = mesh_mod.probe_device_plane(
            timeout=60.0, deadline=30.0, rank=0)
        assert kind == "ok", (kind, detail)
        kind, _ = mesh_mod.probe_device_plane(
            timeout=60.0, deadline=6.0, rank=2)
        assert kind == "deadline", kind
        assert deadline_mod.orphaned_probe_processes() == []

    def test_wedged_plane_dies_at_its_internal_deadline(self):
        """The injected wedge (coll/tpu.WEDGE_ENV) hangs the child
        INSIDE the collective region; the internal watchdog kills it
        from the inside — the structured "deadline" outcome, never an
        indefinite XLA hang."""
        env = dict(os.environ)
        env[coll_tpu.WEDGE_ENV] = coll_tpu.WEDGE_ALL
        before = spc.read("device_probe_misses")
        kind, detail = mesh_mod.probe_device_plane(
            timeout=60.0, deadline=8.0, env=env)
        assert kind == "deadline", (kind, detail)
        assert spc.read("device_probe_misses") - before == 1
        assert deadline_mod.orphaned_probe_processes() == []


class TestProbeInProcess:
    """On a TPU the chip belongs to the probing process: the probe runs
    in-process under its deadline and never starts a child."""

    @pytest.fixture
    def on_tpu(self, monkeypatch):
        monkeypatch.setattr(mesh_mod, "_holds_tpu", lambda: True)

        def no_child(*a, **kw):
            raise AssertionError("a probe child would need the chip")

        monkeypatch.setattr(deadline_mod, "run_probe", no_child)

    def test_healthy_plane_answers_ok(self, on_tpu):
        import json

        kind, detail = mesh_mod.probe_device_plane(deadline=60.0)
        assert kind == "ok", detail
        info = json.loads(detail)
        assert info["n"] >= 1 and info["psum"] == sum(range(info["n"]))

    def test_wedge_hits_the_deadline(self, on_tpu, monkeypatch):
        monkeypatch.setenv(coll_tpu.WEDGE_ENV, "2")
        before = spc.read("device_probe_misses")
        kind, _ = mesh_mod.probe_device_plane(deadline=60.0, rank=0)
        assert kind == "ok"  # the hook names rank 2, not rank 0
        kind, detail = mesh_mod.probe_device_plane(deadline=0.5, rank=2)
        assert kind == "deadline", detail
        assert spc.read("device_probe_misses") - before == 1


class TestWedgePlan:
    def test_wedge_composes_with_kill_plans(self):
        plan = FaultPlan(seed=5).kill_ranks([1, 2], after_ops=3) \
            .wedge_device(3, after_steps=2)
        assert plan.victims == frozenset({1, 2})
        assert plan.device_victims == frozenset({3})
        assert plan.kill_for(3) is None  # planes stay independent
        assert plan.wedge_for(1) is None
        assert plan.wedge_for(3) == 2

    def test_wedge_validation(self):
        with pytest.raises(errors.ArgError):
            FaultPlan().wedge_device(0, after_steps=-1)

    def test_unscheduled_rank_never_fires(self):
        plan = FaultPlan().wedge_device(1, after_steps=0)
        wedge = plan.arm_device(0)  # rank 0 has no wedge
        for _ in range(10):
            wedge.tick()
        assert not wedge.fired

    def test_fire_parks_until_release_then_raises_typed(self):
        state = ulfm.FailureState(4)
        wedge = WedgedDevice(2, after_steps=1, state=state)
        out = {}

        def victim():
            try:
                wedge.tick()   # step 1: survives
                wedge.tick()   # step 2: fires — parks here
            except errors.DeviceFault as e:
                out["fault"] = e

        t = threading.Thread(target=victim, daemon=True)
        t.start()
        deadline = time.monotonic() + 5.0
        while not wedge.fired and time.monotonic() < deadline:
            time.sleep(0.01)
        assert wedge.fired and "fault" not in out  # parked, not raised
        # the hook is SCOPED to the wedged rank's probes (a healthy
        # rank sharing the process keeps getting healthy answers)
        assert os.environ.get(coll_tpu.WEDGE_ENV) == "2"
        wedge.release(errors.DeviceFault("classified",
                                         failed_ranks=[2]))
        t.join(5.0)
        assert not t.is_alive()
        assert out["fault"].failed_ranks == (2,)
        assert os.environ.get(coll_tpu.WEDGE_ENV) is None

    def test_hold_wedge_ignores_release(self):
        wedge = WedgedDevice(1, after_steps=0, hold=True)
        unwound = threading.Event()

        def victim():
            try:
                wedge.tick()
            except errors.DeviceFault:
                unwound.set()

        t = threading.Thread(target=victim, daemon=True)
        t.start()
        deadline = time.monotonic() + 5.0
        while not wedge.fired and time.monotonic() < deadline:
            time.sleep(0.01)
        wedge.release()
        assert not unwound.wait(0.3), \
            "a hold wedge must stay parked (only SIGKILL ends it)"
        # the parked daemon thread is the process-death analog; clear
        # the wedge hook it exported so later probes in this test
        # session answer again
        os.environ.pop(coll_tpu.WEDGE_ENV, None)


class TestSurvivorMesh:
    def test_drops_failed_indices(self):
        m = mesh_mod.world_mesh()
        n = m.devices.size
        surv = mesh_mod.survivor_mesh(m, failed=[1, n - 1])
        assert surv.devices.size == n - 2
        kept = set(np.asarray(surv.devices).flat)
        flat = list(np.asarray(m.devices).flat)
        assert not (kept & {flat[1], flat[n - 1]})
        assert surv.axis_names == m.axis_names

    def test_multiaxis_drops_along_named_axis(self):
        m = mesh_mod.make_mesh({"dp": 4, "tp": 2})
        surv = mesh_mod.survivor_mesh(m, failed=[2], axis="dp")
        assert surv.shape["dp"] == 3 and surv.shape["tp"] == 2

    def test_empty_survivor_set_raises(self):
        m = mesh_mod.make_mesh({"dp": 2, "tp": 4})
        with pytest.raises(errors.ArgError):
            mesh_mod.survivor_mesh(m, failed=[0, 1], axis="dp")
        with pytest.raises(errors.ArgError):
            mesh_mod.survivor_mesh(m, failed=[], axis="nope")


def _train_setup(rank: int, dim: int = 8) -> np.ndarray:
    """Deterministic per-rank fixed batch target."""
    r = np.random.default_rng(100 + rank)
    return r.normal(size=dim).astype(np.float32)


def _local_grad(w: np.ndarray, target: np.ndarray):
    loss = float(np.mean((w - target) ** 2))
    grad = ((2.0 / w.size) * (w - target)).astype(np.float32)
    return loss, grad


def _rebuild_full(zopt, leaves):
    """Rebuild a full-state pytree from its leaves (run_tcp_ft results
    cross threads as plain values; the treedef is the optimizer's)."""
    import jax

    treedef = jax.tree_util.tree_structure(zopt._opt_state)
    return jax.tree_util.tree_unflatten(treedef, leaves)


class TestZeroReshard:
    """ZeroOptimizer.full_state()/reshard(): optimizer chunks gather to
    every rank and re-shard onto a different-size endpoint with the
    training trajectory preserved."""

    def test_full_state_gathers_and_reshards_across_sizes(self):
        import jax
        import optax

        from zhpe_ompi_tpu.parallel.zero import ZeroOptimizer

        n, dim = 3, 10
        params = {"w": np.arange(dim, dtype=np.float32)}
        grads = {"w": np.ones(dim, np.float32)}

        def prog(p):
            zopt = ZeroOptimizer(p, optax.adam(1e-2), params)
            p1 = zopt.step(params, grads)
            full = zopt.full_state()
            zopt.reshard(p, full)  # same-size identity round trip
            p2 = zopt.step(p1, grads)
            return (np.asarray(p2["w"]),
                    [np.asarray(x) for x in
                     jax.tree_util.tree_leaves(full)])

        res = run_tcp_ft(n, prog)
        for r in range(1, n):
            np.testing.assert_allclose(res[r][0], res[0][0], rtol=1e-6)
            for a, b in zip(res[r][1], res[0][1]):
                np.testing.assert_allclose(a, b, rtol=1e-6)
        # reference: a SIZE-1 endpoint adopting the distributed full
        # state after one step produces the same second step (grads
        # are identical on every rank, so the distributed mean equals
        # the single-rank gradient)
        class P1:
            rank, size = 0, 1

        zr = ZeroOptimizer(P1(), optax.adam(1e-2), params, weight=1.0)
        q1 = zr.step(params, grads)
        zr.reshard(P1(), _rebuild_full(zr, res[0][1]))
        q2 = zr.step(q1, grads)
        np.testing.assert_allclose(np.asarray(q2["w"]), res[0][0],
                                   rtol=1e-5)


class TestDeviceWedgeRecoveryThreadPlane:
    """The in-process drill: a 4-rank ft job hits a wedged device
    participant mid-training — typed cause="device" classification
    (the wedged rank's own guard), notice flood to every survivor,
    consensus shrink, checkpoint rollback, optimizer re-shard onto the
    survivor endpoint, and SHRUNKEN training that matches the
    fault-free reference arithmetic.  No detector false positive
    anywhere (the session gate re-proves it suite-wide)."""

    N = 4
    VICTIM = 2
    WEDGE_AT = 2  # completes 2 steps, wedges entering step 3
    STEPS = 6
    DIM = 8

    def _reference_losses(self, phases, w0, probe_rank):
        """Fault-free single-process reference: the same arithmetic
        the distributed loop runs — per-step update from the MEAN
        gradient over the phase's rank set (what reduce-scatter of the
        1/n-weighted blocks computes), with the rank set switching
        between phases exactly where the shrink lands.  Returns
        ``probe_rank``'s LOCAL loss trajectory (what that rank's loop
        records) and the final params."""
        import optax

        from zhpe_ompi_tpu.parallel.zero import ZeroOptimizer

        class P1:
            rank, size = 0, 1

        zopt = ZeroOptimizer(P1(), optax.adam(1e-2), {"w": w0},
                             weight=1.0)
        params = {"w": w0.copy()}
        probe_target = _train_setup(probe_rank, self.DIM)
        losses = []
        for ranks, steps in phases:
            targets = [_train_setup(r, self.DIM) for r in ranks]
            for _ in range(steps):
                losses.append(_local_grad(params["w"],
                                          probe_target)[0])
                grad = np.mean(
                    [_local_grad(params["w"], t)[1] for t in targets],
                    axis=0).astype(np.float32)
                params = zopt.step(params, {"w": grad})
        return losses, np.asarray(params["w"])

    def test_wedge_classify_flood_shrink_rollback_reshard(
            self, fresh_vars, tmp_path):
        import optax

        from zhpe_ompi_tpu.mca import var as mca_var
        from zhpe_ompi_tpu.parallel.zero import ZeroOptimizer

        mca_var.set_var("ft_detector_period", 0.05)
        # the heartbeat window is HUGE: the wedged rank keeps beating
        # (device wedge, not process death) — only the device probe
        # can classify this failure mode
        mca_var.set_var("ft_detector_timeout", 60.0)
        n, victim = self.N, self.VICTIM
        plan = FaultPlan(seed=9).wedge_device(victim,
                                              after_steps=self.WEDGE_AT)
        w0 = np.zeros(self.DIM, np.float32)
        faults0 = spc.read("device_faults")

        def prog(p):
            from zhpe_ompi_tpu.coll import host as coll_host

            p.set_errhandler(errh.ERRORS_RETURN)
            target = _train_setup(p.rank, self.DIM)
            ck = Checkpointer(str(tmp_path / f"r{p.rank}"), keep=10,
                              check_quiescent=False)
            zopt = ZeroOptimizer(p, optax.adam(1e-2), {"w": w0})
            wedge = plan.arm_device(p.rank, state=p.ft_state)
            probe = mesh_mod.DeviceLivenessProbe(
                state=p.ft_state, rank=p.rank, enable=True,
                probe_fn=_wedge_probe(wedge), deadline=0.3)
            probe.on_fault = lambda f: (p.flood_device_fault(f),
                                        wedge.release(f))
            params = {"w": w0.copy()}
            losses = []
            step = 0
            try:
                while step < self.STEPS:
                    with probe.guard():
                        wedge.tick()
                        loss, grad = _local_grad(params["w"], target)
                        params = zopt.step(params, {"w": grad})
                    step += 1
                    losses.append(loss)
                    ck.save(step, {"params": params,
                                   "opt": zopt.full_state()},
                            blocking=True)
                return ("clean", losses)
            except errors.DeviceFault as e:
                assert p.rank in e.failed_ranks
                return ("wedged", step)
            except (errors.ProcFailed, errors.ProcFailedPending,
                    errors.Revoked):
                # unblock the peers still parked in the collective
                p.revoke(coll_host.COLL_CID)
                assert p.ft_state.wait_failed(victim, timeout=10.0)
                # the transport symptom may win the classification
                # race (the wedged rank's sm teardown mid-send); the
                # typed device pair refines it when the flood lands
                deadline = time.monotonic() + 10.0
                while p.ft_state.cause_of(victim) != "device" \
                        and time.monotonic() < deadline:
                    time.sleep(0.02)
                assert p.ft_state.cause_of(victim) == "device", \
                    p.ft_state.cause_of(victim)
                p.failure_ack()
                sh = p.shrink()
                # ROLLBACK + REMESH: restore the last quiescent
                # snapshot and re-shard the optimizer partition onto
                # the survivor endpoint
                snap, ck_step = ck.restore()
                params = {"w": np.asarray(snap["params"]["w"])}
                zopt.reshard(sh, snap["opt"])
                del losses[ck_step:]
                step = ck_step
                while step < self.STEPS:
                    loss, grad = _local_grad(params["w"], target)
                    params = zopt.step(params, {"w": grad})
                    step += 1
                    losses.append(loss)
                # synchronize before close: a fast survivor's goodbye
                # must not poison a peer's trailing reduce_scatter
                sh.barrier()
                return ("survivor", losses, np.asarray(params["w"]))

        res = run_tcp_ft(n, prog)
        assert res[victim][0] == "wedged"
        survivors = [r for r in range(n) if r != victim]
        for r in survivors:
            assert res[r][0] == "survivor", res[r]
        for r in survivors[1:]:
            np.testing.assert_allclose(res[r][2], res[survivors[0]][2],
                                       rtol=1e-6)
        # the post-recovery trajectory equals the fault-free reference:
        # 2 full-size steps, rollback to the step-2 snapshot, then 4
        # survivor-size steps — the "correct post-recovery loss" gate
        ref_losses, ref_w = self._reference_losses(
            [(list(range(n)), self.WEDGE_AT),
             (survivors, self.STEPS - self.WEDGE_AT)], w0,
            probe_rank=survivors[0])
        np.testing.assert_allclose(res[survivors[0]][1], ref_losses,
                                   rtol=1e-4)
        np.testing.assert_allclose(res[survivors[0]][2], ref_w,
                                   rtol=1e-4)
        # exactly ONE device classification: the victim's own guard
        # (survivors learned through the typed notice flood)
        assert spc.read("device_faults") - faults0 == 1

    def test_mixed_host_and_device_storm(self, fresh_vars):
        """One plan, both planes: a host-plane kill AND a device wedge
        in the same job — every survivor classifies both corpses with
        their own typed causes and one shrink absorbs both."""
        from zhpe_ompi_tpu.mca import var as mca_var

        mca_var.set_var("ft_detector_period", 0.05)
        mca_var.set_var("ft_detector_timeout", 60.0)
        n, kill_victim, wedge_victim = 4, 1, 3
        plan = FaultPlan(seed=11) \
            .kill_rank(kill_victim, after_ops=0) \
            .wedge_device(wedge_victim, after_steps=0)
        assert plan.victims == frozenset({kill_victim})
        assert plan.device_victims == frozenset({wedge_victim})

        def prog(p):
            from zhpe_ompi_tpu.coll import host as coll_host

            p.set_errhandler(errh.ERRORS_RETURN)
            wedge = plan.arm_device(p.rank, state=p.ft_state)
            probe = mesh_mod.DeviceLivenessProbe(
                state=p.ft_state, rank=p.rank, enable=True,
                probe_fn=_wedge_probe(wedge, "hung"), deadline=0.3)
            probe.on_fault = lambda f: (p.flood_device_fault(f),
                                        wedge.release(f))
            inj = plan.arm(p)
            try:
                with probe.guard():
                    wedge.tick()
                    # the host-plane victim dies inside this collective
                    inj.allreduce(np.full(8, float(p.rank + 1)),
                                  ops.SUM)
            except errors.DeviceFault as e:
                assert p.rank in e.failed_ranks
                return "wedged"
            except (errors.ProcFailed, errors.ProcFailedPending,
                    errors.Revoked):
                p.revoke(coll_host.COLL_CID)
            assert p.ft_state.wait_failed(kill_victim, timeout=10.0)
            assert p.ft_state.wait_failed(wedge_victim, timeout=10.0)
            deadline = time.monotonic() + 10.0
            while p.ft_state.cause_of(wedge_victim) != "device" \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            assert p.ft_state.cause_of(wedge_victim) == "device"
            p.failure_ack()
            sh = p.shrink()
            total = sh.allreduce(np.full(4, 1.0), ops.SUM)
            return (sh.size, float(np.asarray(total)[0]))

        res = run_tcp_ft(n, prog)
        assert res[kill_victim] == "killed"
        assert res[wedge_victim] == "wedged"
        survivors = [r for r in range(n)
                     if r not in (kill_victim, wedge_victim)]
        for r in survivors:
            assert res[r] == (2, 2.0), res[r]


class TestFtTrainLoop:
    """models/ftloop.FtTrainLoop plumbing that needs no fault: the
    guarded step loop, checkpoint cadence, and the restore path a
    replacement takes (the slow DVM drill exercises the full
    recovery)."""

    def _proc_stub(self):
        class Stub:
            rank, size = 0, 1
            ft_state = ulfm.FailureState(1)
        return Stub()

    @staticmethod
    def _step_fn(target):
        def step_fn(ep, state, i):
            loss, grad = _local_grad(state["w"], target)
            return {"w": state["w"] - 0.1 * grad}, loss
        return step_fn

    def test_runs_steps_and_checkpoints(self, tmp_path):
        from zhpe_ompi_tpu.models.ftloop import FtTrainLoop

        loop = FtTrainLoop(
            self._proc_stub(), step_fn=self._step_fn(_train_setup(0)),
            state={"w": np.zeros(8, np.float32)},
            checkpointer=Checkpointer(str(tmp_path), keep=10,
                                      check_quiescent=False),
            ckpt_every=2)
        state, losses = loop.run(5)
        assert len(losses) == 5
        assert losses[-1] < losses[0]  # it learns
        # step-0 snapshot + every-2 cadence + the final step
        assert loop.ckpt.all_steps() == [0, 2, 4, 5]

    def test_restore_resumes_the_exact_trajectory(self, tmp_path):
        from zhpe_ompi_tpu.models.ftloop import FtTrainLoop

        step_fn = self._step_fn(_train_setup(0))
        ck = Checkpointer(str(tmp_path), keep=20,
                          check_quiescent=False)
        first = FtTrainLoop(self._proc_stub(), step_fn=step_fn,
                            state={"w": np.zeros(8, np.float32)},
                            checkpointer=ck, ckpt_every=1)
        first.run(8)
        full_losses = list(first.losses)
        # a "replacement" restores the step-6 snapshot and continues:
        # its trailing losses must equal the unbroken run's
        second = FtTrainLoop(self._proc_stub(), step_fn=step_fn,
                             state={"w": np.zeros(8, np.float32)},
                             checkpointer=ck, ckpt_every=1)
        second.restore(None)  # latest is step 8; pick 6 explicitly
        second.state, step = ck.restore(6)
        second.step_i = step
        second.run(8)
        np.testing.assert_allclose(second.losses, full_losses[6:8],
                                   rtol=1e-6)

    def test_rejoin_restore_threads_shardings_fn(self, tmp_path,
                                                 monkeypatch):
        """The device-plane restore leg: a replacement's (and the
        rollback's) checkpoint restore passes shardings_fn(ep) through
        to Checkpointer.restore, so sharded state materializes directly
        onto the endpoint's mesh instead of staging on the host."""
        from zhpe_ompi_tpu.models.ftloop import FtTrainLoop

        step_fn = self._step_fn(_train_setup(0))
        ck = Checkpointer(str(tmp_path), check_quiescent=False)
        FtTrainLoop(self._proc_stub(), step_fn=step_fn,
                    state={"w": np.zeros(8, np.float32)},
                    checkpointer=ck, ckpt_every=1).run(2)
        seen = []
        orig = ck.restore

        def spying_restore(step=None, shardings=None):
            seen.append(shardings)
            return orig(step, shardings)

        ck.restore = spying_restore
        monkeypatch.setenv("ZMPI_REJOIN", "1")
        loop = FtTrainLoop(
            self._proc_stub(), step_fn=step_fn,
            state={"w": np.zeros(8, np.float32)}, checkpointer=ck,
            ckpt_every=1,
            shardings_fn=lambda ep: {"w": None})
        loop.run(2)
        assert seen == [{"w": None}]  # the hook's tree reached restore
        assert loop.step_i == 2

    def test_typed_fault_without_respawner_is_loud(self, tmp_path):
        from zhpe_ompi_tpu.models.ftloop import FtTrainLoop

        def step_fn(ep, state, i):
            raise errors.ProcFailed("peer died", failed_ranks=[1])

        loop = FtTrainLoop(
            self._proc_stub(), step_fn=step_fn, state={"x": 1},
            checkpointer=Checkpointer(str(tmp_path),
                                      check_quiescent=False))
        with pytest.raises(errors.UnsupportedError):
            loop.run(1)

    def test_own_device_fault_reraises(self, tmp_path):
        from zhpe_ompi_tpu.models.ftloop import FtTrainLoop

        def step_fn(ep, state, i):
            raise errors.DeviceFault("me", failed_ranks=[0])

        loop = FtTrainLoop(
            self._proc_stub(), step_fn=step_fn, state={"x": 1},
            checkpointer=Checkpointer(str(tmp_path),
                                      check_quiescent=False),
            respawner=lambda victims: None)
        with pytest.raises(errors.DeviceFault):
            loop.run(1)


_DVM_DEVICE_DRILL_PROG = '''
import os, sys, time
sys.path.insert(0, {repo!r})
import numpy as np
import optax
import zhpe_ompi_tpu as zmpi
from zhpe_ompi_tpu.core import errhandler as errh
from zhpe_ompi_tpu.ft import recovery
from zhpe_ompi_tpu.ft.inject import FaultPlan
from zhpe_ompi_tpu.models.ftloop import FtTrainLoop
from zhpe_ompi_tpu.parallel import mesh as mesh_mod
from zhpe_ompi_tpu.parallel.zero import ZeroOptimizer
from zhpe_ompi_tpu.runtime.checkpoint import Checkpointer

DIM = 8
STEPS = 6
WEDGE_RANK = int(os.environ.get("TEST_WEDGE_RANK", "-1"))
WEDGE_AT = int(os.environ.get("TEST_WEDGE_AT", "2"))

proc = zmpi.host_init()
proc.set_errhandler(errh.ERRORS_RETURN)

rng = np.random.default_rng(100 + proc.rank)
target = rng.normal(size=DIM).astype(np.float32)
w0 = np.zeros(DIM, np.float32)
zopt = None  # bound to the loop's live window below


def step_fn(ep, state, i):
    w = np.asarray(state["params"]["w"], np.float32)
    loss = float(np.mean((w - target) ** 2))
    grad = ((2.0 / w.size) * (w - target)).astype(np.float32)
    params = zopt.step({{"w": w}}, {{"w": grad}})
    return {{"params": params, "opt": zopt.full_state()}}, loss


observed = {{}}


def remesh_fn(ep, state):
    # the survivor-mesh / full-size re-shard leg; also the spot where
    # the AGREED (refined) cause is known — sample it for the gate
    if state.get("opt") is not None:
        zopt.reshard(ep, state["opt"])
    else:
        zopt.proc = ep  # fresh moments, new window
    if WEDGE_RANK >= 0 and proc.rank != WEDGE_RANK:
        c = proc.ft_state.cause_of(WEDGE_RANK)
        if c:
            observed.setdefault("cause", c)


plan = FaultPlan(seed=3)
if WEDGE_RANK >= 0 and os.environ.get("ZMPI_REJOIN") != "1":
    # the wedge fires in the FIRST incarnation only: a respawned
    # replacement re-arming the same schedule would wedge itself at
    # the same step forever (observed: an endless respawn carousel)
    plan.wedge_device(WEDGE_RANK, after_steps=WEDGE_AT)
# hold=True: the victim process NEVER unwinds — healthy heartbeats,
# hung device — until the recovery respawn SIGKILLs it (the PRRTE
# declared-dead-incarnation contract; "never an XLA hang" means the
# JOB moves on, not that the wedge resolves)
wedge = plan.arm_device(proc.rank, state=proc.ft_state, hold=True)
probe = mesh_mod.DeviceLivenessProbe(
    state=proc.ft_state, rank=proc.rank, enable=True,
    timeout=float(os.environ.get("TEST_PROBE_TIMEOUT", "40")),
    deadline=float(os.environ.get("TEST_PROBE_DEADLINE", "8")))

loop = FtTrainLoop(
    proc, step_fn=step_fn,
    state={{"params": {{"w": w0.copy()}}, "opt": None}},
    checkpointer=Checkpointer(
        os.path.join(os.environ["TEST_CKPT"], f"r{{proc.rank}}"),
        keep=20, check_quiescent=False),
    ckpt_every=1, probe=probe, wedge=wedge,
    respawner=recovery.daemon_respawn, remesh_fn=remesh_fn)
# the optimizer's collectives ride the loop's LIVE window (the
# revocable, generation-isolated channel recovery depends on);
# remesh_fn re-binds it on every window change
zopt = ZeroOptimizer(loop.live, optax.adam(1e-2), {{"w": w0}})
state, losses = loop.run(STEPS)
print(f"TRAIN-OK rank={{proc.rank}} size={{proc.size}} "
      f"recoveries={{loop.recoveries}} steps={{len(losses)}} "
      f"final={{losses[-1]:.6f}} "
      f"cause={{observed.get('cause', '-')}}", flush=True)
zmpi.host_finalize()
'''


@pytest.mark.slow
class TestDeviceFaultTrainRecoveryDvm:
    """THE acceptance drill (ISSUE 14): a models/ train loop over a
    real-process ft DVM job survives an injected wedged-participant
    psum — typed cause="device" classification (never a detector false
    positive, never an XLA hang: the victim process stays parked until
    the respawn SIGKILLs it), consensus shrink, optimizer re-shard,
    checkpoint rollback, daemon respawn, resume at FULL size — and the
    post-recovery losses equal the fault-free run's, rank for rank."""

    N = 3
    VICTIM = 1

    def _launch(self, tmp_path, wedge: bool):
        import io
        import re

        from zhpe_ompi_tpu.runtime import dvm as dvm_mod

        repo = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        tag = "wedge" if wedge else "ref"
        prog = tmp_path / f"drill_{tag}.py"
        prog.write_text(_DVM_DEVICE_DRILL_PROG.format(repo=repo))
        env = {
            "TEST_CKPT": str(tmp_path / f"ckpt_{tag}"),
            "TEST_WEDGE_RANK": str(self.VICTIM) if wedge else "-1",
            "TEST_WEDGE_AT": "2",
            "TEST_PROBE_DEADLINE": "8",
            "TEST_PROBE_TIMEOUT": "40",
        }
        old = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        d = dvm_mod.Dvm()
        try:
            cli = dvm_mod.DvmClient(d.address)
            out, err = io.StringIO(), io.StringIO()
            rc = cli.launch(
                self.N, [str(prog)], ft=True, timeout=240.0,
                # the heartbeat window is deliberately huge AND the
                # victim keeps beating: only the device probe can
                # classify this failure mode
                mca=[("ft_detector_period", "2.0"),
                     ("ft_detector_timeout", "120.0")],
                stdout=out, stderr=err,
            )
            text = out.getvalue()
            assert rc == 0, (text, err.getvalue())
            rows = re.findall(
                r"TRAIN-OK rank=(\d+) size=(\d+) recoveries=(\d+) "
                r"steps=(\d+) final=([\d.]+) cause=(\S+)", text)
            stat = cli.stat()
            cli.stop()
            cli.close()
            return rows, stat
        finally:
            d.stop()
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    def test_train_loop_survives_wedged_participant(self, tmp_path):
        from zhpe_ompi_tpu.ft import ulfm as ulfm_mod
        from zhpe_ompi_tpu.runtime import dvm as dvm_mod
        from zhpe_ompi_tpu.runtime import spc as spc_mod

        fps0 = ulfm_mod.false_positive_count()
        before = spc_mod.snapshot()
        ref_rows, _ = self._launch(tmp_path, wedge=False)
        assert len(ref_rows) == self.N
        ref_final = {int(r): float(f)
                     for r, _, _, _, f, _ in ref_rows}
        assert all(int(rec) == 0 for _, _, rec, _, _, _ in ref_rows)

        rows, stat = self._launch(tmp_path, wedge=True)
        # every rank finished at FULL size: the survivors (one
        # recovery each) and the respawned replacement (zero — its
        # loop began at the rolled-back step)
        assert len(rows) == self.N, rows
        by_rank = {int(r): (int(s), int(rec), int(st), float(f), c)
                   for r, s, rec, st, f, c in rows}
        assert set(by_rank) == set(range(self.N))
        for r, (size, recoveries, steps, final, cause) in \
                by_rank.items():
            assert size == self.N
            if r == self.VICTIM:
                # the replacement: restored the rolled-back step-2
                # snapshot and ran the remaining 4 steps cleanly
                assert recoveries == 0
                assert steps == 4, steps
            else:
                assert recoveries == 1, (r, recoveries)
                assert steps == 6, steps
                # the typed classification, agreed at shrink: DEVICE —
                # never a detector suspicion, never a bare transport
                # symptom
                assert cause == "device", (r, cause)
        # the post-recovery loss is CORRECT: rank for rank, the wedged
        # run converged to the fault-free run's numbers
        for r in range(self.N):
            assert abs(by_rank[r][3] - ref_final[r]) <= 1e-4, (
                r, by_rank[r][3], ref_final[r])
        # one batched respawn; at least one authoritative daemon fault
        # event (the SIGKILLed wedged incarnation's waitpid)
        assert stat["dvm_respawns"] - before.get("dvm_respawns", 0) \
            == 1
        assert stat["pmix"] == {}
        # the device plane's own gates: probes ran, exactly one fault
        # classified, zero detector false positives
        after = spc_mod.snapshot()
        assert after.get("device_probe_rounds", 0) >= \
            before.get("device_probe_rounds", 0)
        assert ulfm_mod.false_positive_count() == fps0
        assert dvm_mod.live_dvms() == []
        assert dvm_mod.orphaned_daemon_processes() == []
