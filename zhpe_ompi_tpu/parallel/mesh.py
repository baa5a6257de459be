"""Device mesh construction — the wire-up plane.

TPU-native replacement for the reference's runtime wire-up
(``ompi_rte_init`` → PMIx modex, ``ompi/runtime/ompi_mpi_init.c:508,667-700``):
on TPU there is no endpoint-address exchange to do — process identity and the
device topology come from ``jax.distributed`` + the platform, and the "modex"
is mesh construction.  ``jax.sharding.Mesh`` over ICI is the analog of the
btl/ofi endpoint set; host-loopback CPU devices are the btl/self+sm analog
(SURVEY.md §5 "Distributed communication backend").
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
import weakref

import numpy as np

import jax
from jax.sharding import Mesh

from ..core import errors
from ..mca import output as mca_output
from ..mca import var as mca_var
from ..runtime import flightrec, spc, ztrace
from ..utils import deadline as deadline_mod

_stream = mca_output.open_stream("rte")

mca_var.register(
    "rte_distributed_init",
    False,
    "Call jax.distributed.initialize() at init (multi-host/multi-process "
    "deployments; the PMIx-client analog)",
    type=bool,
)

# -- device liveness probe (opt-in device_probe_* family) -------------------

mca_var.register(
    "device_probe_enable", False,
    "Arm the device liveness probe around guarded device collectives: "
    "a region that outlives device_probe_deadline triggers a killable-"
    "child probe (tiny psum over the mesh, coll/tpu.PROBE_SRC); a "
    "missed probe classifies a typed cause=\"device\" fault into the "
    "job's FailureState.  Off by default — a probe costs a subprocess "
    "(a thread of this process on TPU, where the chip is this process's)",
    type=bool,
)
mca_var.register(
    "device_probe_timeout", 20.0,
    "Outer kill (seconds) of one device liveness probe child — the "
    "backstop around its internal watchdog deadline",
    type=float,
)
mca_var.register(
    "device_probe_deadline", 12.0,
    "Internal watchdog deadline (seconds) of the probe child (it "
    "os._exits from the inside at expiry — the structured \"deadline\" "
    "outcome), AND the guarded-region deadline that triggers a probe",
    type=float,
)
mca_var.register(
    "device_probe_grace", 2,
    "Probe rounds that may come back \"ok\" while the guarded region "
    "still blocks before the guard stops re-probing (a slow-but-alive "
    "local plane is a peer's fault to classify, never this rank's own)",
    type=int,
)


def distributed_initialize(**kwargs) -> None:
    """Multi-controller wire-up (PMIx_Init analog): join the JAX coordination
    service.  No-op if already initialized."""
    try:
        jax.distributed.initialize(**kwargs)
        mca_output.verbose(1, _stream, "jax.distributed initialized")
    except RuntimeError as e:
        if "already initialized" in str(e).lower():
            mca_output.verbose(1, _stream, "jax.distributed: %s", e)
        else:
            # real wire-up failure (bad coordinator, unreachable service):
            # failing loudly beats silently running at the wrong world size
            raise


def world_devices() -> list:
    """All addressable devices in process order — the proc table analog."""
    return list(jax.devices())


def world_mesh(axis_name: str = "world", devices=None) -> Mesh:
    """1-D mesh over every device: MPI_COMM_WORLD's footprint."""
    devs = np.asarray(devices if devices is not None else world_devices())
    return Mesh(devs, axis_names=(axis_name,))


def survivor_mesh(mesh: Mesh, failed, axis: str | None = None) -> Mesh:
    """The remesh step of the device-plane recovery pipeline: the same
    mesh minus the failed indices along ``axis`` (default: the first
    axis — the data-parallel outer loop).  The survivor mesh is what
    ``zero``/``grad``/``hybrid`` re-shard onto between shrink and
    respawn; a respawned job calls :func:`world_mesh`/:func:`make_mesh`
    again for the full-size resume."""
    axis = axis or mesh.axis_names[0]
    if axis not in mesh.axis_names:
        raise errors.ArgError(
            f"survivor_mesh: axis {axis!r} not in {mesh.axis_names}")
    k = mesh.axis_names.index(axis)
    drop = {int(r) for r in failed}
    arr = np.moveaxis(np.asarray(mesh.devices), k, 0)
    keep = [i for i in range(arr.shape[0]) if i not in drop]
    if not keep:
        raise errors.ArgError(
            f"survivor_mesh: every index of axis {axis!r} failed")
    sp = ztrace.begin(ztrace.REMESH, -1, axis=axis,
                      dropped=sorted(drop)) if ztrace.active else None
    out = Mesh(np.moveaxis(arr[keep], 0, k), axis_names=mesh.axis_names)
    if sp is not None:
        sp.end(survivors=len(keep))
    return out


def make_mesh(axis_sizes: dict[str, int], devices=None) -> Mesh:
    """N-D mesh, e.g. {'dp': 2, 'tp': 4}: the topo-framework analog
    (cartesian topologies, ``ompi/mca/topo``) expressed the TPU way.

    Uses jax's device-assignment heuristics so that, on real hardware, the
    trailing axes land on the fastest ICI dimensions.
    """
    names = tuple(axis_sizes.keys())
    shape = tuple(axis_sizes.values())
    if devices is None:
        try:
            return jax.make_mesh(shape, names)
        except (ValueError, RuntimeError):
            devices = world_devices()
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, axis_names=names)


# -- device liveness probe (the fault loop's device half) -------------------


def _holds_tpu() -> bool:
    """Whether this process drives a TPU.  The chip then belongs to this
    process, and a probe child could not reach it."""
    return jax.default_backend() == "tpu"


def _psum_i(v):
    return jax.lax.psum(v, "i")


def _probe_in_process(deadline: float,
                      rank: int | None) -> tuple[str, str]:
    """The TPU form of the probe: the same tiny psum as
    ``coll/tpu.PROBE_SRC``, run by this process on a daemon thread and
    bounded by ``deadline``.  A wedged collective strands that thread,
    never the caller; the outcomes are the child's ("ok", "deadline",
    "error")."""
    from ..coll import tpu as coll_tpu

    out: dict = {}

    def body():
        try:
            wedge = os.environ.get(coll_tpu.WEDGE_ENV)
            if wedge is not None and wedge in (
                    coll_tpu.WEDGE_ALL, "" if rank is None else str(rank)):
                time.sleep(3600)  # the injected wedge, as in the child
            d = jax.devices()
            s = jax.pmap(_psum_i, axis_name="i")(
                jax.numpy.arange(float(len(d))))
            out["ok"] = json.dumps({"n": len(d), "platform": d[0].platform,
                                    "psum": float(s[0])})
        except Exception as e:  # noqa: BLE001 - the structured "error"
            out["error"] = f"{type(e).__name__}: {e}"

    t = threading.Thread(target=body, daemon=True,
                         name="device-probe-inproc")
    t.start()
    t.join(deadline)
    if t.is_alive():
        return "deadline", (
            f"in-process probe hit its deadline ({deadline:.0f}s)")
    if "error" in out:
        return "error", out["error"]
    return "ok", out["ok"]


def probe_device_plane(timeout: float | None = None,
                       deadline: float | None = None,
                       env: dict | None = None,
                       rank: int | None = None) -> tuple[str, str]:
    """One device liveness probe: the tiny deadline-bounded psum
    (``coll/tpu.PROBE_SRC``).  Off-TPU it runs in a killable child
    through the shared ``utils/deadline`` idiom, so a wedged
    ``jax.devices()`` OR a wedged collective dies from the inside at the
    child's internal watchdog.  On a TPU the chip belongs to this
    process, so the probe runs here (:func:`_probe_in_process`) and
    never starts a child that would need the chip; ``timeout`` and
    ``env`` then do not apply.

    Returns the structured ``(kind, detail)``: "ok" (detail = device
    JSON), "hung", "deadline", "error".  Counts ``device_probe_rounds``
    (and ``device_probe_misses`` on hung/deadline) and records the
    DEVICE_PROBE ztrace span, so an OSU ``--plane device`` row and a
    postmortem timeline both see every round."""
    from ..coll import tpu as coll_tpu

    timeout = float(mca_var.get("device_probe_timeout", 20.0)) \
        if timeout is None else float(timeout)
    deadline = float(mca_var.get("device_probe_deadline", 12.0)) \
        if deadline is None else float(deadline)
    spc.record("device_probe_rounds")
    sp = ztrace.begin(ztrace.DEVICE_PROBE, -1) if ztrace.active else None
    if _holds_tpu():
        kind, detail = _probe_in_process(deadline, rank)
    else:
        if rank is not None:
            # scope the wedge-injection hook: the child wedges only when
            # the hook names THIS rank (or "all" = the whole process) —
            # a healthy rank sharing the process must get a healthy
            # answer
            env = dict(os.environ if env is None else env)
            env[coll_tpu.PROBE_RANK_ENV] = str(int(rank))
        kind, detail = deadline_mod.run_probe(
            coll_tpu.PROBE_SRC, timeout, deadline, env=env)
    if kind in ("hung", "deadline"):
        spc.record("device_probe_misses")
    if sp is not None:
        sp.end(kind=kind)
    return kind, detail


class DeviceLivenessProbe:
    """The armed guard: a deadline around a device-collective region,
    feeding missed probes into the SAME :class:`~zhpe_ompi_tpu.ft.ulfm.
    FailureState` the host-plane detectors feed — the device half of
    the fault loop.

    Usage (the models/ftloop shape)::

        probe = DeviceLivenessProbe(state=proc.ft_state, rank=proc.rank,
                                    on_fault=proc.flood_device_fault)
        ...
        with probe.guard():
            loss = step(params, batch)   # may wedge mid-psum

    A region that outlives ``device_probe_deadline`` triggers one
    probe (:func:`probe_device_plane`) from the watchdog thread (the
    region itself cannot be killed — the XLA dispatch holds the
    caller's thread):

    - probe MISSED ("hung"/"deadline"): the local device plane is
      wedged — classify a typed ``cause="device"`` fault for THIS rank
      into the FailureState (flooding notices exactly like transport
      deaths do, via ``on_fault``), count ``device_faults``, record the
      DEVICE_FAULT flightrec event.
    - probe OK: the local plane answers — the region is slow, or a
      REMOTE participant wedged (that rank's own guard classifies it;
      its notice unwinds us).  Re-arm, up to ``device_probe_grace``
      ok-rounds, then stop probing and leave the wait to the host
      plane.

    ``probe_fn`` is injectable (tests drill the ladder without paying
    a subprocess per case); the default is :func:`probe_device_plane`.
    ``guard()`` is a no-op unless ``device_probe_enable`` is on or the
    probe was constructed with ``enable=True`` — opt-in, per contract.
    """

    def __init__(self, state=None, rank: int = -1, on_fault=None,
                 probe_fn=None, enable: bool | None = None,
                 timeout: float | None = None,
                 deadline: float | None = None,
                 grace: int | None = None):
        self.state = state
        self.rank = int(rank)
        self.on_fault = on_fault
        self.probe_fn = probe_fn  # None = probe_device_plane, rank-scoped
        self.enabled = bool(mca_var.get("device_probe_enable", False)) \
            if enable is None else bool(enable)
        self.timeout = timeout
        self.deadline = float(mca_var.get("device_probe_deadline", 12.0)) \
            if deadline is None else float(deadline)
        self.grace = int(mca_var.get("device_probe_grace", 2)) \
            if grace is None else int(grace)
        self.fault: errors.DeviceFault | None = None

    # -- classification ----------------------------------------------------

    def classify(self, kind: str, detail: str) -> errors.DeviceFault:
        """A missed probe becomes a typed device fault: counted,
        flight-recorded, marked into the FailureState (cause="device" —
        never a detector suspicion, so the zero-false-positive gate
        keeps its meaning), and handed to ``on_fault`` (the wire
        plane's notice flood / the test's wedge release)."""
        fault = errors.DeviceFault(
            f"device plane missed its liveness deadline ({kind}: "
            f"{detail})",
            failed_ranks=[self.rank] if self.rank >= 0 else (),
            kind=kind,
        )
        spc.record("device_faults")
        flightrec.record(flightrec.DEVICE_FAULT, rank=self.rank,
                         kind=kind)
        if ztrace.active:
            ztrace.instant(ztrace.FT_CLASS, self.rank,
                           failed=self.rank, cause="device")
        if self.state is not None and self.rank >= 0:
            self.state.mark_failed(self.rank, cause="device")
        self.fault = fault
        if self.on_fault is not None:
            self.on_fault(fault)
        return fault

    def probe_once(self) -> tuple[str, str]:
        if self.probe_fn is not None:
            return self.probe_fn(timeout=self.timeout,
                                 deadline=self.deadline)
        return probe_device_plane(
            timeout=self.timeout, deadline=self.deadline,
            rank=self.rank if self.rank >= 0 else None)

    # -- the armed guard ---------------------------------------------------

    def _expired(self, watchdog) -> None:
        """Watchdog-thread body: the guarded region outlived its
        deadline.  Probe; classify a miss; tolerate up to ``grace``
        ok-rounds before going quiet (re-arming forever would turn a
        long legitimate region into a polling loop)."""
        for _ in range(max(1, self.grace)):
            kind, detail = self.probe_once()
            if watchdog._disarmed.is_set():
                return  # the region finished while we probed: no fault
            if kind in ("hung", "deadline"):
                self.classify(kind, detail)
                return
            # ok/error: the plane answered (an error is a health
            # problem, not a wedge — loud in the probe counters, not a
            # classification); wait out one more deadline
            if watchdog._disarmed.wait(self.deadline):
                return
        mca_output.verbose(
            1, _stream,
            "device probe guard: region still blocked after %d ok "
            "rounds; leaving the wait to the host plane", self.grace,
        )

    def guard(self, deadline: float | None = None):
        """Context manager arming the deadline around one device-
        collective region (one train step).  No-op when disabled."""
        if not self.enabled:
            return contextlib.nullcontext()
        wd_box: list = []
        wd = deadline_mod.Watchdog(
            float(deadline if deadline is not None else self.deadline),
            on_expire=lambda: self._expired(wd_box[0]),
            name=f"device-probe-guard-{self.rank}",
        )
        wd_box.append(wd)
        return wd


# -- the always-on background prober (the fleet-health half) ----------------

mca_var.register(
    "dvm_device_probe_interval_ms", 0,
    "Interval (milliseconds) of the ALWAYS-ON background device "
    "prober (DeviceProber): between guarded regions it runs the same "
    "killable-child liveness probe the guard runs, so a wedge that "
    "lands OUTSIDE a guarded region still classifies (cause=\"device\","
    " the typed DeviceFault path) within one interval plus one probe "
    "timeout instead of at the next collective; 0 (the default) = off",
    type=int,
)

_live_probers: weakref.WeakSet = weakref.WeakSet()


def live_prober_threads() -> list[str]:
    """Background prober threads still RUNNING — must be [] once every
    owner stopped its prober (the conftest session gate; a stopped
    prober's thread finishing one last probe call is not a leak, the
    deadline-watchdog contract)."""
    out = []
    for p in list(_live_probers):
        t = p._thread
        if t is not None and t.is_alive() and not p._stop.is_set():
            out.append(t.name)
    return out


class DeviceProber:
    """Detector-style background device prober — the always-on half of
    the device fault loop.  The :class:`DeviceLivenessProbe` guard only
    watches INSIDE guarded regions (a train step); a device plane that
    wedges between steps — data loading, checkpointing, an idle serving
    process — classifies only at the NEXT collective.  This thread
    probes on ``dvm_device_probe_interval_ms`` whenever no guarded
    region is active (:meth:`region` brackets them), feeding the same
    typed ``DeviceFault``/FailureState path via the probe's
    ``classify``, so an out-of-region wedge classifies in bounded time
    (one interval + one probe timeout).

    Counters: every background round records ``device_probes``; a miss
    records ``device_probe_faults`` (on top of the probe family's own
    ``device_probe_rounds``/``device_probe_misses``).  Hygiene:
    :func:`live_prober_threads` must be [] once owners stop — the
    models/ftloop seam starts the prober at ``run()`` entry and stops
    it on the way out."""

    def __init__(self, probe: DeviceLivenessProbe,
                 interval_ms: int | None = None):
        self.probe = probe
        ms = int(mca_var.get("dvm_device_probe_interval_ms", 0)) \
            if interval_ms is None else int(interval_ms)
        self.interval_s = ms / 1000.0
        self._stop = threading.Event()
        self._busy = 0
        self._busy_lock = threading.Lock()
        self._thread: threading.Thread | None = None
        _live_probers.add(self)

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive() \
            and not self._stop.is_set()

    def start(self) -> "DeviceProber":
        """Arm the background thread; a no-op when the interval is 0
        (the opt-in gate) or the prober already runs."""
        if self.interval_s <= 0 or self.running:
            return self
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True,
            name=f"device-prober-{self.probe.rank}",
        )
        self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            with self._busy_lock:
                busy = self._busy > 0
            if busy or self.probe.fault is not None:
                # a guarded region owns this window (its watchdog
                # classifies), or a fault already classified and the
                # recovery path owns the plane until it clears
                continue
            kind, detail = self.probe.probe_once()
            spc.record("device_probes")
            if self._stop.is_set():
                return  # outcome after stop is dropped (watchdog rule)
            with self._busy_lock:
                busy = self._busy > 0
            if busy:
                continue  # a region started mid-probe: its guard owns it
            if kind in ("hung", "deadline"):
                spc.record("device_probe_faults")
                self.probe.classify(kind, detail)

    @contextlib.contextmanager
    def region(self, inner=None):
        """Bracket a guarded region (optionally entering ``inner`` —
        the probe's guard — inside the bracket): the background thread
        goes quiet while any region is active, so the two halves never
        double-probe one wedge."""
        with self._busy_lock:
            self._busy += 1
        try:
            if inner is not None:
                with inner:
                    yield
            else:
                yield
        finally:
            with self._busy_lock:
                self._busy -= 1

    def stop(self, join_timeout: float = 1.0) -> None:
        """Stop probing.  The join is a short tidy-up (a thread still
        inside a probe subprocess is bounded by the probe's outer kill
        and its outcome is dropped) — the leak gate counts only
        running probers."""
        self._stop.set()
        t = self._thread
        if t is not None and t.is_alive() \
                and t is not threading.current_thread():
            t.join(join_timeout)
