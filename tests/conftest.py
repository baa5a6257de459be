"""Test configuration.

Forces an 8-device virtual CPU platform before jax is imported anywhere, the
analog of the reference's single-host multi-rank loopback testing via
btl/self + btl/sm (SURVEY.md §4): any N-rank collective/pt2pt test runs on one
host with no TPU.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Lock-order witness ON for the whole suite (default-off for users and
# benchmarks): transport locks constructed after this point are
# lockdep-instrumented, the per-thread acquisition graph accumulates
# across every test, and the session gate below asserts zero inversion
# cycles.  Must be set before any zhpe_ompi_tpu transport module is
# imported (lock construction reads it).
os.environ.setdefault("ZMPI_LOCKDEP", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Pin the platform at the jax-config level too, whatever JAX_PLATFORMS the
# caller exported: tests are CPU-loopback by design, and a test process
# must never take a chip.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-second stress cases excluded from the tier-1 run "
        "(selected out by -m 'not slow')",
    )


@pytest.fixture(scope="session", autouse=True)
def _ulfm_detector_hygiene():
    """Suite-wide ULFM + recovery acceptance gates, checked once at
    session end: the heartbeat failure detector must produce ZERO false
    positives across a clean run (suspicions of ranks no fault plan
    killed), no detector thread may leak past its test's fixtures, no
    RESPAWNED-rank thread may outlive the recovery test that grew the
    job back to full size, and no checkpoint directory a rollback
    touched may be left holding orphaned ``.tmp``/``.old`` partials."""
    yield
    from zhpe_ompi_tpu.ft import recovery, ulfm

    fps = ulfm.false_positive_count()
    assert fps == 0, (
        f"failure detector produced {fps} false positive(s) — a rank "
        "was suspected dead that no fault plan ever killed"
    )
    leaked = ulfm.live_detectors()
    assert not leaked, f"heartbeat detector threads leaked: {leaked}"
    respawned = recovery.live_respawn_threads()
    assert not respawned, (
        f"respawned-rank threads leaked past their recovery test: "
        f"{respawned}"
    )
    partials = recovery.orphaned_checkpoint_partials()
    assert not partials, (
        f"recovery left orphaned checkpoint partials on disk: {partials}"
    )
    from zhpe_ompi_tpu.pt2pt import tcp as tcp_mod

    pushers = tcp_mod.live_push_threads()
    assert not pushers, (
        f"rendezvous push-pool threads leaked past their proc's "
        f"close(): {pushers}"
    )
    incomplete = tcp_mod.live_incomplete_send_requests()
    assert not incomplete, (
        f"deferred SendRequests left incomplete past their proc's "
        f"close()/sever() (waiters would wedge; the drain-or-abandon "
        f"teardown contract): {incomplete}"
    )
    parked = tcp_mod.orphaned_rndv_descriptors()
    assert not parked, (
        f"parked rendezvous descriptors orphaned past their proc's "
        f"close() (pinned caller buffers nobody will ever push): "
        f"{parked}"
    )
    from zhpe_ompi_tpu.pt2pt import engine_mux as engine_mod

    engines = engine_mod.live_engines()
    assert not engines, (
        f"channel-engine reader threads leaked past their owner's "
        f"close() (every TcpProc/FramedRpcServer closes its engine in "
        f"its teardown ladder): {engines}"
    )
    chans = engine_mod.leaked_channels()
    assert not chans, (
        f"framed channels still registered on an engine at session end "
        f"(their owner unregistered neither on close nor on detach): "
        f"{chans}"
    )
    from zhpe_ompi_tpu.pt2pt import sm as sm_mod

    orphans = sm_mod.orphaned_ring_files()
    assert not orphans, (
        f"Python-plane /dev/shm ring segments leaked past their proc's "
        f"close() (the C-plane lifecycle contract): {orphans}"
    )
    polls = sm_mod.live_poll_threads()
    assert not polls, f"sm poll threads leaked: {polls}"
    audits = sm_mod.segment_audit_failures()
    assert not audits, (
        f"sm segment close-time audits failed (the demand-mapping "
        f"contract: footprint matches the allocation bitmap, no ring "
        f"materialized for a peer that never sent, zero orphaned "
        f"directory entries): {audits}"
    )
    from zhpe_ompi_tpu.pt2pt import groups as groups_mod

    windows = groups_mod.leaked_tag_windows()
    assert not windows, (
        f"han group-view tag windows leaked past their endpoint's "
        f"close(): {windows}"
    )
    elections = groups_mod.live_election_threads()
    assert not elections, (
        f"han leader-election threads leaked (election is the "
        f"synchronous min-rank rule; no thread may outlive it): "
        f"{elections}"
    )
    from zhpe_ompi_tpu.runtime import dvm as dvm_mod
    from zhpe_ompi_tpu.runtime import pmix as pmix_mod

    daemons = dvm_mod.live_dvms()
    assert not daemons, (
        f"in-process runtime daemons left listening past their test's "
        f"stop(): {daemons}"
    )
    zprted = dvm_mod.orphaned_daemon_processes()
    assert not zprted, (
        f"zprted daemon processes orphaned past the suite (every test "
        f"that spawns one owns its stop/kill; --parent children scan "
        f"the same cmdline shape): {zprted}"
    )
    tickets = dvm_mod.queued_admission_tickets()
    assert not tickets, (
        f"admission tickets left queued past the suite (a launch "
        f"handler died without cancel/release — the queue head is "
        f"wedged): {tickets}"
    )
    from zhpe_ompi_tpu.runtime import dvmtree as dvmtree_mod

    stale_cache = dvmtree_mod.stale_cache_state()
    assert not stale_cache, (
        f"routed-store cache state left at session end (a child "
        f"daemon's leaf cache dies with its daemon's stop(); an open "
        f"routed store past the suite is a leaked tree): {stale_cache}"
    )
    placement_audits = dvmtree_mod.placement_audit_failures()
    assert not placement_audits, (
        f"placement audits failed during the suite without being "
        f"cleared by the test that injected them (two live jobs were "
        f"about to share sessions/namespaces/exclusive subtrees): "
        f"{placement_audits}"
    )
    from zhpe_ompi_tpu.parallel import mesh as mesh_mod

    probers = mesh_mod.live_prober_threads()
    assert not probers, (
        f"background device-prober threads left running past their "
        f"owner's stop() (the always-on prober dies with its loop): "
        f"{probers}"
    )
    servers = pmix_mod.live_servers()
    assert not servers, (
        f"PMIx servers left listening past their owner's close(): "
        f"{servers}"
    )
    stale_ns = pmix_mod.stale_namespaces()
    assert not stale_ns, (
        f"stale PMIx namespace state left after the suite (the daemon "
        f"destroys a job's namespace when the job ends): {stale_ns}"
    )
    from zhpe_ompi_tpu.runtime import spc as spc_mod

    publishers = spc_mod.live_publisher_threads()
    assert not publishers, (
        f"metrics-publisher threads leaked past their proc's close() "
        f"(the final-flush-then-stop contract): {publishers}"
    )
    stale_keys = pmix_mod.stale_metric_keys()
    assert not stale_keys, (
        f"stale metrics:*/flightrec:*/trace:* keys left in a live "
        f"store after the suite (namespace destroy drops a job's "
        f"whole keyspace — these outlived theirs): {stale_keys}"
    )
    from zhpe_ompi_tpu.runtime import ztrace as ztrace_mod

    armed = ztrace_mod.armed_count()
    assert armed == 0 and not ztrace_mod.active, (
        f"ztrace left ARMED at session end (refcount {armed}) — a "
        f"test or publisher armed the tracing plane and never "
        f"disarmed it; every later send would pay span recording "
        f"and wire-context bytes (the zero-overhead-when-off "
        f"contract)"
    )
    scrapers = dvm_mod.live_metrics_listeners()
    assert not scrapers, (
        f"metrics HTTP listeners left bound past their daemon's "
        f"stop(): {scrapers}"
    )
    from zhpe_ompi_tpu.utils import deadline as deadline_mod

    watchdogs = deadline_mod.live_watchdog_threads()
    assert not watchdogs, (
        f"deadline watchdog threads leaked past their guard's exit "
        f"(every probe guard disarms on region return): {watchdogs}"
    )
    probes = deadline_mod.orphaned_probe_processes()
    assert not probes, (
        f"probe subprocesses orphaned past their run_probe call (ok/"
        f"deadline/error children are reaped, hung ones killed): "
        f"{probes}"
    )
    from zhpe_ompi_tpu.io import ckptio as ckptio_mod

    shard_tmps = ckptio_mod.orphaned_shard_temps()
    assert not shard_tmps, (
        f"collective checkpoint plane left orphaned shard temp files "
        f"(every aggregator write is tmp+fsync+rename; a .tmp past the "
        f"suite is a crashed writer nobody healed): {shard_tmps}"
    )
    ckpt_writers = ckptio_mod.live_writer_threads()
    assert not ckpt_writers, (
        f"checkpoint writer/aggregator threads leaked past their "
        f"checkpointer's wait() (the drain-before-done contract): "
        f"{ckpt_writers}"
    )
    torn_steps = ckptio_mod.incomplete_manifests()
    assert not torn_steps, (
        f"incomplete checkpoint manifests left at session end (a step "
        f"directory with no complete manifest is a torn checkpoint — "
        f"restore ignores it, but tests must heal() what they tear): "
        f"{torn_steps}"
    )
    from zhpe_ompi_tpu.utils import lockdep

    inversions = lockdep.cycles()
    assert not inversions, (
        f"lock-order witness recorded inversion cycle(s) across the "
        f"suite (two threads took the named locks in opposite order "
        f"somewhere — the ch.lock/_rndv_lock bug class): {inversions}"
    )
    from zhpe_ompi_tpu.tools import ztune as ztune_mod

    sweepers = ztune_mod.orphaned_sweep_processes()
    assert not sweepers, (
        f"ztune sweep worker processes orphaned past the suite (every "
        f"--real-procs sweep kills its rank interpreters on every "
        f"exit path): {sweepers}"
    )
    tables = pmix_mod.stale_tuned_tables()
    assert not tables, (
        f"stale tuned-table namespace state left in a live store after "
        f"the suite (a test that publishes a ztune table destroys the "
        f"ztune namespace or closes the store): {tables}"
    )
    from zhpe_ompi_tpu.models import inferloop as inferloop_mod

    servers = inferloop_mod.live_worker_threads()
    assert not servers, (
        f"inference serving threads leaked past their loop's stop() "
        f"(rank 0's stop broadcasts the shutdown; every rank's worker "
        f"exits through the same step boundary): {servers}"
    )
    parked = inferloop_mod.parked_tickets()
    assert not parked, (
        f"request-queue tickets left parked at session end (a serving "
        f"plane drains by serving, failing, or evicting every "
        f"submitted request — a parked ticket is a caller wedged in "
        f"result() forever): {parked}"
    )


@pytest.fixture(autouse=True)
def _ulfm_expected_kill_isolation():
    """Per-test isolation for the detector-accuracy bookkeeping: the
    ranks a fault plan killed are forgotten after each test, so the
    session-wide zero-false-positive gate keeps full strength (a rank
    number one test legitimately killed must not excuse a later test's
    false suspicion of the same number)."""
    yield
    from zhpe_ompi_tpu.ft import ulfm

    ulfm.clear_expected_failures()


@pytest.fixture()
def fresh_vars():
    """Snapshot/restore the MCA var registry around a test."""
    from zhpe_ompi_tpu.mca import var as mca_var

    saved = {v.name: (v._value, v._source) for v in mca_var.registry.all_vars()}
    yield mca_var.registry
    for v in mca_var.registry.all_vars():
        if v.name in saved:
            v._value, v._source = saved[v.name]
