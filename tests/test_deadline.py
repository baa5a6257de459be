"""utils/deadline — the shared deadline-armed killable-probe idiom
(the device liveness probe arms the same machinery).  Fast: every case
uses a stub probe source, never jax, never a real backend."""

import time

from zhpe_ompi_tpu.utils import deadline


class TestRunProbe:
    def test_ok_probe_reports_stdout(self):
        kind, detail = deadline.run_probe(
            "print('alive')\n", timeout_s=30.0, deadline_s=30.0)
        assert kind == "ok"
        assert detail == "alive"

    def test_internal_deadline_kills_a_wedge_from_the_inside(self):
        """A probe that wedges after the preamble armed the watchdog
        exits by itself, well inside the outer kill."""
        t0 = time.perf_counter()
        kind, detail = deadline.run_probe(
            "time.sleep(60)\n", timeout_s=30.0, deadline_s=0.5)
        elapsed = time.perf_counter() - t0
        assert kind == "deadline"
        assert "internal deadline" in detail
        assert elapsed < 10.0, (
            f"deadline probe took {elapsed:.1f}s — the internal "
            "watchdog did not fire")

    def test_outer_timeout_backstops_a_disarmed_watchdog(self):
        """deadline_s=0 disarms the child watchdog (the preamble's
        contract); the outer kill still bounds the hang."""
        kind, detail = deadline.run_probe(
            "time.sleep(60)\n", timeout_s=1.0, deadline_s=0.0)
        assert kind == "hung"
        assert "hung" in detail

    def test_error_reports_rc_and_stderr(self):
        kind, detail = deadline.run_probe(
            "sys.stderr.write('boom')\nsys.exit(7)\n",
            timeout_s=30.0, deadline_s=30.0)
        assert kind == "error"
        assert "rc=7" in detail and "boom" in detail

    def test_error_with_deadline_word_is_not_a_hang(self):
        """A fast FAILURE whose stderr says DEADLINE_EXCEEDED (a common
        transient accelerator status) must classify as an ordinary
        error — only the structured outcomes name a wedge."""
        kind, _ = deadline.run_probe(
            "sys.stderr.write('DEADLINE_EXCEEDED: busy')\n"
            "sys.exit(1)\n", timeout_s=30.0, deadline_s=30.0)
        assert kind == "error"

    def test_no_probe_child_leaks(self):
        """Every outcome reaps its child — including the killed hung
        one (the conftest session gate's per-call form)."""
        deadline.run_probe("print(1)\n", 30.0, 30.0)
        deadline.run_probe("time.sleep(60)\n", 1.0, 0.0)   # hung+killed
        deadline.run_probe("time.sleep(60)\n", 30.0, 0.3)  # deadline
        deadline.run_probe("sys.exit(3)\n", 30.0, 30.0)    # rc==3 is
        # indistinguishable from the watchdog's by design: the rc IS
        # the structured channel
        assert deadline.orphaned_probe_processes() == []

    def test_deadline_env_reaches_the_child(self):
        """The preamble reads DEADLINE_ENV — a probe that PRINTS it
        proves run_probe exported the right value."""
        kind, detail = deadline.run_probe(
            f"print(os.environ['{deadline.DEADLINE_ENV}'])\n",
            timeout_s=30.0, deadline_s=7.5)
        assert kind == "ok"
        assert float(detail) == 7.5


class TestWatchdog:
    def test_fast_region_never_fires(self):
        fired = []
        with deadline.Watchdog(5.0, on_expire=lambda: fired.append(1)):
            pass
        assert fired == []
        assert deadline.live_watchdog_threads() == []

    def test_expiry_fires_on_the_watchdog_thread(self):
        import threading

        fired = []
        done = threading.Event()

        def on_expire():
            fired.append(threading.current_thread().name)
            done.set()

        wd = deadline.Watchdog(0.05, on_expire=on_expire,
                               name="wd-test").arm()
        assert done.wait(5.0)
        assert wd.expired
        assert fired == ["wd-test"]
        wd.disarm()
        assert deadline.live_watchdog_threads() == []

    def test_disarm_before_expiry_is_quiet(self):
        fired = []
        wd = deadline.Watchdog(10.0,
                               on_expire=lambda: fired.append(1)).arm()
        wd.disarm()
        assert fired == [] and not wd.expired
        assert deadline.live_watchdog_threads() == []

    def test_exception_inside_region_still_disarms(self):
        fired = []
        try:
            with deadline.Watchdog(10.0,
                                   on_expire=lambda: fired.append(1)):
                raise ValueError("region failed")
        except ValueError:
            pass
        assert fired == []
        assert deadline.live_watchdog_threads() == []
