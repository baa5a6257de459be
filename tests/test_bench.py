"""The chip-facing script (chip_smoke.py) and the killable probe idiom
the device plane keeps (utils/deadline.run_probe).  Fast: on the CPU the
script must refuse before measuring anything, and every probe case uses
a stub source, never a real backend."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import chip_smoke
from zhpe_ompi_tpu.utils import deadline

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_probe(timeout_s, deadline_s, src):
    return deadline.run_probe(src, timeout_s, deadline_s)


def _watchdog_prelude() -> str:
    """The watchdog must be armed before the jax import — that
    ordering IS the deadline guarantee for a wedged jax.devices().
    run_probe prepends watchdog_preamble() to every child, so the
    ASSEMBLED device probe (coll/tpu.PROBE_SRC) is checked here."""
    from zhpe_ompi_tpu.coll import tpu as coll_tpu

    assembled = deadline.watchdog_preamble() + coll_tpu.PROBE_SRC
    head, sep, _ = assembled.partition("import jax")
    assert sep, "PROBE_SRC no longer imports jax?"
    assert "threading.Thread" in head, (
        "the probe watchdog must start BEFORE the jax import — a hang "
        "inside jax.devices() is exactly what it exists to kill"
    )
    return ""  # run_probe arms the preamble itself; callers pass bodies


class TestProbeDeadline:
    def test_hung_probe_dies_on_internal_deadline(self):
        """A probe that wedges after arming the watchdog exits by
        itself, well inside the outer subprocess timeout."""
        src = _watchdog_prelude() + "import time as _t\n_t.sleep(60)\n"
        t0 = time.perf_counter()
        kind, detail = _run_probe(timeout_s=30.0, deadline_s=0.5,
                                        src=src)
        elapsed = time.perf_counter() - t0
        assert kind == "deadline"
        assert "internal deadline" in detail
        assert elapsed < 10.0, (
            f"deadline probe took {elapsed:.1f}s — the internal "
            "watchdog did not fire"
        )

    def test_outer_timeout_still_backstops(self):
        """A probe that hangs with the watchdog DISABLED (deadline 0)
        is killed by the outer subprocess timeout — the backstop the
        internal deadline rides inside."""
        src = _watchdog_prelude() + "import time as _t\n_t.sleep(60)\n"
        kind, detail = _run_probe(timeout_s=1.0, deadline_s=0.0,
                                        src=src)
        assert kind == "hung"
        assert "hung" in detail

    def test_healthy_probe_reports_devices(self):
        src = ("import json\n"
               "print(json.dumps({'n': 1, 'platform': 'stub'}))\n")
        kind, detail = _run_probe(timeout_s=30.0, deadline_s=30.0,
                                         src=src)
        assert kind == "ok"
        assert json.loads(detail) == {"n": 1, "platform": "stub"}

    def test_failing_probe_reports_rc_and_stderr(self):
        src = "import sys\nsys.stderr.write('boom')\nsys.exit(7)\n"
        kind, detail = _run_probe(timeout_s=30.0, deadline_s=30.0,
                                         src=src)
        assert kind == "error"
        assert "rc=7" in detail and "boom" in detail

    def test_error_with_deadline_word_is_not_a_hang(self):
        """A fast FAILURE whose stderr happens to say DEADLINE_EXCEEDED
        (a common transient accelerator status) must classify as an
        ordinary error — the retry ladder rides errors out with
        backoff, and only true hangs cut it short."""
        src = ("import sys\n"
               "sys.stderr.write('DEADLINE_EXCEEDED: tpu busy')\n"
               "sys.exit(1)\n")
        kind, detail = _run_probe(timeout_s=30.0, deadline_s=30.0,
                                        src=src)
        assert kind == "error"


class TestCompileCache:
    @pytest.fixture
    def cache_dir(self):
        import jax

        before = jax.config.jax_compilation_cache_dir
        yield lambda: jax.config.jax_compilation_cache_dir
        jax.config.update("jax_compilation_cache_dir", before)

    def test_default_is_fixed_repo_path(self, cache_dir, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        chip_smoke.use_compile_cache()
        assert cache_dir() == os.path.join(_REPO, ".jax_cache")

    def test_env_dir_is_left_to_jax(self, cache_dir, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        before = cache_dir()
        chip_smoke.use_compile_cache()
        assert cache_dir() == before


class TestChipSmoke:
    def test_cpu_run_exits_nonzero_without_ok_line(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "chip_smoke.py"], cwd=_REPO, env=env,
            capture_output=True, text=True, timeout=120)
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout
        assert "not a TPU" in proc.stderr

    @pytest.mark.parametrize("opname", chip_smoke.COLL_OPS)
    def test_numpy_reference_matches_framework(self, opname):
        """The collectives phase's numpy expectations agree with the
        framework on the CPU loopback mesh (auto algorithm)."""
        import zhpe_ompi_tpu as zmpi

        world = zmpi.init()
        xs = np.random.default_rng(3).standard_normal(
            (world.size, 8 * world.size)).astype(np.float32)
        for op in ("SUM", "MAX") if opname == "allreduce" else (None,):
            got = chip_smoke._run_collective(world, opname, xs, op,
                                             root=world.size - 1)
            want = chip_smoke._expected(opname, xs, op, world.size - 1)
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
