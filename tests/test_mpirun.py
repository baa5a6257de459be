"""zmpirun launcher tests — the reference's launch surface
(mpirun → prte, ``ompi/tools/mpirun/Makefile.am:11-15``) exercised the way
``test/simple/`` exercises it: tiny programs under the launcher, plus the
abort/teardown path (``test/simple/delayed_abort.c`` shape).

These spawn REAL OS processes; every rank's endpoint comes up through the
ZMPI_* env contract via zmpi.host_init().
"""

import io
import os
import sys
import textwrap

import pytest

from zhpe_ompi_tpu.tools import mpirun

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _script(tmp_path, body: str) -> str:
    p = tmp_path / "prog.py"
    p.write_text(
        "import sys\n"
        f"sys.path.insert(0, {_REPO!r})\n" + textwrap.dedent(body)
    )
    return str(p)


def _launch(n, argv, timeout=60.0, **kw):
    out, err = io.StringIO(), io.StringIO()
    rc = mpirun.launch(n, argv, stdout=out, stderr=err, timeout=timeout,
                       **kw)
    return rc, out.getvalue(), err.getvalue()


def test_ring_example():
    rc, out, err = _launch(
        3, [os.path.join(_REPO, "examples", "zmpirun_ring.py")]
    )
    assert rc == 0, err
    assert "PASSED" in out
    # IOF prefixes: rank 0's lines carry the [0] tag
    assert "[0] " in out


def test_collectives_across_processes(tmp_path):
    prog = _script(tmp_path, """
        import zhpe_ompi_tpu as zmpi
        from zhpe_ompi_tpu import ops as zops

        proc = zmpi.host_init()
        vals = proc.allgather(proc.rank * 10)
        assert vals == [0, 10, 20], vals
        got = proc.bcast("hello" if proc.rank == 1 else None, root=1)
        assert got == "hello"
        m = proc.allreduce(proc.rank, zops.MAX)
        assert m == proc.size - 1
        print(f"rank {proc.rank} OK")
        zmpi.host_finalize()
    """)
    rc, out, err = _launch(3, [prog])
    assert rc == 0, err
    assert out.count("OK") == 3


def test_abort_tears_down_job(tmp_path):
    # one rank exits nonzero; the launcher must kill the others (which
    # block forever) and surface the failing code — MPI_Abort semantics
    prog = _script(tmp_path, """
        import sys, time
        import zhpe_ompi_tpu as zmpi

        proc = zmpi.host_init()
        if proc.rank == 1:
            sys.exit(7)
        time.sleep(600)
    """)
    rc, out, err = _launch(3, [prog])
    assert rc == 7
    assert "rank 1 exited with code 7" in err


def test_mca_forwarding(tmp_path):
    prog = _script(tmp_path, """
        import zhpe_ompi_tpu as zmpi

        proc = zmpi.host_init()  # imports pt2pt.tcp, registering tcp_* vars
        val = zmpi.mca_var.get("tcp_eager_limit", None)
        print(f"rank {proc.rank} eager={val}")
        zmpi.host_finalize()
    """)
    rc, out, err = _launch(2, [prog], mca=[("tcp_eager_limit", "4096")])
    assert rc == 0, err
    assert out.count("eager=4096") == 2


def test_job_timeout(tmp_path):
    prog = _script(tmp_path, """
        import time
        time.sleep(600)
    """)
    out, err = io.StringIO(), io.StringIO()
    rc = mpirun.launch(2, [prog], stdout=out, stderr=err, timeout=3.0)
    assert rc == 124
    assert "timeout" in err.getvalue()


def test_cli_entrypoint(tmp_path):
    # python -m zhpe_ompi_tpu.tools.mpirun parses and runs end to end
    import subprocess

    prog = _script(tmp_path, "print('cli-ok')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-m", "zhpe_ompi_tpu.tools.mpirun",
         "-n", "2", "--no-tag-output", prog],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert res.returncode == 0, res.stderr
    assert res.stdout.count("cli-ok") == 2


def test_c_program_under_launcher(tmp_path):
    """A compiled C rank (ABI shim) launches under zmpirun: the shim's
    MPI_Init honors ZMPI_COORD_EXTERNAL and joins the launcher-hosted
    rendezvous as a client — C and the launcher speak one wire-up."""
    import subprocess

    from zhpe_ompi_tpu.tools import zmpicc

    binary = tmp_path / "ring_c"
    subprocess.run(
        ["gcc", os.path.join(_REPO, "examples", "ring_c.c"),
         "-o", str(binary)] + zmpicc.compile_flags() + zmpicc.link_flags(),
        check=True, capture_output=True, text=True,
    )
    rc, out, err = _launch(3, [str(binary)])
    assert rc == 0, err
    assert "PASSED" in out or "ring" in out.lower(), out


def test_name_publishing_across_ranks(tmp_path):
    """MPI_Publish_name/Lookup_name through the launcher-hosted name
    server (the ompi-server analog): one rank publishes, another looks
    the service up — discovery with no out-of-band exchange."""
    prog = _script(tmp_path, """
        import zhpe_ompi_tpu as zmpi
        from zhpe_ompi_tpu.comm import dpm_wire
        from zhpe_ompi_tpu.core import errors

        proc = zmpi.host_init()
        if proc.rank == 0:
            dpm_wire.publish_name("svc", "10.0.0.1:4242")
            proc.barrier()
            proc.barrier()  # rank 1 looked it up
            dpm_wire.unpublish_name("svc")
            proc.barrier()
        else:
            proc.barrier()
            assert dpm_wire.lookup_name("svc") == "10.0.0.1:4242"
            proc.barrier()
            proc.barrier()  # rank 0 unpublished
            try:
                dpm_wire.lookup_name("svc")
            except errors.ArgError:
                print("NS-OK")
            else:
                raise SystemExit("lookup after unpublish succeeded")
        zmpi.host_finalize()
    """)
    rc, out, err = _launch(2, [prog])
    assert rc == 0, err
    assert "NS-OK" in out


def test_zmpicc_wrapper_compile_and_launch(tmp_path):
    """zmpicc (the mpicc wrapper analog) compiles examples/ring_c.c with
    no manual flags, and the binary runs under zmpirun — the reference's
    whole C toolchain loop: wrapper compiler -> launcher."""
    import subprocess

    binary = str(tmp_path / "ring_c")
    res = subprocess.run(
        [sys.executable, "-m", "zhpe_ompi_tpu.tools.zmpicc",
         os.path.join(_REPO, "examples", "ring_c.c"), "-o", binary],
        capture_output=True, text=True, timeout=180,
        env={**os.environ, "PYTHONPATH": _REPO},
    )
    assert res.returncode == 0, res.stderr
    showme = subprocess.run(
        [sys.executable, "-m", "zhpe_ompi_tpu.tools.zmpicc", "--showme"],
        capture_output=True, text=True, timeout=180,
        env={**os.environ, "PYTHONPATH": _REPO},
    )
    assert "-lzompi_mpi" in showme.stdout
    rc, out, err = _launch(4, [binary])
    assert rc == 0, err


def test_mpmd_mixed_c_and_python(tmp_path):
    """MPMD (-n 1 C-binary : -n 2 python): one COMM_WORLD, mixed
    languages, one wire protocol.  The C rank (rank 0) sendrecvs with
    Python ranks through the shim."""
    import subprocess

    from zhpe_ompi_tpu.tools import zmpicc

    csrc = tmp_path / "head.c"
    csrc.write_text(textwrap.dedent("""
        #include <stdio.h>
        #include "zompi_mpi.h"
        int main(int argc, char **argv) {
            int rank, size, v;
            MPI_Init(&argc, &argv);
            MPI_Comm_rank(MPI_COMM_WORLD, &rank);
            MPI_Comm_size(MPI_COMM_WORLD, &size);
            for (int r = 1; r < size; r++) {
                v = 100 + r;
                MPI_Send(&v, 1, MPI_INT, r, 5, MPI_COMM_WORLD);
            }
            int total = 0;
            for (int r = 1; r < size; r++) {
                MPI_Status st;
                MPI_Recv(&v, 1, MPI_INT, r, 6, MPI_COMM_WORLD, &st);
                total += v;
            }
            printf("HEAD total=%d\\n", total);
            MPI_Finalize();
            return total == 406 ? 0 : 1;  /* 2*101 + 2*102 */
        }
    """))
    binary = str(tmp_path / "head")
    subprocess.run(
        ["gcc", str(csrc), "-o", binary]
        + zmpicc.compile_flags() + zmpicc.link_flags(),
        check=True, capture_output=True, text=True,
    )
    pyprog = _script(tmp_path, """
        import numpy as np
        import zhpe_ompi_tpu as zmpi

        proc = zmpi.host_init()
        got = proc.recv(source=0, tag=5)
        v = int(np.asarray(got).reshape(-1)[0])
        proc.send(np.asarray([2 * v], np.int32), 0, tag=6)
    """)
    out, err = io.StringIO(), io.StringIO()
    rc = mpirun.launch_mpmd(
        [(1, [binary]), (2, [pyprog])],
        stdout=out, stderr=err, timeout=120.0,
    )
    assert rc == 0, err.getvalue()
    assert "HEAD total=406" in out.getvalue()


def test_cli_mpmd_colon_syntax(tmp_path):
    import subprocess

    a = _script(tmp_path, "print('A-rank')\n")
    bp = tmp_path / "b.py"
    bp.write_text(
        f"import sys\nsys.path.insert(0, {_REPO!r})\nprint('B-rank')\n")
    b = str(bp)
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-m", "zhpe_ompi_tpu.tools.mpirun",
         "-n", "2", "--no-tag-output", a, ":", "-n", "1", b],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert res.returncode == 0, res.stderr
    assert res.stdout.count("A-rank") == 2
    assert res.stdout.count("B-rank") == 1


def test_zero_train_example():
    """ZeRO-1 example under the launcher: 2 slices, partitioned state,
    decreasing loss."""
    rc, out, err = _launch(
        2, [os.path.join(_REPO, "examples", "zmpirun_zero_train.py")],
        timeout=150.0,
    )
    assert rc == 0, err
    assert out.count("PASSED") == 2


def test_signal_hygiene_sigterm(tmp_path):
    """zmpirun signal hygiene: SIGTERM to the launcher is forwarded to
    the job, every child is reaped, the rendezvous port is released,
    and the launcher exits 128+sig — a Ctrl-C must not orphan ranks
    still holding sockets and /dev/shm rings."""
    import signal
    import subprocess
    import time

    pid_dir = tmp_path / "pids"
    pid_dir.mkdir()
    prog = _script(tmp_path, f"""
        import os, time
        open(os.path.join({str(pid_dir)!r}, str(os.getpid())), "w").close()
        time.sleep(600)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.Popen(
        [sys.executable, "-m", "zhpe_ompi_tpu.tools.mpirun",
         "-n", "2", prog],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True,
    )
    try:
        deadline = time.monotonic() + 60.0
        while len(list(pid_dir.iterdir())) < 2:
            assert time.monotonic() < deadline, "ranks never started"
            assert p.poll() is None, p.communicate()
            time.sleep(0.05)
        p.send_signal(signal.SIGTERM)
        rc = p.wait(timeout=30.0)
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
    assert rc == 128 + signal.SIGTERM, p.communicate()
    # children reaped: no rank process may survive the launcher
    deadline = time.monotonic() + 10.0
    pids = [int(f.name) for f in pid_dir.iterdir()]
    while time.monotonic() < deadline:
        alive = []
        for pid in pids:
            try:
                os.kill(pid, 0)
                alive.append(pid)
            except ProcessLookupError:
                pass
        if not alive:
            break
        time.sleep(0.1)
    assert not alive, f"orphaned rank processes: {alive}"


class TestDvm:
    """Runtime-plane daemon (zprted) lifecycle matrix: a resident VM
    hosts the PMIx store across jobs, launches sequential jobs into
    itself, stops clean, and rides over a just-stopped predecessor's
    port (stale-socket retry)."""

    def _mod(self):
        from zhpe_ompi_tpu.runtime import dvm as dvm_mod
        return dvm_mod

    def _prog(self, tmp_path):
        return _script(tmp_path, """
            import zhpe_ompi_tpu as zmpi

            proc = zmpi.host_init()
            vals = proc.allgather(proc.rank + 1)
            assert vals == [1, 2], vals
            print(f"rank {proc.rank} OK")
            zmpi.host_finalize()
        """)

    def test_two_sequential_jobs_one_dvm(self, tmp_path):
        """Start → launch two jobs into ONE resident VM → stop: the
        store outlives each job (namespace destroyed at job end), the
        daemon outlives both."""
        from zhpe_ompi_tpu.runtime import pmix as pmix_mod
        from zhpe_ompi_tpu.runtime import spc

        dvm_mod = self._mod()
        prog = self._prog(tmp_path)
        jobs0 = spc.read("dvm_jobs_launched")
        d = dvm_mod.Dvm()
        try:
            cli = dvm_mod.DvmClient(d.address)
            assert cli.ping()
            out, err = io.StringIO(), io.StringIO()
            rc1 = cli.launch(2, [prog], timeout=90.0, stdout=out,
                             stderr=err)
            job1 = cli.last_job_id
            rc2 = cli.launch(2, [prog], timeout=90.0, stdout=out,
                             stderr=err)
            assert (rc1, rc2) == (0, 0), err.getvalue()
            assert cli.last_job_id != job1  # a NEW job, same VM
            assert out.getvalue().count("OK") == 4
            stat = cli.stat()
            assert stat["dvm_jobs_launched"] - jobs0 == 2
            # per-job namespaces were destroyed when the jobs ended
            assert stat["pmix"] == {}
            cli.close()
        finally:
            d.stop()
        assert dvm_mod.live_dvms() == []
        assert pmix_mod.live_servers() == []
        assert pmix_mod.stale_namespaces() == []

    def test_starved_iof_drain_never_loses_the_final_line(
            self, tmp_path, monkeypatch):
        """The finalize-skew regression (intermittent in
        TestDvmMultiVictimRecovery since PR 11): job exit accounting
        fires on the last waitpid, but a rank's final stdout line is
        still in its pipe until the IOF drain THREAD pumps it — a
        drain starved by scheduler load past a short per-thread join
        bound lost the line to a client that stopped reading at the
        exit frame.  Starvation is simulated deterministically (the
        last rank's stdout drain sleeps 3 s before pumping — beyond
        the old 2 s bound, inside the shared _IOF_DRAIN_GRACE): the
        exit frame must WAIT, and every line must reach the client."""
        import time as time_mod

        dvm_mod = self._mod()
        prog = _script(tmp_path, """
            import os

            print(f"LAST-LINE rank={os.environ['ZMPI_RANK']}",
                  flush=True)
        """)
        orig = dvm_mod.Dvm._drain_iof

        def starved(self, job, rank, label, stream):
            if rank == 1 and label == "":
                time_mod.sleep(3.0)  # the starved scheduler slot
            orig(self, job, rank, label, stream)

        monkeypatch.setattr(dvm_mod.Dvm, "_drain_iof", starved)
        d = dvm_mod.Dvm()
        try:
            cli = dvm_mod.DvmClient(d.address)
            out, err = io.StringIO(), io.StringIO()
            rc = cli.launch(2, [prog], timeout=60.0, stdout=out,
                            stderr=err)
            assert rc == 0, (out.getvalue(), err.getvalue())
            text = out.getvalue()
            for r in (0, 1):
                assert f"LAST-LINE rank={r}" in text, (
                    f"rank {r}'s final line raced the exit frame: "
                    f"{text!r}")
            cli.close()
        finally:
            d.stop()
        assert dvm_mod.live_dvms() == []

    def test_abort_semantics_in_dvm_job(self, tmp_path):
        """A non-ft daemon job keeps the zmpirun MPI_Abort contract:
        one rank exits nonzero, the daemon kills the rest and the job
        surfaces the failing code."""
        dvm_mod = self._mod()
        prog = _script(tmp_path, """
            import sys, time
            import zhpe_ompi_tpu as zmpi

            proc = zmpi.host_init()
            if proc.rank == 1:
                sys.exit(7)
            time.sleep(600)
        """)
        d = dvm_mod.Dvm()
        try:
            cli = dvm_mod.DvmClient(d.address)
            out, err = io.StringIO(), io.StringIO()
            rc = cli.launch(3, [prog], timeout=90.0, stdout=out,
                            stderr=err)
            assert rc == 7
            assert "rank 1 exited with code 7" in err.getvalue()
            cli.close()
        finally:
            d.stop()

    def test_stop_then_rebind_same_ports(self):
        """Stale-socket retry: a daemon restarted onto the ports of a
        JUST-stopped predecessor must bind over the TIME_WAIT corpses
        (SO_REUSEADDR on both listeners)."""
        dvm_mod = self._mod()
        d1 = dvm_mod.Dvm()
        port, pmix_port = d1.address[1], d1.pmix.address[1]
        cli = dvm_mod.DvmClient(d1.address)
        assert cli.ping()
        assert cli.stop() is True  # stop via RPC, not object call
        cli.close()
        assert d1.wait(10.0)
        d2 = dvm_mod.Dvm(port=port, pmix_port=pmix_port)
        try:
            cli2 = dvm_mod.DvmClient(d2.address)
            assert cli2.ping()
            cli2.close()
        finally:
            d2.stop()
        assert dvm_mod.live_dvms() == []

    def test_zprted_subprocess_and_dvm_cli(self, tmp_path):
        """The real daemon shape: zprted as its OWN process (python -m
        zhpe_ompi_tpu.runtime.dvm), a job launched into it through the
        zmpirun --dvm CLI path, orderly stop, clean exit."""
        import subprocess

        dvm_mod = self._mod()
        prog = self._prog(tmp_path)
        env = dict(os.environ)
        env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
        daemon = subprocess.Popen(
            [sys.executable, "-m", "zhpe_ompi_tpu.runtime.dvm"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )
        try:
            # bounded ready-line read: a daemon that dies before
            # printing must fail THIS test, not hang the suite
            import select

            r, _, _ = select.select([daemon.stdout], [], [], 60.0)
            assert r, "zprted never printed its ready line"
            ready = daemon.stdout.readline()
            assert ready.startswith("zprted ready"), (
                ready, daemon.stderr.read() if daemon.poll() else "")
            addr = ready.split("dvm=")[1].split()[0]
            out, err = io.StringIO(), io.StringIO()
            rc = mpirun.launch_dvm(addr, 2, [prog], timeout=90.0,
                                   stdout=out, stderr=err)
            assert rc == 0, err.getvalue()
            assert out.getvalue().count("OK") == 2
            cli = dvm_mod.DvmClient(addr)
            cli.stop()
            cli.close()
            assert daemon.wait(timeout=15.0) == 0
        finally:
            if daemon.poll() is None:
                daemon.kill()
                daemon.wait()
        assert dvm_mod.orphaned_daemon_processes() == []


class TestTpuHost:
    """One process per chip: on a TPU host the first rank that touches
    JAX takes every chip, so several local ranks are refused loudly."""

    @pytest.fixture
    def tpu_host(self, monkeypatch):
        monkeypatch.setattr(mpirun, "tpu_chips", lambda: 4)
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)

    def test_several_ranks_are_refused(self, tpu_host, tmp_path):
        prog = _script(tmp_path, "raise SystemExit('must not start')\n")
        with pytest.raises(RuntimeError, match="contend for its 4 chip"):
            _launch(2, [prog])

    @pytest.mark.parametrize("n,env", [
        (1, {}),                          # one process drives every chip
        (4, {"JAX_PLATFORMS": "cpu"}),    # host-plane ranks pinned to CPU
    ])
    def test_allowed_layouts(self, tpu_host, n, env):
        mpirun.refuse_shared_chips(n, env)

    def test_host_without_chips_is_unaffected(self, monkeypatch):
        monkeypatch.setattr(mpirun, "tpu_chips", lambda: 0)
        mpirun.refuse_shared_chips(8, {})
