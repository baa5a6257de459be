"""Host-plane pt2pt: matching engine + thread-rank universe.

Models the reference's test strategy: pure-host matching tests (the
datatype-engine style), then runtime smoke tests shaped like test/simple's
ring/hello programs (SURVEY.md §4).
"""

import time

import numpy as np
import pytest

from zhpe_ompi_tpu.core import errors
from zhpe_ompi_tpu.mca import var as mca_var
from zhpe_ompi_tpu.pt2pt import matching, requests
from zhpe_ompi_tpu.pt2pt.matching import ANY_SOURCE, ANY_TAG, Envelope
from zhpe_ompi_tpu.pt2pt.universe import LocalUniverse


class TestMatchingEngine:
    def _collect(self):
        got = []
        return got, lambda env, p: got.append((env, p))

    def test_posted_then_incoming(self):
        eng = matching.MatchingEngine()
        got, cb = self._collect()
        eng.post_recv(0, 5, 0, cb)
        eng.incoming(Envelope(0, 5, 0, 0), "hello")
        assert got == [(Envelope(0, 5, 0, 0), "hello")]

    def test_unexpected_then_posted(self):
        eng = matching.MatchingEngine()
        eng.incoming(Envelope(2, 9, 0, 0), "early")
        got, cb = self._collect()
        eng.post_recv(2, 9, 0, cb)
        assert got[0][1] == "early"

    def test_wildcards(self):
        eng = matching.MatchingEngine()
        got, cb = self._collect()
        eng.post_recv(ANY_SOURCE, ANY_TAG, 0, cb)
        eng.incoming(Envelope(3, 42, 0, 0), "x")
        assert got[0][0].src == 3 and got[0][0].tag == 42

    def test_tag_mismatch_parks(self):
        eng = matching.MatchingEngine()
        got, cb = self._collect()
        eng.post_recv(0, 1, 0, cb)
        eng.incoming(Envelope(0, 2, 0, 0), "wrong tag")
        assert not got
        assert eng.stats()["unexpected"] == 1

    def test_comm_isolation(self):
        eng = matching.MatchingEngine()
        got, cb = self._collect()
        eng.post_recv(ANY_SOURCE, ANY_TAG, cid=7, on_match=cb)
        eng.incoming(Envelope(0, 0, 3, 0), "other comm")
        assert not got

    def test_ordering_same_source(self):
        eng = matching.MatchingEngine()
        eng.incoming(Envelope(0, 5, 0, 0), "first")
        eng.incoming(Envelope(0, 5, 0, 1), "second")
        got, cb = self._collect()
        eng.post_recv(0, 5, 0, cb)
        eng.post_recv(0, 5, 0, cb)
        assert [p for _, p in got] == ["first", "second"]

    def test_probe(self):
        eng = matching.MatchingEngine()
        assert eng.probe(ANY_SOURCE, ANY_TAG, 0) is None
        eng.incoming(Envelope(1, 8, 0, 0), "peek me")
        env = eng.probe(ANY_SOURCE, 8, 0)
        assert env.src == 1
        assert eng.stats()["unexpected"] == 1  # probe does not consume


class TestUniverse:
    def test_ring(self):
        """examples/ring_c.c analog: token passes around 4 ranks."""
        uni = LocalUniverse(4)

        def main(ctx):
            token = 10 if ctx.rank == 0 else None
            if ctx.rank == 0:
                ctx.send(token, dest=1, tag=0)
                token = ctx.recv(source=3, tag=0)
            else:
                token = ctx.recv(source=ctx.rank - 1, tag=0)
                ctx.send(token + 1, dest=(ctx.rank + 1) % 4, tag=0)
            return token

        results = uni.run(main)
        assert results[0] == 13  # incremented by ranks 1..3

    def test_any_source(self):
        uni = LocalUniverse(3)

        def main(ctx):
            if ctx.rank == 0:
                vals = sorted(
                    ctx.recv(source=ANY_SOURCE, tag=1) for _ in range(2)
                )
                return vals
            ctx.send(ctx.rank * 100, dest=0, tag=1)

        assert uni.run(main)[0] == [100, 200]

    def test_status_reports_source(self):
        uni = LocalUniverse(2)

        def main(ctx):
            if ctx.rank == 0:
                val, st = ctx.recv(source=ANY_SOURCE, tag=ANY_TAG,
                                   return_status=True)
                return (val, st.source, st.tag)
            ctx.send("payload", dest=0, tag=9)

        assert uni.run(main)[0] == ("payload", 1, 9)

    def test_rendezvous_large_message(self, fresh_vars):
        mca_var.set_var("pt2pt_eager_limit", 1024)
        try:
            uni = LocalUniverse(2)
            big = np.arange(100_000, dtype=np.float32)

            def main(ctx):
                if ctx.rank == 0:
                    req = ctx.isend(big, dest=1, tag=3)
                    assert not req.done  # rendezvous: not yet matched
                    req.wait()
                    return "sent"
                got = ctx.recv(source=0, tag=3)
                return float(got.sum())

            res = uni.run(main)
            assert res[1] == float(big.sum())
        finally:
            mca_var.unset("pt2pt_eager_limit")

    def test_eager_send_buffer_reuse(self):
        """MPI contract: after a completed (eager) send, mutating the send
        buffer must not corrupt the message."""
        uni = LocalUniverse(2)

        def main(ctx):
            if ctx.rank == 0:
                buf = np.ones(8, np.float32)
                ctx.send(buf, dest=1, tag=0)
                buf[:] = -1  # reuse immediately
                return None
            got = ctx.recv(source=0, tag=0)
            return got.tolist()

        assert uni.run(main)[1] == [1.0] * 8

    def test_isend_irecv_waitall(self):
        uni = LocalUniverse(2)

        def main(ctx):
            if ctx.rank == 0:
                reqs = [ctx.isend(i, dest=1, tag=i) for i in range(5)]
                requests.wait_all(reqs)
                return None
            reqs = [ctx.irecv(source=0, tag=i) for i in range(5)]
            return requests.wait_all(reqs)

        assert uni.run(main)[1] == list(range(5))

    def test_probe_then_recv(self):
        uni = LocalUniverse(2)

        def main(ctx):
            if ctx.rank == 0:
                ctx.send("x", dest=1, tag=77)
                return None
            env = None
            while env is None:
                env = ctx.probe()
            assert env.tag == 77
            return ctx.recv(source=env.src, tag=env.tag)

        assert uni.run(main)[1] == "x"

    def test_sendrecv(self):
        uni = LocalUniverse(2)

        def main(ctx):
            other = 1 - ctx.rank
            return ctx.sendrecv(f"from{ctx.rank}", dest=other, source=other)

        assert uni.run(main) == ["from1", "from0"]

    def test_barrier(self):
        uni = LocalUniverse(5)
        order = []

        def main(ctx):
            ctx.barrier()
            order.append(ctx.rank)
            ctx.barrier()
            return len(order)

        res = uni.run(main)
        assert all(r == 5 for r in res)  # all ranks passed barrier 1 first

    def test_deadlock_detection(self):
        uni = LocalUniverse(2)

        def main(ctx):
            return ctx.recv(source=1 - ctx.rank, tag=0)  # both block

        with pytest.raises(errors.InternalError):
            uni.run(main, timeout=0.5)
        # release both parked ranks: a posted receive left behind keeps
        # the universe live and fails a later file's quiescence check
        uni.contexts[0].send(0, dest=1, tag=0)
        uni.contexts[1].send(0, dest=0, tag=0)
        deadline = time.monotonic() + 10.0
        while any(c.engine.stats()["posted"] for c in uni.contexts):
            assert time.monotonic() < deadline, "parked ranks never woke"
            time.sleep(0.01)

    def test_rendezvous_buffer_reuse(self, fresh_vars):
        """Regression: after a rendezvous send completes, mutating the send
        buffer must not corrupt the in-flight message."""
        mca_var.set_var("pt2pt_eager_limit", 64)
        try:
            uni = LocalUniverse(2)
            import threading

            gate = threading.Event()

            def main(ctx):
                if ctx.rank == 0:
                    buf = np.ones(1000, np.float64)
                    ctx.send(buf, dest=1, tag=0)
                    buf[:] = -1  # reuse right after completion
                    gate.set()
                    return None
                got = ctx.recv(source=0, tag=0)
                gate.wait(5)  # sender has clobbered its buffer by now
                # if the handoff aliased the sender's buffer, got is -1s
                return float(got.sum())

            assert uni.run(main)[1] == 1000.0
        finally:
            mca_var.unset("pt2pt_eager_limit")

    def test_rndv_lookalike_payload_is_not_special(self):
        """Regression: a user payload shaped like the old in-band sentinel
        must be delivered verbatim, not trigger rendezvous handling."""
        uni = LocalUniverse(2)

        def main(ctx):
            if ctx.rank == 0:
                ctx.send(("__rndv__", 0, 0), dest=1, tag=1)
                return None
            return ctx.recv(source=0, tag=1)

        assert uni.run(main)[1] == ("__rndv__", 0, 0)

    def test_jax_array_payload(self):
        import jax.numpy as jnp

        uni = LocalUniverse(2)

        def main(ctx):
            if ctx.rank == 0:
                ctx.send(jnp.arange(4.0), dest=1)
                return None
            return np.asarray(ctx.recv(source=0)).tolist()

        assert uni.run(main)[1] == [0.0, 1.0, 2.0, 3.0]


class TestSendrecvParkRelease:
    """A poisoned/abandoned rendezvous send's parked payload is
    RELEASED (no universe-lifetime pin) and a late CTS for a released
    id is a no-op, not a KeyError out of the progress loop (the ZL001
    follow-through on the thread plane)."""

    def test_release_drops_parked_entry(self):
        uni = LocalUniverse(2)

        def main(ctx):
            if ctx.rank != 0:
                return True
            big = np.zeros(100_000)  # > pt2pt_eager_limit: parks
            req = ctx.isend(big, dest=1, tag=5)
            with ctx._lock:
                parked = len(ctx._pending_rndv)
            ctx._release_parked_sends(req)
            with ctx._lock:
                after = len(ctx._pending_rndv)
            return (parked, after)

        res = uni.run(main)
        assert res[0] == (1, 0)

    def test_late_cts_for_released_id_is_noop(self):
        uni = LocalUniverse(2)

        def main(ctx):
            if ctx.rank != 0:
                return True
            big = np.zeros(100_000)
            req = ctx.isend(big, dest=1, tag=6)
            with ctx._lock:
                (rndv_id,) = list(ctx._pending_rndv)
            ctx._release_parked_sends(req)
            # the partner's CTS lands AFTER the release: progress must
            # swallow it (no KeyError, no delivery, no completion)
            ctx.mailbox.put(("cts", rndv_id, 0, lambda payload: None))
            ctx.progress()
            return req.done

        res = uni.run(main)
        assert res[0] is False  # released, never completed by the CTS


class TestGetCount:
    """MPI_Get_count semantics over received payloads."""

    def test_count_from_array_payload(self):
        from zhpe_ompi_tpu.datatype import INT32_T
        from zhpe_ompi_tpu.pt2pt.requests import get_count
        from zhpe_ompi_tpu.pt2pt.universe import LocalUniverse

        uni = LocalUniverse(2)

        def prog(ctx):
            if ctx.rank == 0:
                ctx.send(np.arange(6, dtype=np.int32), dest=1, tag=3)
                return None
            val, st = ctx.recv(source=0, tag=3, return_status=True)
            assert st.source == 0 and st.tag == 3
            assert st.count_bytes == 24
            assert get_count(st, INT32_T) == 6
            return True

        assert uni.run(prog)[1] is True

    def test_undefined_for_object_and_partial(self):
        from zhpe_ompi_tpu.datatype import INT32_T, create_contiguous
        from zhpe_ompi_tpu.pt2pt.requests import (
            Status,
            UNDEFINED,
            get_count,
        )

        assert get_count(Status(count_bytes=-1), INT32_T) == UNDEFINED
        # 10 bytes is not a whole number of 8-byte elements
        t = create_contiguous(2, INT32_T)
        assert get_count(Status(count_bytes=10), t) == UNDEFINED
        assert get_count(Status(count_bytes=16), t) == 2


class TestMatchingBins:
    """The (cid, src) hash-bin index under the classic matching
    semantics: per-source FIFO, true cross-source arrival order for
    ANY_SOURCE, post-order merge of wildcard vs specific receives,
    exact stats — and the comparison-count SPC gate that keeps the
    bins from silently regressing to linear scans."""

    def test_any_source_matches_in_cross_source_arrival_order(self):
        eng = matching.MatchingEngine()
        eng.incoming(Envelope(3, 1, 0, 0), "a")
        eng.incoming(Envelope(1, 1, 0, 0), "b")
        eng.incoming(Envelope(3, 1, 0, 1), "c")
        eng.incoming(Envelope(0, 1, 0, 0), "d")
        got = []
        for _ in range(4):
            eng.post_recv(ANY_SOURCE, 1, 0, lambda e, p: got.append(p))
        assert got == ["a", "b", "c", "d"]

    def test_any_source_skips_mismatched_tags_per_bin(self):
        eng = matching.MatchingEngine()
        eng.incoming(Envelope(0, 9, 0, 0), "wrong")   # earliest arrival
        eng.incoming(Envelope(1, 5, 0, 0), "right")
        got = []
        eng.post_recv(ANY_SOURCE, 5, 0, lambda e, p: got.append(p))
        assert got == ["right"]
        assert eng.stats()["unexpected"] == 1  # "wrong" still parked

    def test_wildcard_vs_specific_posted_merge_by_post_order(self):
        eng = matching.MatchingEngine()
        order = []
        eng.post_recv(ANY_SOURCE, ANY_TAG, 0,
                      lambda e, p: order.append(("wild", p)))
        eng.post_recv(2, ANY_TAG, 0,
                      lambda e, p: order.append(("spec", p)))
        eng.incoming(Envelope(2, 9, 0, 0), "x")  # wildcard posted first
        eng.incoming(Envelope(2, 9, 0, 1), "y")
        assert order == [("wild", "x"), ("spec", "y")]

    def test_specific_before_wildcard_when_posted_first(self):
        eng = matching.MatchingEngine()
        order = []
        eng.post_recv(2, ANY_TAG, 0, lambda e, p: order.append(("spec", p)))
        eng.post_recv(ANY_SOURCE, ANY_TAG, 0,
                      lambda e, p: order.append(("wild", p)))
        eng.incoming(Envelope(2, 9, 0, 0), "x")
        eng.incoming(Envelope(3, 9, 0, 0), "y")  # only the wildcard fits
        assert order == [("spec", "x"), ("wild", "y")]

    def test_per_source_fifo_with_tag_skips(self):
        eng = matching.MatchingEngine()
        eng.incoming(Envelope(0, 5, 0, 0), "t5-first")
        eng.incoming(Envelope(0, 6, 0, 1), "t6")
        eng.incoming(Envelope(0, 5, 0, 2), "t5-second")
        got = []
        eng.post_recv(0, 6, 0, lambda e, p: got.append(p))
        eng.post_recv(0, 5, 0, lambda e, p: got.append(p))
        eng.post_recv(0, 5, 0, lambda e, p: got.append(p))
        assert got == ["t6", "t5-first", "t5-second"]
        assert eng.stats() == {"posted": 0, "unexpected": 0}

    def test_probe_and_extract_ride_the_bins(self):
        eng = matching.MatchingEngine()
        eng.incoming(Envelope(4, 8, 2, 0), "keep")
        eng.incoming(Envelope(5, 8, 2, 1), "take")
        assert eng.probe(ANY_SOURCE, 8, 2).src == 4
        env, payload = eng.extract(5, 8, 2)
        assert payload == "take"
        assert eng.stats()["unexpected"] == 1
        assert eng.extract(5, 8, 2) is None

    def test_stats_excluding_exact_counts(self):
        eng = matching.MatchingEngine()
        eng.post_recv(ANY_SOURCE, 1, 7, lambda e, p: None)
        eng.post_recv(4, 1, 7, lambda e, p: None)
        eng.post_recv(4, 1, 9, lambda e, p: None)
        eng.incoming(Envelope(4, 99, 7, 0), "u")
        eng.incoming(Envelope(5, 99, 8, 0), "v")
        assert eng.stats() == {"posted": 3, "unexpected": 2}
        # ANY_SOURCE posted rows are unattributable by source: counted
        # unless their cid is exempt
        assert eng.stats_excluding([4]) == {"posted": 1, "unexpected": 1}
        assert eng.stats_excluding([], cids=[7]) == \
            {"posted": 1, "unexpected": 1}
        assert eng.stats_excluding([5], cids=[7, 9]) == \
            {"posted": 0, "unexpected": 0}

    def test_comparison_count_gate_on_wildcard_mix(self):
        """The satellite's SPC gate: a 64-posted/64-unexpected wildcard
        mix must cost the BINNED comparison counts, not the linear
        ones.  Deterministic inputs -> deterministic counts: the park
        phase scans only the 4-entry specific bin + the 32-entry
        wildcard bin per arrival (2304 total; a linear engine walks all
        64 posted per arrival = 4096), and the drain phase finds each
        parked message at its source bin's head (64 total; linear
        ~2080)."""
        from zhpe_ompi_tpu.runtime import spc

        eng = matching.MatchingEngine()
        for i in range(32):
            eng.post_recv(i % 8, 1000 + i, 0, lambda e, p: None)
        for i in range(32):
            eng.post_recv(ANY_SOURCE, 2000 + i, 0, lambda e, p: None)
        c0 = spc.read("match_comparisons")
        for i in range(64):
            eng.incoming(Envelope(i % 8, 3000 + i, 0, i), i)
        park = spc.read("match_comparisons") - c0
        assert 0 < park <= 2304, park  # linear would be 4096
        c1 = spc.read("match_comparisons")
        got = []
        for i in range(64):
            eng.post_recv(i % 8, 3000 + i, 0, lambda e, p: got.append(p))
        drain = spc.read("match_comparisons") - c1
        assert len(got) == 64
        assert 0 < drain <= 64, drain  # linear would be ~2080
        assert eng.stats()["unexpected"] == 0

    def test_unexpected_depth_watermark(self):
        from zhpe_ompi_tpu.runtime import spc

        assert "match_unexpected_max_depth" in spc.WATERMARK
        before = spc.read("match_unexpected_max_depth")
        eng = matching.MatchingEngine()
        n = max(before, 0) + 17
        for i in range(n):
            eng.incoming(Envelope(0, 4000 + i, 3, i), i)
        assert spc.read("match_unexpected_max_depth") >= n
        # a watermark, not a sum: another engine's shallow backlog
        # cannot LOWER it
        high = spc.read("match_unexpected_max_depth")
        eng2 = matching.MatchingEngine()
        eng2.incoming(Envelope(0, 1, 0, 0), "x")
        assert spc.read("match_unexpected_max_depth") == high
