"""Collective correctness tests on the 8-device CPU loopback mesh.

Every algorithm is compared against a numpy reference — the analog of the
reference's external MPI correctness suites run over btl/self+sm
(SURVEY.md §4).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import zhpe_ompi_tpu as zmpi
from zhpe_ompi_tpu.coll import algorithms as alg
from zhpe_ompi_tpu.coll import tpu as xla_mod

N = 8


@pytest.fixture(scope="module")
def world():
    return zmpi.init()


def run_spmd(comm, fn, x_global, out_specs=None):
    """Shard x_global along dim0 over the comm axis and run fn per-device."""
    from jax.sharding import PartitionSpec as P

    xs = comm.device_put_sharded(jnp.asarray(x_global))
    return np.asarray(comm.run(fn, xs, out_specs=out_specs))


def rng(seed=0):
    return np.random.default_rng(seed)


ALLREDUCE_ALGS = [
    alg.allreduce_recursive_doubling,
    alg.allreduce_ring,
    alg.allreduce_rabenseifner,
    alg.allreduce_linear,
    alg.allreduce_nonoverlapping,
    alg.allreduce_segmented_ring,
    xla_mod.allreduce,
]


class TestAllreduce:
    @pytest.mark.parametrize("algo", ALLREDUCE_ALGS,
                             ids=lambda f: f.__name__)
    def test_sum(self, world, algo):
        x = rng(1).normal(size=(N, 5)).astype(np.float32)
        out = run_spmd(world, lambda s: algo(world, s, zmpi.SUM), x)
        expect = np.tile(x.sum(axis=0), (N, 1)).reshape(out.shape)
        np.testing.assert_allclose(out, expect, rtol=1e-5)

    @pytest.mark.parametrize("algo", ALLREDUCE_ALGS,
                             ids=lambda f: f.__name__)
    def test_max(self, world, algo):
        x = rng(2).normal(size=(N, 7)).astype(np.float32)
        out = run_spmd(world, lambda s: algo(world, s, zmpi.MAX), x)
        expect = np.tile(x.max(axis=0), (N, 1)).reshape(out.shape)
        np.testing.assert_allclose(out, expect)

    def test_prod_xla_fallback(self, world):
        x = (rng(3).normal(size=(N, 4)) * 0.5 + 1).astype(np.float32)
        out = run_spmd(world, lambda s: xla_mod.allreduce(world, s, zmpi.PROD), x)
        expect = np.tile(np.prod(x, axis=0), (N, 1)).reshape(out.shape)
        np.testing.assert_allclose(out, expect, rtol=1e-5)

    def test_band(self, world):
        x = rng(4).integers(0, 255, size=(N, 6)).astype(np.int32)
        out = run_spmd(
            world, lambda s: alg.allreduce_recursive_doubling(world, s, zmpi.BAND), x
        )
        expect = np.tile(np.bitwise_and.reduce(x, axis=0), (N, 1))
        np.testing.assert_array_equal(out, expect.reshape(out.shape))

    def test_nonuniform_split_xla(self, world):
        """Non-uniform (5+3) splits ride XLA index groups; the algorithmic
        path refuses them with a clear error."""
        sub = world.split([0] * 5 + [1] * 3)
        x = rng(5).normal(size=(N, 3)).astype(np.float32)
        out = run_spmd(sub, lambda s: xla_mod.allreduce(sub, s, zmpi.SUM), x)
        expect = np.empty_like(x)
        expect[:5] = x[:5].sum(axis=0)
        expect[5:] = x[5:].sum(axis=0)
        np.testing.assert_allclose(out.reshape(N, 3), expect, rtol=1e-5)
        with pytest.raises(zmpi.errors.CommError):
            run_spmd(
                sub,
                lambda s: alg.allreduce_recursive_doubling(sub, s, zmpi.SUM),
                x,
            )

    def test_odd_size_recursive_doubling(self, world):
        """Non-power-of-two UNIFORM size (the pow2-adjust path): 2 groups of
        4 would be pow2, so use a world split into one group via incl of 8 -
        instead exercise n=8 vs a 2x(n=4)... the true odd case needs a
        non-pow2 uniform group: split 8 ranks into [0..5] is non-uniform, so
        build a 6-device sub-mesh world instead."""
        import zhpe_ompi_tpu.parallel.mesh as mesh_mod
        import jax

        devs = jax.devices()[:6]
        m = mesh_mod.world_mesh(axis_name="w6", devices=devs)
        comm = zmpi.Communicator(m, "w6", name="w6comm")
        x = rng(5).normal(size=(6, 3)).astype(np.float32)
        out = np.asarray(
            comm.run(
                lambda s: alg.allreduce_recursive_doubling(comm, s, zmpi.SUM),
                comm.device_put_sharded(jnp.asarray(x)),
            )
        )
        np.testing.assert_allclose(
            out.reshape(6, 3), np.tile(x.sum(axis=0), (6, 1)), rtol=1e-5
        )

    def test_bf16(self, world):
        x = rng(6).normal(size=(N, 8)).astype("bfloat16")
        out = run_spmd(world, lambda s: xla_mod.allreduce(world, s, zmpi.SUM), x)
        expect = np.tile(
            x.astype(np.float32).sum(axis=0), (N, 1)
        ).reshape(out.shape)
        np.testing.assert_allclose(out.astype(np.float32), expect, rtol=0.05)

    def test_maxloc_pairs(self, world):
        vals = rng(7).normal(size=(N, 4)).astype(np.float32)
        idxs = np.tile(np.arange(N, dtype=np.int32)[:, None], (1, 4))

        def body(v, i):
            r, loc = alg.allreduce_recursive_doubling(
                world, (v, i), zmpi.MAXLOC
            )
            return r, loc

        from jax.sharding import PartitionSpec as P

        v = world.device_put_sharded(jnp.asarray(vals))
        i = world.device_put_sharded(jnp.asarray(idxs))
        rv, ri = world.run(body, v, i, in_specs=(P("world"), P("world")),
                           out_specs=(P("world"), P("world")))
        expect_v = vals.max(axis=0)
        expect_i = vals.argmax(axis=0)
        np.testing.assert_allclose(np.asarray(rv).reshape(N, 4)[0], expect_v)
        np.testing.assert_array_equal(np.asarray(ri).reshape(N, 4)[0], expect_i)


class TestBcast:
    @pytest.mark.parametrize("algo,root", [
        (alg.bcast_binomial, 0),
        (alg.bcast_binomial, 3),
        (alg.bcast_chain, 0),
        (alg.bcast_chain, 5),
        (alg.bcast_scatter_allgather, 0),
        (alg.bcast_scatter_allgather, 2),
        (alg.bcast_linear, 0),
        (alg.bcast_linear, 4),
        (alg.bcast_binary, 0),
        (alg.bcast_binary, 3),
        (alg.bcast_pipeline, 0),
        (alg.bcast_pipeline, 2),
        (alg.bcast_split_binary, 0),
        (alg.bcast_split_binary, 5),
        (alg.bcast_knomial, 0),
        (alg.bcast_knomial, 1),
        (xla_mod.bcast, 0),
        (xla_mod.bcast, 6),
    ], ids=lambda p: getattr(p, "__name__", str(p)))
    def test_bcast(self, world, algo, root):
        x = rng(8).normal(size=(N, 9)).astype(np.float32)
        out = run_spmd(world, lambda s: algo(world, s, root), x)
        expect = np.tile(x[root], (N, 1)).reshape(out.shape)
        np.testing.assert_allclose(out, expect)


class TestReduce:
    @pytest.mark.parametrize("algo", [
        alg.reduce_binomial, alg.reduce_chain, alg.reduce_pipeline,
        alg.reduce_binary, alg.reduce_rabenseifner, alg.reduce_linear,
        alg.reduce_in_order_binary,
    ], ids=lambda f: f.__name__)
    @pytest.mark.parametrize("root", [0, 4])
    def test_sum(self, world, algo, root):
        x = rng(9).normal(size=(N, 5)).astype(np.float32)
        out = run_spmd(
            world, lambda s: algo(world, s, zmpi.SUM, root), x
        ).reshape(N, 5)
        np.testing.assert_allclose(out[root], x.sum(axis=0), rtol=1e-5)


class TestAllgather:
    @pytest.mark.parametrize("algo", [
        alg.allgather_ring, alg.allgather_bruck,
        alg.allgather_recursive_doubling, alg.allgather_neighbor_exchange,
        alg.allgather_linear, xla_mod.allgather,
    ], ids=lambda f: f.__name__)
    def test_allgather(self, world, algo):
        x = rng(10).normal(size=(N, 2)).astype(np.float32)
        from jax.sharding import PartitionSpec as P

        out = run_spmd(world, lambda s: algo(world, s), x,
                       out_specs=P("world"))
        # each device outputs the full (N*2,) concatenation; sharded output
        # over N devices gives (N * N * 2 / N,)... collect one device's view
        out = out.reshape(N, -1)[0] if out.size == N * N * 2 else out
        np.testing.assert_allclose(out.reshape(-1), x.reshape(-1))


class TestAlltoall:
    @pytest.mark.parametrize("algo", [
        alg.alltoall_pairwise, alg.alltoall_bruck, alg.alltoall_linear,
        alg.alltoall_linear_sync, xla_mod.alltoall,
    ], ids=lambda f: f.__name__)
    def test_alltoall(self, world, algo):
        # global matrix: row i holds blocks destined to each rank
        m = 3
        x = np.arange(N * N * m, dtype=np.float32).reshape(N, N * m)
        out = run_spmd(world, lambda s: algo(world, s.reshape(N * m)), x)
        out = out.reshape(N, N, m)
        blocks = x.reshape(N, N, m)
        expect = np.swapaxes(blocks, 0, 1)  # transpose of blocks
        np.testing.assert_allclose(out, expect)


class TestReduceScatter:
    @pytest.mark.parametrize("algo", [
        alg.reduce_scatter_ring, alg.reduce_scatter_recursive_halving,
        alg.reduce_scatter_nonoverlapping, alg.reduce_scatter_butterfly,
        alg.reduce_scatter_block_linear,
        alg.reduce_scatter_block_recursive_doubling,
        alg.reduce_scatter_block_recursive_halving,
        alg.reduce_scatter_block_butterfly,
        xla_mod.reduce_scatter, xla_mod.reduce_scatter_block,
    ], ids=lambda f: f.__name__)
    def test_sum(self, world, algo):
        m = 2
        x = rng(11).normal(size=(N, N * m)).astype(np.float32)
        out = run_spmd(
            world, lambda s: algo(world, s.reshape(N * m), zmpi.SUM), x
        )
        total = x.sum(axis=0).reshape(N, m)
        np.testing.assert_allclose(out.reshape(N, m), total, rtol=1e-5)


class TestScanBarrier:
    def test_scan(self, world):
        x = rng(12).normal(size=(N, 4)).astype(np.float32)
        out = run_spmd(
            world, lambda s: alg.scan_recursive_doubling(world, s, zmpi.SUM), x
        ).reshape(N, 4)
        np.testing.assert_allclose(out, np.cumsum(x, axis=0), rtol=1e-4)

    def test_exscan(self, world):
        x = rng(13).normal(size=(N, 4)).astype(np.float32)
        out = run_spmd(
            world, lambda s: alg.exscan_recursive_doubling(world, s, zmpi.SUM), x
        ).reshape(N, 4)
        expect = np.vstack([np.zeros((1, 4), np.float32),
                            np.cumsum(x, axis=0)[:-1]])
        np.testing.assert_allclose(out, expect, rtol=1e-4)

    def test_exscan_prod(self, world):
        """Regression: exscan must be correct for non-SUM ops (the zero-fill
        of a shifted *input* is only an identity for SUM)."""
        x = np.arange(1, N + 1, dtype=np.float32).reshape(N, 1)
        out = run_spmd(
            world,
            lambda s: alg.exscan_recursive_doubling(world, s, zmpi.PROD), x,
        ).reshape(N)
        expect = np.concatenate([[0], np.cumprod(x.reshape(N))[:-1]])
        np.testing.assert_allclose(out[1:], expect[1:])  # rank 0 undefined

    def test_exscan_max_negative(self, world):
        x = (-np.arange(1, N + 1, dtype=np.float32)).reshape(N, 1)
        out = run_spmd(
            world,
            lambda s: alg.exscan_recursive_doubling(world, s, zmpi.MAX), x,
        ).reshape(N)
        expect = np.maximum.accumulate(x.reshape(N))[:-1]
        np.testing.assert_allclose(out[1:], expect)

    def test_scan_linear(self, world):
        x = rng(12).normal(size=(N, 4)).astype(np.float32)
        out = run_spmd(
            world, lambda s: alg.scan_linear(world, s, zmpi.SUM), x
        ).reshape(N, 4)
        np.testing.assert_allclose(out, np.cumsum(x, axis=0), rtol=1e-4)

    def test_exscan_linear_prod(self, world):
        x = np.arange(1, N + 1, dtype=np.float32).reshape(N, 1)
        out = run_spmd(
            world, lambda s: alg.exscan_linear(world, s, zmpi.PROD), x
        ).reshape(N)
        expect = np.concatenate([[0], np.cumprod(x.reshape(N))[:-1]])
        np.testing.assert_allclose(out[1:], expect[1:])  # rank 0 undefined

    @pytest.mark.parametrize("algo", [
        alg.barrier_dissemination, alg.barrier_double_ring,
        alg.barrier_recursive_doubling, alg.barrier_tree,
        alg.barrier_linear, xla_mod.barrier,
    ], ids=lambda f: f.__name__)
    def test_barrier(self, world, algo):
        out = run_spmd(world, lambda s: algo(world) + 0 * s[0],
                       np.zeros((N, 1), np.float32))
        assert np.all(out == 0)


class TestScatter:
    @pytest.mark.parametrize("algo", [alg.scatter_linear,
                                      alg.scatter_binomial],
                             ids=lambda f: f.__name__)
    @pytest.mark.parametrize("root", [0, 3])
    def test_scatter(self, world, algo, root):
        x = np.arange(N * 2, dtype=np.float32)
        xs = np.tile(x, (N, 1))  # every rank holds the (root's) buffer
        out = run_spmd(world, lambda s: algo(world, s, root), xs)
        np.testing.assert_allclose(out.reshape(N, 2), x.reshape(N, 2))


class TestGatherBinomial:
    @pytest.mark.parametrize("root", [0, 3])
    def test_gather(self, world, root):
        x = rng(21).normal(size=(N, 2)).astype(np.float32)
        out = run_spmd(
            world, lambda s: alg.gather_binomial(world, s, root), x,
        )
        # result significant at root: check root's slice of the output
        out = out.reshape(N, N * 2)
        np.testing.assert_allclose(out[root], x.reshape(-1))


class TestAlltoallv:
    def _counts(self):
        # counts[i][j]: rows i sends to j — deliberately ragged
        return [[(i + j) % 3 for j in range(N)] for i in range(N)]

    @pytest.mark.parametrize("impl", ["alg", "xla"])
    def test_alltoallv(self, world, impl):
        counts = self._counts()
        mx = max(max(r) for r in counts)
        data = rng(22).normal(size=(N, N, mx, 2)).astype(np.float32)
        # zero out rows beyond the count so the reference is unambiguous
        for i in range(N):
            for j in range(N):
                data[i, j, counts[i][j]:] = 0.0
        fn = (alg.alltoallv_padded if impl == "alg" else xla_mod.alltoallv)
        out = run_spmd(
            world,
            lambda s: fn(world, s.reshape(N, mx, 2), counts),
            data.reshape(N, N * mx * 2),
        )
        out = out.reshape(N, N, mx, 2)
        expect = np.swapaxes(data, 0, 1)
        np.testing.assert_allclose(out, expect)


class TestAllgatherv:
    def test_allgatherv(self, world):
        counts = [1, 2, 1, 3, 1, 2, 1, 1]
        mx = max(counts)
        data = rng(14).normal(size=(N, mx)).astype(np.float32)
        out = run_spmd(
            world,
            lambda s: alg.allgatherv_concat(world, s.reshape(mx), counts),
            data,
        )
        expect = np.concatenate([data[i, : counts[i]] for i in range(N)])
        np.testing.assert_allclose(out.reshape(N, -1)[0], expect)


class TestTwoProc:
    """Exercise the real n==2 branches of the two_proc algorithms on 2-rank
    split communicators (cf. coll_base_allgather.c:598, alltoall.c:490,
    barrier.c:291)."""

    @pytest.fixture(scope="class")
    def pairs_comm(self, world):
        return world.split([i // 2 for i in range(N)])  # 4 groups of 2

    def test_allgather_two_proc(self, world, pairs_comm):
        x = rng(30).normal(size=(N, 3)).astype(np.float32)
        out = run_spmd(
            pairs_comm, lambda s: alg.allgather_two_proc(pairs_comm, s), x
        ).reshape(N, 2, 3)
        for g in range(N // 2):
            expect = x[2 * g : 2 * g + 2]
            np.testing.assert_allclose(out[2 * g], expect)
            np.testing.assert_allclose(out[2 * g + 1], expect)

    def test_alltoall_two_proc(self, world, pairs_comm):
        x = np.arange(N * 4, dtype=np.float32).reshape(N, 4)
        out = run_spmd(
            pairs_comm,
            lambda s: alg.alltoall_two_proc(pairs_comm, s.reshape(4)), x,
        ).reshape(N, 2, 2)
        blocks = x.reshape(N, 2, 2)
        for g in range(N // 2):
            a, b = 2 * g, 2 * g + 1
            np.testing.assert_allclose(out[a], [blocks[a, 0], blocks[b, 0]])
            np.testing.assert_allclose(out[b], [blocks[a, 1], blocks[b, 1]])

    def test_barrier_two_proc(self, world, pairs_comm):
        out = run_spmd(
            pairs_comm,
            lambda s: alg.barrier_two_proc(pairs_comm) + 0 * s[0],
            np.zeros((N, 1), np.float32),
        )
        assert np.all(out == 0)


class TestBarrierNotFolded:
    """Regression: `token * 0` on int32 lets XLA constant-fold the token and
    dead-code-eliminate the barrier's collectives.  The compiled HLO must
    retain its collective ops."""

    @pytest.mark.parametrize("algo", [
        alg.barrier_dissemination, alg.barrier_double_ring,
        alg.barrier_recursive_doubling, alg.barrier_tree,
        alg.barrier_linear, xla_mod.barrier,
    ], ids=lambda f: f.__name__)
    def test_collectives_survive_compilation(self, world, algo):
        from jax.sharding import PartitionSpec as P

        def step(s):
            tok = algo(world, token=s)
            return s + tok.astype(s.dtype)

        fn = jax.shard_map(
            step, mesh=world.mesh, in_specs=P("world"), out_specs=P("world")
        )
        txt = jax.jit(fn).lower(
            jnp.zeros((N, 2), jnp.float32)
        ).compile().as_text()
        assert ("collective-permute" in txt) or ("all-reduce" in txt), (
            f"{algo.__name__}: barrier collectives were optimized away"
        )


class TestSplitComms:
    def test_split_allreduce_xla(self, world):
        sub = world.split([i % 2 for i in range(N)])  # even/odd groups
        x = rng(15).normal(size=(N, 3)).astype(np.float32)
        out = run_spmd(sub, lambda s: xla_mod.allreduce(sub, s, zmpi.SUM), x)
        expect = np.empty_like(x)
        expect[::2] = x[::2].sum(axis=0)
        expect[1::2] = x[1::2].sum(axis=0)
        np.testing.assert_allclose(out.reshape(N, 3), expect, rtol=1e-5)

    def test_split_ring(self, world):
        sub = world.split([0, 0, 0, 0, 1, 1, 1, 1])
        x = rng(16).normal(size=(N, 8)).astype(np.float32)
        out = run_spmd(sub, lambda s: alg.allreduce_ring(sub, s, zmpi.SUM), x)
        expect = np.empty_like(x)
        expect[:4] = x[:4].sum(axis=0)
        expect[4:] = x[4:].sum(axis=0)
        np.testing.assert_allclose(out.reshape(N, 8), expect, rtol=1e-5)


class TestTunedAutoPath:
    """The decision layer's auto path (round-3: large scatter/gather route
    to binomial ppermute trees instead of the p-x-bytes XLA forms)."""

    def test_decide_scatter_gather_by_size(self, world):
        from zhpe_ompi_tpu.coll import tuned

        small = np.zeros(8, np.float32)
        large = np.zeros(1 << 20, np.float32)  # 4 MB > coll_tuned_large_msg
        assert tuned.decide("scatter", world, small) == "xla"
        assert tuned.decide("scatter", world, large) == "binomial"
        assert tuned.decide("gather", world, small) == "xla"
        assert tuned.decide("gather", world, large) == "binomial"

    def test_large_scatter_auto_correct(self, world):
        """The auto path's binomial scatter must agree with the xla form."""
        per = 4096  # 8 ranks x 4096 f32 = 128 KB... below large; force via var
        from zhpe_ompi_tpu.mca import var as mca_var

        x = np.arange(N * N * per, dtype=np.float32).reshape(N, N * per)
        old = mca_var.get("coll_tuned_large_msg")
        mca_var.set_var("coll_tuned_large_msg", 1024)
        try:
            out = run_spmd(
                world, lambda s: world.scatter(s, 0), x
            ).reshape(N, per)
        finally:
            mca_var.set_var("coll_tuned_large_msg", old)
        # each rank gets block r of root 0's buffer
        expect = x[0].reshape(N, per)
        np.testing.assert_allclose(out, expect)

    def test_large_gather_auto_correct(self, world):
        from zhpe_ompi_tpu.mca import var as mca_var

        per = 2048
        x = np.arange(N * per, dtype=np.float32).reshape(N, per)
        old = mca_var.get("coll_tuned_large_msg")
        mca_var.set_var("coll_tuned_large_msg", 1024)
        try:
            out = run_spmd(
                world, lambda s: world.gather(s, 0), x
            )
        finally:
            mca_var.set_var("coll_tuned_large_msg", old)
        out = out.reshape(N, N, per)
        # gather result is significant at root only (MPI semantics; the
        # binomial tree leaves non-root ranks with partial buffers)
        np.testing.assert_allclose(out[0], x)


class TestShippedProfiles:
    """Round-4 (VERDICT Missing #4): the v5e-8 ICI placeholder profile —
    committed, loadable through coll_tuned_dynamic_rules, every rule
    naming a real algorithm, and explicitly marked unmeasured."""

    def test_profile_ships_and_is_documented(self):
        from zhpe_ompi_tpu.coll import tuned

        profs = tuned.profiles()
        assert "v5e8_ici" in profs
        text = open(profs["v5e8_ici"], encoding="utf-8").read()
        assert "UNMEASURED" in text  # the honesty marker
        assert "loopback" in text    # the calibration caveat

    def test_profile_rules_name_real_algorithms(self):
        from zhpe_ompi_tpu.coll import tuned

        path = tuned.profiles()["v5e8_ici"]
        n_rules = 0
        for line in open(path, encoding="utf-8"):
            parts = line.split("#")[0].split()
            if not parts:
                continue
            op, cmin, bmin, algname = (
                parts[0], int(parts[1]), int(parts[2]), parts[3])
            assert algname in tuned._ALG_TABLES[op], (op, algname)
            n_rules += 1
        assert n_rules >= 5

    def test_profile_drives_decide(self, world, fresh_vars):
        """Loading the profile flips the large-message allreduce choice
        to the profile's rule; small messages keep the fixed decision."""
        import numpy as np

        from zhpe_ompi_tpu import ops as zops
        from zhpe_ompi_tpu.coll import tuned
        from zhpe_ompi_tpu.mca import var as mca_var

        tuned._register_params()  # var registration (component init)
        mca_var.set_var("coll_tuned_dynamic_rules",
                        tuned.profiles()["v5e8_ici"])
        big = np.zeros(2 * 1024 * 1024, np.float32)  # 8 MiB >= 4 MiB rule
        small = np.zeros(8, np.float32)
        assert tuned.decide("allreduce", world, big,
                            zops.SUM) == "segmented_ring"
        assert tuned.decide("allreduce", world, small, zops.SUM) != \
            "segmented_ring"


class TestDynamicRulesFile:
    """The dynamic-rules loader's contract (PR-6 satellite):
    most-specific-line-wins ordering, malformed/unknown lines degrade
    LOUDLY to the fixed default instead of raising, and `han` rule
    lines validate for the hierarchical host ops only."""

    def _rules(self, tmp_path, text):
        from zhpe_ompi_tpu.coll import tuned

        path = tmp_path / "test.rules"
        path.write_text(text)
        tuned._rules_cache.pop(str(path), None)
        return str(path)

    def test_most_specific_line_wins(self, tmp_path, fresh_vars):
        from zhpe_ompi_tpu.coll import tuned
        from zhpe_ompi_tpu.mca import var as mca_var

        tuned._register_params()
        path = self._rules(tmp_path, "\n".join([
            "allreduce 0 0 linear",
            "allreduce 4 0 ring",
            "allreduce 4 1048576 rabenseifner",
            "# comment line",
        ]))
        mca_var.set_var("coll_tuned_dynamic_rules", path)
        try:
            assert tuned._dynamic_rule("allreduce", 2, 10) == "linear"
            assert tuned._dynamic_rule("allreduce", 8, 10) == "ring"
            assert tuned._dynamic_rule("allreduce", 8, 2 << 20) == \
                "rabenseifner"
            assert tuned._dynamic_rule("bcast", 8, 10) is None
        finally:
            mca_var.registry.unset("coll_tuned_dynamic_rules")
            tuned._rules_cache.pop(path, None)

    def test_malformed_lines_degrade_loudly_not_raise(self, tmp_path,
                                                      fresh_vars):
        """Bad field counts, non-integer thresholds, unknown ops, and
        unknown algorithm names are each skipped per line; the valid
        line still applies and nothing raises out of the decision."""
        from zhpe_ompi_tpu.coll import tuned

        path = self._rules(tmp_path, "\n".join([
            "allreduce x y ring",          # non-integer thresholds
            "allreduce 0",                 # wrong field count
            "bogus_op 0 0 ring",           # unknown op
            "allreduce 0 0 bogus_alg",     # unknown algorithm
            "scan 0 0 han",                # han on a non-han op
            # (alltoallv gained a han schedule in the serving-plane
            # PR, so it is no longer the non-han fixture here)
            "allreduce 0 0 ring",          # the one valid line
        ]))
        rules = tuned._load_rules(path)
        assert rules == [("allreduce", 0, 0, "ring")]

    def test_unreadable_file_degrades_not_raises(self, tmp_path):
        from zhpe_ompi_tpu.coll import tuned

        assert tuned._load_rules(str(tmp_path / "missing.rules")) == []

    def test_han_line_validates_for_host_ops(self, tmp_path):
        from zhpe_ompi_tpu.coll import tuned

        text = "\n".join(
            f"{op} 4 1024 han" for op in sorted(tuned._HAN_RULE_OPS)
        )
        path = self._rules(tmp_path, text)
        rules = tuned._load_rules(path)
        assert len(rules) == len(tuned._HAN_RULE_OPS)
        assert all(alg == "han" for *_rest, alg in rules)

    def test_device_decide_never_returns_han(self, world, tmp_path,
                                             fresh_vars):
        """A han rule line is a HOST-plane request: the device-plane
        decision (XLA algorithm tables) must fall back to its fixed
        choice, never hand the dispatcher an algorithm its table does
        not hold."""
        import numpy as np

        from zhpe_ompi_tpu import ops as zops
        from zhpe_ompi_tpu.coll import tuned
        from zhpe_ompi_tpu.mca import var as mca_var

        tuned._register_params()
        path = self._rules(tmp_path, "allreduce 0 0 han\n")
        mca_var.set_var("coll_tuned_dynamic_rules", path)
        try:
            choice = tuned.decide("allreduce", world,
                                  np.zeros(8, np.float32), zops.SUM)
            assert choice in tuned.ALLREDUCE_ALGS
        finally:
            mca_var.registry.unset("coll_tuned_dynamic_rules")
            tuned._rules_cache.pop(path, None)
