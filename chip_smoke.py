"""Chip smoke: drive the device plane once on a TPU, through the entry
points a user calls, and check what comes out.  The train step's numbers
and correctness come from the benchmark (``python3 perfbench/run.py``).

    python3 chip_smoke.py             # one chip: device, collectives
    python3 chip_smoke.py --chips 4   # four chips: every forced tuned
                                      # algorithm

One process; nothing here starts a child.  Each phase prints one JSON
line; the last line is ``{"ok": true, "device": {...}}``.  Any failed
check raises, so the script exits nonzero with no ``ok`` line — also when
JAX finds no TPU.  There is no CPU branch.

Phases:

- device: platform, device_kind and count as JAX reports them.
- collectives: allreduce (SUM, MAX), bcast, allgather, alltoall and
  reduce_scatter through the world ``Communicator`` (``comm_select`` ->
  coll/tuned) at 4 KiB and 4 MiB per rank, against numpy.  With
  ``--chips 4`` every algorithm of coll/tuned's table is forced in turn
  (the ``coll_tuned_<op>_algorithm`` MCA variable, which
  ``ZMPI_MCA_coll_tuned_<op>_algorithm`` sets from the environment), at
  sizes below and above ``coll_tuned_large_msg``.
"""

import argparse
import json
import os

import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))

# Collectives vs numpy, f32: only summation order differs.
COLL_RTOL, COLL_ATOL = 1e-5, 1e-5
COLL_BYTES = (4 << 10, 4 << 20)  # below and above coll_tuned_large_msg
COLL_OPS = ("allreduce", "bcast", "allgather", "alltoall",
            "reduce_scatter")


def emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


def _fail(msg: str):
    raise RuntimeError(f"chip_smoke: {msg}")


def device_phase(chips: int):
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        _fail(f"JAX found platform {d0.platform!r}, not a TPU")
    if len(devs) < chips:
        _fail(f"--chips {chips} but JAX sees {len(devs)} device(s)")
    emit(phase="device", platform=d0.platform, kind=d0.device_kind,
         count=len(devs))
    return devs


def _expected(opname, xs, op, root):
    """numpy reference: per-rank outputs, stacked on axis 0."""
    n = xs.shape[0]
    if opname == "allreduce":
        red = xs.sum(0) if op == "SUM" else xs.max(0)
        return np.broadcast_to(red, xs.shape)
    if opname == "bcast":
        return np.broadcast_to(xs[root], xs.shape)
    if opname == "allgather":
        return np.broadcast_to(xs.reshape(-1), (n, xs.size))
    blocks = xs.reshape(n, n, -1)  # [src rank, dest block]
    if opname == "alltoall":
        return blocks.swapaxes(0, 1).reshape(n, -1)
    return blocks.sum(0)  # reduce_scatter SUM: rank r keeps block r


def _run_collective(world, opname, xs, op, root):
    import jax.numpy as jnp

    import zhpe_ompi_tpu as zmpi

    calls = {
        "allreduce": lambda s: world.allreduce(s, getattr(zmpi, op)),
        "bcast": lambda s: world.bcast(s, root),
        "allgather": world.allgather,
        "alltoall": world.alltoall,
        "reduce_scatter": lambda s: world.reduce_scatter(s, zmpi.SUM),
    }
    fn = calls[opname]
    out = world.run(lambda s: fn(s[0])[None],
                    world.device_put_sharded(jnp.asarray(xs)))
    return np.asarray(out)


def collectives_phase(devs, force_algorithms: bool) -> int:
    """Every COLL_OPS op through the world communicator, compared with
    numpy; returns the number of checks."""
    import zhpe_ompi_tpu as zmpi
    from zhpe_ompi_tpu.coll import tuned

    world = zmpi.init(devices=devs)
    n = world.size
    if world.mesh.devices.size != len(devs):
        _fail(f"world mesh spans {world.mesh.devices.size} devices")
    rng = np.random.default_rng(0)
    root = n - 1
    checks = 0
    for opname in COLL_OPS:
        component = world.coll[opname][1]
        if component != "tuned":
            _fail(f"{opname} selected {component!r}, not coll/tuned")
        var = f"coll_tuned_{opname}_algorithm"
        algs = list(tuned._ALG_TABLES[opname]) if force_algorithms \
            else ["auto"]
        for alg in algs:
            zmpi.mca_var.set_var(var, alg)
            try:
                for nbytes in COLL_BYTES:
                    xs = rng.standard_normal(
                        (n, nbytes // 4)).astype(np.float32)
                    for op in (("SUM", "MAX") if opname == "allreduce"
                               else (None,)):
                        got = _run_collective(world, opname, xs, op, root)
                        want = _expected(opname, xs, op, root)
                        if not np.allclose(got, want, rtol=COLL_RTOL,
                                           atol=COLL_ATOL):
                            _fail(f"{opname} {op or ''} alg={alg} "
                                  f"{nbytes} B differs from numpy: max "
                                  f"|err| {np.abs(got - want).max()}")
                        checks += 1
            finally:
                zmpi.mca_var.unset(var)
    emit(phase="collectives", world=n, forced_algorithms=force_algorithms,
         bytes=list(COLL_BYTES), checks=checks)
    return checks


def use_compile_cache() -> None:
    """JAX's persistent compile cache: the directory in
    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself), else a
    fixed ``<repo>/.jax_cache`` — the path is part of the cache key, so
    it must not move between runs."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_REPO, ".jax_cache"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devs_all = device_phase(args.chips)
    use_compile_cache()
    collectives_phase(devs_all[:args.chips],
                      force_algorithms=args.chips == 4)
    d0 = devs_all[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs_all)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
