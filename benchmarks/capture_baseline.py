"""Capture the per-algorithm collective baselines (VERDICT round-2 item 4).

Sweeps every tuned algorithm of the four headline collectives over the
OSU size ladder on the 8-virtual-CPU loopback mesh (the btl/self+sm
analog), plus the host-plane ping-pong, and writes the artifact
``benchmarks/baseline_cpu8.json`` that BASELINE.md cites.  The measured
crossovers set the tuned thresholds' defaults (provenance comments in
coll/tuned.py point back here).

Run (a CPU sweep by design; it never takes a chip):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/capture_baseline.py
"""

import json
import os
import platform
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# the sweep's per-algorithm matrix: every tuned table entry that runs on
# the auto path or exists for forced selection
SWEEPS = {
    "allreduce": ["xla", "linear", "nonoverlapping", "recursive_doubling",
                  "ring", "segmented_ring", "rabenseifner"],
    "bcast": ["xla", "linear", "chain", "pipeline", "split_binary",
              "binary", "binomial", "knomial", "scatter_allgather"],
    "allgather": ["xla", "linear", "bruck", "recursive_doubling", "ring",
                  "neighbor_exchange"],
    "alltoall": ["xla", "linear", "pairwise", "bruck", "linear_sync"],
}

SMALL_MAX = 4 << 20    # per-algorithm ladder: 4B .. 4MB (x16 steps)
LARGE_MAX = 64 << 20   # crossover ladder for the allreduce contenders


def main() -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    from benchmarks.osu_zmpi import _sizes, bench_collective, bench_pt2pt

    n_dev = len(jax.devices())
    rows = []
    for opname, algs in SWEEPS.items():
        for algname in algs:
            print(f"sweep {opname}/{algname} ...", flush=True)
            rows += bench_collective(
                opname, algname, max_size=SMALL_MAX, iters=10
            )
    # fine ladder for the auto-path contenders at large sizes
    for algname in ("recursive_doubling", "ring", "rabenseifner"):
        print(f"sweep allreduce/{algname} large ...", flush=True)
        rows += [
            dict(r, ladder="large")
            for r in bench_collective(
                "allreduce", algname, max_size=LARGE_MAX, iters=5
            )
        ]
    print("sweep pt2pt ...", flush=True)
    rows += bench_pt2pt(max_size=SMALL_MAX, iters=30)

    artifact = {
        "host": platform.node(),
        "platform": "cpu-loopback",
        "n_devices": n_dev,
        "rows": rows,
    }
    out = os.path.join(REPO, "benchmarks", "baseline_cpu8.json")
    with open(out, "w", encoding="utf-8") as fh:
        json.dump(artifact, fh, indent=1)
    print(f"wrote {out} ({len(rows)} rows)")

    # crossover report: for each op/size, which algorithm won
    by_size: dict = {}
    for r in rows:
        if r.get("ladder") or r["op"] == "pt2pt_pingpong":
            continue
        key = (r["op"], r["bytes"])
        if key not in by_size or r["latency_us"] < by_size[key][1]:
            by_size[key] = (r["algorithm"], r["latency_us"])
    for (op, nbytes), (algname, lat) in sorted(by_size.items()):
        print(f"best {op:>10} @{nbytes:>9}B: {algname:<20} {lat:9.1f} us")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
