"""VERDICT round-2 item 7: explain vs_baseline > 1.

Dumps the optimized HLO of the framework train step and the plain-JAX
baseline step (exactly as bench.py builds them) and reports whether they
differ.  Identical HLO => any persistent timing delta is measurement
noise and vs_baseline should read ~1.0.

Run from repo root: python benchmarks/hlo_diff.py  (CPU or TPU; module
structure only)
"""

import difflib
import re
import sys

sys.path.insert(0, ".")

import numpy as np


def canon(text: str) -> str:
    """Canonicalize HLO text: strip metadata/ids that differ between two
    otherwise-identical programs."""
    out = []
    for line in text.splitlines():
        stripped = line.strip()
        # source-location tables (stack frame indexes): pure metadata
        if re.match(r'^\d+ (\{[^}]*\}|")', stripped):
            continue
        line = re.sub(r"metadata=\{[^}]*\}", "", line)
        line = re.sub(r'"[^"]*"', '""', line)
        # computation/instruction numbering suffixes (.NN) differ freely
        line = re.sub(r"\.\d+", "", line)
        # argument names differ between the two harness functions
        # (params/tokens/targets vs p/tok/tgt) — not part of the program
        line = re.sub(r"params__(\w+?)__", r"p__\1__", line)
        line = line.replace("%tokens", "%tok").replace("%targets", "%tgt")
        line = line.replace("tokens:", "tok:").replace("targets:", "tgt:")
        out.append(line.rstrip())
    return "\n".join(out)


def main():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import bench
    import zhpe_ompi_tpu as zmpi
    from zhpe_ompi_tpu.models import transformer as tfm

    mesh = bench.dp_tp_mesh(jax.devices())
    dp, tp = mesh.shape["dp"], mesh.shape["tp"]
    dp_comm = zmpi.Communicator(mesh, "dp", name="hlo_dp")
    tp_comm = zmpi.Communicator(mesh, "tp", name="hlo_tp") if tp > 1 else None

    if jax.devices()[0].platform == "tpu":
        cfg = tfm.Config(vocab=8192, d_model=1024, n_heads=16, d_ff=4096,
                         n_layers=4, seq=512, dtype=jnp.bfloat16)
        batch = 8 * dp
    else:
        cfg = tfm.Config(vocab=256, d_model=128, n_heads=8, d_ff=512,
                         n_layers=2, seq=128, dtype=jnp.float32)
        batch = 2 * dp

    r = np.random.default_rng(0)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(r.integers(0, cfg.vocab, (batch, cfg.seq)))
    targets = jnp.asarray(r.integers(0, cfg.vocab, (batch, cfg.seq)))

    step_fw, specs = tfm.make_train_step(cfg, mesh, dp_comm, tp_comm)
    step_pl = bench.make_plain_step(cfg, mesh, specs)

    sharded = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
               for k, v in params.items()}
    dspec = NamedSharding(mesh, P("dp"))
    tok = jax.device_put(tokens, dspec)
    tgt = jax.device_put(targets, dspec)

    hlo_fw = canon(
        step_fw.lower(sharded, tok, tgt).compile()
        .as_text())
    hlo_pl = canon(
        step_pl.lower(sharded, tok, tgt).compile()
        .as_text())
    if hlo_fw == hlo_pl:
        print("HLO IDENTICAL: framework and plain paths compile to the "
              "same program; vs_baseline deltas are measurement noise.")
        return 0
    fw_lines, pl_lines = hlo_fw.splitlines(), hlo_pl.splitlines()
    diff = list(difflib.unified_diff(pl_lines, fw_lines,
                                     "plain", "framework", lineterm="", n=0))
    print(f"HLO DIFFERS: {len(diff)} diff lines "
          f"(fw {len(fw_lines)} vs plain {len(pl_lines)} lines)")
    for line in diff[:80]:
        print(line)
    return 1


if __name__ == "__main__":
    sys.exit(main())
