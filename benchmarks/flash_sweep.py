"""Flash-attention block-size sweep on the real chip: flash vs naive,
forward and grad, at seq 512 and 4096, across (block_q, block_k) tiles.
Scalar-output discipline (see component_probe.py: fetching a large
output times the copy to the host, not the kernel).

Run from repo root: python benchmarks/flash_sweep.py [seq ...]
"""

import sys
import time

sys.path.insert(0, ".")

import numpy as np


def bench(fn, *args, iters=10):
    out = fn(*args)
    for _ in range(2):
        out = fn(*args)
    float(out)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        float(out)
        times.append((time.perf_counter() - t0) / iters)
    return float(np.median(times[1:]))


def main():
    import jax
    import jax.numpy as jnp

    from zhpe_ompi_tpu.ops import flash_attention as fa

    seqs = [int(s) for s in sys.argv[1:]] or [512, 4096]
    B, H, hd = 8, 16, 64
    for S in seqs:
        if S >= 2048:
            B_eff = max(1, B // (S // 1024))
        else:
            B_eff = B
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (B_eff, S, H, hd), jnp.bfloat16)
        k = jax.random.normal(key, (B_eff, S, H, hd), jnp.bfloat16)
        v = jax.random.normal(key, (B_eff, S, H, hd), jnp.bfloat16)

        naive_fwd = jax.jit(lambda a, b, c: jnp.sum(
            fa.attn_reference(a, b, c).astype(jnp.float32)))
        try:
            t = bench(naive_fwd, q, k, v)
            print(f"S={S:5d} naive  fwd: {t*1e3:8.2f} ms", flush=True)
        except Exception as e:
            print(f"S={S:5d} naive  fwd: FAILED {type(e).__name__}",
                  flush=True)

        def naive_loss(a, b, c):
            return jnp.sum(fa.attn_reference(a, b, c).astype(jnp.float32))

        try:
            t = bench(jax.jit(lambda a, b, c: jnp.sum(
                jax.grad(naive_loss)(a, b, c).astype(jnp.float32))),
                q, k, v)
            print(f"S={S:5d} naive grad: {t*1e3:8.2f} ms", flush=True)
        except Exception as e:
            print(f"S={S:5d} naive grad: FAILED {type(e).__name__}",
                  flush=True)

        for bq, bk in [(256, 256), (512, 512), (512, 1024), (1024, 1024)]:
            if S % bq or S % bk:
                continue

            def flash_fwd(a, b, c, bq=bq, bk=bk):
                return jnp.sum(fa.flash_attention(
                    a, b, c, causal=True, block_q=bq, block_k=bk,
                    force=True).astype(jnp.float32))

            try:
                t = bench(jax.jit(flash_fwd), q, k, v)
                print(f"S={S:5d} flash({bq:4d},{bk:4d}) fwd: "
                      f"{t*1e3:8.2f} ms", flush=True)
                t = bench(jax.jit(
                    lambda a, b, c, bq=bq, bk=bk: jnp.sum(jax.grad(
                        lambda x: flash_fwd(x, b, c, bq, bk))(a)
                        .astype(jnp.float32))), q, k, v)
                print(f"S={S:5d} flash({bq:4d},{bk:4d}) grad: "
                      f"{t*1e3:8.2f} ms", flush=True)
            except Exception as e:
                print(f"S={S:5d} flash({bq:4d},{bk:4d}): FAILED "
                      f"{type(e).__name__}: {str(e)[:100]}", flush=True)


if __name__ == "__main__":
    main()
