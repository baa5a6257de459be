"""Component-level timing of the headline config on the real chip.

Every timed function is wrapped to reduce its output to ONE scalar
inside jit, so the forced host fetch is 4 bytes and the window bounds
device work, not the device-to-host copy of a large output.

Run from repo root: python benchmarks/component_probe.py
"""

import sys
import time

sys.path.insert(0, ".")

import numpy as np


def scalarize(fn):
    import jax
    import jax.numpy as jnp

    def wrapped(*args):
        out = fn(*args)
        leaves = jax.tree.leaves(out)
        return sum(jnp.sum(l).astype(jnp.float32) for l in leaves[:4])

    return jax.jit(wrapped)


def bench_fn(fn, *args, iters=20, warm=3):
    out = fn(*args)
    for _ in range(warm):
        out = fn(*args)
    float(out)
    times = []
    for _ in range(4):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        float(out)  # scalar fetch bounds the window
        times.append((time.perf_counter() - t0) / iters)
    return float(np.median(times[1:]))  # drop the boost window


def main():
    import jax
    import jax.numpy as jnp

    from zhpe_ompi_tpu.models import transformer as tfm

    cfg = tfm.Config(vocab=8192, d_model=1024, n_heads=16, d_ff=4096,
                     n_layers=4, seq=512, dtype=jnp.bfloat16)
    cfg_naive = tfm.Config(vocab=8192, d_model=1024, n_heads=16, d_ff=4096,
                           n_layers=4, seq=512, dtype=jnp.bfloat16,
                           flash=False)
    batch = 8
    r = np.random.default_rng(0)
    params = jax.device_put(tfm.init_params(cfg, jax.random.PRNGKey(0)))
    tok = jnp.asarray(r.integers(0, cfg.vocab, (batch, cfg.seq)))
    tgt = jnp.asarray(r.integers(0, cfg.vocab, (batch, cfg.seq)))

    import os

    phase = os.environ.get("PROBE_PHASE", "1")
    rows = []
    if phase == "1":
        rows = [
            ("fwd_hidden flash", scalarize(
                lambda p, t: tfm.forward_hidden(p, t, cfg)), (params, tok)),
            ("loss fwd flash", scalarize(
                lambda p, a, b: tfm.loss_fn(p, a, b, cfg)),
             (params, tok, tgt)),
            ("grad flash", scalarize(jax.value_and_grad(
                lambda p, a, b: tfm.loss_fn(p, a, b, cfg))),
             (params, tok, tgt)),
        ]
    elif phase == "naive":
        rows = [
            ("fwd_hidden naive", scalarize(
                lambda p, t: tfm.forward_hidden(p, t, cfg_naive)),
             (params, tok)),
            ("grad naive", scalarize(jax.value_and_grad(
                lambda p, a, b: tfm.loss_fn(p, a, b, cfg_naive))),
             (params, tok, tgt)),
        ]
    for name, fn, args in rows:
        t = bench_fn(fn, *args)
        print(f"{name:20s}: {t*1e3:7.2f} ms", flush=True)

    if phase == "1":
        # SGD tail
        grads = jax.jit(jax.grad(
            lambda p, a, b: tfm.loss_fn(p, a, b, cfg)))(params, tok, tgt)

        def sgd(p, g):
            return jax.tree.map(
                lambda a, b: (a - 1e-2 * b).astype(a.dtype), p, g)

        t = bench_fn(scalarize(sgd), params, grads)
        print(f"{'sgd update':20s}: {t*1e3:7.2f} ms", flush=True)
    if phase != "2":
        return

    # pure-matmul ceiling at the model's shapes
    BT = batch * cfg.seq
    key = jax.random.PRNGKey(1)
    x0 = jax.random.normal(key, (BT, 1024), jnp.bfloat16)
    ws = {
        "wq": jax.random.normal(key, (1024, 3072), jnp.bfloat16),
        "wo": jax.random.normal(key, (1024, 1024), jnp.bfloat16),
        "w1": jax.random.normal(key, (1024, 4096), jnp.bfloat16),
        "w2": jax.random.normal(key, (4096, 1024), jnp.bfloat16),
        "emb": jax.random.normal(key, (1024, 8192), jnp.bfloat16),
    }

    def mm(x, w):
        for _ in range(cfg.n_layers):
            a = x @ w["wq"]
            b = a[:, :1024] @ w["wo"]
            c = x @ w["w1"]
            d = c @ w["w2"]
            x = (x + b + d) / 30.0
        return (x @ w["emb"]).astype(jnp.float32)

    fl = (cfg.n_layers * (BT * 1024 * 3072 + BT * 1024 * 1024
                          + BT * 1024 * 4096 + BT * 4096 * 1024)
          + BT * 1024 * 8192) * 2
    t = bench_fn(scalarize(mm), x0, ws)
    print(f"{'matmul-only fwd':20s}: {t*1e3:7.2f} ms "
          f"({fl/t/1e12:.0f} TFLOP/s attained)", flush=True)


if __name__ == "__main__":
    main()
