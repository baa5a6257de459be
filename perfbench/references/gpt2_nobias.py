"""Plain float32 reference of the configurations' model family: GPT-2
without biases, without a position table and with LayerNorm without beta
(the departures listed in each config file), trained with AdamW.

It imports nothing of the program under test.  It shares with the program
only the layout of the parameter dict, so that leaves can be compared one
by one:

    embed (V, D)   wqkv (L, D, 3, D)   wo (L, D, D)   w1 (L, D, F)
    w2 (L, F, D)   ln1 (L, D)          ln2 (L, D)     lnf (D,)

Every matmul runs at ``Precision.HIGHEST`` in float32.  ``mode="fp8"`` is
the control: every matmul operand is rounded to float8 e4m3 with a
per-tensor scale, and the gradient flowing back into each matmul output
to float8 e5m2, the way fp8 training recipes do it.  ``fault`` plants one
of the faults a training step can have, for the calibration readings.

The weights of a run are made here too (``init_params``), from the seed,
by the benchmark: the program is handed them, and the reference makes them
again from the same seed rather than taking the program's arrays.

So that the float32 pass fits one chip at long sequences, the reference
is computed in blocks; each is a departure from the textbook layout, not
from its arithmetic:

- rows: ``block_rows(S)`` rows of the batch at a time, a budget of
  ``BLOCK_TOKENS`` tokens (4 rows at S 1024, 1 at S 8192), their losses and
  gradients summed;
- attention: query blocks of ``Q_BLOCK`` positions, each against the keys
  up to its own end under the causal mask, each under ``jax.checkpoint`` so
  that the backward recomputes its scores rather than keeping them;
- the unembedding and logsumexp: chunks of ``LOSS_CHUNK`` positions, each
  under ``jax.checkpoint``, their sums added.

Up to ``Q_BLOCK`` (``LOSS_CHUNK``) positions there is one block, and the
computation is the unblocked one, op for op.  Blocking changes only the
order of float32 sums; in the fp8 control, each block's operands take a
scale of their own.

``flops_per_step`` gives the model operations a training step of the
configuration requires, which the ``mfu`` metric reads; ``dims`` the
sizes other readers need.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
BLOCK_TOKENS = 4096  # tokens of the batch run at once
Q_BLOCK = 1024       # query positions of one block of attention
LOSS_CHUNK = 1024    # positions of one chunk of the unembedding and its loss
_NEG = -1e30


def block_rows(seq: int) -> int:
    """Rows of the batch run at once, so that the f32 pass fits."""
    return max(1, BLOCK_TOKENS // seq)


def dims(cfg: dict) -> dict:
    d = cfg["n_embd"]
    return {"V": cfg["vocab_size"], "D": d, "H": cfg["n_head"],
            "hd": d // cfg["n_head"], "F": cfg["n_inner"],
            "L": cfg["n_layer"], "eps": cfg["layer_norm_epsilon"]}


def flops_per_step(cfg: dict, traffic: dict) -> float:
    """Required model operations of one training step: 6 x the matmul
    parameters x tokens (forward 2, backward 4; the unembedding counts, the
    embedding lookup does not) plus causal attention, 6 x L x B x S^2 x D
    (the full square's 12, halved for the mask).  Recomputation under remat
    and a chunked loss's second pass over the logits do not count."""
    m = dims(cfg)
    D, F, V, L = m["D"], m["F"], m["V"], m["L"]
    B, S = traffic["global_batch"], traffic["seq"]
    matmul_params = L * (4 * D * D + 2 * D * F) + V * D
    return 6.0 * matmul_params * B * S + 6.0 * L * B * S * S * D


def init_params(cfg: dict, key) -> dict:
    """GPT-2's published init (initializer_range 0.02, residual
    projections scaled by 1/sqrt(2 n_layer), gains 1), in float32."""
    m = dims(cfg)
    V, D, F, L = m["V"], m["D"], m["F"], m["L"]
    std = cfg["initializer_range"]
    proj = std / math.sqrt(2 * L)
    k = jax.random.split(key, 5)

    def nrm(kk, shape, s):
        return jax.random.normal(kk, shape, jnp.float32) * s

    return {
        "embed": nrm(k[0], (V, D), std),
        "wqkv": nrm(k[1], (L, D, 3, D), std),
        "wo": nrm(k[2], (L, D, D), proj),
        "w1": nrm(k[3], (L, D, F), std),
        "w2": nrm(k[4], (L, F, D), proj),
        "ln1": jnp.ones((L, D), jnp.float32),
        "ln2": jnp.ones((L, D), jnp.float32),
        "lnf": jnp.ones((D,), jnp.float32),
    }


# ------------------------------------------------------------- fp8 control


def _qdq(x, dtype):
    """Round ``x`` to ``dtype`` with a per-tensor scale that maps its
    largest magnitude to the format's largest finite value."""
    top = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / top, 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _q_operand(x):
    return _qdq(x, jnp.float8_e4m3fn)


_q_operand.defvjp(lambda x: (_qdq(x, jnp.float8_e4m3fn), None),
                  lambda _, ct: (ct,))


@jax.custom_vjp
def _q_cotangent(y):
    return y


_q_cotangent.defvjp(lambda y: (y, None),
                    lambda _, ct: (_qdq(ct, jnp.float8_e5m2),))


def _mm(eq, a, b, mode):
    if mode == "fp8":
        a, b = _q_operand(a), _q_operand(b)
    out = jnp.einsum(eq, a, b, precision=HIGHEST,
                     preferred_element_type=jnp.float32)
    return _q_cotangent(out) if mode == "fp8" else out


# ------------------------------------------------------------------ model


def _ln(x, g, eps):
    m = x.mean(-1, keepdims=True)
    c = x - m
    v = (c * c).mean(-1, keepdims=True)
    return c / jnp.sqrt(v + eps) * g


def _gelu_tanh(u):
    return 0.5 * u * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (u + 0.044715 * u * u * u)))


def _attend(q, k, v, q0, mode):
    """Causal attention of the queries at positions ``q0`` onwards over
    the keys ``k``, ``v`` from position 0."""
    s = _mm("bqhd,bkhd->bhqk", q, k, mode) * (q.shape[-1] ** -0.5)
    causal = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool), q0)
    s = jnp.where(causal, s, _NEG)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return _mm("bhqk,bkhd->bqhd", p, v, mode)


def attention(q, k, v, mode):
    """Causal attention over query blocks of ``Q_BLOCK`` positions, each
    seeing the keys up to its own end; one block up to ``Q_BLOCK``."""
    S = q.shape[1]
    if S <= Q_BLOCK:
        return _attend(q, k, v, 0, mode)
    out = []
    for q0 in range(0, S, Q_BLOCK):
        end = min(q0 + Q_BLOCK, S)
        f = jax.checkpoint(functools.partial(_attend, q0=q0, mode=mode))
        out.append(f(q[:, q0:end], k[:, :end], v[:, :end]))
    return jnp.concatenate(out, axis=1)


def _block(x, layer, m, mode, fault):
    wqkv, wo, w1, w2, g1, g2 = layer
    B, S, D = x.shape
    H, hd = m["H"], m["hd"]
    h = _ln(x, g1, m["eps"])
    qkv = _mm("bsd,dce->bsce", h, wqkv, mode)
    q, k, v = (qkv[:, :, i].reshape(B, S, H, hd) for i in range(3))
    o = attention(q, k, v, mode).reshape(B, S, D)
    F = w1.shape[-1]
    if fault == "tp_partial":
        # the tensor-parallel all-reduce left out: tp rank 0 of two keeps
        # its own heads' and its own d_ff half's partial products
        o, wo = o[..., :D // 2], wo[:D // 2]
    x = x + _mm("bse,ed->bsd", o, wo, mode)
    h = _ln(x, g2, m["eps"])
    u = _gelu_tanh(_mm("bsd,df->bsf", h, w1, mode))
    if fault == "tp_partial":
        u, w2 = u[..., :F // 2], w2[:F // 2]
    return x + _mm("bsf,fd->bsd", u, w2, mode)


def _loss_chunk(x, targets, embed, mode):
    """Sum over the chunk's tokens of -log p(target)."""
    logits = _mm("bsd,vd->bsv", x, embed, mode)
    mx = logits.max(-1, keepdims=True)
    lse = jnp.log(jnp.exp(logits - mx).sum(-1)) + mx[..., 0]
    tl = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.sum(lse - tl)


def loss_sum(x, targets, embed, mode):
    """Sum of the token losses from the final hidden states, over chunks
    of ``LOSS_CHUNK`` positions; one chunk up to ``LOSS_CHUNK``."""
    S = x.shape[1]
    if S <= LOSS_CHUNK:
        return _loss_chunk(x, targets, embed, mode)
    f = jax.checkpoint(functools.partial(_loss_chunk, mode=mode))
    parts = [f(x[:, c0:c0 + LOSS_CHUNK], targets[:, c0:c0 + LOSS_CHUNK],
               embed) for c0 in range(0, S, LOSS_CHUNK)]
    return jnp.sum(jnp.stack(parts))


def token_loss_sum(params, tokens, targets, m, mode="f32", fault=None,
                   constrain=None):
    """Sum over the block's tokens of -log p(target): the forward runs
    layer by layer (``scan`` with each layer rematerialised), so a block
    of rows fits."""
    x = params["embed"][tokens]
    if constrain is not None:
        x = constrain(x)
    layers = tuple(params[k] for k in
                   ("wqkv", "wo", "w1", "w2", "ln1", "ln2"))
    body = jax.checkpoint(
        lambda c, layer: (_block(c, layer, m, mode, fault), None))
    x, _ = lax.scan(body, x, layers)
    x = _ln(x, params["lnf"], m["eps"])
    return loss_sum(x, targets, params["embed"], mode)


def loss_and_grads(params, tokens, targets, m, block_rows, mode="f32",
                   fault=None, constrain=None):
    """Mean token loss over the whole batch and its gradient, computed
    over blocks of ``block_rows`` rows and summed."""
    if fault == "half_batch":
        # half of the batch left out, the mean taken over the rest
        half = tokens.shape[0] // 2
        tokens, targets = tokens[:half], targets[:half]
    rows, S = tokens.shape
    block_rows = min(block_rows, rows)
    n = rows * S
    tb = tokens.reshape(rows // block_rows, block_rows, S)
    gb = targets.reshape(rows // block_rows, block_rows, S)
    vg = jax.value_and_grad(
        lambda p, t, y: token_loss_sum(p, t, y, m, mode, fault, constrain)
        / n)

    def one(acc, blk):
        lsum, gacc = acc
        l, g = vg(params, *blk)
        return (lsum + l, jax.tree.map(jnp.add, gacc, g)), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = lax.scan(one, zero, (tb, gb))
    return loss, grads


def adamw_update(params, mu, nu, grads, t, opt):
    """optax.adamw's arithmetic, written out: bias-corrected moments,
    eps outside the square root, decoupled weight decay on every leaf."""
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, m, v):
        u = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
        return p - opt["lr"] * (u + opt["weight_decay"] * p)

    return jax.tree.map(upd, params, mu, nu), mu, nu


def leaf_norms(tree) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def train_readings(cfg, opt, batches, key, shardings=None, mode="f32",
                   fault=None, constrain=None) -> dict:
    """Follow the program's first steps from the weights made from
    ``key``: the loss of each step, the per-leaf norm of the first
    gradient, and the per-leaf norm of the parameters' change after the
    last step.  ``batches`` is a list of (tokens, targets) device arrays;
    ``shardings`` (a dict of leaf shardings, or None) places the state."""
    m = dims(cfg)
    if fault == "unchanged":
        # a step that returns its state unchanged
        opt = dict(opt, lr=0.0, weight_decay=0.0)
    init = jax.jit(functools.partial(init_params, cfg),
                   out_shardings=shardings)

    def step(p, mu, nu, tok, tgt, t):
        loss, g = loss_and_grads(p, tok, tgt, m, block_rows(tok.shape[1]),
                                 mode, fault, constrain)
        p, mu, nu = adamw_update(p, mu, nu, g, t, opt)
        return p, mu, nu, loss, leaf_norms(g)

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=shardings)
    p = init(key)
    mu, nu = zeros(p), zeros(p)
    losses, grad1 = [], None
    for t, (tok, tgt) in enumerate(batches, start=1):
        p, mu, nu, loss, gn = step(p, mu, nu, tok, tgt,
                                   jnp.float32(t))
        losses.append(loss)
        if grad1 is None:
            grad1 = gn
    del mu, nu
    change = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(p, init(key))
    return jax.device_get({"loss": jnp.stack(losses), "grad1": grad1,
                           "change": change})
