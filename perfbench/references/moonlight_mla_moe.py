"""Plain float32 reference of the Moonlight-16B-A3B configuration: the
DeepSeek-V3 block (multi-head latent attention, a dense first layer,
sigmoid-routed experts beside shared experts, RMSNorm, an untied head),
cut as the configuration file states, trained with AdamW.

It imports nothing of the program under test.  It shares with the program
only the layout of the parameter dict, so that leaves can be compared one
by one (L layers, Ld dense, Lm = L - Ld expert layers, X experts held):

    embed, head (V, D)      final_norm (D,)
    attn_norm, mlp_norm (L, D)   wq (L, D, H*(nope+rope))
    wkv_a (L, D, r+rope)    kv_norm (L, r)   wkv_b (L, r, H*(nope+dv))
    wo (L, H*dv, D)         dense_gate, dense_up (Ld, D, F)
    dense_down (Ld, F, D)   router (Lm, D, E)
    e_gate, e_up (Lm, X, D, Fe)  e_down (Lm, X, Fe, D)
    s_gate, s_up (Lm, D, Fs)     s_down (Lm, Fs, D)

The cut, as in the program: the router keeps all E outputs and top-k;
expert ``e`` lives on chip ``e // experts_per_chip`` of the deployment, and
only the first ``X / experts_per_chip`` chips are here, so a pick of an
expert past them adds nothing.  The batch is routed in the blocks of rows
each chip of this host holds (``global_batch / chips`` rows), and a block
may send each chip at most ``capacity_factor`` times its even share of
assignments, the rest dropped in token order and then pick rank.

Every matmul runs at ``Precision.HIGHEST`` in float32.  ``mode="fp8"`` is
the control: every matmul operand is rounded to float8 e4m3 with a
per-tensor scale, and the gradient flowing back into each matmul output to
float8 e5m2.  ``fault`` plants one of the faults a training step can have,
for the calibration readings: ``unchanged`` (a step that leaves the state
as it was), ``half_batch`` (half the rows left out) and ``a2a_local``
(the exchange left out: each block's tokens reach only the experts of its
own chip).  The mode and the faults reach the step as data, so one
compiled step serves the reference, the control and every fault.

So that it fits, the reference is computed in blocks; each is a departure
from the textbook layout, not from its arithmetic:

- rows: one chip's block of rows at a time, their losses and gradients
  summed (the routing is per block anyway);
- attention: query blocks of ``Q_BLOCK`` positions (``lax.map``), each
  against every key under the causal mask, under ``jax.checkpoint``;
- experts: the block's kept assignments sorted by expert, each expert's
  run of rows padded to whole blocks of ``EXPERT_ROWS`` rows, and a scan
  over those blocks, each computing one expert over its rows;
- the head and its logsumexp: chunks of ``LOSS_CHUNK`` positions
  (``lax.map``), under ``jax.checkpoint``.

``flops_per_step`` gives the model operations a training step requires,
which the ``mfu`` metric reads; ``dims`` the sizes other readers need.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
Q_BLOCK = 512       # query positions of one block of attention
LOSS_CHUNK = 1024   # positions of one chunk of the head and its loss
EXPERT_ROWS = 512   # rows of one block of an expert's sorted rows
FAULTS = (None, "unchanged", "half_batch", "a2a_local")
_NEG = -1e30


def dims(cfg: dict) -> dict:
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    L, Ld = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    X, per = cfg["n_routed_experts"], cfg["experts_per_chip"]
    return {
        "V": cfg["vocab_size"], "D": cfg["hidden_size"],
        "H": cfg["num_attention_heads"],
        # the flash kernels' work and bytes at q/k width 192 and v width
        # 128 are, term for term, those of one width of (192 + 128) / 2
        "hd": (nope + rope + dv) // 2,
        "L": L, "Ld": Ld, "Lm": L - Ld, "nope": nope, "rope": rope,
        "dv": dv, "r": cfg["kv_lora_rank"], "F": cfg["intermediate_size"],
        "Fe": cfg["moe_intermediate_size"],
        "Fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "E": cfg["n_routed_experts_published"], "X": X, "per_chip": per,
        "chips": X // per, "k": cfg["num_experts_per_tok"],
        "scale": cfg["routed_scaling_factor"],
        "cap": cfg["capacity_factor"], "eps": cfg["rms_norm_eps"],
        "theta": cfg["rope_theta"],
    }


def matmul_params_per_token(m: dict) -> float:
    """Matmul parameters one token uses: attention in every layer, the
    dense SwiGLU, each expert layer's shared experts, router and the
    expected computed picks (k of E chosen, X of E held here), the head."""
    H, D = m["H"], m["D"]
    mla = D * H * (m["nope"] + m["rope"]) + D * (m["r"] + m["rope"]) \
        + m["r"] * H * (m["nope"] + m["dv"]) + H * m["dv"] * D
    expert = 3 * D * m["Fe"]
    moe = 3 * D * m["Fs"] + D * m["E"] + m["k"] * m["X"] / m["E"] * expert
    return m["L"] * mla + m["Ld"] * 3 * D * m["F"] + m["Lm"] * moe \
        + m["V"] * D


def flops_per_step(cfg: dict, traffic: dict) -> float:
    """Required model operations of one training step: 6 x the matmul
    parameters a token uses x tokens, plus causal attention, 3 x B x S^2
    x H x (q/k width + v width) per layer (forward QK^T and PV, backward
    dP, dV, dQ and dK, halved for the mask).  Recomputation under remat
    and the chunked loss's second pass over the logits do not count."""
    m = dims(cfg)
    B, S = traffic["global_batch"], traffic["seq"]
    attn = 3.0 * B * S * S * m["H"] * (m["nope"] + m["rope"] + m["dv"]) \
        * m["L"]
    return 6.0 * matmul_params_per_token(m) * B * S + attn


def init_params(cfg: dict, key) -> dict:
    """DeepSeek-V3's init (every linear and the embedding N(0, 0.02),
    norm gains 1), the router's too, in float32."""
    m = dims(cfg)
    D, H, V, L, Ld, Lm = m["D"], m["H"], m["V"], m["L"], m["Ld"], m["Lm"]
    X, Fe, Fs = m["X"], m["Fe"], m["Fs"]
    std = cfg["initializer_range"]
    shapes = {
        "embed": (V, D), "head": (V, D),
        "wq": (L, D, H * (m["nope"] + m["rope"])),
        "wkv_a": (L, D, m["r"] + m["rope"]),
        "wkv_b": (L, m["r"], H * (m["nope"] + m["dv"])),
        "wo": (L, H * m["dv"], D),
        "dense_gate": (Ld, D, m["F"]), "dense_up": (Ld, D, m["F"]),
        "dense_down": (Ld, m["F"], D),
        "router": (Lm, D, m["E"]),
        "e_gate": (Lm, X, D, Fe), "e_up": (Lm, X, D, Fe),
        "e_down": (Lm, X, Fe, D),
        "s_gate": (Lm, D, Fs), "s_up": (Lm, D, Fs), "s_down": (Lm, Fs, D),
    }
    keys = jax.random.split(key, len(shapes))
    p = {k: jax.random.normal(kk, s, jnp.float32) * std
         for kk, (k, s) in zip(keys, shapes.items())}
    p.update(attn_norm=jnp.ones((L, D), jnp.float32),
             kv_norm=jnp.ones((L, m["r"]), jnp.float32),
             mlp_norm=jnp.ones((L, D), jnp.float32),
             final_norm=jnp.ones((D,), jnp.float32))
    return p


# ------------------------------------------------------------- fp8 control


def _qdq(x, dtype):
    """Round ``x`` to ``dtype`` with a per-tensor scale that maps its
    largest magnitude to the format's largest finite value."""
    top = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / top, 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _q_operand(x, on):
    return jnp.where(on, _qdq(x, jnp.float8_e4m3fn), x)


_q_operand.defvjp(lambda x, on: (_q_operand(x, on), None),
                  lambda _, ct: (ct, None))


@jax.custom_vjp
def _q_cotangent(y, on):
    return y


_q_cotangent.defvjp(lambda y, on: (y, on),
                    lambda on, ct: (jnp.where(on, _qdq(ct, jnp.float8_e5m2),
                                              ct), None))


def _mm(eq, a, b, mode):
    """``mode`` is "f32", "fp8", or a traced flag, true for fp8, so that
    one compiled step serves the reference and the control."""
    on = mode == "fp8" if isinstance(mode, str) else mode
    if on is not False:
        a, b = _q_operand(a, on), _q_operand(b, on)
    out = jnp.einsum(eq, a, b, precision=HIGHEST,
                     preferred_element_type=jnp.float32)
    return out if on is False else _q_cotangent(out, on)


# ------------------------------------------------------------------ model


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """DeepSeek's rotary embedding of (B, S, h, d) at positions 0..S-1:
    the projection's interleaved pairs (x[2i], x[2i+1]) are regrouped as
    halves (evens, then odds), and the halves rotated against each other
    by position x theta^(-2i/d)."""
    B, S, h, d = x.shape
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(np.concatenate([ang, ang], -1)), jnp.float32)
    sin = jnp.asarray(np.sin(np.concatenate([ang, ang], -1)), jnp.float32)
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos[:, None] + rot * sin[:, None]


def _attend(q, k, v, q0, mode):
    """Causal attention of the queries at positions ``q0`` onwards over
    the keys ``k``, ``v`` from position 0, at q's width^-0.5; heads
    first, (H, B, S, d)."""
    s = _mm("hbqd,hbkd->hbqk", q, k, mode) * (q.shape[-1] ** -0.5)
    row = q0 + lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
    col = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
    s = jnp.where(col <= row, s, _NEG)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return _mm("hbqk,hbkd->hbqd", p, v, mode)


def _blocks(x, axis, n):
    """``x`` split along ``axis`` into ``n`` equal blocks, stacked first."""
    shape = x.shape[:axis] + (n, x.shape[axis] // n) + x.shape[axis + 1:]
    return jnp.moveaxis(x.reshape(shape), axis, 0)


def _unblocks(x, axis):
    x = jnp.moveaxis(x, 0, axis)
    return x.reshape(x.shape[:axis] + (-1,) + x.shape[axis + 2:])


def attention(q, k, v, mode):
    """Over query blocks of ``Q_BLOCK`` positions, heads first, each
    block against every key under the causal mask."""
    S = q.shape[2]
    if S <= Q_BLOCK:
        return _attend(q, k, v, 0, mode)
    n = S // Q_BLOCK

    @jax.checkpoint
    def one(b):
        qb, i = b
        return _attend(qb, k, v, i * Q_BLOCK, mode)

    return _unblocks(lax.map(one, (_blocks(q, 2, n), jnp.arange(n))), 2)


def _mla(x, lp, m, mode, constrain=None):
    B, S, _ = x.shape
    H, nope, rope, dv, r = m["H"], m["nope"], m["rope"], m["dv"], m["r"]
    h = _rms(x, lp["attn_norm"], m["eps"])
    q = _mm("bsd,de->bse", h, lp["wq"], mode).reshape(B, S, H, nope + rope)
    kv_a = _mm("bsd,de->bse", h, lp["wkv_a"], mode)
    c = _rms(kv_a[..., :r], lp["kv_norm"], m["eps"])
    kv = _mm("bsr,re->bse", c, lp["wkv_b"], mode).reshape(B, S, H, nope + dv)
    k_pe = _rope(kv_a[:, :, None, r:], m["theta"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], m["theta"])],
                        -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (B, S, H, rope))], -1)
    # heads first, so that attention splits over the chips by head
    q, k, v = (a.transpose(2, 0, 1, 3) for a in (q, k, kv[..., nope:]))
    if constrain is not None:
        q, k, v = constrain(q), constrain(k), constrain(v)
    o = attention(q, k, v, mode).transpose(1, 2, 0, 3)
    return _mm("bse,ed->bsd", o.reshape(B, S, H * dv), lp["wo"], mode)


def _swiglu(h, wg, wu, wd, mode, eq="bsd,df->bsf", eq2="bsf,fd->bsd"):
    g = _mm(eq, h, wg, mode)
    return _mm(eq2, jax.nn.silu(g) * _mm(eq, h, wu, mode), wd, mode)


def route(h, router, m, src, mode="f32", local=False):
    """This block's picks: ``(expert (T, k), gate (T, k), keep (T, k))``.
    Sigmoid scores; top-k on score + the correction bias (held at zero);
    gates normalised over the k and scaled.  An assignment is kept when its
    expert's chip is here (with ``local``, the a2a_local fault: is chip
    ``src``) and the
    chip has not yet taken ``cap`` of this block's assignments, counted
    in token order and then pick rank."""
    T = h.shape[0]
    k = m["k"]
    s = jax.nn.sigmoid(_mm("td,de->te", h, router, mode))
    bias = jnp.zeros((m["E"],), jnp.float32)
    _, expert = lax.top_k(s + bias, k)
    gate = jnp.take_along_axis(s, expert, -1)
    gate = gate / (gate.sum(-1, keepdims=True) + 1e-20) * m["scale"]
    chip = expert // m["per_chip"]
    here = jnp.where(local, chip == src, chip < m["chips"])
    cap = max(1, int(m["cap"] * T * k * m["per_chip"] / m["E"]))
    flat = jnp.where(here, chip, m["chips"]).reshape(-1)
    taken = jnp.zeros((T * k,), jnp.int32)
    for c in range(m["chips"]):
        mine = flat == c
        taken = jnp.where(mine, jnp.cumsum(mine.astype(jnp.int32)), taken)
    keep = here & (taken.reshape(T, k) <= cap)
    return expert, gate, keep


def experts(h, expert, gate, keep, eg, eu, ed, mode="f32"):
    """Sum over each token's kept picks of gate x expert(h): the kept
    assignments sorted by expert, each expert's rows padded to whole
    blocks of ``EXPERT_ROWS``, a scan over the blocks."""
    T, D = h.shape
    X, R = eg.shape[0], EXPERT_ROWS
    n = expert.size
    e = jnp.where(keep, expert, X).reshape(-1)
    order = jnp.argsort(e, stable=True)
    e_s = e[order]
    tok_s = (jnp.arange(n) // expert.shape[1])[order]
    gate_s = gate.reshape(-1)[order]
    counts = jnp.sum(e[:, None] == jnp.arange(X)[None], 0)
    padded = (counts + R - 1) // R * R
    ends = jnp.cumsum(padded)
    first = jnp.cumsum(counts) - counts   # start of each run, sorted
    ec = jnp.minimum(e_s, X - 1)
    row = jnp.where(e_s < X, ends[ec] - padded[ec] + jnp.arange(n)
                    - first[ec], -1)
    nb = -(-(n + X * (R - 1)) // R)
    rows = nb * R
    row = jnp.where(row < 0, rows, row)
    row_tok = jnp.full((rows,), T).at[row].set(tok_s, mode="drop")
    row_gate = jnp.zeros((rows,), jnp.float32).at[row].set(gate_s,
                                                           mode="drop")
    blk_e = jnp.minimum(
        jnp.sum(jnp.arange(nb)[:, None] * R >= ends[None, :], 1), X - 1)
    hp = jnp.concatenate([h, jnp.zeros((1, D), h.dtype)])

    @jax.checkpoint
    def block(_, b):
        tok, ex, g = b
        y = _swiglu(hp[tok], eg[ex], eu[ex], ed[ex], mode, "td,df->tf",
                    "tf,fd->td")
        return None, y * g[:, None]

    _, ys = lax.scan(block, None, (row_tok.reshape(nb, R), blk_e,
                                   row_gate.reshape(nb, R)))
    out = jnp.zeros((T + 1, D), jnp.float32).at[row_tok].add(
        ys.reshape(rows, D))
    return out[:T]


def _moe(x, lp, m, src, mode, local, constrain):
    B, S, D = x.shape
    h = _rms(x, lp["mlp_norm"], m["eps"]).reshape(B * S, D)
    if constrain is not None:
        h = constrain(h)
    expert, gate, keep = route(h, lp["router"], m, src, mode, local)
    y = experts(h, expert, gate, keep, lp["e_gate"], lp["e_up"],
                lp["e_down"], mode)
    y = y + _swiglu(h, lp["s_gate"], lp["s_up"], lp["s_down"], mode,
                    "td,df->tf", "tf,fd->td")
    return x + y.reshape(B, S, D)


def _loss_chunk(x, targets, head, mode):
    logits = _mm("bsd,vd->bsv", x, head, mode)
    mx = logits.max(-1, keepdims=True)
    lse = jnp.log(jnp.exp(logits - mx).sum(-1)) + mx[..., 0]
    tl = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.sum(lse - tl)


def loss_sum(x, targets, head, mode):
    """Over chunks of ``LOSS_CHUNK`` positions."""
    S = x.shape[1]
    if S <= LOSS_CHUNK:
        return _loss_chunk(x, targets, head, mode)
    n = S // LOSS_CHUNK
    f = jax.checkpoint(lambda b: _loss_chunk(b[0], b[1], head, mode))
    return jnp.sum(lax.map(f, (_blocks(x, 1, n), _blocks(targets, 1, n))))


_ATTN = ("attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo", "mlp_norm")


def token_loss_sum(params, tokens, targets, m, src, mode="f32", local=False,
                   constrain=None):
    """Sum over one chip's block of rows of -log p(target), layer by layer
    (``scan``, each layer rematerialised)."""
    Ld = m["Ld"]
    x = params["embed"][tokens]

    def dense(x, lp):
        x = x + _mla(x, lp, m, mode, constrain)
        h = _rms(x, lp["mlp_norm"], m["eps"])
        return x + _swiglu(h, lp["dense_gate"], lp["dense_up"],
                           lp["dense_down"], mode), None

    def sparse(x, lp):
        x = x + _mla(x, lp, m, mode, constrain)
        return _moe(x, lp, m, src, mode, local, constrain), None

    dl = {k: params[k][:Ld] for k in _ATTN}
    dl.update({k: params[k] for k in ("dense_gate", "dense_up",
                                      "dense_down")})
    sl = {k: params[k][Ld:] for k in _ATTN}
    sl.update({k: params[k] for k in ("router", "e_gate", "e_up", "e_down",
                                      "s_gate", "s_up", "s_down")})
    x, _ = lax.scan(jax.checkpoint(dense), x, dl)
    x, _ = lax.scan(jax.checkpoint(sparse), x, sl)
    x = _rms(x, params["final_norm"], m["eps"])
    return loss_sum(x, targets, params["head"], mode)


def loss_and_grads(params, tokens, targets, m, mode="f32", local=False,
                   half=False, constrain=None):
    """Mean token loss over the whole batch and its gradient, one chip's
    block of rows at a time, summed.  ``half`` (the half_batch fault)
    leaves out the second half of the blocks, the mean taken over the
    rest; ``local`` is the a2a_local fault (:func:`route`)."""
    rows, S = tokens.shape
    per = max(1, rows // m["chips"])
    nb = rows // per
    weight = jnp.where(half & (jnp.arange(nb) >= nb // 2), 0.0, 1.0)
    n = rows * S * jnp.where(half, 0.5, 1.0)
    tb = tokens.reshape(nb, per, S)
    gb = targets.reshape(nb, per, S)
    vg = jax.value_and_grad(
        lambda p, t, y, c, w: token_loss_sum(p, t, y, m, c, mode, local,
                                             constrain) * w / n)

    def one(acc, blk):
        lsum, gacc = acc
        l, g = vg(params, *blk)
        return (lsum + l, jax.tree.map(jnp.add, gacc, g)), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = lax.scan(one, zero,
                                (tb, gb, jnp.arange(nb), weight))
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
    ``shardings`` (a dict of leaf shardings, or None) places the state;
    ``constrain`` (or None) splits an array over the chips along its
    first axis: a block's (tokens, D) activations, attention's heads."""
    m = dims(cfg)
    if fault not in FAULTS or mode not in ("f32", "fp8"):
        raise ValueError(f"unknown fault {fault!r} or mode {mode!r}")
    still = fault == "unchanged"
    knobs = {"fp8": jnp.asarray(mode == "fp8"), "lr": jnp.float32(0.0 if still else opt["lr"]),
             "weight_decay": jnp.float32(0.0 if still
                                         else opt["weight_decay"]),
             "local": jnp.asarray(fault == "a2a_local"),
             "half": jnp.asarray(fault == "half_batch")}
    init = jax.jit(functools.partial(init_params, cfg),
                   out_shardings=shardings)

    def step(p, mu, nu, tok, tgt, t, knobs):
        loss, g = loss_and_grads(p, tok, tgt, m, knobs["fp8"],
                                 knobs["local"], knobs["half"], constrain)
        p, mu, nu = adamw_update(p, mu, nu, g, t, dict(
            opt, lr=knobs["lr"], weight_decay=knobs["weight_decay"]))
        return p, mu, nu, loss, leaf_norms(g)

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=shardings)
    p = init(key)
    mu, nu = zeros(p), zeros(p)
    losses, grad1 = [], None
    for t, (tok, tgt) in enumerate(batches, start=1):
        p, mu, nu, loss, gn = step(p, mu, nu, tok, tgt, jnp.float32(t),
                                   knobs)
        losses.append(loss)
        if grad1 is None:
            grad1 = gn
    del mu, nu
    change = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(p, init(key))
    return jax.device_get({"loss": jnp.stack(losses), "grad1": grad1,
                           "change": change})


def layer_reference(h, router, eg, eu, ed, sg, su, sd, m, src=0,
                    mode="f32", local=False):
    """One expert layer's output on the normed tokens ``h`` (T, D) routed
    as one block: routed experts plus shared experts, without the
    residual.  For tests."""
    expert, gate, keep = route(h, router, m, src, mode, local)
    return experts(h, expert, gate, keep, eg, eu, ed, mode) + _swiglu(
        h, sg, su, sd, mode, "td,df->tf", "tf,fd->td")

