"""The latent-attention expert model (models/mla_moe.py) and its expert
layer (models/moe.py) against the plain f32 reference of the block
(perfbench/references/moonlight_mla_moe.py), at a tiny size on the CPU:
the MLA block, the router, the share of the experts each chip holds, a
whole train step over dp 4, and the faults the benchmark's comparison
must catch."""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import zhpe_ompi_tpu as zmpi
from zhpe_ompi_tpu.models import mla_moe, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "perfbench") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "perfbench"))

import harness  # noqa: E402

REF = harness.load_module(ROOT, "references", "moonlight_mla_moe")
CELL = "moonlight-train-ep4-s4096"

# the configuration file's keys at a tiny size: 4 chips of 2 experts each
# hold 8 of the router's 16
CFG = {
    "hidden_size": 64, "num_attention_heads": 4, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_shared_experts": 2, "n_routed_experts": 8,
    "n_routed_experts_published": 16, "experts_per_chip": 2,
    "num_experts_per_tok": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "vocab_size": 128, "rms_norm_eps": 1e-5,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "capacity_factor": 2.0, "initializer_range": 0.1,
}
OPT = {"lr": 1e-2, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
       "weight_decay": 0.1}
B, S = 8, 32


def _config(**kw) -> mla_moe.Config:
    c = CFG
    return mla_moe.Config(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], qk_nope=c["qk_nope_head_dim"],
        qk_rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
        kv_rank=c["kv_lora_rank"], dense_ff=c["intermediate_size"],
        expert_ff=c["moe_intermediate_size"],
        n_experts=c["n_routed_experts_published"],
        top_k=c["num_experts_per_tok"], n_shared=c["n_shared_experts"],
        routed_scale=c["routed_scaling_factor"],
        n_layers=c["num_hidden_layers"], n_dense=c["first_k_dense_replace"],
        rope_theta=c["rope_theta"], eps=c["rms_norm_eps"],
        capacity_factor=c["capacity_factor"], dtype=jnp.float32,
        **kw)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


def _tokens(seed):
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.integers(0, CFG["vocab_size"], (B, S)), jnp.int32),
            jnp.asarray(r.integers(0, CFG["vocab_size"], (B, S)), jnp.int32))


def test_layout_is_the_references():
    cfg = _config()
    ours = jax.eval_shape(lambda: mla_moe.init_params(
        cfg, jax.random.PRNGKey(0), CFG["n_routed_experts"]))
    ref = jax.eval_shape(lambda: REF.init_params(CFG, jax.random.PRNGKey(0)))
    assert {k: v.shape for k, v in ours.items()} == \
        {k: v.shape for k, v in ref.items()}
    assert set(mla_moe.param_specs("dp")) == set(ref)


@pytest.mark.parametrize("flash", [False, True], ids=["jnp", "kernel"])
def test_mla_block_forward_and_grads(flash):
    """One MLA block (v narrower than q and k, rotary key shared by the
    heads) against the reference's, values and gradients."""
    cfg = _config(flash=flash)
    m = REF.dims(CFG)
    p = REF.init_params(CFG, jax.random.PRNGKey(1))
    lp = {k: p[k][1] for k in REF._ATTN}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, S, CFG["hidden_size"]))
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    cos, sin = mla_moe.rope_tables(S, cfg.qk_rope, cfg.rope_theta)

    def ours(x, lp):
        return jnp.sum(mla_moe._mla(x, lp, cfg, cos, sin) * w)

    def ref(x, lp):
        return jnp.sum(REF._mla(x, lp, m, "f32") * w)

    np.testing.assert_allclose(
        np.asarray(mla_moe._mla(x, lp, cfg, cos, sin)),
        np.asarray(REF._mla(x, lp, m, "f32")), rtol=1e-4, atol=1e-5)
    (gx, gp), (rx, rp) = (jax.grad(f, argnums=(0, 1))(x, lp)
                          for f in (ours, ref))
    assert _rel(gx, rx) < 1e-4
    for k in rp:
        assert _rel(gp[k], rp[k]) < 1e-4, k


@pytest.mark.parametrize("factor", [2.0, 0.3], ids=["cap2", "cap0.3"])
def test_router_picks_gates_and_drops(factor):
    """moe.route against the reference's routing: the same top-k on the
    sigmoid scores, normalised and scaled gates, picks of experts on
    chips not present left out, and the same assignments dropped past
    capacity (token order, then pick rank)."""
    m = dict(REF.dims(CFG), cap=factor)
    T, D = 64, CFG["hidden_size"]
    h = jax.random.normal(jax.random.PRNGKey(4), (T, D))
    router = jax.random.normal(jax.random.PRNGKey(5), (D, m["E"])) * 0.3
    expert, gate, keep = REF.route(h, router, m, 0)
    cap = moe.capacity(T, m["k"], m["per_chip"], m["E"], factor)
    logits = jnp.dot(h, router, precision=jax.lax.Precision.HIGHEST)
    r = moe.route(logits, m["k"], m["chips"], m["per_chip"], cap,
                  score="sigmoid", norm=True,
                  scale=m["scale"])
    np.testing.assert_array_equal(np.asarray(r.expert), np.asarray(expert))
    np.testing.assert_allclose(np.asarray(r.gate), np.asarray(gate),
                               rtol=1e-6)
    ours_keep = np.asarray(r.slot) < m["chips"] * cap
    np.testing.assert_array_equal(ours_keep, np.asarray(keep))
    present = np.asarray(expert) // m["per_chip"] < m["chips"]
    assert int(r.dropped) == int((present & ~np.asarray(keep)).sum())
    if factor < 1:
        assert int(r.dropped) > 0
    else:
        assert int(r.dropped) == 0
    # each destination's slots are distinct and within its capacity
    slots = np.asarray(r.slot)[ours_keep]
    assert len(set(slots.tolist())) == len(slots)


def test_shares_add_up_to_the_uncut_layer():
    """The 8 chips' shares of one expert layer: each chip's routed part
    (the same function on one rank, holding its 2 experts, router width
    and top-k as published), summed, plus the shared experts counted
    once, give the uncut reference layer with all 16 experts."""
    full = dict(CFG, n_routed_experts=16, capacity_factor=1e3)
    m = REF.dims(full)
    assert m["chips"] == 8
    T, D = 64, CFG["hidden_size"]
    p = REF.init_params(full, jax.random.PRNGKey(6))
    lp = {k: p[k][0] for k in ("router", "e_gate", "e_up", "e_down",
                               "s_gate", "s_up", "s_down")}
    lp["router"] = lp["router"] * 3.0
    h = jax.random.normal(jax.random.PRNGKey(7), (T, D))
    uncut = REF.layer_reference(h, lp["router"], lp["e_gate"], lp["e_up"],
                                lp["e_down"], lp["s_gate"], lp["s_up"],
                                lp["s_down"], m)
    logits = jnp.dot(h, lp["router"], precision=jax.lax.Precision.HIGHEST)
    total = jnp.zeros((T, D))
    per = m["per_chip"]
    for c in range(m["chips"]):
        mine = np.arange(c * per, (c + 1) * per)
        # this chip's experts first: the layer's one rank holds them
        perm = np.concatenate([mine, np.setdiff1d(np.arange(m["E"]), mine)])
        y, st = moe.expert_layer(
            h, logits[:, perm], m["k"],
            lambda rows, eid: moe.grouped_swiglu(
                rows, eid, lp["e_gate"][mine], lp["e_up"][mine],
                lp["e_down"][mine]),
            None, held=per, capacity_factor=1e3, score="sigmoid",
            norm=True, scale=m["scale"])
        assert int(st["dropped"]) == 0
        total = total + y
    shared = REF._swiglu(h, lp["s_gate"], lp["s_up"], lp["s_down"], "f32",
                         "td,df->tf", "tf,fd->td")
    assert _rel(total + shared, uncut) < 1e-5


@pytest.mark.parametrize("routing", ["spread", "one_expert", "none"])
def test_grouped_swiglu_covers_the_whole_buffer(routing, monkeypatch):
    """The grouped matmuls take every row of the buffer, however the rows
    fall to the held experts, so a step's work does not follow the
    routing; the received rows match each expert's own SwiGLU and the
    empty rows (zeros) come out zero, in values and gradients."""
    held, M, D, F = 4, 48, 16, 8
    r = np.random.default_rng(3)
    eid = {"spread": r.integers(0, held + 1, M),
           "one_expert": np.where(r.random(M) < 0.5, 2, held),
           "none": np.full(M, held)}[routing]
    eid = jnp.asarray(eid, jnp.int32)
    full = eid < held
    rows = jnp.where(full[:, None],
                     jnp.asarray(r.normal(size=(M, D)), jnp.float32), 0)
    w = [jnp.asarray(r.normal(size=s), jnp.float32)
         for s in ((held, D, F), (held, D, F), (held, F, D))]
    seen = []
    real = jax.lax.ragged_dot

    def spy(a, b, sizes, **kw):
        seen.append((a.shape[0], sizes))
        return real(a, b, sizes, **kw)

    monkeypatch.setattr(moe.lax, "ragged_dot", spy)

    def f(rows, wg, wu, wd):
        return moe.grouped_swiglu(rows, eid, wg, wu, wd)

    def want(rows, wg, wu, wd):
        e = jnp.minimum(eid, held - 1)
        h = jax.nn.silu(jnp.einsum("md,mdf->mf", rows, wg[e])) \
            * jnp.einsum("md,mdf->mf", rows, wu[e])
        return jnp.where(full[:, None],
                         jnp.einsum("mf,mfd->md", h, wd[e]), 0)

    got = f(rows, *w)
    assert len(seen) == 3
    for n, sizes in seen:
        assert n == M and int(jnp.sum(sizes)) == M
    assert _rel(got, want(rows, *w)) < 1e-5
    assert not np.any(np.asarray(got)[~np.asarray(full)])
    ct = jnp.asarray(r.normal(size=(M, D)), jnp.float32)
    g = jax.grad(lambda *a: jnp.sum(f(*a) * ct), argnums=(0, 1, 2, 3))(
        rows, *w)
    gw = jax.grad(lambda *a: jnp.sum(want(*a) * ct), argnums=(0, 1, 2, 3))(
        rows, *w)
    for a, b in zip(g[1:], gw[1:]):
        assert _rel(a, b) < 1e-5 or float(jnp.max(jnp.abs(b))) == 0
    assert _rel(g[0] * full[:, None], gw[0]) < 1e-5


def _mesh(n=4):
    return Mesh(np.asarray(jax.devices()[:n]), ("dp",))


def test_train_step_dp4_matches_reference():
    """A whole step through make_train_step_optax over dp 4, experts
    sharded over dp and exchanged by the communicator's all-to-all,
    against the reference's loss and gradients; the step's counters
    read back through MPI_T."""
    from zhpe_ompi_tpu.tools import mpit

    mesh = _mesh()
    cfg = _config(flash=False, remat=True, ce_chunk=32)
    comm = zmpi.Communicator(mesh, "dp", name="moe_test_dp")
    init_opt, step, specs = mla_moe.make_train_step(
        cfg, mesh, comm, optimizer=optax.sgd(1.0))
    assert specs["e_gate"] == P(None, "dp", None, None)
    shard = {k: NamedSharding(mesh, s) for k, s in specs.items()}
    init = jax.jit(lambda k: REF.init_params(CFG, k), out_shardings=shard)
    key = jax.random.PRNGKey(8)
    tok, tgt = _tokens(9)
    data = NamedSharding(mesh, P("dp"))
    params = init(key)
    new, _, loss = step(params, init_opt(params), jax.device_put(tok, data),
                        jax.device_put(tgt, data))
    grads = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                         jax.device_get(init(key)), jax.device_get(new))
    rloss, rgrads = jax.jit(lambda p: REF.loss_and_grads(
        p, tok, tgt, REF.dims(CFG)))(REF.init_params(CFG, key))
    assert abs(float(loss) - float(rloss)) < 1e-5 * abs(float(rloss))
    for k in rgrads:
        assert _rel(grads[k], rgrads[k]) < 2e-4, k
    pv = mpit.registered_pvars()
    assert pv["moe_dropped_assignments"].reader() >= 0
    # 4 chips x 16 tokens x 4 picks, 2 of the 16 experts on each chip
    rows = pv["moe_max_expert_rows"].reader()
    assert 0 < rows <= 4 * 2 * S * CFG["num_experts_per_tok"]
    # every held expert's rows, in each of the expert layers
    routed = pv["moe_routed_rows"].reader()
    layers = CFG["num_hidden_layers"] - CFG["first_k_dense_replace"]
    assert rows <= routed <= layers * 4 * 2 * S * CFG["num_experts_per_tok"]


def test_optimizer_state_lies_with_its_expert_shard():
    """AdamW's moments of the dp-sharded experts are placed as the
    experts are (XLA alone would leave the zeros whole on one chip)."""
    mesh = _mesh()
    comm = zmpi.Communicator(mesh, "dp", name="moe_test_opt")
    init_opt, _, specs = mla_moe.make_train_step(
        _config(flash=False), mesh, comm, optimizer=optax.adamw(1e-3))
    shard = {k: NamedSharding(mesh, s) for k, s in specs.items()}
    params = jax.jit(lambda k: REF.init_params(CFG, k),
                     out_shardings=shard)(jax.random.PRNGKey(12))
    mu = init_opt(params)[0].mu
    for k in params:
        assert mu[k].sharding.is_equivalent_to(shard[k], mu[k].ndim), k
    per_chip = mu["e_gate"].addressable_shards[0].data.shape
    assert per_chip[1] == CFG["experts_per_chip"]


def _limits():
    with open(os.path.join(ROOT, "perfbench", "workloads",
                           CELL + ".json")) as f:
        return json.load(f)["limits"]


@pytest.mark.parametrize("mode,fault", [
    ("fp8", None), ("f32", "unchanged"), ("f32", "half_batch"),
    ("f32", "a2a_local")])
def test_faults_exceed_the_cells_limits(mode, fault):
    """The control (fp8) and every planted fault take a compared number
    past the cell's limits; the reference against itself stays inside."""
    lm = harness.load_module(ROOT, "drivers", "lm_train")
    key = jax.random.PRNGKey(10)
    batches = [_tokens(11 + i) for i in range(3)]
    ref = REF.train_readings(CFG, OPT, batches, key)
    ok, checks = harness.decide(lm.compare(ref, ref), _limits())
    assert ok, checks
    got = REF.train_readings(CFG, OPT, batches, key, mode=mode, fault=fault)
    ok, checks = harness.decide(lm.compare(got, ref), _limits())
    assert not ok, checks
