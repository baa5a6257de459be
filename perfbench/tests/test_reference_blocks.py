"""The GPT-2 reference computed in blocks of positions (query blocks of
attention, chunks of the loss) against the same reference in one block,
on the CPU at a small size, and a long-sequence cell whose reference runs
in several blocks, driven through the whole run."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import run
import test_run

CFG = {"n_embd": 64, "n_head": 2, "n_inner": 256, "n_layer": 2,
       "vocab_size": 512, "layer_norm_epsilon": 1e-5,
       "initializer_range": 0.02}


@pytest.fixture
def ref():
    return harness.load_module(run.ROOT, "references", "gpt2_nobias")


def _loss_and_grads(ref, S, block, monkeypatch):
    monkeypatch.setattr(ref, "Q_BLOCK", block)
    monkeypatch.setattr(ref, "LOSS_CHUNK", block)
    m = ref.dims(CFG)
    params = ref.init_params(CFG, jax.random.PRNGKey(3))
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, S + 1), 0,
                             CFG["vocab_size"])
    return jax.jit(jax.value_and_grad(
        lambda p: ref.token_loss_sum(p, ids[:, :-1], ids[:, 1:], m)))(params)


@pytest.mark.parametrize("S", [256, 200], ids=["whole_blocks",
                                               "short_last_block"])
def test_blocks_agree_with_one_block(ref, monkeypatch, S):
    """Four query blocks and loss chunks of 64 positions against one of
    S.  Blocking changes only the order of float32 sums (each block's
    softmax is over the same keys, each chunk's logsumexp over the same
    logits), so the two agree to float32 rounding: rtol 1e-5, on the loss
    and on every gradient leaf as a whole (its norm of the difference
    against its norm)."""
    blocked = _loss_and_grads(ref, S, 64, monkeypatch)
    whole = _loss_and_grads(ref, S, S, monkeypatch)
    np.testing.assert_allclose(blocked[0], whole[0], rtol=1e-5)
    for k, g in whole[1].items():
        diff = float(jnp.linalg.norm(blocked[1][k] - g))
        assert diff <= 1e-5 * float(jnp.linalg.norm(g)), k


def test_one_block_is_the_unblocked_computation(ref):
    """Up to Q_BLOCK (LOSS_CHUNK) positions the blocked calls trace to the
    one-block computation, op for op."""
    S = ref.Q_BLOCK
    q = jax.ShapeDtypeStruct((1, S, 2, 8), jnp.float32)
    assert str(jax.make_jaxpr(lambda a, b, c: ref.attention(
        a, b, c, "f32"))(q, q, q)) == str(jax.make_jaxpr(
            lambda a, b, c: ref._attend(a, b, c, 0, "f32"))(q, q, q))
    S = ref.LOSS_CHUNK
    x = jax.ShapeDtypeStruct((1, S, 8), jnp.float32)
    y = jax.ShapeDtypeStruct((1, S), jnp.int32)
    e = jax.ShapeDtypeStruct((16, 8), jnp.float32)
    assert str(jax.make_jaxpr(lambda a, b, c: ref.loss_sum(
        a, b, c, "f32"))(x, y, e)) == str(jax.make_jaxpr(
            lambda a, b, c: ref._loss_chunk(a, b, c, "f32"))(x, y, e))


def test_rows_a_block_are_a_token_budget(ref):
    assert ref.block_rows(1024) == 4
    assert ref.block_rows(8192) == 1
    assert ref.block_rows(128) == 32


# ------------------------------------------- a cell in several blocks


ARGS = ["--seed", "6442450967", "--seconds", "1", "--trace", "0"]


@pytest.fixture
def long_root(tiny_root, monkeypatch):
    """The tiny configuration at S 256 under the long-sequence cell's
    workload, its reference in query blocks and loss chunks of 64."""
    pb = os.path.join(tiny_root, "perfbench")
    with open(os.path.join(pb, "traffic", "zipf-s8192-b2.json")) as f:
        tr = json.load(f)
    tr.update(name="zipf-s256-b2", seq=256, token_ids=500)
    with open(os.path.join(pb, "traffic", "zipf-s256-b2.json"), "w") as f:
        json.dump(tr, f)
    with open(os.path.join(pb, "workloads", "gpt2m-train-s8192.json")) as f:
        wl = json.load(f)
    wl.update(name="tiny-long")
    wl["program"]["ce_chunk"] = 128
    with open(os.path.join(pb, "workloads", "tiny-long.json"), "w") as f:
        json.dump(wl, f)
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny-long", "config": "tiny",
                               "traffic": "zipf-s256-b2", "chips": 1,
                               "why": "test"})
    with open(os.path.join(tiny_root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    ref = harness.reference_module(harness.load_cell(tiny_root, "tiny-long"))
    monkeypatch.setattr(ref, "Q_BLOCK", 64)
    monkeypatch.setattr(ref, "LOSS_CHUNK", 64)
    return tiny_root


def test_long_cell_matches_its_blocked_reference(long_root, capsys):
    assert run.main(["--workload", "tiny-long"] + ARGS, root=long_root,
                    look_for_chip=False) == 0
    res = test_run._result(capsys)
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == {"loss_gap", "grad1_gap", "change_gap"}


@pytest.mark.parametrize("fault", [
    test_run._unchanged, test_run._half_batch, test_run._answer_altered],
    ids=["state_unchanged", "half_batch", "answer_altered"])
def test_long_cell_fault_is_not_correct(long_root, capsys, monkeypatch,
                                        fault):
    fault(test_run._driver(long_root), monkeypatch)
    assert run.main(["--workload", "tiny-long"] + ARGS, root=long_root,
                    look_for_chip=False) == 0
    res = test_run._result(capsys)
    assert res["correct"] is False, res["checks"]


def test_long_cell_control_is_not_correct(long_root):
    """The control: the blocked reference in fp8 in the program's place."""
    cell = harness.load_cell(long_root, "tiny-long")
    drv = test_run._driver(long_root)
    devs = jax.devices()[:1]
    job = drv.Job(cell, devs, 11)
    batches = job.check_batches()
    job.free()
    ref = drv.reference_readings(cell, devs, 11, batches)
    ctl = drv.reference_readings(cell, devs, 11, batches, mode="fp8")
    ok, checks = harness.decide(drv.compare(ctl, ref),
                                cell.workload["limits"])
    assert not ok, checks
