"""The `moe_train` kind's whole run on the CPU at a tiny size, on four
virtual devices: the program against the reference under the committed
cell's limits, the routing counters after the window, and the counts of
the new metric readers."""

import json
import os
import shutil

import pytest

import harness
import run

from conftest import PB, REPO, _read, _write

CELL = "moonlight-train-ep4-s4096"
ARGS = ["--seed", "4294967311", "--seconds", "1", "--trace", "0"]


@pytest.fixture
def moe_root(tmp_path):
    """The committed benchmark plus a tiny copy of the Moonlight cell
    (4 chips of 2 experts, 8 of the router's 16) held to its limits."""
    root = tmp_path / "checkout"
    shutil.copytree(PB, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))
    pb = root / "perfbench"
    bench = _read(os.path.join(REPO, "BENCHMARK.json"))
    cfg = _read(pb / "configs" / "moonlight-16b-a3b.json")
    cfg.update(name="tiny-moe", hidden_size=64, num_attention_heads=4,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               kv_lora_rank=32, intermediate_size=96,
               moe_intermediate_size=32, n_routed_experts=8,
               n_routed_experts_published=16, experts_per_chip=2,
               num_experts_per_tok=4, num_hidden_layers=3, vocab_size=256)
    _write(pb / "configs" / "tiny-moe.json", cfg)
    tr = _read(pb / "traffic" / "zipf-s4096-b8-v20480.json")
    tr.update(name="zipf-s64-b8-v256", seq=64, token_ids=256)
    _write(pb / "traffic" / "zipf-s64-b8-v256.json", tr)
    wl = _read(pb / "workloads" / f"{CELL}.json")
    wl.update(name="tiny-moe-4")
    wl["program"]["ce_chunk"] = 64
    _write(pb / "workloads" / "tiny-moe-4.json", wl)
    bench["workloads"].append({"name": "tiny-moe-4", "config": "tiny-moe",
                               "traffic": "zipf-s64-b8-v256", "chips": 4,
                               "why": "test"})
    bench["configs"].append({"name": "tiny-moe", "source": "test",
                             "file": "perfbench/configs/tiny-moe.json",
                             "reduced": [], "why": "test"})
    _write(root / "BENCHMARK.json", bench)
    return str(root)


def test_program_matches_reference(moe_root, capsys):
    assert run.main(["--workload", "tiny-moe-4"] + ARGS, root=moe_root,
                    look_for_chip=False) == 0
    cap = capsys.readouterr()
    res = json.loads(cap.out.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"tokens_per_s", "peak_hbm_gib",
                                   "setup_s"}
    assert "moe_dropped_assignments" in cap.err
    assert "moe_max_expert_rows" in cap.err


def test_counts_of_the_cell():
    c = harness.load_cell(run.ROOT, CELL)
    ref = harness.reference_module(c)
    m = ref.dims(c.config)
    assert (m["H"], m["hd"], m["L"]) == (16, 160, 5)
    # 6 x 353.5M active matmul parameters a token, and causal attention
    # 3 S^2 H (192 + 128) per row and layer, over 32,768 tokens
    assert ref.matmul_params_per_token(m) == pytest.approx(353.5e6, rel=2e-4)
    got = ref.flops_per_step(c.config, c.traffic)
    want = 6 * ref.matmul_params_per_token(m) * 32768 \
        + 3 * 8 * 4096 ** 2 * 16 * 320 * 5
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(7.98e13, rel=2e-3)
    gmm = harness.load_module(run.ROOT, "metrics", "moe.gmm_roofline")
    flops, _ = gmm.required(m, 24576, 2)
    # 24,576 rows a chip (a balanced router's 4 x 8,192 tokens x 6 x
    # 8/64), three matmuls, forward and backward (x3), in 4 expert layers
    assert flops == 24576 * 3 * 2 * 2048 * 1408 * 3 * 4


def test_gmm_share_counts_the_rows_routed(monkeypatch):
    """moe.gmm_roofline counts the rows the program reports it routed,
    and reads nothing where the program reports none."""
    from zhpe_ompi_tpu.tools import mpit

    gmm = harness.load_module(run.ROOT, "metrics", "moe.gmm_roofline")
    c = harness.load_cell(run.ROOT, CELL)
    m = harness.reference_module(c).dims(c.config)
    ops = [["%ragged-dot-none.1 = bf16[8] custom-call(%x)", 0, 4e6],
           ["%fusion.2 = bf16[8] fusion(%y), kind=kLoop", 0, 9e6]]
    ctx = {"cell": c, "chips": 4, "peak": {"bf16_flops": 197e12,
                                           "hbm_bytes_per_s": 819e9},
           "trace": {"steps": 2, "devices": [{"ops": ops}] * 4}}

    class Pvar:
        def __init__(self, v):
            self.reader = lambda: v

    routed = 4 * 4 * 10000  # 10,000 rows a chip in each of 4 layers
    monkeypatch.setattr(mpit, "registered_pvars",
                        lambda: {"moe_routed_rows": Pvar(routed)})
    flops, nbytes = gmm.required(m, 10000, 2)
    # 4 ms of ragged-dot on each chip over 2 steps: 2 ms a step
    want = max(flops / 197e12, nbytes / 819e9) / 2e-3 * 100
    assert gmm.read(ctx) == pytest.approx(want, rel=1e-12)
    monkeypatch.setattr(mpit, "registered_pvars", lambda: {})
    assert gmm.read(ctx) is None


def test_exposed_exchange_joins_start_and_done():
    a2a = harness.load_module(run.ROOT, "metrics", "moe.a2a_exposed_ms")
    ops = [["%all-to-all-start.1 = (bf16[8]) all-to-all-start(%x)", 0, 10],
           ["%fusion.2 = bf16[8] fusion(%y), kind=kLoop", 20, 30],
           ["%all-to-all-done.1 = bf16[8] all-to-all-done("
            "(bf16[8]) %all-to-all-start.1)", 60, 10],
           ["%all-to-all.3 = bf16[8] all-to-all(%z)", 100, 5]]
    got, other = a2a.intervals(ops)
    assert sorted(got) == [(0, 70), (100, 105)]
    assert other == [(20, 50)]
    red = {"steps": 1, "devices": [{"ops": ops}]}
    # 0..70 less the fusion's 20..50, and the 5 of the plain all-to-all
    assert a2a.read({"trace": red}) == pytest.approx((40 + 5) / 1e6)
