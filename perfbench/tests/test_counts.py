"""The operation and byte counts of the metric readers, against hand
counts."""

import harness
import pytest

import run


def _metric(name):
    return harness.load_module(run.ROOT, "metrics", name)


def _cell(name):
    return harness.load_cell(run.ROOT, name)


def _flops(c):
    return harness.reference_module(c).flops_per_step(c.config, c.traffic)


def _dims(c):
    return harness.reference_module(c).dims(c.config)


def test_model_flops_gpt2_medium():
    c = _cell("gpt2m-train-s1024")
    # 24 layers x (4 x 1024^2 + 2 x 1024 x 4096) + 50,304 x 1024
    params = 24 * (4 * 1024 ** 2 + 2 * 1024 * 4096) + 50304 * 1024
    assert params == 353_501_184
    tokens = 16 * 1024
    # causal attention: half of 12 L B S^2 D
    attn = 12 * 24 * 16 * 1024 ** 2 * 1024 // 2
    got = _flops(c)
    assert got == 6 * params * tokens + attn
    assert got == pytest.approx(37.22e12, rel=1e-3)


def test_model_flops_gpt2_medium_s8192():
    c = _cell("gpt2m-train-s8192")
    # the same 353,501,184 matmul parameters and 16,384 tokens a step;
    # causal attention 6 L B S^2 D at S 8192, B 2
    got = _flops(c)
    assert got == 6 * 353_501_184 * 16_384 + 6 * 24 * 2 * 8192 ** 2 * 1024
    assert got == 54_541_789_691_904


def test_every_configuration_reference_states_its_counts():
    bench = harness.load_benchmark(run.ROOT)
    for w in bench["workloads"]:
        ref = harness.reference_module(_cell(w["name"]))
        assert callable(ref.flops_per_step), w["name"]
        assert callable(ref.dims), w["name"]


def test_flash_counts_causal_half():
    c = _cell("gpt2m-train-s1024")
    flops, nbytes = _metric("flash_roofline").required(
        _dims(c), c.traffic, 1, 1, 2)
    # per (row, head, layer): forward QK^T + PV = 4 S^2 hd, backward four
    # matmuls = 8 S^2 hd, all halved by the mask
    S, hd, b, h, L = 1024, 64, 16, 16, 24
    assert flops == (4 + 8) * S * S * hd / 2 * b * h * L
    tile = b * h * S * hd * 2
    rows = b * h * S * 4
    assert nbytes == ((4 + 7) * tile + 3 * rows) * L


def test_flash_counts_split_over_dp_tp():
    c = _cell("gpt2l-train-dp2tp2")
    m = _dims(c)
    one = _metric("flash_roofline").required(m, c.traffic, 1, 1, 2)
    split = _metric("flash_roofline").required(m, c.traffic, 2, 2, 2)
    assert split[0] == pytest.approx(one[0] / 4)
    assert split[1] == pytest.approx(one[1] / 4)


LN_FWD = ("%closed_call.75 = (bf16[8192,1280]{1,0:T(8,128)(2,1)S(1)}, "
          "f32[8192,1]{1,0:T(8,128)}, f32[8192,1]{1,0:T(8,128)}) "
          "custom-call(bf16[8192,1280]{1,0:T(8,128)(2,1)} %bitcast.375, "
          "f32[1,1280]{1,0:T(1,128)S(1)} %fusion.9), "
          "custom_call_target=\"tpu_custom_call\", operand_layout_"
          "constraints={bf16[8192,1280]{1,0}, f32[1,1280]{1,0}}")


def test_layernorm_bytes_from_the_call():
    ln = _metric("layernorm_roofline")
    # y, mean, rstd written; x, gain read; the layout constraints repeat
    # the operands and do not count
    assert ln.arrays(LN_FWD) == [(8192 * 1280 * 2, True), (8192 * 4, False),
                                 (8192 * 4, False), (8192 * 1280 * 2, False),
                                 (1280 * 4, True)]
    # its rows are written to on-chip memory: no HBM roofline bounds it
    assert not ln.streams_from_hbm(LN_FWD)
    assert ln.streams_from_hbm(LN_FWD.replace("(2,1)S(1)}, f32[8192",
                                              "(2,1)}, f32[8192"))


def test_peaks_table_and_unknown_device():
    pk = harness.peak(run.ROOT, "TPU v5 lite")
    assert pk["bf16_flops"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(RuntimeError, match="no published peak"):
        harness.peak(run.ROOT, "TPU v9 imaginary")
