"""flash_roofline: the flash-attention kernels' share of their roofline,
in %: the least time the chip needs for the work the causal forward and
backward require (recomputation not counted), over the device time of the
kernels' events (the forward that remat runs again included)."""

import harness
import trace_reduce

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def required(m: dict, traffic: dict, dp: int, tp: int, elem_bytes: int):
    """(flops, bytes) one chip's kernels need per step; ``m`` is the
    reference module's ``dims`` of the configuration (heads ``H``, head
    width ``hd``, layers ``L``)."""
    b = traffic["global_batch"] // dp
    h = m["H"] // tp
    hd = m["hd"]
    S, L = traffic["seq"], m["L"]
    # per (row, head): forward QK^T and PV, backward dP, dV, dQ, dK, each
    # 2 S^2 hd at full square, halved by the causal mask
    flops = 6.0 * S * S * hd * b * h * L
    tile = b * h * S * hd * elem_bytes
    rows = b * h * S * 4  # f32 per-row lse / delta
    fwd = 4 * tile + rows          # read q, k, v; write o, lse
    bwd = 7 * tile + 2 * rows      # read q, k, v, dO, lse, delta; write dq, dk, dv
    return flops, float(fwd + bwd) * L


def read(ctx):
    red, cell = ctx["trace"], ctx["cell"]
    seconds = trace_reduce.op_seconds(
        red, lambda text: trace_reduce.pallas_kernel(text) in KERNELS)
    if seconds <= 0:
        return None
    mesh = cell.workload["mesh"]
    m = harness.reference_module(cell).dims(cell.config)
    flops, nbytes = required(m, cell.traffic, mesh["dp"], mesh["tp"], 2)
    pk = ctx["peak"]
    bound = max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * bound * ctx["chips"] * red["steps"] / seconds
