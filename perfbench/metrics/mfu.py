"""mfu: the whole step's share of the chips' bf16 peak, in %.

The required model operations per step are the configuration's reference
module's count (``flops_per_step``: the forward and backward the model
needs, recomputation not counted), times the steps over the traced
window."""

import harness


def read(ctx):
    red = ctx["trace"]
    if red["steps"] == 0 or red["window_s"] <= 0:
        return None
    cell = ctx["cell"]
    flops = harness.reference_module(cell).flops_per_step(cell.config,
                                                           cell.traffic)
    rate = flops * red["steps"] / red["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peak"]["bf16_flops"])
