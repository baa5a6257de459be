"""moe.gmm_roofline: the expert layers' grouped matmuls' share of their
roofline, in %: the least time the chip needs for the rows the held
experts received (forward and backward; remat's rerun of the forward not
counted), over the device time of the grouped-matmul operations, found by
their instruction name (``ragged-dot``, the compiler's kernel for
``lax.ragged_dot``, and its group metadata), the rerun included.  The
program's grouped matmuls cover each chip's whole dispatch buffer, so the
time of its empty rows counts as lost.

The rows are the program's own count, read in the process that ran the
steps: the ``moe_routed_rows`` performance variable (``tools/mpit``), the
rows the held experts received in the last traced step, summed over the
expert layers and the chips, taken for each traced step.  A program that
does not publish it gives no reading."""

import harness
import trace_reduce

PREFIX = "ragged-dot"
ROWS_PVAR = "moe_routed_rows"


def required(m: dict, rows: float, elem_bytes: int):
    """(flops, bytes) one chip's grouped matmuls need per step for
    ``rows`` rows received in each expert layer; ``m`` is the reference
    module's ``dims``.  Three matmuls (gate, up, down) forward, each twice
    backward (the rows' and the weights' gradients), 2 x D x Fe a row
    each.  Bytes: each of the nine reads its rows and the held experts'
    weights and writes its result once."""
    D, Fe, L = m["D"], m["Fe"], m["Lm"]
    flops = 3 * 3 * 2.0 * rows * D * Fe * L
    weights = m["per_chip"] * D * Fe
    one = (rows * D + weights + rows * Fe) * elem_bytes
    return flops, 9.0 * one * L


def routed_rows():
    """The program's last step's routed rows, or None where it publishes
    none."""
    try:
        from zhpe_ompi_tpu.tools import mpit
    except ImportError:
        return None
    pv = mpit.registered_pvars().get(ROWS_PVAR)
    return None if pv is None else pv.reader()


def read(ctx):
    red, cell = ctx["trace"], ctx["cell"]
    seconds = trace_reduce.op_seconds(
        red, lambda text: trace_reduce.instruction(text)[0].startswith(
            PREFIX))
    total = routed_rows()
    if seconds <= 0 or not total:
        return None
    m = harness.reference_module(cell).dims(cell.config)
    rows = total / ctx["chips"] / m["Lm"]
    flops, nbytes = required(m, rows, 2)
    pk = ctx["peak"]
    bound = max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * bound * ctx["chips"] * red["steps"] / seconds
