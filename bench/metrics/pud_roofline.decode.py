"""Roofline share of the bit-plane kernels inside the decode step.

The least time of one step's kernels is, per projection, the larger of its
logical bytes (bit-planes without the window pad, plus input and output)
over the HBM peak and its 2*B*K*N operations over the int8 peak
(``bench/work.py``); summed and times the decode steps the trace saw, over
the kernels' device time in those steps."""
from bench import trace, work

KERNELS = r"_gemv_placed_kernel|_gemv_kernel|bitplane"
DECODE = r"_step_fn"


def read(ctx):
    tr, peaks, w = ctx.get("trace"), ctx.get("peaks"), ctx.get("pud_decode_step")
    if tr is None or not peaks or w is None:
        return None
    busy = trace.op_seconds(tr, KERNELS, DECODE)
    steps = trace.module_count(tr, DECODE)
    if busy <= 0 or steps <= 0:
        return None
    least = steps * work.least_seconds(w, peaks["int8_ops_s"],
                                       peaks["hbm_bytes_s"])
    return 100.0 * least / busy
