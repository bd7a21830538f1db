"""Roofline share of ``calib_iter_fused``: the bytes one iteration needs
(levels, offsets and bias at 4 bytes a column, operand bits at one bit;
``bench/work.calib_iteration``) over the HBM peak, times the iterations
the trace saw, over the kernel's device time."""
from bench import trace, work

KERNEL = r"_calib_iter_kernel|calib_iter"


def read(ctx):
    tr, peaks, it = ctx.get("trace"), ctx.get("peaks"), ctx.get("calib_iteration")
    if tr is None or not peaks or it is None:
        return None
    busy = trace.op_seconds(tr, KERNEL)
    n = trace.op_count(tr, KERNEL)
    if busy <= 0 or n <= 0:
        return None
    least = n * work.least_seconds(it, peaks["int8_ops_s"], peaks["hbm_bytes_s"])
    return 100.0 * least / busy
