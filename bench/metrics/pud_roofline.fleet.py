"""Roofline share of the sharded bit-plane kernels inside a fleet's decode
step.

Per projection, one shard computes its own columns (the packs'
``shard_widths``) at the projection's K: the least time of a step's
kernels on a shard is the larger of those logical bytes over the HBM peak
and 2*B*K*N_shard over the int8 peak, summed over projections
(``bench/work_fleet.py``).  Averaged over the shards, times the decode
steps the trace saw, over the kernels' device time in those steps, which
the trace averages over the devices too.  A run that records no split
ran on one chip: one shard with every column (``pud_decode_step``)."""
from bench import trace, work

KERNELS = r"_gemv_placed_kernel|_gemv_kernel|bitplane"
DECODE = r"_step_fn"


def read(ctx):
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    shards = ctx.get("pud_decode_step_shards") or (
        [ctx["pud_decode_step"]] if ctx.get("pud_decode_step") else None)
    if tr is None or not peaks or not shards:
        return None
    busy = trace.op_seconds(tr, KERNELS, DECODE)
    steps = trace.module_count(tr, DECODE)
    if busy <= 0 or steps <= 0:
        return None
    least = sum(work.least_seconds(w, peaks["int8_ops_s"],
                                   peaks["hbm_bytes_s"])
                for w in shards) / len(shards)
    return 100.0 * steps * least / busy
