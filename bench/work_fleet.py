"""Work of the bit-plane kernels in one tensor-parallel decode step, per
model shard, from shapes alone.

On a fleet every chip computes its own columns of each PUD projection: its
shard's width at the projection's K.  The widths are the split the packs
record (each ``ShardedPackedTensor.shard_widths``); the work of a width is
``bench/work.py``'s, so a one-chip cell and a fleet cell count alike.
"""
from __future__ import annotations

from bench import work


def projection_key(pack_name: str) -> str:
    """``layers_0_dense/mixer/wi`` -> ``mixer.wi``; ``unembed/w`` ->
    ``unembed``: the names ``work.pud_projections`` gives."""
    parts = pack_name.split("/")
    return "unembed" if parts[0] == "unembed" else ".".join(parts[-2:])


def pud_decode_step_shards(cfg: dict, batch: int,
                           shard_widths: dict) -> list[work.Work]:
    """Logical work of one decode step's bit-plane kernels on each shard,
    for ``batch`` slots; ``shard_widths`` maps each projection (as
    ``projection_key`` names it) to its per-shard column counts."""
    wb = cfg["pud"]["weight_bits"]
    n_shards = len(next(iter(shard_widths.values())))
    out = []
    for m in range(n_shards):
        total = work.Work(0.0, 0.0)
        for name, k, _, count in work.pud_projections(cfg):
            w = work.placed_bitplane_gemm(batch, k, shard_widths[name][m], wb)
            total = total + w.scale(count)
        out.append(total)
    return out
