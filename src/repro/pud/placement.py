"""Physical column placement: calibration masks -> the serving layout.

The paper's calibration decides *which physical columns* are safe to compute
on (Eq. 1, Table I); this module is the layer that makes serving actually run
on those columns.  ``plan_placement`` takes the fleet's per-column error-prone
masks (``core/ecr.measure_ecr_fleet``) and maps every packed projection's
logical columns onto error-free physical columns across the
``(channel, bank, subarray)`` grid — greedy first-fit bin-packing inside a
subarray with spill into the next one.  The result is a ``Placement`` pytree:
per-tensor column index maps plus a capacity report, persisted alongside the
calibration table by ``runtime/calib_cache.py``.

Layout model (matches the MVDRAM weight layout of kernels/bitplane_gemv.py):
a packed ``[K, N]`` projection occupies one physical column per output column
n — its WB bit-planes live in that column's rows — so a tensor's demand is N
columns per stacked slice.  Physical columns are numbered subarray-major:
``global_col = subarray_index * n_cols + col``.

**Block-aligned windows** (the format the placed kernels block over): a
tensor's N logical columns split into blocks of ``block_cols`` (the largest
divisor of N <= ``PLACE_BLOCK``, mirroring the kernel's N-tile choice).
Each block's columns are consecutive usable physical columns; the physical
span they cover — including the faulty columns interleaved between them —
becomes one *window block*, and every window block pads to the common
per-tensor stride ``window_block`` (the max span rounded up to a multiple
of 128 lanes).  The materialized
window is the concatenation of these blocks, so logical block j's columns
all live inside window slice ``[j*window_block, (j+1)*window_block)`` and
the placed kernel streams exactly one window block per N-tile instead of
holding the whole physical region in VMEM.  ``local_cols`` are absolute
window positions (block base + in-block offset), which is what the packer
scatters to and what ``col_ids`` store; faulty columns inside a block's
span are materialized (holding zero planes, marked in ``faulty``) and
never addressed, while pad positions beyond a span back no physical column
at all.

Fault model (``inject_read_faults``): an error-prone column is one whose
sense-amp threshold offset exceeds the SiMRA margin (pud/physics), so its
reads saturate to a *stuck* value regardless of the stored charge —
``offset < 0`` lowers the threshold and reads 1, otherwise 0.  Injecting
this corruption into the physical planes breaks serving numerics exactly
when a logical column was placed on a faulty physical column; a placement
built with ``avoid_faulty=True`` is immune by construction.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import zipfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import N_BLOCK, largest_divisor

PLACEMENT_FORMAT = "pud-placement-v3"
_PLACEMENT_FORMAT_V2 = "pud-placement-v2"
_PLACEMENT_FORMAT_V1 = "pud-placement-v1"

# Logical columns per window block: the kernels' N tile, so one window
# block feeds exactly one (full-size) output tile by construction.
PLACE_BLOCK = N_BLOCK

#: The window stride rounds up to a whole number of 128-column lane tiles:
#: the chip's compiler tiles a block's last dimension only in multiples of
#: 128.  Columns past a block's span are zero padding and never addressed.
WINDOW_ALIGN = 128


def aligned_window(span: int) -> int:
    """Smallest multiple of :data:`WINDOW_ALIGN` >= ``span``."""
    return -(-int(span) // WINDOW_ALIGN) * WINDOW_ALIGN


class PlacementError(RuntimeError):
    """Raised when the error-free capacity cannot hold the requested layout."""


@dataclasses.dataclass(frozen=True)
class PlacementRequest:
    """Column demand of one packable projection.

    ``block_cols`` forces the window-block width instead of the default
    ``largest_divisor(n_cols, PLACE_BLOCK)`` rule.  Sharded packing uses
    this: every model shard plans its column slice with the *full* tensor's
    block width so the per-shard window geometry stays uniform across the
    mesh (see ``shard_column_slices``).
    """

    name: str                 # tensor path, e.g. "layers_0_dense/mixer/wi"
    n_cols: int               # logical (output) columns per slice
    n_slices: int = 0         # leading stacked-layer count; 0 = unstacked
    block_cols: int = 0       # forced window-block width; 0 = derive

    @property
    def total_cols(self) -> int:
        return self.n_cols * max(1, self.n_slices)


def requests_fingerprint(requests: list[PlacementRequest]) -> str:
    """Stable short hash of a request list (keys persisted placements)."""
    blob = json.dumps([
        (r.name, r.n_cols, r.n_slices) if not r.block_cols
        else (r.name, r.n_cols, r.n_slices, r.block_cols)
        for r in requests])
    return hashlib.sha256(blob.encode()).hexdigest()[:10]


def shard_column_slices(n_cols: int,
                        n_shards: int) -> tuple[tuple[tuple[int, int], ...],
                                                int]:
    """Split a tensor's N columns across model shards on block boundaries.

    Returns ``(((lo, hi), ...), block_cols)``: one half-open column span per
    shard plus the block width the split respects — the *full* tensor's
    ``largest_divisor(n_cols, PLACE_BLOCK)``, the same width the unsharded
    allocator would pick.  Every shard owns a whole number of window blocks
    (earlier shards take the remainder blocks), so no placement window ever
    straddles a shard; when there are fewer blocks than shards the trailing
    shards own zero columns and serve pure padding.
    """
    if n_cols <= 0 or n_shards <= 0:
        raise PlacementError(
            f"shard_column_slices needs positive n_cols/n_shards, got "
            f"{n_cols}/{n_shards}")
    block_cols = largest_divisor(n_cols, PLACE_BLOCK)
    n_blocks = n_cols // block_cols
    base, extra = divmod(n_blocks, n_shards)
    spans, lo = [], 0
    for i in range(n_shards):
        hi = lo + (base + (1 if i < extra else 0)) * block_cols
        spans.append((lo, hi))
        lo = hi
    return tuple(spans), block_cols


@dataclasses.dataclass
class TensorPlacement:
    """Column index maps of one placed tensor (block-aligned windows).

    Shapes: unstacked tensors use ``[N]`` maps; stacked use ``[L, N]`` with
    per-slice windows (all slices share ``block_cols``/``window_block`` so
    stacked planes keep a uniform shape for ``lax.scan``).  ``phys_cols``
    are global physical column ids; ``block_starts`` give the physical
    column each window block originates at; ``faulty``/``stuck`` describe
    the error-prone columns inside the materialized window (length
    ``region_size = n_blocks * window_block``) for the fault-injection
    model.
    """

    phys_cols: np.ndarray      # [L?, N] int32 global physical column ids
    block_cols: int            # logical columns per block (B)
    window_block: int          # window stride per block (P_blk >= max span)
    block_starts: np.ndarray   # [L?, NB] int32 physical origin per block
    faulty: np.ndarray         # [L?, W] bool — error-prone cols in window
    stuck: np.ndarray          # [L?, W] int8 — read value of faulty cols

    @property
    def n_blocks(self) -> int:
        return self.block_starts.shape[-1]

    @property
    def region_size(self) -> int:
        """Materialized window length W = n_blocks * window_block."""
        return self.n_blocks * self.window_block

    @property
    def local_cols(self) -> np.ndarray:
        """[L?, N] absolute window positions (what ``col_ids`` store):
        block base + offset of the physical column inside its block span."""
        n = self.phys_cols.shape[-1]
        blk = np.arange(n) // self.block_cols                  # [N]
        base = (blk * self.window_block).astype(np.int64)      # [N]
        if self.phys_cols.ndim == 1:
            starts = self.block_starts[blk]
        else:
            starts = self.block_starts[:, blk]
        return (base + self.phys_cols - starts).astype(np.int32)


@dataclasses.dataclass
class Placement:
    """Device-wide placement: per-tensor maps + capacity accounting."""

    entries: dict[str, TensorPlacement]
    grid_shape: tuple[int, int, int]
    n_cols_per_subarray: int
    used_per_subarray: np.ndarray      # [G] int32 columns holding weights
    usable_per_subarray: np.ndarray    # [G] int32 allocatable columns
    avoid_faulty: bool

    @property
    def n_subarrays(self) -> int:
        return int(np.prod(self.grid_shape))

    @property
    def used_total(self) -> int:
        return int(self.used_per_subarray.sum())

    @property
    def usable_total(self) -> int:
        return int(self.usable_per_subarray.sum())

    @property
    def occupancy(self) -> float:
        """Fraction of allocatable (error-free) columns holding weights."""
        return self.used_total / max(1, self.usable_total)

    @property
    def spilled_tensors(self) -> list[str]:
        """Tensors whose slices cross a subarray boundary."""
        n = self.n_cols_per_subarray
        out = []
        for name, tp in self.entries.items():
            cols = tp.phys_cols
            if (cols // n).min() != (cols // n).max():
                out.append(name)
        return out

    def capacity_report(self) -> dict:
        used = self.used_per_subarray
        return {
            "n_subarrays": self.n_subarrays,
            "n_cols_per_subarray": self.n_cols_per_subarray,
            "usable_cols": self.usable_total,
            "used_cols": self.used_total,
            "occupancy": self.occupancy,
            "occupied_subarrays": int((used > 0).sum()),
            "spilled_tensors": self.spilled_tensors,
            "avoid_faulty": self.avoid_faulty,
        }


def _register(cls, array_fields, aux_fields):
    def flatten(obj):
        children = tuple(getattr(obj, f) for f in array_fields)
        aux = tuple(getattr(obj, f) for f in aux_fields)
        return children, aux

    def unflatten(aux, children):
        return cls(**dict(zip(array_fields, children)),
                   **dict(zip(aux_fields, aux)))

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)


_register(TensorPlacement,
          ("phys_cols", "block_starts", "faulty", "stuck"),
          ("block_cols", "window_block"))
_register(Placement,
          ("entries", "used_per_subarray", "usable_per_subarray"),
          ("grid_shape", "n_cols_per_subarray", "avoid_faulty"))


# ---------------------------------------------------------------------------
# Allocator
# ---------------------------------------------------------------------------


def _stuck_values(global_cols: np.ndarray,
                  sense_offsets: np.ndarray | None) -> np.ndarray:
    """Stuck read value of faulty columns (pud/physics sense convention).

    With the per-column sense offsets available, a negative offset lowers
    the threshold so every read saturates to 1; positive saturates to 0.
    From a warm cache only the masks persist — fall back to a deterministic
    per-column value so injection stays reproducible.
    """
    if sense_offsets is not None:
        flat = np.asarray(sense_offsets).reshape(-1)
        return (flat[global_cols] < 0).astype(np.int8)
    return (global_cols % 2).astype(np.int8)


def _slice_blocks(cols: np.ndarray, block_cols: int):
    """Split one slice's columns into blocks; returns (starts, spans)."""
    nb = cols.size // block_cols
    chunks = cols.reshape(nb, block_cols)
    starts = chunks[:, 0].astype(np.int64)
    spans = (chunks[:, -1] - chunks[:, 0] + 1).astype(np.int64)
    return starts, spans


def _window_masks(starts: np.ndarray, spans: np.ndarray, window_block: int,
                  flat_faulty: np.ndarray,
                  sense_offsets) -> tuple[np.ndarray, np.ndarray]:
    """Faulty/stuck masks of one slice's materialized window.

    Window position j*window_block + t backs physical column
    ``starts[j] + t`` when t < spans[j]; positions past a block's span are
    pure padding (no physical column: never faulty, zero stuck value).
    """
    nb = starts.size
    n_total = flat_faulty.size
    faulty = np.zeros(nb * window_block, bool)
    stuck = np.zeros(nb * window_block, np.int8)
    for j in range(nb):
        t = np.arange(min(int(spans[j]), window_block), dtype=np.int64)
        phys = starts[j] + t
        t = t[phys < n_total]
        phys = phys[phys < n_total]
        faulty[j * window_block + t] = flat_faulty[phys]
        stuck[j * window_block + t] = _stuck_values(phys, sense_offsets)
    return faulty, stuck


def plan_placement(
    masks,                              # [G, n_cols] bool, True = error-prone
    requests: list[PlacementRequest],
    *,
    avoid_faulty: bool = True,
    sense_offsets=None,                 # [G, n_cols] float, optional
) -> Placement:
    """Greedy first-fit allocation of every request onto the column grid.

    Requests are placed in order; each slice draws consecutive usable
    columns from the current subarray and spills into the next when the
    subarray is exhausted.  ``avoid_faulty=False`` builds the *identity*
    layout (logical columns land on physical columns in raw order, faulty
    or not) — the comparison baseline for fault injection.

    Raises ``PlacementError`` when total demand exceeds usable capacity.
    """
    masks = np.asarray(masks, bool)
    g, n_cols = masks.shape
    flat_faulty = masks.reshape(-1)
    if avoid_faulty:
        usable_ids = np.nonzero(~flat_faulty)[0].astype(np.int64)
    else:
        usable_ids = np.arange(g * n_cols, dtype=np.int64)

    demand = sum(r.total_cols for r in requests)
    if demand > usable_ids.size:
        raise PlacementError(
            f"placement demand {demand} columns exceeds usable capacity "
            f"{usable_ids.size} ({g} subarrays x {n_cols} cols, "
            f"avoid_faulty={avoid_faulty})")

    entries: dict[str, TensorPlacement] = {}
    cursor = 0
    for req in requests:
        n_slices = max(1, req.n_slices)
        block_cols = req.block_cols or largest_divisor(req.n_cols,
                                                       PLACE_BLOCK)
        if block_cols > PLACE_BLOCK or req.n_cols % block_cols:
            raise PlacementError(
                f"request {req.name!r}: forced block_cols {block_cols} "
                f"must divide n_cols {req.n_cols} and stay within "
                f"PLACE_BLOCK {PLACE_BLOCK}")
        slice_cols, slice_starts, slice_spans = [], [], []
        for _ in range(n_slices):
            cols = usable_ids[cursor:cursor + req.n_cols]
            cursor += req.n_cols
            starts, spans = _slice_blocks(cols, block_cols)
            slice_cols.append(cols.astype(np.int32))
            slice_starts.append(starts)
            slice_spans.append(spans)
        window_block = aligned_window(max(s.max() for s in slice_spans))

        faulty, stuck = [], []
        for starts, spans in zip(slice_starts, slice_spans):
            f, s = _window_masks(starts, spans, window_block, flat_faulty,
                                 sense_offsets)
            faulty.append(f)
            stuck.append(s)

        if req.n_slices:
            tp = TensorPlacement(
                phys_cols=np.stack(slice_cols),
                block_cols=block_cols, window_block=window_block,
                block_starts=np.stack(slice_starts).astype(np.int32),
                faulty=np.stack(faulty), stuck=np.stack(stuck))
        else:
            tp = TensorPlacement(
                phys_cols=slice_cols[0],
                block_cols=block_cols, window_block=window_block,
                block_starts=slice_starts[0].astype(np.int32),
                faulty=faulty[0], stuck=stuck[0])
        entries[req.name] = tp

    used = np.zeros(g * n_cols, bool)
    used[usable_ids[:cursor]] = True
    usable_per = (~masks).sum(axis=1) if avoid_faulty \
        else np.full(g, n_cols)
    return Placement(
        entries=entries,
        grid_shape=(1, 1, g),
        n_cols_per_subarray=n_cols,
        used_per_subarray=used.reshape(g, n_cols).sum(axis=1)
                              .astype(np.int32),
        usable_per_subarray=np.asarray(usable_per, np.int32),
        avoid_faulty=avoid_faulty,
    )


def plan_for_grid(masks, requests, grid_shape, **kw) -> Placement:
    """``plan_placement`` with the true (channels, banks, subarrays) shape."""
    p = plan_placement(masks, requests, **kw)
    return dataclasses.replace(p, grid_shape=tuple(grid_shape))


def refresh_fault_state(placement: Placement, masks,
                        sense_offsets=None) -> Placement:
    """Recompute every entry's faulty/stuck window masks from new masks.

    Drift changes *which* columns are error-prone, not where tensors live:
    the column maps were planned at calibration time and the packs built
    from them.  This re-reads each materialized window's fault state out of
    fresh (drifted) per-column masks, which is exactly what
    ``inject_read_faults`` needs to model serving from the aged device — a
    column that went bad after planning now corrupts the window position it
    backs.  Capacity accounting keeps its calibration-time values;
    re-planning against the new masks is the recovery path's job, not this
    view's.
    """
    masks = np.asarray(masks, bool)
    flat_faulty = masks.reshape(-1)
    entries: dict[str, TensorPlacement] = {}
    for name, tp in placement.entries.items():
        stacked = tp.phys_cols.ndim == 2
        slices = tp.phys_cols if stacked else tp.phys_cols[None]
        faulty, stuck = [], []
        for cols in slices:
            starts, spans = _slice_blocks(
                np.asarray(cols, np.int64), tp.block_cols)
            f, s = _window_masks(starts, spans, tp.window_block,
                                 flat_faulty, sense_offsets)
            faulty.append(f)
            stuck.append(s)
        entries[name] = dataclasses.replace(
            tp,
            faulty=np.stack(faulty) if stacked else faulty[0],
            stuck=np.stack(stuck) if stacked else stuck[0])
    return dataclasses.replace(placement, entries=entries)


# ---------------------------------------------------------------------------
# Fault injection (pud/physics stuck-read model)
# ---------------------------------------------------------------------------


def corrupt_planes(planes: jax.Array, tp: TensorPlacement) -> jax.Array:
    """Replace every bit stored on an error-prone column with its stuck read.

    planes: [WB, K(/8), W] (or [L, WB, K(/8), W]); the trailing axis is the
    materialized window of ``tp``.  Column-wide corruption — every bit-plane
    and row of a faulty column reads the same stuck value.  Works on both
    plane layouts: in the bit-packed one a stuck-1 column reads 0xFF words
    (all eight K rows of every plane bit saturate high), stuck-0 reads 0x00.
    """
    faulty = jnp.asarray(tp.faulty)[..., None, None, :]
    stuck = jnp.asarray(tp.stuck)[..., None, None, :]
    if planes.dtype == jnp.uint8:      # bit-packed words: saturate the byte
        stuck = stuck.astype(jnp.uint8) * jnp.uint8(0xFF)
    else:
        stuck = stuck.astype(planes.dtype)
    return jnp.where(faulty, stuck, planes)


def inject_read_faults(packed_params: dict, placement: Placement) -> dict:
    """Simulate serving from the real (faulty) device.

    Walks a ``pack_for_serving`` output tree and corrupts the physical
    planes of every placed pack per ``corrupt_planes``.  With
    ``avoid_faulty=True`` placements the gather indices never touch a
    corrupted column, so serving numerics are bit-identical; identity
    placements put logical columns on faulty physical columns and break.
    """

    from .packed import as_packed_tensor, is_pack

    def walk(tree, path):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for key, sub in tree.items():
            if key.endswith("_pud") and is_pack(sub) and "col_ids" in sub:
                name = "/".join(path + (key[: -len("_pud")],))
                tp = placement.entries.get(name)
                if tp is None:
                    raise KeyError(
                        f"packed tensor {name!r} has no placement entry "
                        f"(have: {sorted(placement.entries)})")
                pt = as_packed_tensor(sub)
                out[key] = pt.replace(planes=corrupt_planes(pt.planes, tp))
            elif isinstance(sub, dict):
                out[key] = walk(sub, path + (key,))
            else:
                out[key] = sub
        return out

    return walk(packed_params, ())


# ---------------------------------------------------------------------------
# Serialization (used by runtime/calib_cache.py)
# ---------------------------------------------------------------------------


def save_placement_npz(path, placement: Placement) -> None:
    """Write a Placement to ``path`` as a single .npz (no pickle)."""
    meta = {
        "format": PLACEMENT_FORMAT,
        "names": list(placement.entries),
        "block_cols": [placement.entries[n].block_cols
                       for n in placement.entries],
        "window_blocks": [placement.entries[n].window_block
                          for n in placement.entries],
        "grid_shape": list(placement.grid_shape),
        "n_cols_per_subarray": placement.n_cols_per_subarray,
        "avoid_faulty": placement.avoid_faulty,
    }
    arrays = {
        "meta": np.array(json.dumps(meta)),
        "used": np.asarray(placement.used_per_subarray, np.int32),
        "usable": np.asarray(placement.usable_per_subarray, np.int32),
    }
    for i, name in enumerate(placement.entries):
        tp = placement.entries[name]
        arrays[f"e{i}_phys"] = np.asarray(tp.phys_cols, np.int32)
        arrays[f"e{i}_start"] = np.asarray(tp.block_starts, np.int32)
        arrays[f"e{i}_faulty"] = np.asarray(tp.faulty, bool)
        arrays[f"e{i}_stuck"] = np.asarray(tp.stuck, np.int8)
    with open(path, "wb") as f:
        np.savez(f, **arrays)


def _upgrade_v1_entry(phys: np.ndarray, region_start: np.ndarray,
                      region_size: int, faulty_v1: np.ndarray,
                      stuck_v1: np.ndarray) -> TensorPlacement:
    """Rebuild the block-aligned window from a PR-2/PR-3 era (v1) entry.

    A v1 entry materialized one physical span per slice: window position p
    backed physical column ``region_start + p``.  The block structure is
    fully derivable — block origins come from ``phys_cols`` (the same
    ``PLACE_BLOCK`` divisor rule the allocator uses), and each window
    block's faulty/stuck values are re-read out of the v1 span at offset
    ``block_start - region_start``.
    """
    n = phys.shape[-1]
    block_cols = largest_divisor(n, PLACE_BLOCK)
    stacked = phys.ndim == 2
    slices = phys if stacked else phys[None]
    r_starts = (np.asarray(region_start).reshape(-1) if stacked
                else np.asarray([region_start]))
    f_v1 = faulty_v1 if stacked else faulty_v1[None]
    s_v1 = stuck_v1 if stacked else stuck_v1[None]

    all_starts, all_spans = [], []
    for cols in slices:
        starts, spans = _slice_blocks(cols.astype(np.int64), block_cols)
        all_starts.append(starts)
        all_spans.append(spans)
    window_block = aligned_window(max(s.max() for s in all_spans))

    faulty, stuck = [], []
    for starts, spans, r0, f1, s1 in zip(all_starts, all_spans, r_starts,
                                         f_v1, s_v1):
        nb = starts.size
        f = np.zeros(nb * window_block, bool)
        s = np.zeros(nb * window_block, np.int8)
        for j in range(nb):
            t = np.arange(min(int(spans[j]), window_block), dtype=np.int64)
            src = starts[j] - int(r0) + t
            t = t[(src >= 0) & (src < region_size)]
            src = src[(src >= 0) & (src < region_size)]
            f[j * window_block + t] = f1[src]
            s[j * window_block + t] = s1[src]
        faulty.append(f)
        stuck.append(s)

    return TensorPlacement(
        phys_cols=phys,
        block_cols=block_cols, window_block=window_block,
        block_starts=(np.stack(all_starts).astype(np.int32) if stacked
                      else all_starts[0].astype(np.int32)),
        faulty=(np.stack(faulty) if stacked else faulty[0]),
        stuck=(np.stack(stuck) if stacked else stuck[0]))


def _realign_entry(phys: np.ndarray, block_cols: int, window_block: int,
                   block_starts: np.ndarray, faulty: np.ndarray,
                   stuck: np.ndarray) -> TensorPlacement:
    """Load a v2 entry, whose stride was the raw max span, at the aligned
    stride: each window block keeps its columns at the same in-block
    offsets and gains zero padding at its end."""
    stride = aligned_window(window_block)
    if stride != window_block:
        nb = block_starts.shape[-1]
        lead = faulty.shape[:-1]

        def pad(a):
            blocks = a.reshape(lead + (nb, window_block))
            blocks = np.pad(blocks, [(0, 0)] * (blocks.ndim - 1)
                            + [(0, stride - window_block)])
            return blocks.reshape(lead + (nb * stride,))

        faulty, stuck = pad(faulty), pad(stuck)
    return TensorPlacement(phys_cols=phys, block_cols=block_cols,
                           window_block=stride, block_starts=block_starts,
                           faulty=faulty, stuck=stuck)


def load_placement_npz(path) -> Placement | None:
    """Read a Placement back; None on any corruption or format mismatch.

    v1 archives (one physical span per slice, no block structure) load
    through ``_upgrade_v1_entry``; v2 archives (block-aligned, stride not
    lane-aligned) through ``_realign_entry`` — old caches keep their
    placements instead of re-planning.
    """
    try:
        with np.load(path, allow_pickle=False) as z:
            meta = json.loads(str(z["meta"]))
            fmt = meta.get("format")
            if fmt not in (PLACEMENT_FORMAT, _PLACEMENT_FORMAT_V2,
                           _PLACEMENT_FORMAT_V1):
                return None
            entries = {}
            for i, name in enumerate(meta["names"]):
                if fmt == _PLACEMENT_FORMAT_V1:
                    entries[name] = _upgrade_v1_entry(
                        z[f"e{i}_phys"], z[f"e{i}_start"],
                        int(meta["region_sizes"][i]),
                        z[f"e{i}_faulty"], z[f"e{i}_stuck"])
                else:
                    entries[name] = _realign_entry(
                        z[f"e{i}_phys"], int(meta["block_cols"][i]),
                        int(meta["window_blocks"][i]), z[f"e{i}_start"],
                        z[f"e{i}_faulty"], z[f"e{i}_stuck"])
            return Placement(
                entries=entries,
                grid_shape=tuple(meta["grid_shape"]),
                n_cols_per_subarray=int(meta["n_cols_per_subarray"]),
                used_per_subarray=z["used"],
                usable_per_subarray=z["usable"],
                avoid_faulty=bool(meta["avoid_faulty"]))
    except (OSError, ValueError, KeyError, EOFError, json.JSONDecodeError,
            zipfile.BadZipFile):
        return None
