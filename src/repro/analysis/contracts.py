"""Kernel contract checker: verify bit-plane kernel invariants statically.

Given (shape, layout, window structure) — *without executing a kernel* —
recompute everything the four bit-plane entry points (gemv/gemm ×
logical/placed) derive at trace time and verify the invariants they assume:

  * **tile selection** — the divisor-based block sizes and grid the kernel
    wrappers will pick (same ``largest_divisor`` rule, same caps: the
    constants are imported from ``kernels.ops``, so the checker cannot
    drift from the kernels);
  * **bitpack8 metadata** — ``logical_k`` consistent with the activation K
    and the stored word count (``Kw == ceil(K/8)``, the ``pack_plane_words``
    guarantee);
  * **placed windows** — ``window_block`` tiles the physical window, each
    window block has capacity for its logical block, and (when values are
    available) every ``col_ids`` entry lands statically inside its block's
    window slice;
  * **folded weight bits** — ``mode="folded"`` feeds the MXU an int8
    folded weight (<= 2^WB - 1), so WB <= 7;
  * **VMEM budget** — the per-grid-step footprint derived from the
    BlockSpecs (streamed blocks double-buffered + compute transients: the
    int32 unpack/fold stages, the int8 MXU operands, the placed one-hot
    column selection) stays under :data:`VMEM_BUDGET_BYTES`.  This is the check that outlaws the
    pre-block-alignment "whole window per K-tile" layout.

Violations raise :class:`ContractViolation` naming the kernel, the failed
invariant, and (where it localizes) the tile.  The kernels raise the same
error type from their own runtime checks; this module is the superset that
runs before any array exists.

Integration points: ``kernels.ops.pud_matmul(check_contracts=True)`` is the
opt-in pre-flight; the ``interpret`` backend (kernels/backends.py) runs the
check unconditionally; ``python -m repro.analysis`` sweeps
:func:`default_matrix` plus :func:`adversarial_fixtures` as the CI gate.

The per-grid-step VMEM budget table in docs/kernels.md is *generated* from
this module (``python -m repro.analysis --write-docs``) so the doc math can
never drift from the code again.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.kernels.ops import (B_BLOCK, K_BLOCK, N_BLOCK, heuristic_block,
                               largest_divisor)

from .errors import ContractViolation

LAYOUTS = ("dense", "bitpack8")
ENTRIES = ("gemv", "gemm")

#: Per-grid-step footprint cap: streamed blocks (double-buffered) plus
#: compute transients must fit well inside one TPU core's ~16 MiB VMEM,
#: leaving headroom for the pipeline and scalar state.  Every shipped
#: config sits 2-3 orders of magnitude below this; what it outlaws is the
#: degenerate whole-window placed layout (a fleet-sized window dragged into
#: VMEM per K-tile — the exact bug the block-aligned layout removed).
VMEM_BUDGET_BYTES = 4 * 1024 * 1024

_KERNEL_NAMES = {
    ("gemv", False): "bitplane_gemv",
    ("gemv", True): "bitplane_gemv_placed",
    ("gemm", False): "bitplane_gemm",
    ("gemm", True): "bitplane_gemm_placed",
}


@dataclasses.dataclass(frozen=True)
class KernelCall:
    """Static description of one kernel invocation (shapes only, no arrays).

    ``plane_k`` is the planes' K-axis extent as stored: the word count
    ``Kw`` for ``layout="bitpack8"``, the row count ``K`` for dense.
    ``window`` is the physical window length of a placed call (None =
    logical layout); ``window_block`` follows the kernel convention
    (None = whole window as a single block, the hand-built-pack
    degenerate case).
    """

    entry: str                     # "gemv" | "gemm"
    b: int                         # activation rows
    k: int                         # activation (logical) reduction length
    n: int                         # logical output columns
    wb: int = 4                    # bit-planes
    layout: str = "dense"
    plane_k: int | None = None     # planes.shape[-2]; default: derived
    logical_k: int | None = None   # bitpack8 pack metadata
    window: int | None = None      # physical window length W (placed)
    window_block: int | None = None
    mode: str = "folded"
    # Tuned tile overrides (kernels/autotune.py); None = the divisor
    # heuristic the wrappers default to.
    b_block: int | None = None
    n_block: int | None = None
    k_block: int | None = None

    @property
    def placed(self) -> bool:
        return self.window is not None

    @property
    def kernel(self) -> str:
        return _KERNEL_NAMES[(self.entry, self.placed)]

    def resolved_plane_k(self) -> int:
        if self.plane_k is not None:
            return self.plane_k
        return -(-self.k // 8) if self.layout == "bitpack8" else self.k


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """The derived execution plan: what the kernel wrapper will build.

    ``vmem`` maps operand/transient names to their per-grid-step bytes;
    ``grid`` matches the wrapper's ``pallas_call`` grid exactly
    ((N/Nb, K-steps) for GeMV, (B/Bb, N/Nb, K-steps) for GEMM).
    """

    kernel: str
    grid: tuple[int, ...]
    bb: int                        # batch rows per block
    nb: int                        # output columns per block
    x_kb: int                      # activation K rows per block
    plane_kb: int                  # plane K rows (or words) per block
    block_cols: int                # logical columns per window block
    window_block: int | None       # resolved window stride (placed only)
    vmem: dict[str, int]

    @property
    def streamed_bytes(self) -> int:
        return (self.vmem["x"] + self.vmem["planes"]
                + self.vmem.get("col_ids", 0))

    @property
    def vmem_total_bytes(self) -> int:
        """Budget-relevant total: streamed blocks double-buffered, the
        resident output accumulator and compute transients once."""
        return (2 * self.streamed_bytes + self.vmem["out"]
                + self.vmem.get("transient", 0))


def _k_plan(call: KernelCall) -> tuple[int, int, int]:
    """Replicates ``bitplane_gemv._k_tiling``: (plane_kb, x_kb, k_steps).

    ``k_steps`` counts padded grid steps — an explicit ``k_block`` (or the
    degenerate-tile pow2 fallback) pads the reduction axis with zeros, which
    contribute nothing to the integer dot products.
    """
    kernel = call.kernel
    if call.layout == "bitpack8":
        kw = call.resolved_plane_k()
        if (call.logical_k or kw * 8) != call.k or call.k > kw * 8:
            raise ContractViolation(
                kernel, "bitpack8-logical-k",
                f"x K={call.k} inconsistent with word planes Kw={kw} "
                f"(logical_k={call.logical_k})")
        if kw != -(-call.k // 8):
            raise ContractViolation(
                kernel, "bitpack8-word-count",
                f"stored word count Kw={kw} != ceil(K/8)="
                f"{-(-call.k // 8)} for K={call.k} — the pack was not "
                "built by pack_plane_words")
        if call.k_block is not None:
            if call.k_block <= 0 or call.k_block % 8:
                raise ContractViolation(
                    kernel, "tile-plan",
                    f"bitpack8 k_block {call.k_block} must be a positive "
                    "multiple of 8 (whole word rows)")
            kwb = call.k_block // 8
        else:
            kwb = heuristic_block(kw, K_BLOCK // 8)
        return kwb, kwb * 8, -(-kw // kwb)
    if call.layout != "dense":
        raise ContractViolation(
            kernel, "layout",
            f"unknown plane layout {call.layout!r}; one of {LAYOUTS}")
    if call.resolved_plane_k() != call.k:
        raise ContractViolation(
            kernel, "k-mismatch",
            f"x K={call.k} vs planes K={call.resolved_plane_k()}")
    if call.k_block is not None:
        if call.k_block <= 0:
            raise ContractViolation(
                kernel, "tile-plan",
                f"k_block {call.k_block} must be positive")
        kb = call.k_block
    else:
        kb = heuristic_block(call.k, K_BLOCK)
    return kb, kb, -(-call.k // kb)


def _n_plan(call: KernelCall) -> tuple[int, int, int | None, int]:
    """Replicates the wrappers' N/window tiling:
    (nb, block_cols, pwb, n_pad)."""
    kernel = call.kernel
    if not call.placed:
        if call.n_block is not None:
            if call.n_block <= 0:
                raise ContractViolation(
                    kernel, "tile-plan",
                    f"n_block {call.n_block} must be positive")
            nb = call.n_block
        else:
            nb = heuristic_block(call.n, N_BLOCK)
        return nb, call.n, None, -(-call.n // nb) * nb
    w_len = call.window
    pwb = call.window_block or w_len
    if pwb <= 0 or w_len % pwb or call.n % (w_len // pwb):
        raise ContractViolation(
            kernel, "window-tiling",
            f"window length {w_len} / window_block {pwb} does not tile "
            f"N={call.n}")
    n_blocks = w_len // pwb
    block_cols = call.n // n_blocks
    if block_cols > pwb:
        raise ContractViolation(
            kernel, "window-capacity",
            f"window_block {pwb} cannot hold {block_cols} logical columns "
            f"per block ({n_blocks} blocks for N={call.n})")
    if call.n_block is not None:
        if call.n_block <= 0 or block_cols % call.n_block:
            raise ContractViolation(
                kernel, "tile-plan",
                f"placed n_block {call.n_block} must divide the "
                f"{block_cols} logical columns per window block")
        nb = call.n_block
    else:
        nb = largest_divisor(block_cols, N_BLOCK)
    return nb, block_cols, pwb, call.n


def plan_kernel(call: KernelCall) -> TilePlan:
    """Recompute the tile plan of ``call`` and verify its invariants.

    Raises :class:`ContractViolation` on the first violated invariant;
    otherwise returns the :class:`TilePlan` the kernel wrapper will
    materialize (same divisor rule, caps, and grid construction).
    """
    if call.entry not in ENTRIES:
        raise ContractViolation(
            call.kernel, "entry", f"unknown entry {call.entry!r}")
    if min(call.b, call.k, call.n, call.wb) < 1:
        raise ContractViolation(
            call.kernel, "shape",
            f"non-positive dimension in B={call.b} K={call.k} N={call.n} "
            f"WB={call.wb}")
    if call.mode == "folded" and call.wb > 7:
        raise ContractViolation(
            call.kernel, "folded-weight-bits",
            f"folded mode needs WB <= 7 (an int8 MXU operand), got "
            f"{call.wb}")
    plane_kb, x_kb, k_steps = _k_plan(call)
    nb, block_cols, pwb, n_pad = _n_plan(call)

    if call.entry == "gemm":
        if call.b_block is not None and call.b_block <= 0:
            raise ContractViolation(
                call.kernel, "tile-plan",
                f"b_block {call.b_block} must be positive")
        bb = (min(call.b_block, call.b) if call.b_block is not None
              else min(call.b, B_BLOCK))
        bp = -(-call.b // bb) * bb                    # zero-row batch pad
        grid: tuple[int, ...] = (bp // bb, n_pad // nb, k_steps)
    else:
        if call.b_block is not None:
            raise ContractViolation(
                call.kernel, "tile-plan",
                "b_block override is meaningless for the gemv entry — it "
                "keeps the whole batch in one block")
        bb = call.b                                   # whole batch, one block
        grid = (n_pad // nb, k_steps)

    # Internal consistency of the recomputation itself: the grid must tile
    # the (padded) operands exactly — block selection guarantees it, so a
    # failure here means the checker no longer matches the kernels.
    padded_k = plane_kb * k_steps * (8 if call.layout == "bitpack8" else 1)
    if x_kb * k_steps != padded_k or grid[-2] * nb != n_pad:
        raise ContractViolation(
            call.kernel, "tile-selection",
            f"recomputed tiling does not cover the operand: grid {grid}, "
            f"nb={nb}, x_kb={x_kb}, k_steps={k_steps}")

    plane_cols = pwb if call.placed else nb
    vmem = {
        "x": bb * x_kb,                               # int8
        "planes": call.wb * plane_kb * plane_cols,    # int8/uint8 words
        "out": 4 * bb * nb,                           # int32 accumulator
    }
    if call.placed:
        vmem["col_ids"] = 4 * nb
    transient = 0
    if call.placed:
        # one-hot column selection on the MXU: words select in bf16 with
        # an f32 result, dense 0/1 planes in int8 with an int32 result
        sel = 2 if call.layout == "bitpack8" else 1
        transient += sel * pwb * nb                   # selection matrix
        transient += sel * call.wb * plane_kb * pwb   # window operand copy
        transient += 4 * call.wb * plane_kb * nb      # selected columns
    if call.layout == "bitpack8":
        transient += 4 * call.wb * x_kb * nb          # int32 shift-mask stage
        transient += call.wb * x_kb * nb              # int8 unpacked tile
    if call.mode == "folded":
        transient += 4 * x_kb * nb                    # int32 fold
        transient += x_kb * nb                        # int8 MXU operand
    else:
        transient += 4 * bb * nb                      # shifted plane partial
    vmem["transient"] = transient

    plan = TilePlan(kernel=call.kernel, grid=grid, bb=bb, nb=nb, x_kb=x_kb,
                    plane_kb=plane_kb, block_cols=block_cols,
                    window_block=pwb, vmem=vmem)
    if plan.vmem_total_bytes > VMEM_BUDGET_BYTES:
        raise ContractViolation(
            call.kernel, "vmem-budget",
            f"per-grid-step footprint {plan.vmem_total_bytes} B exceeds "
            f"the {VMEM_BUDGET_BYTES} B budget (blocks: {vmem})")
    return plan


def check_col_ids(col_ids, n: int, window: int, window_block: int | None,
                  block_cols: int, kernel: str) -> None:
    """Verify a concrete ``col_ids`` map against the block-aligned layout.

    Every logical column's window position must fall inside its block's
    window slice ``[blk*window_block, (blk+1)*window_block)`` — that is the
    static guarantee the placed BlockSpecs rely on to stream one window
    block per N-tile.
    """
    ids = np.asarray(col_ids).reshape(-1, n)          # [L?, N] -> slices
    pwb = window_block or window
    blk = np.arange(n) // block_cols
    lo, hi = blk * pwb, (blk + 1) * pwb
    for sl in ids:
        if (sl < 0).any() or (sl >= window).any():
            bad = int(np.argmax((sl < 0) | (sl >= window)))
            raise ContractViolation(
                kernel, "col-ids-range",
                f"col_ids[{bad}]={int(sl[bad])} outside window "
                f"[0, {window})")
        out = (sl < lo) | (sl >= hi)
        if out.any():
            bad = int(np.argmax(out))
            raise ContractViolation(
                kernel, "col-ids-range",
                f"col_ids[{bad}]={int(sl[bad])} escapes its window block "
                f"slice [{int(lo[bad])}, {int(hi[bad])})",
                tile=int(blk[bad]))


def check_shard_slices(spans, n: int, block_cols: int,
                       kernel: str = "sharded_gemm") -> None:
    """Verify a model-shard column split against the block-aligned layout.

    ``spans`` are per-shard half-open ``(lo, hi)`` column ranges (what
    ``pud.placement.shard_column_slices`` emits).  The placed kernels
    stream whole ``block_cols``-wide window blocks, so a shard boundary
    that lands mid-block would make one window straddle two devices — the
    invariant here is that every span starts and ends on a block multiple,
    the spans tile ``[0, n)`` contiguously in order, and no span is
    negative.  Raises :class:`ContractViolation` (invariant
    ``"shard-straddle"``) on the first violation.
    """
    if block_cols <= 0 or n % block_cols:
        raise ContractViolation(
            kernel, "shard-straddle",
            f"block_cols {block_cols} does not tile N={n}")
    lo_expect = 0
    for i, (lo, hi) in enumerate(spans):
        if lo != lo_expect or hi < lo:
            raise ContractViolation(
                kernel, "shard-straddle",
                f"shard {i} span [{lo}, {hi}) does not continue the "
                f"previous shard (expected lo={lo_expect})", tile=i)
        if lo % block_cols or hi % block_cols:
            raise ContractViolation(
                kernel, "shard-straddle",
                f"shard {i} span [{lo}, {hi}) straddles a {block_cols}-"
                "column window block — placement windows must stay whole "
                "per shard", tile=i)
        lo_expect = hi
    if lo_expect != n:
        raise ContractViolation(
            kernel, "shard-straddle",
            f"shard spans cover [0, {lo_expect}) but the tensor has "
            f"N={n} columns")


def _concrete(a):
    """Best-effort numpy view of ``a``; None for tracers (shape-only
    checks still run under jit, value checks are skipped)."""
    if a is None:
        return None
    if isinstance(a, np.ndarray):
        return a
    import jax

    if isinstance(a, jax.core.Tracer):
        return None
    try:
        return np.asarray(a)
    except Exception:
        return None


def check_kernel_args(entry: str, x_shape, planes_shape, *,
                      layout: str = "dense", logical_k: int | None = None,
                      col_ids=None, window_block: int | None = None,
                      mode: str = "folded", wb: int | None = None,
                      b_block: int | None = None,
                      n_block: int | None = None,
                      k_block: int | None = None) -> TilePlan:
    """Pre-flight an actual kernel call from its argument shapes.

    This is what ``pud_matmul(check_contracts=True)`` and the ``interpret``
    backend run: shapes in, :class:`TilePlan` out, :class:`ContractViolation`
    on any violated invariant.  ``col_ids`` may be an array (value-checked
    when concrete) or an int column count (shape checks only).
    ``b_block``/``n_block``/``k_block`` are tuned tile overrides, verified
    against the same invariants as the derived tiles.
    """
    b, k = int(x_shape[-2]), int(x_shape[-1])
    wb_ = int(wb if wb is not None else planes_shape[-3])
    plane_k, last = int(planes_shape[-2]), int(planes_shape[-1])
    if col_ids is None:
        call = KernelCall(entry=entry, b=b, k=k, n=last, wb=wb_,
                          layout=layout, plane_k=plane_k,
                          logical_k=logical_k, mode=mode, b_block=b_block,
                          n_block=n_block, k_block=k_block)
        return plan_kernel(call)
    n = col_ids if isinstance(col_ids, int) else int(np.shape(col_ids)[-1])
    call = KernelCall(entry=entry, b=b, k=k, n=n, wb=wb_, layout=layout,
                      plane_k=plane_k, logical_k=logical_k, window=last,
                      window_block=window_block, mode=mode, b_block=b_block,
                      n_block=n_block, k_block=k_block)
    plan = plan_kernel(call)
    ids = None if isinstance(col_ids, int) else _concrete(col_ids)
    if ids is not None:
        check_col_ids(ids, n, last, window_block, plan.block_cols,
                      call.kernel)
    return plan


def _plan_field(plan, field):
    if isinstance(plan, dict):
        return plan.get(field)
    return getattr(plan, field, None)


def check_tile_plan(plan, entry: str, x_shape, planes_shape, *,
                    layout: str = "dense", logical_k: int | None = None,
                    col_ids=None, window_block: int | None = None,
                    mode: str = "folded", wb: int | None = None) -> TilePlan:
    """Pre-flight an externally-supplied tuned tile plan.

    ``plan`` carries ``b_block``/``n_block``/``k_block``/``window_block``/
    ``mode`` fields (a ``kernels.autotune.TunedTile`` or a plain dict — a
    tuning-cache entry deserializes to either); the remaining arguments
    describe the call exactly like :func:`check_kernel_args`, with
    ``window_block`` naming the *pack's* block-aligned stride.

    A tuned ``window_block`` must be a whole multiple of the pack stride
    whose multiplier divides the block count — grouping c adjacent window
    blocks keeps every column's in-block residue arithmetic exact (column t
    of logical block r inside a group starts at residue ``r*pwb + t``).
    Anything else would silently gather the wrong physical columns, so it
    raises ``ContractViolation('window-stride')`` here, before any kernel
    runs.  All other overrides flow through the same invariants as derived
    tiles (:func:`check_kernel_args`), including the VMEM budget gate.
    """
    tuned_wb = _plan_field(plan, "window_block")
    eff_window_block = window_block
    if tuned_wb is not None:
        if col_ids is None:
            raise ContractViolation(
                _KERNEL_NAMES[(entry, False)], "tile-plan",
                f"window_block override {tuned_wb} on a logical "
                "(non-placed) call")
        kernel = _KERNEL_NAMES[(entry, True)]
        w_len = int(planes_shape[-1])
        pack_wb = window_block or w_len
        n_blocks = w_len // pack_wb if pack_wb and w_len % pack_wb == 0 else 0
        if (tuned_wb <= 0 or tuned_wb % pack_wb
                or n_blocks % (tuned_wb // pack_wb)):
            raise ContractViolation(
                kernel, "window-stride",
                f"tuned window_block {tuned_wb} must be a multiple of the "
                f"pack stride {pack_wb} whose multiplier divides the "
                f"{n_blocks} window blocks — the placed layout is fixed "
                "at pack time")
        eff_window_block = tuned_wb
    return check_kernel_args(
        entry, x_shape, planes_shape, layout=layout, logical_k=logical_k,
        col_ids=col_ids, window_block=eff_window_block,
        mode=_plan_field(plan, "mode") or mode, wb=wb,
        b_block=_plan_field(plan, "b_block"),
        n_block=_plan_field(plan, "n_block"),
        k_block=_plan_field(plan, "k_block"))


def check_pack(pt, batch: int = 1, entry: str | None = None,
               mode: str = "folded") -> list[TilePlan]:
    """Contract-check a ``PackedTensor`` for every entry point it can serve.

    Stacked packs ([L, WB, Kw, N] planes) check one representative slice
    shape plus every slice's ``col_ids`` values.  Returns the plans (one
    per entry checked).
    """
    from repro.pud.packed import as_packed_tensor

    pt = as_packed_tensor(pt)
    entries = (entry,) if entry else ENTRIES
    plane_shape = pt.planes.shape[-3:]
    x_shape = (batch, pt.k)
    plans = []
    for e in entries:
        plans.append(check_kernel_args(
            e, x_shape, plane_shape, layout=pt.layout,
            logical_k=pt.logical_k, col_ids=pt.col_ids,
            window_block=pt.window_block, mode=mode))
    return plans


# ---------------------------------------------------------------------------
# Sweep matrix: the configurations tier-1 exercises, plus adversarial
# fixtures that MUST violate — both sides gate CI.
# ---------------------------------------------------------------------------


def synthetic_placed(n: int, pad: int = 8):
    """A minimal valid block-aligned placement for N logical columns.

    Mirrors the allocator: ``block_cols = largest_divisor(n, N_BLOCK)``
    blocks, each spanning ``block_cols + pad`` window columns (the pad
    standing in for interleaved faulty columns, skipped mid-block like a
    real first-fit plan).  Returns (window, window_block, col_ids [N]).
    """
    block_cols = largest_divisor(n, N_BLOCK)
    n_blocks = n // block_cols
    window_block = block_cols + pad
    offs = np.arange(block_cols)
    offs = offs + (offs >= block_cols // 2) * pad     # gap mid-span
    col_ids = (np.arange(n_blocks)[:, None] * window_block
               + offs[None, :]).reshape(-1).astype(np.int32)
    return n_blocks * window_block, window_block, col_ids


def default_matrix() -> list[tuple[KernelCall, np.ndarray | None]]:
    """(call, col_ids) pairs covering what tier-1 runs: all four entry
    points × both layouts × aligned and odd shapes × both modes."""
    shapes = [(1, 64, 64), (8, 256, 512), (4, 300, 172), (2, 1024, 256)]
    out: list[tuple[KernelCall, np.ndarray | None]] = []
    for b, k, n in shapes:
        for layout in LAYOUTS:
            for entry in ENTRIES:
                for mode in ("planes", "folded"):
                    out.append((KernelCall(
                        entry=entry, b=b, k=k, n=n, layout=layout,
                        logical_k=k if layout == "bitpack8" else None,
                        mode=mode), None))
                window, wblk, ids = synthetic_placed(n)
                out.append((KernelCall(
                    entry=entry, b=b, k=k, n=n, layout=layout,
                    logical_k=k if layout == "bitpack8" else None,
                    window=window, window_block=wblk), ids))
    return out


def adversarial_fixtures() -> list[tuple[str, str, KernelCall,
                                         np.ndarray | None]]:
    """(name, expected invariant, call, col_ids) — each MUST violate."""
    window, wblk, ids = synthetic_placed(512)
    bad_ids = ids.copy()
    bad_ids[7] = window + 3                           # escapes the window
    slice_ids = ids.copy()
    slice_ids[300] = 0                                # wrong block's slice
    return [
        ("oversized-window-block", "window-tiling",
         KernelCall(entry="gemv", b=1, k=256, n=512, window=window,
                    window_block=wblk + 1), ids),
        ("window-under-capacity", "window-capacity",
         KernelCall(entry="gemm", b=4, k=256, n=512, window=256,
                    window_block=128), None),
        ("inconsistent-logical-k", "bitpack8-logical-k",
         KernelCall(entry="gemm", b=8, k=300, n=128, layout="bitpack8",
                    plane_k=32, logical_k=300), None),
        ("word-count-drift", "bitpack8-word-count",
         KernelCall(entry="gemv", b=1, k=96, n=128, layout="bitpack8",
                    plane_k=16, logical_k=96), None),
        ("col-ids-out-of-window", "col-ids-range",
         KernelCall(entry="gemv", b=1, k=256, n=512, window=window,
                    window_block=wblk), bad_ids),
        ("col-ids-wrong-block", "col-ids-range",
         KernelCall(entry="gemm", b=4, k=256, n=512, window=window,
                    window_block=wblk), slice_ids),
        ("whole-window-vmem-blowout", "vmem-budget",
         KernelCall(entry="gemv", b=8, k=2048, n=256, window=1 << 16,
                    window_block=None),
         np.arange(256, dtype=np.int32) * 17),
        # A tuned tile is not exempt from the budget: the autotuner's
        # candidate filter must reject this, exactly as plan_kernel does.
        ("over-budget-tuned-tile", "vmem-budget",
         KernelCall(entry="gemm", b=128, k=4096, n=4096, b_block=128,
                    n_block=4096, k_block=4096), None),
        ("degenerate-negative-tile", "tile-plan",
         KernelCall(entry="gemv", b=1, k=256, n=512, n_block=-64), None),
        ("unknown-layout", "layout",
         KernelCall(entry="gemv", b=1, k=64, n=64, layout="bitpack4"),
         None),
        ("folded-eight-bit", "folded-weight-bits",
         KernelCall(entry="gemm", b=8, k=256, n=256, wb=8, mode="folded"),
         None),
    ]


def _check_pair(call: KernelCall, ids) -> None:
    plan = plan_kernel(call)
    if ids is not None:
        check_col_ids(ids, call.n, call.window, call.window_block,
                      plan.block_cols, call.kernel)


def run_contracts() -> list[str]:
    """The CI contract pass: sweep the valid matrix (must all hold) and the
    adversarial fixtures (must all trip, with the expected invariant).
    Returns human-readable findings; empty means the gate is green."""
    findings: list[str] = []
    for call, ids in default_matrix():
        try:
            _check_pair(call, ids)
        except ContractViolation as e:
            findings.append(
                f"valid config rejected: {call.kernel} "
                f"B={call.b} K={call.k} N={call.n} {call.layout}: {e}")
    for name, invariant, call, ids in adversarial_fixtures():
        try:
            _check_pair(call, ids)
        except ContractViolation as e:
            if e.invariant != invariant:
                findings.append(
                    f"fixture {name!r} tripped {e.invariant!r}, "
                    f"expected {invariant!r}")
        else:
            findings.append(
                f"adversarial fixture {name!r} did not violate "
                f"{invariant!r}")
    return findings


# ---------------------------------------------------------------------------
# Generated VMEM budget table (docs/kernels.md) — the doc math IS this code.
# ---------------------------------------------------------------------------

DOC_BEGIN = "<!-- BEGIN GENERATED: vmem-budget (python -m repro.analysis --write-docs) -->"
DOC_END = "<!-- END GENERATED: vmem-budget -->"

#: Reference operating point of the documented table: serving decode with
#: a full MXU-aligned tile (Kb = Nb = 256) and the placed example at the
#: ~3 % ECR span the placement benchmark measures (264 columns), rounded
#: up to the 128-lane multiple the placer emits.
_DOC_REF = dict(b=8, k=2048, n=2048, wb=4)
_DOC_PLACED_SPAN = 264
_DOC_PLACED_WINDOW_BLOCK = -(-_DOC_PLACED_SPAN // 128) * 128


def _kib(nbytes: int) -> str:
    return f"{nbytes / 1024:.1f} KiB"


def _doc_plans() -> dict[str, TilePlan]:
    b, k, n, wb = (_DOC_REF[f] for f in ("b", "k", "n", "wb"))
    pwb = _DOC_PLACED_WINDOW_BLOCK
    n_blocks = n // largest_divisor(n, N_BLOCK)
    return {
        "dense": plan_kernel(KernelCall(entry="gemv", b=b, k=k, n=n, wb=wb)),
        "bitpack8": plan_kernel(KernelCall(
            entry="gemv", b=b, k=k, n=n, wb=wb, layout="bitpack8",
            logical_k=k)),
        "placed": plan_kernel(KernelCall(
            entry="gemv", b=b, k=k, n=n, wb=wb, layout="bitpack8",
            logical_k=k, window=n_blocks * pwb, window_block=pwb)),
    }


def render_vmem_table() -> str:
    """The markdown VMEM-budget block docs/kernels.md embeds verbatim."""
    p = _doc_plans()
    d, bp, pl = p["dense"], p["bitpack8"], p["placed"]
    ref = _DOC_REF
    rows = [
        f"Derived from `analysis/contracts.py` at B = {ref['b']}, "
        f"WB = {ref['wb']}, Kb = Nb = 256 (K = N = {ref['k']}); the placed "
        "column streams one window block of "
        f"`window_block = {_DOC_PLACED_WINDOW_BLOCK}` (the ≈ 3 % ECR span "
        f"of {_DOC_PLACED_SPAN} columns rounded up to a multiple of 128 "
        "lanes):",
        "",
        "| per-grid-step block | dense (legacy) | bit-packed "
        "| bit-packed placed |",
        "|---|---|---|---|",
        f"| x `[B, Kb]` int8 | {_kib(d.vmem['x'])} | {_kib(bp.vmem['x'])} "
        f"| {_kib(pl.vmem['x'])} |",
        f"| planes `[WB, Kb(/8), Nb/wb]` | {_kib(d.vmem['planes'])} "
        f"| {_kib(bp.vmem['planes'])} | {_kib(pl.vmem['planes'])} |",
        f"| col_ids `[1, Nb]` int32 | — | — | {_kib(pl.vmem['col_ids'])} |",
        f"| out `[B, Nb]` int32 | {_kib(d.vmem['out'])} "
        f"| {_kib(bp.vmem['out'])} | {_kib(pl.vmem['out'])} |",
        f"| **streamed + out** | **{_kib(d.streamed_bytes + d.vmem['out'])}**"
        f" | **{_kib(bp.streamed_bytes + bp.vmem['out'])}**"
        f" | **{_kib(pl.streamed_bytes + pl.vmem['out'])}** |",
        "",
        "Budget check: double-buffered streaming plus compute transients "
        "(int32 bit-unpack and fold stages, int8 MXU operands, the placed "
        "one-hot column selection) must stay under "
        f"**{VMEM_BUDGET_BYTES // (1024 * 1024)} MiB** per step "
        "(`contracts.VMEM_BUDGET_BYTES`) — totals here: "
        f"dense {_kib(d.vmem_total_bytes)}, "
        f"bit-packed {_kib(bp.vmem_total_bytes)}, "
        f"placed {_kib(pl.vmem_total_bytes)}.",
    ]
    return "\n".join(rows)


def doc_table_block() -> str:
    return f"{DOC_BEGIN}\n{render_vmem_table()}\n{DOC_END}"


def write_doc_table(path) -> None:
    """Splice the generated block between the markers in ``path``."""
    text = open(path, encoding="utf-8").read()
    updated = _replace_block(text, path)
    with open(path, "w", encoding="utf-8") as f:
        f.write(updated)


def _replace_block(text: str, path) -> str:
    start, end = text.find(DOC_BEGIN), text.find(DOC_END)
    if start < 0 or end < 0:
        raise ValueError(f"{path}: generated-block markers not found")
    return text[:start] + doc_table_block() + text[end + len(DOC_END):]


def check_doc_table(path) -> list[str]:
    """Doc-drift gate: the committed table must equal the generated one."""
    try:
        text = open(path, encoding="utf-8").read()
    except OSError:
        return [f"{path}: missing (cannot verify generated VMEM table)"]
    if DOC_BEGIN not in text or DOC_END not in text:
        return [f"{path}: generated-block markers not found"]
    if _replace_block(text, path) != text:
        return [f"{path}: VMEM budget table is stale — run "
                "`python -m repro.analysis --write-docs`"]
    return []
