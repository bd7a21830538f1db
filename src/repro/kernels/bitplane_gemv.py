"""Pallas TPU kernel: bit-plane GeMV — the MVDRAM compute pattern on the MXU.

MVDRAM [4] executes low-bit GeMV inside DRAM: weight bits live as *bit-planes*
across 65 536 columns and the product accumulates bit-serially through
MAJ-based adders.  PUDTune's calibration is what makes enough columns reliable
for this to pay off.

TPU adaptation (DESIGN.md §3): bit-serial column adders would waste the MXU.
The TPU-native equivalent keeps the **same HBM data layout** — weights stored
as WB bit-planes W_b in {0,1}, exactly what a PUD subarray would hold — and
turns the bit-serial accumulation into matmuls:

    y = x @ W - 2^{WB-1} * sum_k(x_k)        with  W = sum_b 2^b W_b
      = sum_b 2^b (x @ W_b) - offset         (offset-binary signed weights)

Two execution modes, both lowered by this kernel and oracled by ref.py:

  * ``planes``  — faithful PUD schedule: one MXU pass per bit-plane,
    partial products shifted and accumulated (what the DRAM does, made dense).
  * ``folded``  — beyond-paper optimization: planes are folded to int8 inside
    VMEM (sum_b 2^b W_b) and a single MXU pass per K-tile does the work —
    WB x fewer MXU flops at identical numerics.

Two storage layouts, selected by the static ``layout`` argument:

  * ``"dense"``    — one int8 byte per weight bit, planes [WB, K, N].  The
    legacy format; 8x more HBM bytes than the bits it encodes.
  * ``"bitpack8"`` — eight K rows per uint8 word, planes [WB, ceil(K/8), N].
    The word axis is K (the sublane axis): the unpack inside VMEM is a
    broadcast-shift-mask plus a sublane reshape, while N — the 128-lane
    axis and the placement/gather axis — stays element-addressable.  HBM ->
    VMEM weight traffic and streamed plane residency drop 8x; the dense
    tile exists only as a transient inside the compute stage.

Tiling: grid (N/Nb, K/Kb); K is the reduction axis, accumulated in the output
block across grid steps (out block depends only on the N index).  Block sizes
adapt to the operand: the preferred MXU-aligned tiles are Kb=256, Nb=256, and
non-multiple shapes fall back to the largest divisor (mirroring the GEMM
batch-pad path) instead of asserting.  VMEM per grid step at Kb=Nb=256, WB=4,
B=8: dense streams 4*256*256 planes + 8*256 x + 8*256*4 out ~ 266 KiB;
bit-packed streams 4*32*256 words instead of the planes ~ 42 KiB (see
docs/kernels.md for the full budget math, including the placed window).

The placed variant consumes the *block-aligned* physical window layout
(repro/pud/placement.py): logical N-block j's columns all live inside window
slice [j*window_block, (j+1)*window_block), so the window axis blocks like
any other axis — ``window_block`` columns per grid step instead of the whole
physical window P, and placed VMEM residency is set by the tile, not the
fleet window size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis.errors import ContractViolation

from .platform import interpret_mode

K_BLOCK = 256
N_BLOCK = 256

LAYOUTS = ("dense", "bitpack8")

#: Smallest useful block width: a dimension whose largest divisor under the
#: cap falls below this (a prime N or K, e.g. 509 or 127) would serialize
#: the grid into 1-wide tiles, so the heuristic pads to a power-of-two
#: block instead.
DEGENERATE_TILE_FLOOR = 8


def _largest_divisor(dim: int, cap: int) -> int:
    """Largest block size <= cap that divides dim (>= 1)."""
    for d in range(min(dim, cap), 0, -1):
        if dim % d == 0:
            return d
    return 1


def _pow2_block(dim: int, cap: int) -> int:
    """Smallest power of two >= dim, capped at ``cap`` — the padded-block
    fallback for dimensions without a useful divisor."""
    p = 1
    while p < dim and p < cap:
        p *= 2
    return p


def _heuristic_block(dim: int, cap: int) -> int:
    """Divisor heuristic with the degenerate-tile fix.

    A dim with no divisor >= :data:`DEGENERATE_TILE_FLOOR` under the cap
    (prime N or K) used to select 1-wide tiles and silently serialize the
    grid; it now falls back to a padded power-of-two block (the pad is
    zeros, which contribute nothing to the integer dot products and are
    sliced off the output).
    """
    d = _largest_divisor(dim, cap)
    if d < min(dim, DEGENERATE_TILE_FLOOR):
        return _pow2_block(dim, cap)
    return d


def _unpack_bits(words: jax.Array) -> jax.Array:
    """In-VMEM unpack: [WB, Kw, Nb] uint8 words -> [WB, Kw*8, Nb] int8 bits.

    Broadcast-shift-mask along the sublane (K) axis, LSB-first — the exact
    inverse of ``ref.pack_plane_words``.  The dense tile is a compute-stage
    transient; only the 8x smaller words stream HBM -> VMEM.
    """
    wb, kw, nb = words.shape
    shifts = jnp.arange(8, dtype=jnp.int32)
    bits = (words.astype(jnp.int32)[:, :, None, :]
            >> shifts[None, None, :, None]) & 1
    return bits.reshape(wb, kw * 8, nb).astype(jnp.int8)


def _dot_i8(a: jax.Array, b: jax.Array) -> jax.Array:
    """[M, K] int8 x [K, N] int8 -> [M, N] int32 on the MXU."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _accumulate(x, planes, out_shape, mode: str, n_bits: int):
    """Shared MXU accumulation: x [B, Kb] int8 x planes [WB, Kb, Nb] int8
    -> [B, Nb] int32.  Both MXU operands stay int8 (the chip's integer
    matmul takes int8 x int8 -> int32, not int32 operands)."""
    if mode == "folded":
        # Fold bit-planes to weights in VMEM, single MXU pass.  A folded
        # weight is <= 2^WB - 1, which fits int8 for WB <= 7 (the wrappers
        # reject WB = 8 in this mode).
        w = jnp.zeros(planes.shape[1:], jnp.int32)
        for b in range(n_bits):
            w = w + (planes[b].astype(jnp.int32) << b)
        return _dot_i8(x, w.astype(jnp.int8))
    # Faithful PUD schedule: one pass per plane, shift-accumulate.
    acc = jnp.zeros(out_shape, jnp.int32)
    for b in range(n_bits):
        acc = acc + (_dot_i8(x, planes[b]) << b)
    return acc


def _select_columns(planes: jax.Array, cols: jax.Array) -> jax.Array:
    """Gather window columns: [WB, Kr, P] x cols [1, Nb] -> [WB, Kr, Nb].

    A one-hot selection matrix S[p, n] = (p == cols[n]) applied on the
    MXU — the form the chip's compiler lowers (it has no general 3-D
    gather).  Exact: every output element is one stored value times 1.
    Dense 0/1 planes select in int8; bit-packed words (0..255) select in
    bf16, which holds every byte value exactly.
    """
    wb, kr, p = planes.shape
    nb = cols.shape[-1]
    hit = jax.lax.broadcasted_iota(jnp.int32, (p, nb), 0) == cols
    flat = planes.reshape(wb * kr, p)
    if planes.dtype == jnp.uint8:
        sel = jax.lax.dot_general(
            flat.astype(jnp.int32).astype(jnp.bfloat16),
            hit.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        sel = sel.astype(jnp.int32).astype(jnp.uint8)
    else:
        sel = _dot_i8(flat, hit.astype(jnp.int8)).astype(jnp.int8)
    return sel.reshape(wb, kr, nb)


def check_folded_bits(kernel: str, mode: str, n_bits: int) -> None:
    """Folded mode keeps weights in int8: reject WB > 7."""
    if mode == "folded" and n_bits > 7:
        raise ContractViolation(
            kernel, "folded-weight-bits",
            f"folded mode needs weight_bits <= 7 (an int8 MXU operand), "
            f"got {n_bits}; use mode='planes'")


def _gemv_kernel(x_ref, planes_ref, out_ref, *, mode: str, n_bits: int,
                 k_axis: int = 1, packed: bool = False):
    """``k_axis`` names the grid position of the K reduction axis: 1 for
    the GeMV grid (N, K), 2 for the batch-tiled GEMM grid (B, N, K) —
    bitplane_gemm.py reuses this body with k_axis=2.  ``packed`` marks the
    bit-word layout: the plane tile unpacks inside VMEM."""
    k_idx = pl.program_id(k_axis)

    @pl.when(k_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]                                # [B, Kb] int8
    planes = planes_ref[...]
    if packed:
        planes = _unpack_bits(planes)
    out_ref[...] += _accumulate(x, planes, out_ref.shape, mode, n_bits)


def _gemv_placed_kernel(x_ref, cols_ref, planes_ref, out_ref, *,
                        mode: str, n_bits: int, k_axis: int = 1,
                        packed: bool = False, window_block: int = 0):
    """Placed variant: gather physical columns inside the kernel.

    ``planes_ref`` holds ONE window block [WB, Kb(/8), window_block] of this
    tensor's physical region — the block-aligned placed layout guarantees
    the output block's logical columns all live inside it.  ``cols_ref``
    [1, Nb] carries absolute window positions; the in-block residue is a
    modulo.  The gather is fused with the matmul — the permuted planes
    never round-trip through HBM — and runs on the words *before* the
    unpack in the bit-packed layout (8x cheaper gather).
    """
    k_idx = pl.program_id(k_axis)

    @pl.when(k_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]                                # [B, Kb] int8
    cols = cols_ref[...] % window_block           # [1, Nb] in-block residues
    planes = _select_columns(planes_ref[...], cols)   # [WB, Kb(/8), Nb]
    if packed:
        planes = _unpack_bits(planes)
    out_ref[...] += _accumulate(x, planes, out_ref.shape, mode, n_bits)


def _sign_fix(x: jax.Array, wb: int) -> jax.Array:
    """Offset-binary correction shared by the GeMV and GEMM wrappers:
    planes encode u = w + 2^{WB-1}, so the signed result subtracts
    2^{WB-1} * sum_k x_k per row."""
    return (1 << (wb - 1)) * x.astype(jnp.int32).sum(axis=1, keepdims=True)


def _k_tiling(x: jax.Array, planes: jax.Array, layout: str,
              logical_k: int | None, kernel: str = "bitplane_gemv",
              k_block: int | None = None):
    """Resolve the K-axis tiling for either storage layout.

    Returns (x_padded, planes_padded, planes_k_block, x_k_block, k_steps):
    both operands padded so the block tiles them exactly, the plane/word
    block height, the matching x block width, and the K grid extent.
    Padded x rows are zero, padded word bits are zero, and the sign fix is
    computed from the un-padded x — so the pad contributes exactly nothing
    on both sides.  ``k_block`` is an explicit tuned block in logical-K
    units (a multiple of 8 for bitpack8, where it names whole word rows);
    None picks the degenerate-safe divisor heuristic.  ``kernel`` names
    the entry point in ``ContractViolation`` errors (the same invariants
    the static checker in repro/analysis/contracts.py verifies without
    executing anything).
    """
    k = x.shape[1]
    x = x.astype(jnp.int8)               # the MXU operand dtype
    if layout == "bitpack8":
        kw = planes.shape[1]
        if (logical_k or kw * 8) != k or k > kw * 8:
            raise ContractViolation(
                kernel, "bitpack8-logical-k",
                f"x K={k} inconsistent with word planes Kw={kw} "
                f"(logical_k={logical_k})")
        if k_block is not None:
            if k_block <= 0 or k_block % 8:
                raise ContractViolation(
                    kernel, "tile-plan",
                    f"bitpack8 k_block {k_block} must be a positive "
                    "multiple of 8 (whole word rows)")
            kwb = k_block // 8
        else:
            kwb = _heuristic_block(kw, K_BLOCK // 8)
        kw_pad = -(-kw // kwb) * kwb
        if kw_pad != kw:                 # zero words unpack to zero bits
            planes = jnp.pad(planes, ((0, 0), (0, kw_pad - kw), (0, 0)))
        xp = (jnp.pad(x, ((0, 0), (0, kw_pad * 8 - k)))
              if kw_pad * 8 != k else x)
        return xp, planes, kwb, kwb * 8, kw_pad // kwb
    if layout != "dense":
        raise ContractViolation(
            kernel, "layout",
            f"unknown plane layout {layout!r}; one of {LAYOUTS}")
    if planes.shape[1] != k:
        raise ContractViolation(
            kernel, "k-mismatch",
            f"x {tuple(x.shape)} vs planes {tuple(planes.shape)}")
    if k_block is not None:
        if k_block <= 0:
            raise ContractViolation(
                kernel, "tile-plan", f"k_block {k_block} must be positive")
        kb = k_block
    else:
        kb = _heuristic_block(k, K_BLOCK)
    k_pad = -(-k // kb) * kb
    if k_pad != k:                       # zero x cols x zero plane rows
        x = jnp.pad(x, ((0, 0), (0, k_pad - k)))
        planes = jnp.pad(planes, ((0, 0), (0, k_pad - k), (0, 0)))
    return x, planes, kb, kb, k_pad // kb


def _n_tiling(n: int, n_block: int | None, kernel: str) -> tuple[int, int]:
    """(nb, n_pad) for the logical kernels: an explicit tuned block (the
    operand pads up to a multiple, pad columns are zero planes sliced off
    the output) or the degenerate-safe divisor heuristic."""
    if n_block is not None:
        if n_block <= 0:
            raise ContractViolation(
                kernel, "tile-plan", f"n_block {n_block} must be positive")
        nb = n_block
    else:
        nb = _heuristic_block(n, N_BLOCK)
    return nb, -(-n // nb) * nb


def _placed_n_block(n_block: int | None, block_cols: int,
                    kernel: str) -> int:
    """Placed N-tile: an explicit tuned block must divide the per-window
    logical column count (the placed layout cannot pad the window axis);
    None keeps the divisor heuristic."""
    if n_block is None:
        return _largest_divisor(block_cols, N_BLOCK)
    if n_block <= 0 or block_cols % n_block:
        raise ContractViolation(
            kernel, "tile-plan",
            f"placed n_block {n_block} must divide the {block_cols} "
            "logical columns per window block")
    return n_block


@functools.partial(
    jax.jit,
    static_argnames=("mode", "interpret", "layout", "logical_k",
                     "n_block", "k_block"))
def bitplane_gemv(
    x: jax.Array,        # [B, K] int8 activations
    planes: jax.Array,   # [WB, K, N] int8 bits | [WB, K/8, N] uint8 words
    mode: str = "planes",
    interpret: bool | None = None,
    layout: str = "dense",
    logical_k: int | None = None,
    n_block: int | None = None,
    k_block: int | None = None,
) -> jax.Array:
    """Offset-binary bit-plane GeMV; returns [B, N] int32 of x @ (W - 2^{WB-1}).

    ``planes`` encode unsigned u = w + 2^{WB-1}; the signed correction
    subtracts 2^{WB-1} * sum_k x_k per output.  ``layout`` selects dense
    int8 planes or K-axis bit-words (unpacked inside the kernel).
    ``n_block``/``k_block`` are tuned tile overrides (kernels/autotune.py);
    non-multiple shapes pad with zeros, which the integer dot products
    never see.
    """
    b, k = x.shape
    wb, _, n = planes.shape
    check_folded_bits("bitplane_gemv", mode, wb)
    xp, pp, pkb, xkb, k_steps = _k_tiling(x, planes, layout, logical_k,
                                          k_block=k_block)
    nb, n_pad = _n_tiling(n, n_block, "bitplane_gemv")
    if n_pad != n:                       # zero columns, sliced off below
        pp = jnp.pad(pp, ((0, 0), (0, 0), (0, n_pad - n)))
    grid = (n_pad // nb, k_steps)
    kernel = functools.partial(_gemv_kernel, mode=mode, n_bits=wb,
                               packed=(layout == "bitpack8"))
    unsigned = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, xkb), lambda jn, jk: (0, jk)),
            pl.BlockSpec((wb, pkb, nb), lambda jn, jk: (0, jk, jn)),
        ],
        out_specs=pl.BlockSpec((b, nb), lambda jn, jk: (0, jn)),
        out_shape=jax.ShapeDtypeStruct((b, n_pad), jnp.int32),
        interpret=interpret_mode(interpret),
    )(xp, pp)
    return unsigned[:, :n] - _sign_fix(x, wb)


@functools.partial(
    jax.jit,
    static_argnames=("mode", "interpret", "layout", "logical_k",
                     "window_block", "n_block", "k_block"))
def bitplane_gemv_placed(
    x: jax.Array,         # [B, K] int8 activations
    planes: jax.Array,    # [WB, K(/8), W] physical window (placed layout)
    col_ids: jax.Array,   # [N] int32 logical -> window column map
    mode: str = "planes",
    interpret: bool | None = None,
    layout: str = "dense",
    logical_k: int | None = None,
    window_block: int | None = None,
    n_block: int | None = None,
    k_block: int | None = None,
) -> jax.Array:
    """Column-placed bit-plane GeMV; returns [B, N] like ``bitplane_gemv``.

    ``planes`` is the physically-permuted layout a placement-aware packer
    emits (repro/pud/placement.py): logical column n of the projection lives
    at window position ``col_ids[n]``; the remaining window columns belong
    to faulty/unused physical columns and are never read.  ``window_block``
    is the block-aligned window stride — logical N-block j's columns sit
    inside window slice [j*window_block, (j+1)*window_block), so the kernel
    streams one window block per grid step (None treats the whole window as
    a single block, the degenerate case for hand-built packs).  The gather
    is fused into the kernel per N-block.  Bit-exact vs
    ``ref.bitplane_gemv_placed_ref``.
    """
    b, k = x.shape
    wb, _, w_len = planes.shape
    (n,) = col_ids.shape
    check_folded_bits("bitplane_gemv_placed", mode, wb)
    xp, pp, pkb, xkb, k_steps = _k_tiling(x, planes, layout, logical_k,
                                          kernel="bitplane_gemv_placed",
                                          k_block=k_block)
    pwb = window_block or w_len
    if w_len % pwb or n % (w_len // pwb):
        raise ContractViolation(
            "bitplane_gemv_placed", "window-tiling",
            f"window length {w_len} / window_block {pwb} does not tile "
            f"N={n}")
    block_cols = n // (w_len // pwb)
    nb = _placed_n_block(n_block, block_cols, "bitplane_gemv_placed")
    grid = (n // nb, k_steps)
    kernel = functools.partial(_gemv_placed_kernel, mode=mode, n_bits=wb,
                               packed=(layout == "bitpack8"),
                               window_block=pwb)
    unsigned = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, xkb), lambda jn, jk: (0, jk)),
            pl.BlockSpec((1, nb), lambda jn, jk: (0, jn)),
            # one window block per grid step: the block-aligned layout
            # bounds the gather to this output block's window slice
            pl.BlockSpec((wb, pkb, pwb),
                         lambda jn, jk, _nb=nb, _bc=block_cols:
                         (0, jk, (jn * _nb) // _bc)),
        ],
        out_specs=pl.BlockSpec((b, nb), lambda jn, jk: (0, jn)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.int32),
        interpret=interpret_mode(interpret),
    )(xp, col_ids.astype(jnp.int32)[None, :], pp)
    return unsigned - _sign_fix(x, wb)
