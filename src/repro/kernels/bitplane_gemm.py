"""Pallas TPU kernel: bit-plane GEMM — the batched MVDRAM compute pattern.

``bitplane_gemv.py`` keeps the whole activation batch in one VMEM block,
which is exactly right for the single-vector decode the paper evaluates but
wrong for a serving engine: a continuous-batching scheduler feeds the array
``(B, K)`` operand matrices whose B is the number of in-flight requests (or
B*S prefill rows), and a single unblocked batch axis either blows the VMEM
budget or serializes the MXU.

This kernel is the GEMM generalization: the same HBM bit-plane layout
(weights as WB planes W_b in {0,1} — what a PUD subarray holds), with the
batch axis tiled into the grid:

    grid (B/Bb, N/Nb, K/Kb);  blocks x [Bb, Kb] int8,
    planes [WB, Kb, Nb] int8 (dense) or [WB, Kb/8, Nb] uint8 (bit-packed),
    out [Bb, Nb] int32.

K is the reduction axis (innermost, accumulated in the output block — the
out block index depends only on (b, n)).  Both execution modes of the GeMV
kernel carry over unchanged (``planes`` = one MXU pass per bit-plane,
``folded`` = planes folded to int8 in VMEM, one pass per K-tile), both
storage layouts too (``bitpack8`` words unpack inside VMEM — see
bitplane_gemv.py), and the placed variant fuses the logical->physical
column gather exactly like ``bitplane_gemv_placed``, streaming one
block-aligned window block per grid step.

Ragged batches (a continuous-batching step whose live-slot count is not a
tile multiple) are handled here: B pads up to the batch tile with zero rows,
which cannot perturb other rows — every output element is an independent
integer dot product — and the pad is sliced off after the kernel.  Bit-exact
vs a row-vmapped ``bitplane_gemv`` (enforced in tests/test_bitplane_gemm.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis.errors import ContractViolation

from .platform import interpret_mode

# The kernel bodies are the GeMV ones with the K reduction axis moved to
# grid position 2 (after the new batch axis); only the grid/BlockSpec
# plumbing differs.
from .bitplane_gemv import (_gemv_kernel, _gemv_placed_kernel, _k_tiling,
                            _n_tiling, _placed_n_block, _sign_fix,
                            check_folded_bits)

B_BLOCK = 128
K_BLOCK = 256
N_BLOCK = 256


def _pad_batch(x: jax.Array, bb: int) -> jax.Array:
    b = x.shape[0]
    if b % bb == 0:
        return x
    return jnp.pad(x, ((0, bb - b % bb), (0, 0)))


def _batch_block(b: int, b_block: int | None, kernel: str) -> int:
    """Batch tile: an explicit tuned block (ragged batches pad with zero
    rows, sliced off after the kernel) or the VMEM-bounded default."""
    if b_block is None:
        return min(b, B_BLOCK)
    if b_block <= 0:
        raise ContractViolation(
            kernel, "tile-plan", f"b_block {b_block} must be positive")
    return min(b_block, b)


@functools.partial(
    jax.jit,
    static_argnames=("mode", "interpret", "layout", "logical_k",
                     "b_block", "n_block", "k_block"))
def bitplane_gemm(
    x: jax.Array,        # [B, K] int8 activations (any B, padded here)
    planes: jax.Array,   # [WB, K, N] int8 bits | [WB, K/8, N] uint8 words
    mode: str = "planes",
    interpret: bool | None = None,
    layout: str = "dense",
    logical_k: int | None = None,
    b_block: int | None = None,
    n_block: int | None = None,
    k_block: int | None = None,
) -> jax.Array:
    """Batched offset-binary bit-plane GEMM; returns [B, N] int32 of
    x @ (W - 2^{WB-1}).  Bit-exact vs ``bitplane_gemv`` row by row.
    ``b_block``/``n_block``/``k_block`` are tuned tile overrides
    (kernels/autotune.py); non-multiple shapes pad with zeros."""
    b, k = x.shape
    wb, _, n = planes.shape
    check_folded_bits("bitplane_gemm", mode, wb)
    xp, pp, pkb, xkb, k_steps = _k_tiling(x, planes, layout, logical_k,
                                          kernel="bitplane_gemm",
                                          k_block=k_block)
    nb, n_pad = _n_tiling(n, n_block, "bitplane_gemm")
    if n_pad != n:                       # zero columns, sliced off below
        pp = jnp.pad(pp, ((0, 0), (0, 0), (0, n_pad - n)))
    bb = _batch_block(b, b_block, "bitplane_gemm")
    xp = _pad_batch(xp, bb)
    bp = xp.shape[0]
    grid = (bp // bb, n_pad // nb, k_steps)
    kernel = functools.partial(_gemv_kernel, mode=mode, n_bits=wb, k_axis=2,
                               packed=(layout == "bitpack8"))
    unsigned = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, xkb), lambda jb, jn, jk: (jb, jk)),
            pl.BlockSpec((wb, pkb, nb), lambda jb, jn, jk: (0, jk, jn)),
        ],
        out_specs=pl.BlockSpec((bb, nb), lambda jb, jn, jk: (jb, jn)),
        out_shape=jax.ShapeDtypeStruct((bp, n_pad), jnp.int32),
        interpret=interpret_mode(interpret),
    )(xp, pp)
    return unsigned[:b, :n] - _sign_fix(x, wb)


@functools.partial(
    jax.jit,
    static_argnames=("mode", "interpret", "layout", "logical_k",
                     "window_block", "b_block", "n_block", "k_block"))
def bitplane_gemm_placed(
    x: jax.Array,         # [B, K] int8 activations
    planes: jax.Array,    # [WB, K(/8), W] physical window (placed layout)
    col_ids: jax.Array,   # [N] int32 logical -> window column map
    mode: str = "planes",
    interpret: bool | None = None,
    layout: str = "dense",
    logical_k: int | None = None,
    window_block: int | None = None,
    b_block: int | None = None,
    n_block: int | None = None,
    k_block: int | None = None,
) -> jax.Array:
    """Column-placed batched GEMM; returns [B, N] like ``bitplane_gemm``.

    ``planes`` is the block-aligned physically-permuted window layout a
    placement-aware packer emits (repro/pud/placement.py); the gather is
    fused into the kernel per N-block, streaming ``window_block`` window
    columns per grid step (None = whole window as one block, the degenerate
    hand-built-pack case).  Bit-exact vs ``bitplane_gemv_placed`` row by
    row.
    """
    b, k = x.shape
    wb, _, w_len = planes.shape
    (n,) = col_ids.shape
    check_folded_bits("bitplane_gemm_placed", mode, wb)
    xp, pp, pkb, xkb, k_steps = _k_tiling(x, planes, layout, logical_k,
                                          kernel="bitplane_gemm_placed",
                                          k_block=k_block)
    pwb = window_block or w_len
    if w_len % pwb or n % (w_len // pwb):
        raise ContractViolation(
            "bitplane_gemm_placed", "window-tiling",
            f"window length {w_len} / window_block {pwb} does not tile "
            f"N={n}")
    block_cols = n // (w_len // pwb)
    nb = _placed_n_block(n_block, block_cols, "bitplane_gemm_placed")
    bb = _batch_block(b, b_block, "bitplane_gemm_placed")
    xp = _pad_batch(xp, bb)
    bp = xp.shape[0]
    grid = (bp // bb, n // nb, k_steps)
    kernel = functools.partial(_gemv_placed_kernel, mode=mode, n_bits=wb,
                               k_axis=2, packed=(layout == "bitpack8"),
                               window_block=pwb)
    unsigned = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, xkb), lambda jb, jn, jk: (jb, jk)),
            pl.BlockSpec((1, nb), lambda jb, jn, jk: (0, jn)),
            # one window block per grid step (block-aligned placed layout)
            pl.BlockSpec((wb, pkb, pwb),
                         lambda jb, jn, jk, _nb=nb, _bc=block_cols:
                         (0, jk, (jn * _nb) // _bc)),
        ],
        out_specs=pl.BlockSpec((bb, nb), lambda jb, jn, jk: (jb, jn)),
        out_shape=jax.ShapeDtypeStruct((bp, n), jnp.int32),
        interpret=interpret_mode(interpret),
    )(xp, col_ids.astype(jnp.int32)[None, :], pp)
    return unsigned[:b] - _sign_fix(x, wb)
