"""Pure-jnp oracles for the Pallas kernels (ground truth for allclose tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.pud.physics import NEUTRAL, PhysicsParams


def majx_sense_ref(
    charge: jax.Array,        # [T, R, C]
    sense_offset: jax.Array,  # [C]
    noise: jax.Array,         # [T, C]
    params: PhysicsParams = PhysicsParams(),
    n_fracs: int = 0,
) -> jax.Array:
    n_rows = charge.shape[1]
    v = (charge.sum(axis=1) * params.c_cell_ff
         + NEUTRAL * params.c_bitline_ff) / (
        n_rows * params.c_cell_ff + params.c_bitline_ff)
    swing_sq = ((2.0 * (charge - NEUTRAL)) ** 2).sum(axis=1)
    sigma = jnp.sqrt(params.sigma_dynamic**2
                     + params.sigma_frac**2 * float(n_fracs)
                     + params.sigma_transfer**2 * swing_sq)
    return ((v + sigma * noise) > NEUTRAL + sense_offset[None, :]).astype(
        jnp.float32)


def calib_iter_ref(
    inputs: jax.Array,        # [S, M, C] operand bits as float32
    noise: jax.Array,         # [S, C] standard normal
    levels: jax.Array,        # [C] int32
    sense_offset: jax.Array,  # [C]
    level_v: tuple[tuple[float, ...], ...],   # [L][M+1] bitline voltage
    level_sigma: tuple[float, ...],           # [L] sensing noise sigma
    threshold: float,
    maj_inputs: int = 5,
) -> tuple[jax.Array, jax.Array]:
    """Oracle for kernels/majx.calib_iter_fused (same math, pure jnp)."""
    ones = inputs.sum(axis=1)                                  # [S, C]
    v_tab = jnp.asarray(level_v, jnp.float32)                  # [L, M+1]
    v = v_tab[levels[None, :], ones.astype(jnp.int32)]         # [S, C]
    sigma = jnp.asarray(level_sigma, jnp.float32)[levels]      # [C]
    out = (sigma[None, :] * noise
           > (NEUTRAL + sense_offset[None, :]) - v).astype(jnp.float32)
    truth = (ones > maj_inputs // 2).astype(jnp.float32)
    bias = (out - truth).sum(axis=0) / inputs.shape[0]
    step = jnp.where(bias > threshold, -1, 0) + jnp.where(
        bias < -threshold, 1, 0)
    new_levels = jnp.clip(levels + step, 0, len(level_sigma) - 1)
    return new_levels, bias


def bitplane_gemv_ref(x: jax.Array, planes: jax.Array) -> jax.Array:
    """[B,K] int8 x [WB,K,N] bit-planes -> [B,N] int32 signed GeMV."""
    wb = planes.shape[0]
    weights = sum((planes[b].astype(jnp.int32) << b) for b in range(wb))
    weights = weights - (1 << (wb - 1))
    return jax.lax.dot_general(
        x.astype(jnp.int32), weights, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def bitplane_gemv_placed_ref(x: jax.Array, planes: jax.Array,
                             col_ids: jax.Array) -> jax.Array:
    """Placed oracle: gather logical columns out of the physical window
    [WB, K, P] with ``col_ids`` [N], then the plain bit-plane GeMV."""
    return bitplane_gemv_ref(x, jnp.take(planes, col_ids, axis=2))


def pack_bitplanes(w: jax.Array, n_bits: int) -> jax.Array:
    """Signed int weights [K,N] in [-2^{b-1}, 2^{b-1}) -> [WB,K,N] bit-planes.

    Offset-binary: planes encode u = w + 2^{WB-1} in {0 .. 2^WB - 1}.
    """
    u = (w.astype(jnp.int32) + (1 << (n_bits - 1))).astype(jnp.int32)
    shifts = jnp.arange(n_bits, dtype=jnp.int32)
    planes = (u[None] >> shifts[:, None, None]) & 1
    return planes.astype(jnp.int8)


# ---------------------------------------------------------------------------
# Bit-word packing: the HBM layout the bit-packed serving path streams.
#
# Two candidate word axes exist (docs/kernels.md weighs them); the shipped
# format is the K axis: the N axis is the TPU lane axis AND the placement/
# gather axis, so keeping it element-addressable means ``col_ids`` gathers,
# block-aligned placed windows and per-channel scales all work on words
# unchanged, and the in-kernel unpack is a sublane-axis shift-mask-reshape.
# The N-axis uint32 variant is kept for the format comparison + property
# tests only.
# ---------------------------------------------------------------------------


def pack_plane_words(planes: jax.Array) -> jax.Array:
    """Dense bit-planes [WB, K, N] int8 in {0,1} -> [WB, ceil(K/8), N] uint8.

    Eight consecutive K rows fold into one byte, LSB-first: bit j of word i
    is the plane bit at k = i*8 + j.  K pads up to a byte multiple with zero
    bits (harmless: the kernel zero-pads the matching activation rows).
    """
    wb, k, n = planes.shape
    kw = -(-k // 8)
    p = jnp.pad(planes, ((0, 0), (0, kw * 8 - k), (0, 0)))
    p = p.reshape(wb, kw, 8, n).astype(jnp.uint32)
    shifts = jnp.arange(8, dtype=jnp.uint32)
    return (p << shifts[None, None, :, None]).sum(axis=2).astype(jnp.uint8)


def unpack_plane_words(words: jax.Array, k: int | None = None) -> jax.Array:
    """[WB, Kw, N] uint8 words -> dense [WB, k, N] int8 bit-planes.

    Exact inverse of ``pack_plane_words``; ``k`` slices off the byte-pad
    rows (default: all Kw*8 rows).
    """
    wb, kw, n = words.shape
    shifts = jnp.arange(8, dtype=jnp.int32)
    bits = (words.astype(jnp.int32)[:, :, None, :]
            >> shifts[None, None, :, None]) & 1
    planes = bits.reshape(wb, kw * 8, n).astype(jnp.int8)
    return planes[:, : (kw * 8 if k is None else k), :]


def pack_plane_words_n(planes: jax.Array) -> jax.Array:
    """The rejected candidate axis: [WB, K, N] -> [WB, K, ceil(N/32)] uint32.

    32 consecutive N columns fold into one word, LSB-first.  Kept for the
    round-trip property tests that justify the K-axis choice — packing the
    lane axis would force a lane-interleaving unpack in-kernel and break
    column addressability (placement gathers, per-channel scales).
    """
    wb, k, n = planes.shape
    nw = -(-n // 32)
    p = jnp.pad(planes, ((0, 0), (0, 0), (0, nw * 32 - n)))
    p = p.reshape(wb, k, nw, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return (p << shifts[None, None, None, :]).sum(axis=3).astype(jnp.uint32)


def unpack_plane_words_n(words: jax.Array, n: int | None = None) -> jax.Array:
    """Inverse of ``pack_plane_words_n``: [WB, K, Nw] uint32 -> [WB, K, n]."""
    wb, k, nw = words.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:, :, :, None] >> shifts[None, None, None, :]) & 1
    planes = bits.reshape(wb, k, nw * 32).astype(jnp.int8)
    return planes[:, :, : (nw * 32 if n is None else n)]
