"""Pallas TPU kernel: column-parallel SiMRA/MAJX charge-sharing sensing.

This is the hot loop of calibration and ECR measurement: for a batch of
SiMRA events (trials), share charge across the 8 opened rows of every column,
add sensing noise, and compare against the per-column threshold.

TPU mapping (hardware adaptation, DESIGN.md §3): a DRAM subarray's 65 536
columns map to TPU lanes; one SiMRA event is a small reduction over the
8-row axis.  The kernel tiles [trials × columns] into VMEM blocks of
(TRIAL_BLOCK, 8, COL_BLOCK) charge + (TRIAL_BLOCK, COL_BLOCK) noise, with
COL_BLOCK a multiple of 128 lanes.  All math is VPU elementwise + an 8-wide
reduction — memory-bound by design, so the BlockSpec keeps each block's
working set (8+2 planes * 4 B * COL_BLOCK) comfortably inside VMEM.

Noise is passed in as standard-normal draws (host PRNG) so the kernel is
deterministic and bit-exact against ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis.errors import ContractViolation
from repro.pud.physics import NEUTRAL, PhysicsParams

from .platform import interpret_mode

TRIAL_BLOCK = 8
COL_BLOCK = 1024


def _majx_kernel(charge_ref, offset_ref, noise_ref, out_ref, *,
                 params: PhysicsParams, n_fracs: int):
    charge = charge_ref[...]                      # [Tb, R, Cb]
    offset = offset_ref[...]                      # [Cb]
    noise = noise_ref[...]                        # [Tb, Cb]
    n_rows = charge.shape[1]

    q_sum = charge.sum(axis=1)                    # [Tb, Cb]
    v = (q_sum * params.c_cell_ff + NEUTRAL * params.c_bitline_ff) / (
        n_rows * params.c_cell_ff + params.c_bitline_ff)
    swing_sq = ((2.0 * (charge - NEUTRAL)) ** 2).sum(axis=1)
    var = (params.sigma_dynamic ** 2
           + params.sigma_frac ** 2 * float(n_fracs)
           + params.sigma_transfer ** 2 * swing_sq)
    sigma = jnp.sqrt(var)
    bits = (v + sigma * noise) > (NEUTRAL + offset[None, :])
    out_ref[...] = bits.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Fused calibration iteration (Algorithm 1 inner loop in one pass).
# ---------------------------------------------------------------------------
#
# The unfused path runs three jitted stages per iteration — levels_to_charges
# (gather), maj_outputs (sense), bias/level-step (reduce + select) — each
# round-tripping [S, C]-shaped intermediates through HBM.  This kernel fuses
# them: per column block it looks up the sensing's bitline voltage and noise
# sigma (static tables, below), senses all sample blocks while accumulating
# the per-column bias in the output block (revisited across the innermost
# sample-grid axis), and applies the threshold level step on the last sample
# block.  One HBM read of the inputs, one write of [C] levels + [C] bias.
#
# Operand bits are 0/1, so one sensing's charge sum is (ones among the M
# operands + the level's calibration-row sum + constant rows) and its
# swing^2 sum is (M + the level's calibration-row swing + constant rows):
# the voltage depends only on (level, ones) and the sigma only on the level.
# Both come in as static tables (``core/fleet.sense_tables``) and the
# decision is ``sigma * noise > (0.5 + offset) - v`` — a product and two
# differences, no division, square root or multiply-add a compiler could
# contract — so this kernel and ``ref.calib_iter_ref`` decide identically on
# every backend, not just on the CPU.

CAL_SAMPLE_BLOCKS = (64, 32, 16, 8, 4, 2, 1)
CAL_COL_BLOCKS = (1024, 512, 256, 128)


def _pick_block(n: int, candidates: tuple[int, ...],
                kernel: str = "calib_iter_fused") -> int:
    for c in candidates:
        if n % c == 0:
            return c
    raise ContractViolation(
        kernel, "block-selection",
        f"no block size in {candidates} divides {n}")


def _calib_iter_kernel(inputs_ref, noise_ref, levels_ref, offset_ref,
                       levels_out_ref, bias_ref, *,
                       level_v: tuple[tuple[float, ...], ...],
                       level_sigma: tuple[float, ...],
                       n_samples: int, n_sample_blocks: int,
                       threshold: float, maj_inputs: int):
    j = pl.program_id(2)                          # sample-block (innermost)

    @pl.when(j == 0)
    def _init():
        bias_ref[...] = jnp.zeros_like(bias_ref)

    levels = levels_ref[...]                      # [1, Cb] int32
    inp = inputs_ref[...]                         # [Sb, M, Cb] bits as f32
    noise = noise_ref[...]                        # [Sb, Cb]
    offset = offset_ref[...]                      # [1, Cb]

    # Table lookups as static selects (no dynamic gather on the chip):
    # per column, the voltage for every ones-count and the sigma.
    sigma = jnp.zeros(levels.shape, jnp.float32)
    v_by_ones = [jnp.zeros(levels.shape, jnp.float32)
                 for _ in range(maj_inputs + 1)]
    for lvl, (vs, sg) in enumerate(zip(level_v, level_sigma)):
        sel = levels == lvl
        sigma = jnp.where(sel, jnp.float32(sg), sigma)
        v_by_ones = [jnp.where(sel, jnp.float32(v), acc)
                     for v, acc in zip(vs, v_by_ones)]
    ones = inp.sum(axis=1)                        # [Sb, Cb], exact 0..M
    v = jnp.zeros(ones.shape, jnp.float32)
    for k, vk in enumerate(v_by_ones):
        v = jnp.where(ones == k, vk, v)
    out = (sigma * noise > (NEUTRAL + offset) - v).astype(jnp.float32)
    truth = (ones > maj_inputs // 2).astype(jnp.float32)
    bias_ref[...] += (out - truth).sum(axis=0, keepdims=True) / n_samples

    @pl.when(j == n_sample_blocks - 1)
    def _step():
        bias = bias_ref[...]
        step = jnp.where(bias > threshold, -1, 0) + jnp.where(
            bias < -threshold, 1, 0)
        levels_out_ref[...] = jnp.clip(
            levels + step, 0, len(level_sigma) - 1)


@functools.partial(
    jax.jit, static_argnames=("level_v", "level_sigma", "threshold",
                              "maj_inputs", "interpret"))
def calib_iter_fused(
    inputs: jax.Array,        # [G?, S, M, C] float32 operand bits
    noise: jax.Array,         # [G?, S, C] float32 standard normal
    levels: jax.Array,        # [G?, C] int32 current ladder levels
    sense_offset: jax.Array,  # [G?, C] float32
    level_v: tuple[tuple[float, ...], ...],   # [L][M+1] bitline voltage
    level_sigma: tuple[float, ...],           # [L] sensing noise sigma
    threshold: float,
    maj_inputs: int = 5,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One fused Algorithm-1 iteration; returns (new levels, bias), each
    [G?, C].  ``level_v``/``level_sigma`` come from
    ``core/fleet.sense_tables``.

    An optional leading subarray axis G is part of the kernel grid (a
    ``vmap`` of the kernel would give its per-column blocks a squeezed
    sublane dimension, which the chip's compiler does not tile).  The
    per-column vectors travel as [G, 1, C] so every block's last two
    dimensions are (1, Cb).
    """
    batched = inputs.ndim == 4
    if not batched:
        inputs, noise = inputs[None], noise[None]
        levels, sense_offset = levels[None], sense_offset[None]
    g, s, m, c = inputs.shape
    sb = _pick_block(s, CAL_SAMPLE_BLOCKS)
    cb = _pick_block(c, CAL_COL_BLOCKS)
    grid = (g, c // cb, s // sb)                  # sample axis innermost
    kernel = functools.partial(
        _calib_iter_kernel, level_v=level_v, level_sigma=level_sigma,
        n_samples=s, n_sample_blocks=s // sb, threshold=threshold,
        maj_inputs=maj_inputs)
    col = pl.BlockSpec((None, 1, cb), lambda gi, i, j: (gi, 0, i))
    new_levels, bias = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, sb, m, cb), lambda gi, i, j: (gi, j, 0, i)),
            pl.BlockSpec((None, sb, cb), lambda gi, i, j: (gi, j, i)),
            col,
            col,
        ],
        out_specs=[col, col],
        out_shape=[
            jax.ShapeDtypeStruct((g, 1, c), jnp.int32),
            jax.ShapeDtypeStruct((g, 1, c), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(inputs, noise, levels[:, None, :], sense_offset[:, None, :])
    new_levels, bias = new_levels[:, 0], bias[:, 0]
    if not batched:
        return new_levels[0], bias[0]
    return new_levels, bias


@functools.partial(jax.jit, static_argnames=("params", "n_fracs", "interpret"))
def majx_sense(
    charge: jax.Array,        # [T, R, C] float32 cell charges (V_DD units)
    sense_offset: jax.Array,  # [C] float32
    noise: jax.Array,         # [T, C] float32 standard normal
    params: PhysicsParams = PhysicsParams(),
    n_fracs: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """Sensed bits [T, C] for T SiMRA events over C columns."""
    t, r, c = charge.shape
    if t % TRIAL_BLOCK or c % COL_BLOCK:
        # Not a bare assert: stripped under -O and invisible in a trace.
        raise ContractViolation(
            "majx_sense", "block-alignment",
            f"trials {t} / columns {c} must tile "
            f"({TRIAL_BLOCK}, {COL_BLOCK}) blocks")
    grid = (t // TRIAL_BLOCK, c // COL_BLOCK)
    kernel = functools.partial(_majx_kernel, params=params, n_fracs=n_fracs)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TRIAL_BLOCK, r, COL_BLOCK), lambda i, j: (i, 0, j)),
            pl.BlockSpec((COL_BLOCK,), lambda i, j: (j,)),
            pl.BlockSpec((TRIAL_BLOCK, COL_BLOCK), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((TRIAL_BLOCK, COL_BLOCK), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, c), jnp.float32),
        interpret=interpret_mode(interpret),
    )(charge, sense_offset, noise)
