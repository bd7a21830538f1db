"""Plain reference of the paper's Algorithm 1 over a subarray grid.

Written from the paper and the configuration's physics, not from the
program.  Three calibration rows per column hold the multi-level charging
ladder T_{f1,f2,f3}: row i holds 0.5 + (b_i - 0.5) * alpha**f_i, and the
2**3 sign patterns, deduplicated and sorted by their net offset, are the
ladder's levels.  One MAJ5 sensing shares the charge of the 5 operand rows
and the 3 calibration rows on the bitline:
V = (sum of charges * C_cell + 0.5 * C_bitline) / (8 * C_cell + C_bitline),
with noise of std sqrt(s_dyn**2 + s_frac**2 * fracs + s_tr**2 * swing**2),
and senses 1 when sigma * z > (0.5 + offset) - V.  Each iteration draws
``n_samples`` random operand sets and noise draws for every column, takes
the bias (ones read minus true majorities, over the samples) and steps the
column's level down when the bias exceeds the threshold and up when it
falls below minus the threshold.  The random draws follow the key exactly
as the configuration's engine consumes it: one split per iteration, then
one split into the operand key and the noise key.

``precision="bf16"`` is the control: the same computation with the noise,
offsets, voltages and sigmas in bfloat16, one precision below the float32
the configuration states.
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

NEUTRAL = 0.5


def ladder(frac_counts, alpha: float):
    """(bit pattern per level, sorted by offset; neutral level index)."""
    deltas = [0.5 * alpha ** f for f in frac_counts]
    entries: dict[float, tuple[int, ...]] = {}
    for bits in itertools.product((0, 1), repeat=len(frac_counts)):
        off = round(sum((b - 0.5) * 2 * d for b, d in zip(bits, deltas)), 9)
        entries.setdefault(off, bits)
    offs = sorted(entries)
    neutral = int(np.argmin(np.abs(np.asarray(offs))))
    return [entries[o] for o in offs], neutral


def tables(cfg: dict):
    """Per level: bitline voltage by count of ones among the operands
    [L, M+1], and sensing sigma [L]; float32."""
    ph, cal = cfg["physics"], cfg["calibration"]
    fracs = cfg["grid"]["frac_counts"]
    bits, _ = ladder(fracs, ph["frac_alpha"])
    rc = np.zeros((len(bits), len(fracs)), np.float32)
    for lvl, bb in enumerate(bits):
        for i, (b, f) in enumerate(zip(bb, fracs)):
            rc[lvl, i] = NEUTRAL + (b - NEUTRAL) * ph["frac_alpha"] ** f
    qsum = rc.sum(axis=1)
    swing = ((2.0 * (rc - NEUTRAL)) ** 2).sum(axis=1)
    m = cal["maj_inputs"]
    ones = np.arange(m + 1, dtype=np.float32)
    charge = ones[None, :] + qsum[:, None] + cal["const_charge_sum"]
    c_total = ph["n_simra_rows"] * ph["c_cell_ff"] + ph["c_bitline_ff"]
    v = (charge * ph["c_cell_ff"] + NEUTRAL * ph["c_bitline_ff"]) / c_total
    var = (ph["sigma_dynamic"] ** 2
           + ph["sigma_frac"] ** 2 * np.float32(sum(fracs))
           + ph["sigma_transfer"] ** 2
           * (swing + m + cal["const_swing_sq"]))
    sigma = np.asarray(jnp.sqrt(var), np.float32)
    return np.asarray(v, np.float32), sigma


@functools.partial(jax.jit, static_argnames=("shape", "n_iter", "m",
                                             "threshold", "low"))
def _run(key, offsets, v_table, sigma_table, levels0, *, shape, n_iter, m,
         threshold, low):
    g, s, c = shape
    dt = jnp.bfloat16 if low else jnp.float32
    n_levels = sigma_table.shape[0]
    v_table, sigma_table = v_table.astype(dt), sigma_table.astype(dt)
    thresh_base = (NEUTRAL + offsets.astype(dt))[:, None, :]

    def iteration(levels, it_key):
        k_in, k_noise = jax.random.split(it_key)
        ones = jax.random.bernoulli(k_in, 0.5, (g, s, m, c)).sum(
            axis=2, dtype=jnp.int32)
        noise = jax.random.normal(k_noise, (g, s, c), jnp.float32).astype(dt)
        v = v_table[levels[:, None, :], ones]
        sigma = sigma_table[levels][:, None, :]
        out = sigma * noise > thresh_base - v
        truth = ones > m // 2
        bias = (out.astype(jnp.float32) - truth.astype(jnp.float32)).sum(
            axis=1) / s
        step = (jnp.where(bias > threshold, -1, 0)
                + jnp.where(bias < -threshold, 1, 0))
        return jnp.clip(levels + step, 0, n_levels - 1), None

    levels, _ = jax.lax.scan(iteration, levels0,
                             jax.random.split(key, n_iter))
    return levels


def calibrate(cfg: dict, key, offsets, precision: str = "f32") -> jax.Array:
    """Levels [G, C] of Algorithm 1 for ``key`` on sense offsets [G, C]."""
    if precision not in ("f32", "bf16"):
        raise ValueError(f"unknown precision {precision!r}")
    cal = cfg["calibration"]
    v, sigma = tables(cfg)
    _, neutral = ladder(cfg["grid"]["frac_counts"],
                        cfg["physics"]["frac_alpha"])
    g, c = offsets.shape
    levels0 = jnp.full((g, c), neutral, jnp.int32)
    return _run(key, offsets, jnp.asarray(v), jnp.asarray(sigma), levels0,
                shape=(g, cal["n_samples"], c), n_iter=cal["n_iterations"],
                m=cal["maj_inputs"], threshold=float(cal["threshold"]),
                low=precision == "bf16")
