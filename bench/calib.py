"""Calibration cells: whole-grid ``calibrate_fleet`` calls, one after another.

One run: make the grid's sense offsets from the seed (the benchmark's own
data), warm the fused engine with one calibration, then calibrate the
configuration's whole grid again and again for the window, each call with
a new key drawn from the seed, waiting for each call's levels.  After the
window, calls drawn from the seed are recomputed by the plain reference
(``bench/reference/calibration.py``) and the columns whose level differs
are counted against the cell's limit.
"""
from __future__ import annotations

import gc
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import harness, work
from bench import trace as trace_mod
from bench import weights
from bench.reference import calibration as ref_calib
from bench.runtime import CompileCounter, memory_peak_bytes


def grid_shape(cfg: dict) -> tuple[int, int]:
    g = cfg["grid"]
    return g["n_channels"] * g["n_banks"] * g["n_subarrays"], g["n_cols"]


def make_offsets(cfg: dict, seed: int) -> jax.Array:
    """Per-column sense-amplifier offsets [G, C] of the simulated device."""
    g, c = grid_shape(cfg)
    key = jax.random.fold_in(weights.seed_key(seed), 0x0FF5)
    return jax.jit(lambda k: cfg["physics"]["sigma_static"]
                   * jax.random.normal(k, (g, c), jnp.float32))(key)


def call_key(seed: int, i: int) -> jax.Array:
    return jax.random.fold_in(weights.seed_key(seed), i)


def program_calibrator(cfg: dict):
    """f(key, offsets) -> levels [G, C] through ``calibrate_fleet``."""
    from repro.core.calibrate import CalibrationConfig
    from repro.core.fleet import FleetConfig, calibrate_fleet
    from repro.pud.physics import PhysicsParams
    g = dict(cfg["grid"])
    g["frac_counts"] = tuple(g["frac_counts"])
    grid = FleetConfig(**g)
    cal = dict(cfg["calibration"])
    method = cal.pop("method")
    calib = CalibrationConfig(**cal)
    physics = PhysicsParams(**cfg["physics"])

    def f(key, offsets):
        return calibrate_fleet(key, offsets, grid, physics, calib,
                               method=method).levels
    return f


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool, *,
        state_dir, t_start: float, control: bool = False):
    cfg, mix = cell.config, cell.traffic
    if mix["method"] != cfg["calibration"]["method"]:
        raise ValueError("the mix and the configuration name other engines")
    counter = CompileCounter()
    offsets = make_offsets(cfg, seed)
    calibrate = program_calibrator(cfg)
    calibrate(call_key(seed, 0), offsets).block_until_ready()   # warm-up

    trace_dir = None
    if trace:
        trace_dir = state_dir / "traces" / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
        jax.profiler.start_trace(str(trace_dir))
        seconds = min(seconds, float(mix["trace_seconds"]))
    results: list[np.ndarray] = []
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    counter.start()
    with TraceAnnotation("bench.window"):
        while True:
            with TraceAnnotation("bench.step"):
                levels = calibrate(call_key(seed, len(results) + 1), offsets)
                levels.block_until_ready()
            t = time.perf_counter()
            with TraceAnnotation("bench.record"):
                results.append(np.asarray(levels))
            if t - t0 >= seconds:
                break
    t_end = t
    counter.stop()
    if trace:
        jax.profiler.stop_trace()
    mem = memory_peak_bytes()
    window = t_end - t0
    g, c = grid_shape(cfg)
    cal = cfg["calibration"]
    e2e = {"setup_s": setup_s, "calib_cols_s": len(results) * g * c / window}
    ctx = {"kind": "calib", "window_s": window, "e2e": e2e, "peaks": None,
           "calib_iteration": work.calib_iteration(
               g, cal["n_samples"], cal["maj_inputs"], c),
           "calib_iterations": len(results) * cal["n_iterations"],
           "trace": None}

    # -- correctness: calls drawn from the seed against the reference --------
    del levels
    gc.collect()
    pick = np.random.default_rng([int(seed), 0xCA1]).permutation(
        len(results))[:int(mix["check_calls"])]
    mismatch = 0
    readings = {"calls": len(results)}
    for i in pick:
        key = call_key(seed, int(i) + 1)
        want = np.asarray(ref_calib.calibrate(cfg, key, offsets))
        mismatch += int((results[i] != want).sum())
        if control:
            low = np.asarray(ref_calib.calibrate(cfg, key, offsets, "bf16"))
            readings["control_level_mismatch"] = readings.get(
                "control_level_mismatch", 0) + int((low != want).sum())
    readings["level_mismatch"] = mismatch
    out = harness.Outcome(
        attempted=len(results), failed=0, end_to_end=e2e,
        checks=[harness.check(cell, "level_mismatch", mismatch)],
        ctx=ctx, memory_peak_bytes=mem, compiles_in_window=counter.count)
    if trace:
        tr = trace_mod.load(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = tr
        out.busy_s = trace_mod.busy_seconds(tr)
        out.window_s = tr.window_s
        out.breakdown = {"device_ops": trace_mod.top_ops(tr),
                         "idle_gaps": trace_mod.idle_gaps(tr)}
    return out, readings
