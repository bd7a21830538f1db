"""Fleet calibration engine: grid == per-subarray equivalence, fused Pallas
kernel vs oracle, shard_map path, cache round-trip, fleet ECR/throughput."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.calibrate import CalibrationConfig, identify_calibration
from repro.core.ecr import fleet_ecr_summary, measure_ecr_fleet
from repro.core.fleet import (FleetConfig, calibrate_fleet,
                              fleet_calib_charges, sense_tables,
                              load_or_calibrate, manufacture_fleet,
                              subarray_key)
from repro.core.throughput import fleet_throughput
from repro.kernels.majx import calib_iter_fused
from repro.kernels.ref import calib_iter_ref
from repro.pud.gemv import FleetPerfModel, PUDPerfModel
from repro.pud.physics import PhysicsParams
from repro.runtime.calib_cache import CalibrationTableCache

P = PhysicsParams()
CFG = FleetConfig(n_channels=1, n_banks=2, n_subarrays=2, n_cols=256)
CAL = CalibrationConfig(n_iterations=6, n_samples=128)


def test_manufacture_matches_single_subarray():
    key = jax.random.key(3)
    offs = manufacture_fleet(key, CFG, P)
    assert offs.shape == (CFG.n_subarrays_total, CFG.n_cols)
    for g in (0, 3):
        single = P.sigma_static * jax.random.normal(
            subarray_key(key, g), (CFG.n_cols,), jnp.float32)
        np.testing.assert_array_equal(np.asarray(offs[g]), np.asarray(single))


def test_grid_calibration_matches_per_subarray():
    """vmapped fleet Algorithm 1 == N independent identify_calibration."""
    key = jax.random.key(5)
    offs = manufacture_fleet(key, CFG, P)
    cal = calibrate_fleet(key, offs, CFG, P, CAL, method="per_subarray")
    ladder = CFG.ladder(P)
    for g in range(CFG.n_subarrays_total):
        single = identify_calibration(
            subarray_key(key, g), offs[g], ladder, P, CAL)
        np.testing.assert_array_equal(
            np.asarray(cal.levels[g]), np.asarray(single))


def test_fused_kernel_matches_ref():
    """Fused Pallas calibration iteration vs kernels/ref.py, interpret mode."""
    ladder = CFG.ladder(P)
    level_v, level_sigma = sense_tables(ladder, P)
    key = jax.random.key(11)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s, c = 64, 512
    inputs = jax.random.bernoulli(k1, 0.5, (s, 5, c)).astype(jnp.float32)
    noise = jax.random.normal(k2, (s, c), jnp.float32)
    levels = jax.random.randint(k3, (c,), 0, ladder.n_levels, jnp.int32)
    offs = 0.03 * jax.random.normal(k4, (c,), jnp.float32)
    args = (inputs, noise, levels, offs, level_v, level_sigma, 0.0009, 5)
    got_l, got_b = calib_iter_fused(*args, interpret=True)
    want_l, want_b = calib_iter_ref(*args)
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(want_l))
    np.testing.assert_array_equal(np.asarray(got_b), np.asarray(want_b))
    # levels must actually move off their inputs somewhere (non-trivial step)
    assert (np.asarray(got_l) != np.asarray(levels)).any()


def test_fused_fleet_matches_reference_fleet():
    key = jax.random.key(7)
    offs = manufacture_fleet(key, CFG, P)
    fused = calibrate_fleet(key, offs, CFG, P, CAL, method="fused")
    ref = calibrate_fleet(key, offs, CFG, P, CAL, method="reference")
    np.testing.assert_array_equal(np.asarray(fused.levels),
                                  np.asarray(ref.levels))
    np.testing.assert_allclose(np.asarray(fused.mean_abs_bias),
                               np.asarray(ref.mean_abs_bias), atol=1e-7)
    # the bias walk converges
    hist = np.asarray(fused.mean_abs_bias)
    assert hist[-1] < 0.3 * hist[0]
    assert fused.levels_grid.shape == CFG.grid_shape + (CFG.n_cols,)


def test_fleet_ecr_improves_and_summary():
    key = jax.random.key(13)
    offs = manufacture_fleet(key, CFG, P)
    ladder = CFG.ladder(P)
    cal = calibrate_fleet(key, offs, CFG, P, CAL, method="fused")
    charges = fleet_calib_charges(ladder, cal.levels, P)
    k_ecr = jax.random.key(99)
    ecr, masks = measure_ecr_fleet(k_ecr, offs, charges, P, ladder.n_fracs,
                                   n_trials=1024, chunk=128)
    # uncalibrated (neutral level) fleet for comparison
    from repro.core.offsets import neutral_level
    neutral = jnp.full_like(cal.levels, neutral_level(ladder))
    ecr0, _ = measure_ecr_fleet(
        k_ecr, offs, fleet_calib_charges(ladder, neutral, P), P,
        ladder.n_fracs, n_trials=1024, chunk=128)
    assert float(ecr.mean()) < 0.5 * float(ecr0.mean())
    s = fleet_ecr_summary(masks)
    assert s["n_subarrays"] == CFG.n_subarrays_total
    assert s["cols_total"] == CFG.n_cols_total
    assert 0.0 <= s["min_ecr"] <= s["mean_ecr"] <= s["max_ecr"] <= 1.0
    assert s["error_free_cols_total"] == int((~np.asarray(masks)).sum())


def test_fleet_ecr_row_matches_single_subarray_protocol():
    """Row g of the fleet measurement == single-subarray run w/ folded key."""
    from repro.core.ecr import measure_ecr_maj5
    key = jax.random.key(17)
    offs = manufacture_fleet(key, CFG, P)
    ladder = CFG.ladder(P)
    cal = calibrate_fleet(key, offs, CFG, P, CAL, method="fused")
    charges = fleet_calib_charges(ladder, cal.levels, P)
    k_ecr = jax.random.key(23)
    ecr, masks = measure_ecr_fleet(k_ecr, offs, charges, P, ladder.n_fracs,
                                   n_trials=512, chunk=128)
    g = 1
    single_ecr, single_mask = measure_ecr_maj5(
        jax.random.fold_in(k_ecr, g), offs[g], charges[g], P, ladder.n_fracs,
        n_trials=512, chunk=128)
    np.testing.assert_array_equal(np.asarray(masks[g]),
                                  np.asarray(single_mask))
    assert abs(float(ecr[g]) - single_ecr) < 1e-9


def test_cache_round_trip(tmp_path):
    cache = CalibrationTableCache(tmp_path)
    rng = np.random.default_rng(0)
    levels = rng.integers(
        0, 8, (CFG.n_subarrays_total, CFG.n_cols)).astype(np.int32)
    ecr = np.linspace(0.01, 0.05, CFG.n_subarrays_total).astype(np.float32)
    masks = rng.random((CFG.n_subarrays_total, CFG.n_cols)) < 0.05
    cache.save("dimm7", CFG, P, levels, ecr=ecr, masks=masks,
               metadata={"method": "fused"})
    hit = cache.load("dimm7", CFG, P, verify=True)
    assert hit is not None
    np.testing.assert_array_equal(hit.levels, levels)
    np.testing.assert_array_equal(hit.ecr, ecr)
    np.testing.assert_array_equal(hit.masks, masks)
    assert hit.metadata["method"] == "fused"
    # keyed misses: unknown device, different ladder, different physics
    assert cache.load("other", CFG, P) is None
    import dataclasses
    cfg2 = dataclasses.replace(CFG, frac_counts=(0, 0, 0))
    assert cache.load("dimm7", cfg2, P) is None
    p2 = dataclasses.replace(P, sigma_static=0.05)
    assert cache.load("dimm7", CFG, p2) is None
    assert len(cache.entries()) == 1
    # torn payload (crash mid-write, disk corruption): miss, not crash
    entry = next(iter((tmp_path / "dimm7").glob("*/levels.npy")))
    entry.write_bytes(entry.read_bytes()[:40])
    assert cache.load("dimm7", CFG, P) is None
    assert cache.evict("dimm7") == 1
    assert cache.load("dimm7", CFG, P) is None


def test_load_or_calibrate_hits_without_recalibrating(tmp_path):
    cache = CalibrationTableCache(tmp_path)
    key = jax.random.key(29)
    small = FleetConfig(n_channels=1, n_banks=1, n_subarrays=2, n_cols=256)
    lv1, ecr1, masks1, hit1 = load_or_calibrate(
        cache, "d0", key, small, P, CAL, n_trials_ecr=256)
    assert not hit1
    lv2, ecr2, masks2, hit2 = load_or_calibrate(
        cache, "d0", key, small, P, CAL, n_trials_ecr=256)
    assert hit2
    np.testing.assert_array_equal(np.asarray(lv1), np.asarray(lv2))
    np.testing.assert_allclose(np.asarray(ecr1), np.asarray(ecr2))
    np.testing.assert_array_equal(np.asarray(masks1), np.asarray(masks2))
    # the persisted masks are the ECR measurement's error-prone columns
    np.testing.assert_allclose(np.asarray(masks1).mean(axis=1),
                               np.asarray(ecr1), atol=1e-6)


def test_fleet_throughput_and_perf_model():
    ecr = np.array([0.02, 0.04, 0.03, 0.05])
    add = fleet_throughput("T210", "add8", ecr, n_fracs=3)
    mul = fleet_throughput("T210", "mul8", ecr, n_fracs=3)
    base = fleet_throughput("B300", "add8", np.full(4, 0.466), n_fracs=3)
    assert add.per_subarray.shape == (4,)
    # monotone: lower ECR -> higher rate; aggregate sits inside the envelope
    order = np.argsort(ecr)
    assert (np.diff(add.per_subarray[order]) < 0).all()
    assert add.percentile(0) <= add.aggregate <= add.percentile(100)
    assert add.speedup_vs(base) > 1.5
    assert mul.aggregate != add.aggregate
    # serving model built from the same table
    fleet = FleetPerfModel.from_table(ecr, n_fracs=3)
    point = PUDPerfModel(error_free_frac=1 - float(ecr.mean()), n_fracs=3)
    assert abs(fleet.macs_per_second - point.macs_per_second) < 1e-6 * \
        point.macs_per_second
    assert fleet.worst_subarray_macs_per_second < fleet.macs_per_second


SHARD_PROG = """
    import jax, numpy as np
    from repro.core.calibrate import CalibrationConfig
    from repro.core.fleet import FleetConfig, calibrate_fleet, \\
        manufacture_fleet
    from repro.launch.mesh import make_host_mesh
    from repro.pud.physics import PhysicsParams

    params = PhysicsParams()
    cfg = FleetConfig(n_channels=1, n_banks=2, n_subarrays=4, n_cols=256)
    cal = CalibrationConfig(n_iterations=3, n_samples=64)
    key = jax.random.key(1)
    offs = manufacture_fleet(key, cfg, params)
    mesh = make_host_mesh(2, 2)
    fused = calibrate_fleet(key, offs, cfg, params, cal, mesh=mesh,
                            method="fused")
    ref = calibrate_fleet(key, offs, cfg, params, cal, mesh=mesh,
                          method="reference")
    assert fused.levels.shape == (8, 256)
    np.testing.assert_array_equal(np.asarray(fused.levels),
                                  np.asarray(ref.levels))
    hist = np.asarray(fused.mean_abs_bias)
    assert hist[-1] < hist[0]
    print("SHARD_OK", hist.tolist())
"""


def test_fleet_calibration_shard_map(forced_devices):
    forced_devices(SHARD_PROG, marker="SHARD_OK", devices=4)
