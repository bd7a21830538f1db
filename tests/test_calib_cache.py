"""Calibration-cache failure paths: every corruption/mismatch mode must read
as a miss and fall back to recalibration — never raise, never serve a stale
or torn table."""
import json

import jax
import numpy as np
import pytest

from repro.core.calibrate import CalibrationConfig
from repro.core.fleet import FleetConfig, load_or_calibrate
from repro.pud.physics import PhysicsParams
from repro.runtime.calib_cache import (FORMAT, CalibrationTableCache,
                                       params_fingerprint, table_key)

P = PhysicsParams()
CFG = FleetConfig(n_channels=1, n_banks=1, n_subarrays=2, n_cols=128)
CAL = CalibrationConfig(n_iterations=4, n_samples=64)
KEY = jax.random.key(41)


@pytest.fixture
def warm(tmp_path):
    """A cache warmed by one real load_or_calibrate miss."""
    cache = CalibrationTableCache(tmp_path)
    levels, ecr, masks, hit = load_or_calibrate(
        cache, "dev", KEY, CFG, P, CAL, n_trials_ecr=128)
    assert not hit
    entry = tmp_path / "dev" / table_key(CFG, P)
    assert (entry / "manifest.json").exists()
    return cache, entry, (np.asarray(levels), np.asarray(ecr),
                          np.asarray(masks))


def _reload(cache):
    return load_or_calibrate(cache, "dev", KEY, CFG, P, CAL,
                             n_trials_ecr=128)


def test_warm_hit_is_deterministic(warm):
    cache, _, (levels, ecr, masks) = warm
    lv, e, m, hit = _reload(cache)
    assert hit
    np.testing.assert_array_equal(np.asarray(lv), levels)
    np.testing.assert_allclose(np.asarray(e), ecr)
    np.testing.assert_array_equal(np.asarray(m), masks)


def test_torn_levels_fall_back_to_recalibration(warm):
    cache, entry, (levels, _, _) = warm
    payload = entry / "levels.npy"
    payload.write_bytes(payload.read_bytes()[:40])    # truncated mid-write
    assert cache.load("dev", CFG, P) is None          # miss, not a raise
    lv, _, _, hit = _reload(cache)
    assert not hit                                    # recalibrated ...
    np.testing.assert_array_equal(np.asarray(lv), levels)  # ... same result
    assert cache.load("dev", CFG, P) is not None      # and re-persisted


def test_corrupt_manifest_falls_back(warm):
    cache, entry, _ = warm
    (entry / "manifest.json").write_text("{not json")
    assert cache.load("dev", CFG, P) is None
    _, _, _, hit = _reload(cache)
    assert not hit


def test_version_mismatch_falls_back(warm):
    """A format bump must invalidate old entries instead of misreading."""
    cache, entry, _ = warm
    manifest = json.loads((entry / "manifest.json").read_text())
    assert manifest["format"] == FORMAT
    manifest["format"] = "fleet-calib-v1"             # pre-masks era
    (entry / "manifest.json").write_text(json.dumps(manifest))
    assert cache.load("dev", CFG, P) is None
    _, _, _, hit = _reload(cache)
    assert not hit
    # the recalibration re-saved under the current format
    got = json.loads((entry / "manifest.json").read_text())
    assert got["format"] == FORMAT


def test_fingerprint_mismatch_falls_back(warm):
    """Changed physics constants can never silently reuse a stale table."""
    cache, entry, _ = warm
    manifest = json.loads((entry / "manifest.json").read_text())
    manifest["params_fingerprint"] = "0" * 12
    (entry / "manifest.json").write_text(json.dumps(manifest))
    assert manifest["params_fingerprint"] != params_fingerprint(P)
    assert cache.load("dev", CFG, P) is None
    _, _, _, hit = _reload(cache)
    assert not hit


def test_missing_masks_treated_as_miss(warm):
    """v2 tables without masks can't drive placement: re-identify."""
    cache, entry, _ = warm
    (entry / "masks.npy").unlink()
    table = cache.load("dev", CFG, P)
    assert table is not None and table.masks is None  # load is lenient ...
    _, _, masks, hit = _reload(cache)
    assert not hit and masks is not None              # ... the glue is not


def test_wrong_shape_masks_treated_as_missing(warm):
    cache, entry, _ = warm
    np.save(entry / "masks.npy", np.zeros((1, 3), bool))
    table = cache.load("dev", CFG, P)
    assert table is not None and table.masks is None


def test_evict_then_recalibrate(warm):
    cache, entry, _ = warm
    assert cache.evict("dev") == 1
    assert cache.load("dev", CFG, P) is None
    assert cache.evict("dev") == 0                    # idempotent
    _, _, _, hit = _reload(cache)
    assert not hit
    assert len(cache.entries()) == 1


def test_crashed_staging_dir_swept_on_save(warm, tmp_path):
    cache, entry, _ = warm
    torn = entry.with_name(entry.name + ".tmp-9999")
    torn.mkdir()
    (torn / "levels.npy").write_bytes(b"garbage")
    assert len(cache.entries()) == 1                  # staging is invisible
    lv, ecr, masks, hit = _reload(cache)
    assert hit                                        # real entry untouched
    cache.save("dev", CFG, P, np.asarray(lv), ecr=np.asarray(ecr),
               masks=np.asarray(masks))
    assert not torn.exists()                          # gc on the next save


# ---------------------------------------------------------------------------
# Calibration age metadata (drift monitoring): stamped on save, version-
# tolerant on load — entries written before the metadata existed still read
# as valid tables with unknown age.
# ---------------------------------------------------------------------------

def test_fresh_save_stamps_calibration_age(warm):
    cache, entry, _ = warm
    manifest = json.loads((entry / "manifest.json").read_text())
    calib = manifest["calibration"]
    assert calib["calibrated_at"] > 0
    table = cache.load("dev", CFG, P)
    assert table.calibrated_at == calib["calibrated_at"]
    assert table.params_fingerprint == params_fingerprint(P)
    # load_or_calibrate stamps the physics' nominal temperature
    assert table.assumed_temp_c == P.temp_nominal_c
    assert table.age_days() >= 0.0
    assert table.age_days(now=table.calibrated_at + 86400.0) == \
        pytest.approx(1.0)
    # clock skew can't produce negative ages
    assert table.age_days(now=table.calibrated_at - 60.0) == 0.0


def test_entry_without_calibration_block_loads_with_unknown_age(warm):
    """Pre-metadata entries (same format version) must stay readable."""
    cache, entry, (levels, _, _) = warm
    manifest = json.loads((entry / "manifest.json").read_text())
    del manifest["calibration"]
    (entry / "manifest.json").write_text(json.dumps(manifest))
    table = cache.load("dev", CFG, P)
    assert table is not None                          # still a hit ...
    np.testing.assert_array_equal(table.levels, levels)
    assert table.calibrated_at is None                # ... age unknown
    assert table.assumed_temp_c is None
    assert table.age_days() is None


def test_explicit_calibrated_at_roundtrips(warm):
    cache, entry, (levels, ecr, masks) = warm
    cache.save("dev", CFG, P, levels, ecr=ecr, masks=masks,
               calibrated_at=123456.0, assumed_temp_c=62.5)
    table = cache.load("dev", CFG, P)
    assert table.calibrated_at == 123456.0
    assert table.assumed_temp_c == 62.5


def test_cli_list_shows_age(warm, tmp_path, capsys):
    from repro.runtime.calib_cache import main as cli
    assert cli(["--root", str(tmp_path), "--list"]) == 0
    assert "age " in capsys.readouterr().out


# ---------------------------------------------------------------------------
# CLI (python -m repro.runtime.calib_cache)
# ---------------------------------------------------------------------------

def test_cli_list_and_stats(warm, tmp_path, capsys):
    from repro.runtime.calib_cache import main as cli
    assert cli(["--root", str(tmp_path), "--list"]) == 0
    out = capsys.readouterr().out
    assert "dev" in out and table_key(CFG, P) in out and FORMAT in out
    assert cli(["--root", str(tmp_path), "--stats"]) == 0
    out = capsys.readouterr().out
    assert "devices          1" in out
    assert "table entries    1" in out


def test_cli_evict_and_empty(warm, tmp_path, capsys):
    from repro.runtime.calib_cache import main as cli
    assert cli(["--root", str(tmp_path), "--evict", "dev"]) == 0
    assert "evicted 1 table(s)" in capsys.readouterr().out
    assert cli(["--root", str(tmp_path), "--list"]) == 0
    assert "no cache entries" in capsys.readouterr().out
    # missing root reads as empty, not an error
    assert cli(["--root", str(tmp_path / "nope"), "--stats"]) == 0
    assert "table entries    0" in capsys.readouterr().out


def test_cli_requires_exactly_one_action(tmp_path):
    from repro.runtime.calib_cache import main as cli
    with pytest.raises(SystemExit):
        cli(["--root", str(tmp_path)])
    with pytest.raises(SystemExit):
        cli(["--root", str(tmp_path), "--list", "--stats"])


# ---------------------------------------------------------------------------
# Placement serialization versions (the bit-packed refactor bumped the
# placement format to v2 — block-aligned windows — and the lane-aligned
# stride to v3; v1 and v2 archives must keep loading through the upgrade
# paths, unknown versions miss)
# ---------------------------------------------------------------------------

def _v1_placement_npz(path, plan, masks):
    """Re-serialize a Placement in the PR-2 (pud-placement-v1) archive
    layout: one physical span per slice, ``region_start``/``region_size``
    instead of block structure."""
    flat = np.asarray(masks, bool).reshape(-1)
    arrays = {"used": np.asarray(plan.used_per_subarray, np.int32),
              "usable": np.asarray(plan.usable_per_subarray, np.int32)}
    region_sizes = []
    for i, name in enumerate(plan.entries):
        tp = plan.entries[name]
        phys = np.atleast_2d(np.asarray(tp.phys_cols, np.int64))
        starts = phys[:, 0]
        region = int((phys[:, -1] - phys[:, 0] + 1).max())
        region_sizes.append(region)
        faulty = np.zeros((phys.shape[0], region), bool)
        stuck = np.zeros((phys.shape[0], region), np.int8)
        for s, r0 in enumerate(starts):
            window = np.arange(r0, r0 + region)
            in_dev = window < flat.size
            faulty[s, in_dev] = flat[window[in_dev]]
            stuck[s, in_dev] = (window[in_dev] % 2).astype(np.int8)
        if np.asarray(tp.phys_cols).ndim == 1:
            arrays[f"e{i}_phys"] = np.asarray(tp.phys_cols, np.int32)
            arrays[f"e{i}_start"] = np.int32(starts[0])
            arrays[f"e{i}_faulty"] = faulty[0]
            arrays[f"e{i}_stuck"] = stuck[0]
        else:
            arrays[f"e{i}_phys"] = np.asarray(tp.phys_cols, np.int32)
            arrays[f"e{i}_start"] = starts.astype(np.int32)
            arrays[f"e{i}_faulty"] = faulty
            arrays[f"e{i}_stuck"] = stuck
    meta = {"format": "pud-placement-v1",
            "names": list(plan.entries),
            "region_sizes": region_sizes,
            "grid_shape": list(plan.grid_shape),
            "n_cols_per_subarray": plan.n_cols_per_subarray,
            "avoid_faulty": plan.avoid_faulty}
    arrays["meta"] = np.array(json.dumps(meta))
    with open(path, "wb") as f:
        np.savez(f, **arrays)


def test_v1_placement_archive_upgrades_on_load(tmp_path):
    """A PR-2/PR-3 placement .npz loads into the block-aligned v2 layout
    and serves bit-identically to a freshly planned placement."""
    import jax.numpy as jnp
    from repro.pud.gemv import PUDGemvConfig, pud_linear
    from repro.pud.packer import pack_model, packing_requests
    from repro.pud.placement import (PlacementRequest, load_placement_npz,
                                     plan_placement, save_placement_npz)
    rng = np.random.default_rng(3)
    masks = rng.random((4, 512)) < 0.25
    params = {"m": {"wi": 0.05 * np.asarray(
        rng.standard_normal((64, 96)), np.float32)},
        "s": {"wi": 0.05 * np.asarray(
            rng.standard_normal((2, 64, 96)), np.float32)}}
    params = jax.tree_util.tree_map(jnp.asarray, params)
    cfg = PUDGemvConfig(packable=("wi",))
    reqs = packing_requests(params, cfg, include_unembed=False)
    plan = plan_placement(masks, reqs)

    v1 = tmp_path / "m0_v1.npz"
    _v1_placement_npz(v1, plan, masks)
    up = load_placement_npz(v1)
    assert up is not None
    for name in plan.entries:
        tp, utp = plan.entries[name], up.entries[name]
        np.testing.assert_array_equal(np.asarray(utp.phys_cols),
                                      np.asarray(tp.phys_cols))
        assert utp.block_cols == tp.block_cols
        assert utp.window_block == tp.window_block
        np.testing.assert_array_equal(utp.block_starts, tp.block_starts)
        np.testing.assert_array_equal(utp.faulty, tp.faulty)
    assert up.capacity_report() == plan.capacity_report()

    # packs built from the upgraded placement serve bit-identically
    placed = pack_model(params, cfg, include_unembed=False, placement=up)
    logical = pack_model(params, cfg, include_unembed=False)
    x = jnp.asarray(rng.standard_normal((3, 64)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(pud_linear(x, placed.tensor("m/wi"))),
        np.asarray(pud_linear(x, logical.tensor("m/wi"))))

    # v2 round-trip and unknown-version miss
    v2 = tmp_path / "m0_v2.npz"
    save_placement_npz(v2, plan)
    got = load_placement_npz(v2)
    assert got is not None
    assert got.entries["m/wi"].window_block == plan.entries["m/wi"].window_block
    bad = tmp_path / "bad.npz"
    meta = {"format": "pud-placement-v99", "names": []}
    np.savez(bad, meta=np.array(json.dumps(meta)))
    assert load_placement_npz(bad) is None


def test_v2_placement_archive_realigns_on_load(tmp_path):
    """A v2 archive stored the raw max span as the window stride; it loads
    at the 128-aligned stride with every column at its old in-block
    offset, so packs built from it equal packs from a fresh plan."""
    import jax.numpy as jnp
    from repro.pud.gemv import PUDGemvConfig, pud_linear
    from repro.pud.packer import pack_model, packing_requests
    from repro.pud.placement import (load_placement_npz, plan_placement,
                                     save_placement_npz)
    rng = np.random.default_rng(5)
    masks = rng.random((4, 512)) < 0.05
    params = {"m": {"wi": 0.05 * np.asarray(
        rng.standard_normal((64, 96)), np.float32)},
        "s": {"wi": 0.05 * np.asarray(
            rng.standard_normal((2, 64, 96)), np.float32)}}
    params = jax.tree_util.tree_map(jnp.asarray, params)
    cfg = PUDGemvConfig(packable=("wi",))
    plan = plan_placement(masks, packing_requests(
        params, cfg, include_unembed=False))

    path = tmp_path / "m0_v2.npz"
    save_placement_npz(path, plan)
    with np.load(path) as z:
        arrays = dict(z)
    meta = json.loads(str(arrays["meta"]))
    raws = []
    for i, name in enumerate(meta["names"]):
        tp = plan.entries[name]
        phys = np.atleast_2d(tp.phys_cols).astype(np.int64)
        blocks = phys.reshape(phys.shape[0], -1, tp.block_cols)
        raw = int((blocks[..., -1] - blocks[..., 0] + 1).max())
        assert raw < tp.window_block           # the v2 stride was unaligned
        for f in ("faulty", "stuck"):
            a = arrays[f"e{i}_{f}"]
            cut = a.reshape(a.shape[:-1] + (tp.n_blocks, tp.window_block))
            arrays[f"e{i}_{f}"] = cut[..., :raw].reshape(
                a.shape[:-1] + (tp.n_blocks * raw,))
        raws.append(raw)
    meta.update(format="pud-placement-v2", window_blocks=raws)
    arrays["meta"] = np.array(json.dumps(meta))
    with open(path, "wb") as f:
        np.savez(f, **arrays)

    got = load_placement_npz(path)
    assert got is not None
    for name, tp in plan.entries.items():
        gtp = got.entries[name]
        assert gtp.window_block == tp.window_block
        np.testing.assert_array_equal(gtp.local_cols, tp.local_cols)
        np.testing.assert_array_equal(gtp.faulty, tp.faulty)
        np.testing.assert_array_equal(gtp.stuck, tp.stuck)
    placed = pack_model(params, cfg, include_unembed=False, placement=got)
    fresh = pack_model(params, cfg, include_unembed=False, placement=plan)
    x = jnp.asarray(rng.standard_normal((3, 64)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(pud_linear(x, placed.tensor("m/wi"))),
        np.asarray(pud_linear(x, fresh.tensor("m/wi"))))


def test_v1_placement_in_cache_reads_as_hit(warm, tmp_path):
    """The cache's load_placement path accepts a v1 archive sitting in a
    warm table's placements/ dir (old caches keep their plans)."""
    from repro.pud.placement import PlacementRequest, plan_placement
    cache, entry, (_, _, masks) = warm
    plan = plan_placement(masks, [PlacementRequest("unembed/w", 48, 0)])
    d = entry / "placements"
    d.mkdir(exist_ok=True)
    _v1_placement_npz(d / "legacy.npz", plan, masks)
    got = cache.load_placement("dev", CFG, P, "legacy")
    assert got is not None
    np.testing.assert_array_equal(
        np.asarray(got.entries["unembed/w"].phys_cols),
        np.asarray(plan.entries["unembed/w"].phys_cols))
