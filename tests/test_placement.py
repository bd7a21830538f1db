"""Column-placement subsystem: allocator invariants, placed Pallas kernel
bit-exactness, placement-aware packing, fault injection (the proof that
placement matters), persistence, and the occupancy-derived perf model."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.kernels import ref
from repro.kernels.bitplane_gemv import bitplane_gemv, bitplane_gemv_placed
from repro.models.params import init_params
from repro.pud.gemv import (ATTN_PACKABLE, FFN_PACKABLE, FleetPerfModel,
                            PUDGemvConfig, pack_linear, pud_linear)
from repro.pud.packer import pack_for_serving, packing_requests
from repro.pud.placement import (Placement, PlacementError, PlacementRequest,
                                 inject_read_faults, plan_for_grid,
                                 plan_placement, requests_fingerprint)

PUD_ATTN = PUDGemvConfig(weight_bits=4,
                         packable=FFN_PACKABLE + ATTN_PACKABLE)


def _masks(g=4, c=128, p=0.3, seed=0):
    return np.random.default_rng(seed).random((g, c)) < p


# ---------------------------------------------------------------------------
# Allocator
# ---------------------------------------------------------------------------

def test_allocator_avoids_faulty_unique_and_spills():
    masks = _masks()
    reqs = [PlacementRequest("a", 64, 0), PlacementRequest("b", 100, 2)]
    p = plan_placement(masks, reqs)
    flat = masks.reshape(-1)
    seen = set()
    for name, tp in p.entries.items():
        cols = np.asarray(tp.phys_cols).reshape(-1)
        assert not flat[cols].any(), f"{name} placed on faulty columns"
        assert not (seen & set(cols.tolist())), f"{name} overlaps"
        seen |= set(cols.tolist())
        # local maps address inside the window
        assert (np.asarray(tp.local_cols) >= 0).all()
        assert (np.asarray(tp.local_cols) < tp.region_size).all()
    assert p.used_total == 64 + 2 * 100 == len(seen)
    assert p.usable_total == int((~masks).sum())
    np.testing.assert_array_equal(p.usable_per_subarray,
                                  (~masks).sum(axis=1))
    assert p.used_per_subarray.sum() == p.used_total
    # 264 demanded > ~90 free cols/subarray: something must spill
    assert p.spilled_tensors
    rep = p.capacity_report()
    assert rep["occupancy"] == pytest.approx(p.used_total / p.usable_total)


def test_allocator_identity_layout_is_sequential():
    masks = _masks()
    p = plan_placement(masks, [PlacementRequest("t", 96, 0)],
                       avoid_faulty=False)
    np.testing.assert_array_equal(np.asarray(p.entries["t"].phys_cols),
                                  np.arange(96))
    assert not p.avoid_faulty
    # the identity layout does land on faulty silicon here
    assert masks.reshape(-1)[:96].any()


def test_allocator_capacity_error():
    masks = _masks()
    with pytest.raises(PlacementError, match="exceeds usable capacity"):
        plan_placement(masks, [PlacementRequest("huge", 10**5, 0)])


def test_requests_fingerprint_stable():
    reqs = [PlacementRequest("a", 64, 0), PlacementRequest("b", 32, 2)]
    assert requests_fingerprint(reqs) == requests_fingerprint(list(reqs))
    assert requests_fingerprint(reqs) != requests_fingerprint(reqs[:1])


# ---------------------------------------------------------------------------
# Placed kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,k,n,wb,p", [
    (2, 64, 64, 4, 97), (4, 256, 256, 4, 400), (3, 128, 256, 2, 300),
])
@pytest.mark.parametrize("mode", ["planes", "folded"])
def test_placed_kernel_bit_exact(b, k, n, wb, p, mode):
    kx, kw = jax.random.split(jax.random.key(b + k + n + wb))
    x = jax.random.randint(kx, (b, k), -127, 128, jnp.int32).astype(jnp.int8)
    w = jax.random.randint(kw, (k, n), -(1 << (wb - 1)), 1 << (wb - 1),
                           jnp.int32)
    planes = ref.pack_bitplanes(w, wb)
    cols = np.random.default_rng(p).choice(p, n, replace=False)
    col_ids = jnp.asarray(cols, jnp.int32)
    phys = jnp.zeros((wb, k, p), jnp.int8).at[:, :, col_ids].set(planes)
    got = bitplane_gemv_placed(x, phys, col_ids, mode=mode)
    want = ref.bitplane_gemv_placed_ref(x, phys, col_ids)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # placed result == unplaced kernel on the logical planes
    direct = bitplane_gemv(x, planes, mode=mode)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(direct))


def test_pud_linear_placed_matches_unplaced():
    """Scattering *bit-words* along the window axis == packing the scattered
    dense window: the column axis is untouched by K-axis bit-packing, so a
    hand-built placed pack can be assembled directly from word packs."""
    masks = _masks(g=2, c=256, p=0.2, seed=7)
    kx, kw = jax.random.split(jax.random.key(5))
    x = jax.random.normal(kx, (3, 64), jnp.float32)
    w = 0.05 * jax.random.normal(kw, (64, 128), jnp.float32)
    p = plan_placement(masks, [PlacementRequest("t", 128, 0)])
    tp = p.entries["t"]
    pk = pack_linear(w, 4)                      # bit-packed words by default
    idx = jnp.asarray(np.asarray(tp.local_cols), jnp.int32)
    phys = jnp.zeros(pk["planes"].shape[:2] + (tp.region_size,),
                     jnp.uint8).at[:, :, idx].set(pk["planes"])
    placed_pack = {"planes": phys, "scale": pk["scale"], "col_ids": idx}
    np.testing.assert_array_equal(np.asarray(pud_linear(x, placed_pack)),
                                  np.asarray(pud_linear(x, pk)))
    # and the dense (legacy-layout) hand-built pack agrees bit-for-bit
    dk = pack_linear(w, 4, bitpack=False)
    dense = jnp.zeros(dk["planes"].shape[:2] + (tp.region_size,),
                      jnp.int8).at[:, :, idx].set(dk["planes"])
    np.testing.assert_array_equal(
        np.asarray(pud_linear(x, {"planes": dense, "scale": dk["scale"],
                                  "col_ids": idx})),
        np.asarray(pud_linear(x, pk)))


def test_block_aligned_window_blocks_over_p():
    """The tentpole layout guarantee: a placed tensor with N > PLACE_BLOCK
    gets a multi-block window — every logical block's columns sit inside
    its own window slice (the kernel streams one slice per N-tile instead
    of the whole physical region), and the placed pack is bit-exact."""
    from repro.pud.gemv import pack_linear, pud_linear
    from repro.pud.packer import pack_model
    from repro.pud.placement import PLACE_BLOCK
    n, k = 2 * PLACE_BLOCK, 64
    masks = _masks(g=4, c=512, p=0.15, seed=11)
    plan = plan_placement(masks, [PlacementRequest("m/wi", n, 0)])
    tp = plan.entries["m/wi"]
    assert tp.block_cols == PLACE_BLOCK and tp.n_blocks == 2
    # window stride is bounded by the faulty interleave, not the region span
    assert tp.window_block < tp.phys_cols.max() - tp.phys_cols.min() + 1
    local = np.asarray(tp.local_cols)
    blk = np.arange(n) // tp.block_cols
    assert (local // tp.window_block == blk).all(), \
        "logical block j's columns must live inside window block j"

    w = 0.05 * np.random.default_rng(1).standard_normal((k, n))
    params = {"m": {"wi": jnp.asarray(w, jnp.float32)}}
    pm = pack_model(params, PUDGemvConfig(packable=("wi",)),
                    include_unembed=False, placement=plan)
    pt = pm.tensor("m/wi")
    assert pt.window_block == tp.window_block
    assert pt.planes.shape[-1] == tp.region_size       # blocked window axis
    x = jax.random.normal(jax.random.key(3), (3, k), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(pud_linear(x, pt)),
        np.asarray(pud_linear(x, pack_linear(jnp.asarray(w, jnp.float32),
                                             4))))


@pytest.mark.parametrize("p", [0.0, 0.036, 0.3])
def test_window_block_is_lane_aligned(p):
    """The placer rounds the window stride up to a multiple of 128 lanes
    (the chip's compiler tiles a block's last axis only in such
    multiples).  The pad at the end of each window block backs no physical
    column: it is never addressed and never faulty."""
    from repro.pud.placement import WINDOW_ALIGN
    masks = _masks(g=4, c=2048, p=p, seed=5)
    plan = plan_placement(masks, [PlacementRequest("m/wi", 768, 2),
                                  PlacementRequest("u/w", 96, 0)])
    for tp in plan.entries.values():
        phys = np.atleast_2d(tp.phys_cols).astype(np.int64)
        blocks = phys.reshape(phys.shape[0], -1, tp.block_cols)
        raw = int((blocks[..., -1] - blocks[..., 0] + 1).max())
        assert tp.window_block % WINDOW_ALIGN == 0
        assert raw <= tp.window_block < raw + WINDOW_ALIGN
        offs = np.asarray(tp.local_cols) % tp.window_block
        assert (offs < raw).all()
        faulty = np.atleast_2d(tp.faulty).reshape(
            phys.shape[0], -1, tp.window_block)
        assert not faulty[..., raw:].any()


# ---------------------------------------------------------------------------
# Packing + model integration
# ---------------------------------------------------------------------------

def test_packing_requests_cover_attention_and_unembed():
    model = get("qwen3-1.7b").make_smoke()
    params = init_params(model.param_defs(), jax.random.key(0))
    names = {r.name for r in packing_requests(params, PUD_ATTN)}
    assert "unembed/w" in names
    assert any(n.endswith("attn/wq") for n in names)
    assert any(n.endswith("mixer/wi") for n in names)
    # default config: FFN only, no attention
    ffn_names = {r.name for r in packing_requests(params, PUDGemvConfig())}
    assert not any("attn" in n for n in ffn_names)


def test_attention_packing_decodes():
    model = get("qwen3-1.7b").make_smoke()
    params = init_params(model.param_defs(), jax.random.key(0))
    packed, report = pack_for_serving(params, PUD_ATTN)
    assert any(p.endswith("attn/wq") for p in report["packed"])
    layer_key = next(k for k in packed if k.startswith("layers_"))
    assert "wq_pud" in packed[layer_key]["attn"]
    assert "wq" not in packed[layer_key]["attn"]
    toks = jax.random.randint(jax.random.key(2), (2, 8), 0,
                              model.cfg.vocab, jnp.int32)
    logits_ref, _ = model.prefill(params, toks, max_len=12)
    logits_pud, cache = model.prefill(packed, toks, max_len=12)
    assert not bool(jnp.isnan(logits_pud).any())
    agree = float((jnp.argmax(logits_pud, -1)
                   == jnp.argmax(logits_ref, -1)).mean())
    assert agree >= 0.5, agree
    nxt = jnp.argmax(logits_pud, -1).astype(jnp.int32)[:, None]
    step_logits, _ = model.decode_step(packed, cache, nxt, jnp.int32(8))
    assert not bool(jnp.isnan(step_logits).any())


def test_mla_attention_packing_runs():
    model = get("deepseek-v2-lite-16b").make_smoke()
    params = init_params(model.param_defs(), jax.random.key(0))
    packed, report = pack_for_serving(params, PUD_ATTN)
    assert any(p.endswith("attn/wq") for p in report["packed"])
    toks = jax.random.randint(jax.random.key(1), (2, 8), 0,
                              model.cfg.vocab, jnp.int32)
    logits, cache = model.prefill(packed, toks, max_len=12)
    assert not bool(jnp.isnan(logits).any())
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    step_logits, _ = model.decode_step(packed, cache, nxt, jnp.int32(8))
    assert not bool(jnp.isnan(step_logits).any())


def test_placed_pack_bit_identical_to_logical_pack():
    model = get("qwen3-1.7b").make_smoke()
    params = init_params(model.param_defs(), jax.random.key(0))
    reqs = packing_requests(params, PUD_ATTN)
    placement = plan_placement(_masks(g=8, c=512, p=0.25, seed=3), reqs)
    plain, _ = pack_for_serving(params, PUD_ATTN)
    placed, report = pack_for_serving(params, PUD_ATTN, placement=placement)
    assert report["placed"]
    toks = jax.random.randint(jax.random.key(2), (2, 8), 0,
                              model.cfg.vocab, jnp.int32)
    lg_plain, _ = model.prefill(plain, toks, max_len=12)
    lg_placed, _ = model.prefill(placed, toks, max_len=12)
    np.testing.assert_array_equal(np.asarray(lg_placed), np.asarray(lg_plain))


def test_pack_with_incomplete_placement_raises():
    model = get("qwen3-1.7b").make_smoke()
    params = init_params(model.param_defs(), jax.random.key(0))
    placement = plan_placement(
        _masks(g=8, c=512, seed=1), [PlacementRequest("unembed/w", 256, 0)])
    with pytest.raises(KeyError, match="no entry"):
        pack_for_serving(params, PUD_ATTN, placement=placement)


# ---------------------------------------------------------------------------
# Fault injection: the acceptance test that placement matters
# ---------------------------------------------------------------------------

def test_fault_injection_placed_exact_unplaced_corrupted():
    """Decode logits are bit-identical under injected faulty-column reads
    with placement enabled, and measurably corrupted with it disabled."""
    model = get("qwen3-1.7b").make_smoke()
    params = init_params(model.param_defs(), jax.random.key(0))
    reqs = packing_requests(params, PUD_ATTN)
    masks = _masks(g=8, c=512, p=0.25, seed=3)
    placed_plan = plan_placement(masks, reqs, avoid_faulty=True)
    ident_plan = plan_placement(masks, reqs, avoid_faulty=False)

    packed_placed, _ = pack_for_serving(params, PUD_ATTN,
                                        placement=placed_plan)
    packed_ident, _ = pack_for_serving(params, PUD_ATTN,
                                       placement=ident_plan)

    def decode_logits(p):
        toks = jax.random.randint(jax.random.key(2), (2, 8), 0,
                                  model.cfg.vocab, jnp.int32)
        logits, cache = model.prefill(p, toks, max_len=12)
        out = [logits]
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        for i in range(2):
            logits, cache = model.decode_step(p, cache, nxt, jnp.int32(8 + i))
            out.append(logits)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        return jnp.stack(out, axis=1)

    clean = decode_logits(packed_placed)
    # sanity: identity layout is numerically identical while fault-free
    np.testing.assert_array_equal(np.asarray(decode_logits(packed_ident)),
                                  np.asarray(clean))

    hurt_placed = decode_logits(inject_read_faults(packed_placed,
                                                   placed_plan))
    hurt_ident = decode_logits(inject_read_faults(packed_ident, ident_plan))
    # placement dodges every corrupted column: bit-identical logits
    np.testing.assert_array_equal(np.asarray(hurt_placed), np.asarray(clean))
    # the logical layout computes on faulty columns: logits break
    delta = float(jnp.abs(hurt_ident - clean).max())
    assert delta > 0.1, delta


def test_inject_requires_matching_placement():
    model = get("qwen3-1.7b").make_smoke()
    params = init_params(model.param_defs(), jax.random.key(0))
    reqs = packing_requests(params, PUD_ATTN)
    plan = plan_placement(_masks(g=8, c=512, seed=3), reqs)
    packed, _ = pack_for_serving(params, PUD_ATTN, placement=plan)
    empty = dataclasses.replace(plan, entries={})
    with pytest.raises(KeyError, match="no placement entry"):
        inject_read_faults(packed, empty)


# ---------------------------------------------------------------------------
# Persistence + perf model
# ---------------------------------------------------------------------------

def test_placement_cache_round_trip(tmp_path):
    from repro.core.fleet import FleetConfig
    from repro.pud.physics import PhysicsParams
    from repro.runtime.calib_cache import CalibrationTableCache
    cfg = FleetConfig(n_channels=1, n_banks=1, n_subarrays=4, n_cols=128)
    phys = PhysicsParams()
    cache = CalibrationTableCache(tmp_path)
    masks = _masks(g=4, c=128, seed=9)
    levels = np.zeros((4, 128), np.int32)
    plan = plan_for_grid(masks, [PlacementRequest("unembed/w", 96, 0),
                                 PlacementRequest("l/mixer/wi", 32, 2)],
                         cfg.grid_shape)
    # placement cannot be saved before its table exists
    with pytest.raises(FileNotFoundError):
        cache.save_placement("d1", cfg, phys, "m0", plan)
    cache.save("d1", cfg, phys, levels, masks=masks)
    cache.save_placement("d1", cfg, phys, "m0", plan)
    assert cache.placements("d1", cfg, phys) == ["m0"]
    got = cache.load_placement("d1", cfg, phys, "m0")
    assert got is not None
    assert got.grid_shape == cfg.grid_shape
    assert sorted(got.entries) == sorted(plan.entries)
    for name in plan.entries:
        np.testing.assert_array_equal(got.entries[name].phys_cols,
                                      plan.entries[name].phys_cols)
        np.testing.assert_array_equal(got.entries[name].faulty,
                                      plan.entries[name].faulty)
    assert got.capacity_report() == plan.capacity_report()
    # unknown name and corrupt payload read as misses
    assert cache.load_placement("d1", cfg, phys, "other") is None
    path = next((tmp_path / "d1").glob("*/placements/m0.npz"))
    path.write_bytes(path.read_bytes()[:32])
    assert cache.load_placement("d1", cfg, phys, "m0") is None


def test_fleet_perf_model_from_placement():
    masks = _masks(g=4, c=128, p=0.2, seed=2)
    plan = plan_placement(masks, [PlacementRequest("t", 150, 0)])
    m = FleetPerfModel.from_placement(plan, n_fracs=3)
    used = np.asarray(plan.used_per_subarray, float)
    occ = used[used > 0] / plan.n_cols_per_subarray
    assert len(m.error_free_fracs) == occ.size
    np.testing.assert_allclose(sorted(m.error_free_fracs), sorted(occ))
    # occupancy-derived rate is bounded by the all-error-free rate
    full = FleetPerfModel(error_free_fracs=(1.0,), n_fracs=3)
    assert m.macs_per_second < full.macs_per_second
    with pytest.raises(ValueError):
        FleetPerfModel.from_placement(
            dataclasses.replace(
                plan, used_per_subarray=np.zeros(4, np.int32)))


def test_placement_is_a_pytree():
    plan = plan_placement(_masks(seed=4), [PlacementRequest("t", 32, 0)])
    leaves = jax.tree_util.tree_leaves(plan)
    assert any(l is plan.entries["t"].phys_cols for l in leaves)
    mapped = jax.tree_util.tree_map(lambda x: x, plan)
    assert isinstance(mapped, Placement)
    np.testing.assert_array_equal(mapped.entries["t"].phys_cols,
                                  plan.entries["t"].phys_cols)
