"""Streamed sharded pack on a forced 4-device (1x4) host mesh.

``PUDFleetSession.pack`` takes packable leaves from the host or one layer
at a time (``LayerSource``), packs one layer slice at a time, puts each
shard's arrays on its own device and every other leaf replicated over the
mesh.  The pack must equal, bit for bit, the whole-tree
``pack_model_sharded`` of the same weights held on one device, placed and
logical; and a drift repack must still reuse the untouched shards'
placements while packing from the host-side source.

One subprocess (the mesh needs forced host devices) records the facts;
each test reads its own.
"""
import json

import pytest

PROG = """
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro.api import (CalibrationConfig, DriftSimulator, FleetConfig,
                           LayerSource, PUDGemvConfig, PUDSession,
                           ShardedPackedTensor, pack_model_sharded)
    from repro.launch.mesh import make_host_mesh
    from repro.models.params import init_params
    from repro.models.transformer import LMConfig, TransformerLM

    GRID = FleetConfig(n_channels=1, n_banks=1, n_subarrays=8, n_cols=1024)
    CAL = CalibrationConfig(n_iterations=4, n_samples=64)
    CFG = PUDGemvConfig(weight_bits=4, backend="reference")
    model = TransformerLM(LMConfig(
        name="fleet-pack", n_layers=2, d_model=256, n_heads=4, n_kv_heads=2,
        d_ff=1024, vocab=1024, head_dim=16, loss_chunk=32))
    params = init_params(model.param_defs(), jax.random.key(0))
    mesh = make_host_mesh(1, 4)
    model_of = {d: m for m, col in enumerate(np.asarray(mesh.devices).T)
                for d in col}

    def on_host(tree, path=()):
        # FFN stacks by layer from host arrays; every other leaf on the host
        out = {}
        for k, v in tree.items():
            if isinstance(v, dict):
                out[k] = on_host(v, path + (k,))
                continue
            a = np.asarray(v)
            out[k] = (LayerSource(a.shape, a.dtype, lambda l, a=a: a[l])
                      if "mixer" in path and k in ("wi", "wg", "wo") else a)
        return out

    def arrays(pm):
        out = {}
        for n in pm.packed_names:
            st = pm.tensor(n)
            out[n] = ([np.asarray(a) for a in (st.planes, st.scale,
                                                st.col_ids) if a is not None],
                      (st.shard_widths, st.block_cols, st.window_block))
        return out

    def same(a, b):
        return sorted(a) == sorted(b) and all(
            a[n][1] == b[n][1] and len(a[n][0]) == len(b[n][0]) and all(
                x.dtype == y.dtype and x.shape == y.shape
                and np.array_equal(x, y) for x, y in zip(a[n][0], b[n][0]))
            for n in a)

    facts = {}
    for placed in (False, True):
        fleet = PUDSession.open_fleet(
            "qwen3-1.7b", mesh=mesh, grid=GRID, calib=CAL, key=7,
            n_trials_ecr=128, backend="reference", placement=placed)
        fleet.calibrate()
        pm = fleet.pack(on_host(params), CFG, name=f"stream-{placed}")[0]
        plc = ([fleet.shard(0, m)._placement for m in range(4)]
               if placed else None)
        whole = pack_model_sharded(params, CFG, n_shards=4, placements=plc)
        facts[f"equal_{placed}"] = same(arrays(pm), arrays(whole))
        facts[f"placed_{placed}"] = pm.placed

        own = True
        for n in pm.packed_names:
            st = pm.tensor(n)
            for a in (st.planes, st.scale, st.col_ids):
                if a is None:
                    continue
                pos = a.ndim - (4 if a is st.planes else 2)
                own &= all(sh.index[pos] == slice(model_of[sh.device],
                                                  model_of[sh.device] + 1)
                           for sh in a.addressable_shards)
        facts[f"shards_own_device_{placed}"] = own
        rest = [a for a in jax.tree.leaves(
            pm.params, is_leaf=lambda x: isinstance(x, ShardedPackedTensor))
            if not isinstance(a, ShardedPackedTensor)]
        facts[f"replicated_{placed}"] = bool(rest) and all(
            a.sharding.is_fully_replicated and len(a.devices()) == 4
            for a in rest)
        rep = fleet.pack_report()
        ffn = params["layers_0_dense"]["mixer"]
        one_layer = sum(int(np.prod(ffn[k].shape[1:])) * ffn[k].dtype.itemsize
                        for k in ("wi", "wg", "wo"))
        facts[f"report_{placed}"] = dict(
            layers=rep["layers"], slices=rep["slices"],
            max_weight_bytes=rep["max_weight_bytes"], one_layer=one_layer,
            devices=len(rep["device_bytes"]),
            bytes_positive=min(rep["device_bytes"].values()) > 0)

    # drift repack of shard 1: the others keep their placement objects,
    # and the lane repacks from the host-side source
    src = fleet._pack_args[0]
    facts["source_on_host"] = all(
        not isinstance(src["layers_0_dense"]["mixer"][k], jax.Array)
        for k in ("wi", "wg", "wo")) and isinstance(
        src["unembed"]["w"], np.ndarray)
    facts["source_shares_replicated"] = (
        src["embed"]["table"] is pm.params["embed"]["table"])
    before = [fleet.shard(0, m)._placement for m in range(4)]

    def logical_words(st):
        # each shard's planes gathered at its col_ids: [L, S, WB, Kw, Np]
        planes, ids = np.asarray(st.planes), np.asarray(st.col_ids)
        return np.take_along_axis(planes, ids[:, :, None, None, :], -1)

    wi0 = logical_words(pm.tensor("layers_0_dense/mixer/wi"))
    sim = DriftSimulator.for_session(fleet.shard(0, 1))
    sim.advance(temp_c=3000.0, subarrays=[5])
    new = fleet.recalibrate_shard(1, [5], sim.sense_offsets(),
                                  assumed_temp_c=3000.0)
    facts["reused"] = [fleet.shard(0, m)._placement is before[m]
                       for m in range(4)]
    wi1 = logical_words(new.tensor("layers_0_dense/mixer/wi"))
    facts["untouched_planes_equal"] = all(
        np.array_equal(wi0[:, m], wi1[:, m]) for m in (0, 2, 3))
    facts["repacked_placed"] = new.placed and fleet.packs[0] is new
    print("FLEET_PACK " + json.dumps(facts))
"""


@pytest.fixture(scope="module")
def facts():
    from conftest import run_forced_devices
    r = run_forced_devices(PROG, marker="FLEET_PACK ", devices=4,
                           timeout=900)
    line = next(l for l in r.stdout.splitlines()
                if l.startswith("FLEET_PACK "))
    return json.loads(line[len("FLEET_PACK "):])


@pytest.mark.parametrize("placed", [False, True])
def test_streamed_pack_equals_whole_tree_pack(facts, placed):
    assert facts[f"placed_{placed}"] is placed
    assert facts[f"equal_{placed}"]


@pytest.mark.parametrize("placed", [False, True])
def test_every_shard_sits_on_its_own_device(facts, placed):
    assert facts[f"shards_own_device_{placed}"]


@pytest.mark.parametrize("placed", [False, True])
def test_unpacked_leaves_are_replicated(facts, placed):
    assert facts[f"replicated_{placed}"]


@pytest.mark.parametrize("placed", [False, True])
def test_pack_counters_show_one_layer_at_a_time(facts, placed):
    rep = facts[f"report_{placed}"]
    assert rep["layers"] == 2 and rep["slices"] == 2 * 3 + 1
    assert rep["devices"] == 4 and rep["bytes_positive"]
    # weights arrive from the host: a device holds at most the slice in
    # flight, never more than one layer's projections
    assert 0 < rep["max_weight_bytes"] <= rep["one_layer"]


def test_repack_reuses_untouched_placements_from_host_source(facts):
    assert facts["source_on_host"] and facts["source_shares_replicated"]
    assert facts["reused"] == [True, False, True, True]
    assert facts["untouched_planes_equal"]
    assert facts["repacked_placed"]
