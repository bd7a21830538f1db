"""Serving cells on a tensor-parallel fleet: closed-loop clients against
``FleetServingEngine`` over the configuration's ``mesh`` of chips.

A run is ``bench/serve.py``'s run, step for step: the same clients,
warm-up, window, end-to-end metrics, per-layer context and correctness
check against the plain reference.  Three parts are the fleet's own:

* the session: ``PUDSession.open_fleet`` on a ("data", "model") mesh of the
  configuration's shape; each chip calibrates and places its own grid
  (tables and placements cached in the benchmark's state directory);
* the weights: made from the seed as ``bench/weights.py`` makes them, but
  never whole.  The leaves that stay unpacked are made in one jitted call
  straight onto every chip of the mesh; each stacked packable projection
  is a ``LayerSource`` that makes one layer's slice
  (``weights.leaf(..., layer=l)``), and the unembed is made once and kept
  on the host, so the reference regenerates every value bit for bit;
* the pack: the fleet's streamed pack, which packs one layer slice at a
  time onto each shard's chip; every shard's placement must be planned or
  a hit.

``serve.run`` takes these three through its module names for the length of
the call.  The fleet keeps no buffer past ``serve.run``'s own release of
the engine and session, so the reference runs on a freed device.  The
pack's counters go to standard error.
"""
from __future__ import annotations

import json
import sys
import types
from unittest import mock

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from bench import serve, weights, work_fleet
from bench.serve import family  # noqa: F401  (the cell's families)
from repro.pud.packer import LayerSource


def open_fleet(cfg: dict, state_dir, mesh):
    """``PUDSession.open_fleet`` on ``mesh`` with the configuration's grid,
    its tables and placements cached under ``state_dir``."""
    from repro.core.calibrate import CalibrationConfig
    from repro.core.fleet import FleetConfig
    from repro.pud.physics import PhysicsParams
    from repro.runtime.session import PUDSession
    g = dict(cfg["grid"])
    g["frac_counts"] = tuple(g["frac_counts"])
    cal = dict(cfg["calibration"])
    method = cal.pop("method")
    return PUDSession.open_fleet(
        cfg["name"], mesh=mesh, grid=FleetConfig(**g),
        calib=CalibrationConfig(**cal),
        physics=PhysicsParams(**cfg["physics"]), key=int(cfg["device_key"]),
        method=method, backend=cfg["pud"]["backend"],
        cache_dir=str(state_dir / "pud" / cfg["name"]))


def _packable(path: str, cfg: dict) -> bool:
    parts = path.split("/")
    return ".".join(parts[-2:]) in cfg["pud"]["packable"]


def make_weights(defs, seed: int, cfg: dict, mesh) -> dict:
    """The parameter tree of ``seed`` as the fleet's pack takes it: the
    unpacked leaves replicated on every chip of ``mesh``, each stacked
    packable leaf a ``LayerSource`` and the unembed on the host."""
    root = weights.seed_key(seed)
    kept, flat = [], {}
    for p, s, d in weights.leaves(defs):
        if weights.is_stacked(p) and _packable(p, cfg):
            flat[p] = LayerSource(s, d, _layer_fn(root, p, s, d))
        elif p == "unembed/w":
            flat[p] = np.asarray(jax.jit(
                lambda r: weights.leaf(r, p, s, d))(root))
        else:
            kept.append((p, s, d))

    def build(r):
        return {p: weights.leaf(r, p, s, d) for p, s, d in kept}

    flat.update(jax.jit(build, out_shardings=NamedSharding(mesh, P()))(root))
    return weights.nest(flat)


def _layer_fn(root, path: str, shape, dtype):
    one = jax.jit(lambda r, layer: weights.leaf(r, path, shape, dtype,
                                                layer=layer))
    return lambda layer: one(root, np.uint32(layer))


def run(cell, seed: int, seconds: float, trace: bool, *, state_dir,
        t_start: float, control: bool = False):
    """One run of a fleet serving cell; ``serve.run``'s outcome and
    readings."""
    from repro.launch.mesh import make_host_mesh
    cfg, mix = cell.config, cell.traffic
    mesh = make_host_mesh(int(cfg["mesh"]["data"]), int(cfg["mesh"]["model"]))
    packed = {}

    def pack(fleet, params, cfg):
        pm = _pack(fleet, params, cfg)
        packed["report"] = fleet.pack_report()
        packed["widths"] = {work_fleet.projection_key(n):
                            pm.tensor(n).shard_widths
                            for n in pm.packed_names}
        return pm

    fleet_weights = types.SimpleNamespace(
        leaves=weights.leaves,
        make=lambda defs, s: make_weights(defs, s, cfg, mesh))
    with mock.patch.multiple(
            serve, weights=fleet_weights, pack=pack,
            open_session=lambda cfg, state_dir: open_fleet(cfg, state_dir,
                                                           mesh)):
        out, readings = serve.run(cell, seed, seconds, trace,
                                  state_dir=state_dir, t_start=t_start,
                                  control=control)
    print("fleet pack: " + json.dumps(packed["report"]), file=sys.stderr)
    out.ctx["pud_decode_step_shards"] = work_fleet.pud_decode_step_shards(
        cfg, mix["clients"], packed["widths"])
    return out, readings


def _pack(fleet, params, cfg: dict):
    """The fleet's pack of lane 0; every shard must place."""
    from repro.pud.gemv import PUDGemvConfig
    p = cfg["pud"]
    pm = fleet.pack(params, PUDGemvConfig(
        weight_bits=p["weight_bits"], mode=p["mode"],
        packable=tuple(p["packable"]), backend=p["backend"]),
        include_unembed=bool(p["unembed"]))[0]
    if p["placed"]:
        for row in fleet.sessions:
            for s in row:
                if s.placement_status not in ("planned", "hit"):
                    raise RuntimeError(
                        f"{s.device_id}: placement {s.placement_status}: "
                        f"{s.placement_error}")
    return pm
