"""PUDSession: the one public API for the PUD serving lifecycle.

Everything a workload needs from a calibrated PUD device used to be ~120
lines of hand-wiring per call site: load-or-run fleet calibration, persist
the table, plan column placement from the masks, pack weights into placed
bit-planes, dispatch the kernel, price the serving rate.  ``PUDSession``
owns that chain behind five calls:

    from repro.api import PUDSession

    session = PUDSession.open("qwen3-1.7b", grid=FleetConfig(...),
                              cache_dir="~/.pud-cache", backend="pallas")
    state  = session.calibrate()            # cache hit (ms) or Algorithm 1
    packed = session.pack(params, cfg)      # placement-aware PackedModel
    y      = session.linear(x, "unembed/w") # kernel via the named backend
    rep    = session.perf_report()          # Eq.-1 rates, occupancy, ECR
    extras = session.decode_extras()        # layout/bytes/report diagnostics

The session hides per-device reliability state (which physical columns are
safe) from the workload: callers speak logical tensors, the session speaks
placed physical columns.  Backends (kernels/backends.py) are selectable per
session and per call and are bit-exact against each other, so the same
session code serves the TPU Pallas lowering, the forced interpreter, and the
pure-jnp reference.

A session without ``cache_dir`` still works — calibration runs in memory
and is simply not persisted (the null-cache path); a session that never
calibrates packs onto logical columns, exactly like serving without
``--calib-cache``.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.calibrate import CalibrationConfig
from repro.core.fleet import FleetConfig, load_or_calibrate, manufacture_fleet
from repro.kernels.backends import DEFAULT_BACKEND, backend_names
from repro.pud.gemv import (ECR_BASELINE_B300, ECR_PUDTUNE_T210,
                            FleetPerfAggregate, FleetPerfModel, PUDGemvConfig,
                            PUDPerfModel, pud_linear)
from repro.pud.packed import PackedModel, packed_bytes
from repro.pud.packer import (PackStats, pack_model, pack_model_sharded,
                              packing_requests)
from repro.pud.physics import PhysicsParams
from repro.pud.placement import (Placement, PlacementError, plan_for_grid,
                                 requests_fingerprint, shard_column_slices)
from repro.runtime.calib_cache import CalibrationTableCache


@dataclasses.dataclass
class CalibrationState:
    """One device's reliability state, as loaded or identified."""

    levels: jax.Array          # [G, C] int32 ladder level per column
    ecr: jax.Array             # [G] float32 per-subarray ECR
    masks: jax.Array           # [G, C] bool per-column error-prone mask
    cache_hit: bool
    wall_s: float

    @property
    def mean_ecr(self) -> float:
        return float(np.asarray(self.ecr).mean())


def _restamp_model(pm: PackedModel, stamped: dict) -> PackedModel:
    """Rebuild a ``PackedModel`` with ``stamped[name]`` tensors swapped in
    (same aux metadata — tuning stamps are trace-static pytree aux)."""
    def walk(tree, path):
        out = {}
        for key, sub in tree.items():
            if key.endswith("_pud"):
                name = "/".join(path + (key[: -len("_pud")],))
                out[key] = stamped.get(name, sub)
            elif isinstance(sub, dict):
                out[key] = walk(sub, path + (key,))
            else:
                out[key] = sub
        return out

    return PackedModel(
        params=walk(pm.params, ()),
        packed_names=pm.packed_names,
        skipped_names=pm.skipped_names,
        weight_bits=pm.weight_bits, placed=pm.placed)


class _NullCache:
    """In-memory stand-in when no cache_dir is given: every load misses,
    every save is dropped — calibration still runs, nothing persists."""

    def load(self, *a, **kw):
        return None

    def save(self, *a, **kw):
        return None


class PUDSession:
    """Facade over the calibrate -> cache -> place -> pack -> execute chain.

    Build one with ``PUDSession.open``; the constructor takes the already-
    resolved pieces.
    """

    def __init__(self, *, arch: str | None, fleet_cfg: FleetConfig,
                 cache: CalibrationTableCache | None, device_id: str,
                 backend: str, physics: PhysicsParams,
                 calib: CalibrationConfig, key: jax.Array,
                 placement: bool, method: str, n_trials_ecr: int):
        if backend not in backend_names():
            raise KeyError(f"unknown backend {backend!r}; registered: "
                           f"{backend_names()}")
        self.arch = arch
        self.fleet_cfg = fleet_cfg
        self.cache = cache
        self.device_id = device_id
        self.backend = backend
        self.physics = physics
        self.calib_cfg = calib
        self.key = key
        self.placement_enabled = placement
        self.method = method
        self.n_trials_ecr = n_trials_ecr

        self._state: CalibrationState | None = None
        self._canaries = None                     # core/canary.CanarySet
        self._operating_point: float | None = None
        self._packed: PackedModel | None = None
        self._pack_cfg: PUDGemvConfig | None = None
        self._placement: Placement | None = None
        self._placement_name: str | None = None
        self._placement_status: str | None = None   # hit | planned | skipped
        self._placement_error: str | None = None
        self._tuning_report: dict | None = None

    # -- construction -------------------------------------------------------

    @classmethod
    def open(cls, arch_or_grid: "str | FleetConfig | None" = None, *,
             grid: FleetConfig | None = None,
             cache_dir=None, device_id: str = "dimm0",
             backend: str = DEFAULT_BACKEND,
             physics: PhysicsParams | None = None,
             calib: CalibrationConfig | None = None,
             key: "jax.Array | int" = 0,
             placement: bool = True,
             method: str = "reference",
             n_trials_ecr: int = 1024) -> "PUDSession":
        """Open a session on one device.

        ``arch_or_grid``: either the architecture name this session serves
        (used for perf pricing and placement naming) or the device's
        ``FleetConfig`` grid; pass the other via ``grid``.  ``cache_dir``
        enables persistence (tables + placements survive restarts);
        without it calibration runs in memory.  ``key`` seeds manufacture/
        calibration (an int is wrapped with ``jax.random.key``).
        """
        arch = None
        if isinstance(arch_or_grid, FleetConfig):
            if grid is not None:
                raise ValueError("grid given twice")
            grid = arch_or_grid
        elif arch_or_grid is not None:
            arch = str(arch_or_grid)
        if not isinstance(key, jax.Array):
            key = jax.random.key(int(key))
        return cls(
            arch=arch,
            fleet_cfg=grid or FleetConfig(n_channels=1, n_banks=1,
                                          n_subarrays=16, n_cols=2048),
            cache=(CalibrationTableCache(cache_dir)
                   if cache_dir is not None else None),
            device_id=device_id, backend=backend,
            physics=physics or PhysicsParams(),
            calib=calib or CalibrationConfig(),
            key=key, placement=placement, method=method,
            n_trials_ecr=n_trials_ecr)

    @classmethod
    def at_operating_point(cls, ecr: float, *, arch: str | None = None,
                           n_fracs_cfg: tuple[int, ...] = (2, 1, 0),
                           backend: str = DEFAULT_BACKEND) -> "PUDSession":
        """Session pinned to a fixed mean ECR (e.g. the Table-I operating
        points) instead of a measured device — for pricing/what-if runs."""
        s = cls.open(arch, grid=FleetConfig(frac_counts=n_fracs_cfg),
                     backend=backend)
        s._operating_point = float(ecr)
        return s

    @classmethod
    def open_fleet(cls, arch_or_grid: "str | FleetConfig | None" = None, *,
                   mesh=None, n_data: int | None = None,
                   n_model: int | None = None,
                   grid: FleetConfig | None = None,
                   cache_dir=None, device_id: str = "dimm0",
                   backend: str = DEFAULT_BACKEND,
                   physics: PhysicsParams | None = None,
                   calib: CalibrationConfig | None = None,
                   key: "jax.Array | int" = 0,
                   placement: bool = True,
                   method: str = "reference",
                   n_trials_ecr: int = 1024) -> "PUDFleetSession":
        """Open one logical session per device of a serving mesh.

        ``mesh`` is a ``("data", "model")`` mesh from ``launch/mesh.py``;
        its "model" axis carries tensor-parallel shards of every packable
        projection, its "data" axis independent serving lanes.  Without a
        mesh, pass ``n_data``/``n_model`` explicitly — packing and all
        host-side state management still work (useful for planning tests),
        only sharded *execution* requires the mesh.

        Each of the ``n_data x n_model`` sessions gets its own derived
        ``device_id`` (suffix ``-d{lane}m{shard}``) and its own fold of
        ``key`` — so per-device calibration tables, placements, canaries
        and drift state are fully independent, exactly as physically
        distinct DIMMs would be.
        """
        if mesh is not None:
            if n_data is None:
                n_data = int(mesh.shape["data"])
            if n_model is None:
                n_model = int(mesh.shape["model"])
        if not n_data or not n_model or n_data < 1 or n_model < 1:
            raise ValueError("open_fleet needs a mesh or explicit "
                             "n_data/n_model >= 1")
        if not isinstance(key, jax.Array):
            key = jax.random.key(int(key))
        sessions = [
            [cls.open(arch_or_grid, grid=grid, cache_dir=cache_dir,
                      device_id=f"{device_id}-d{d}m{m}", backend=backend,
                      physics=physics, calib=calib,
                      key=jax.random.fold_in(key, d * n_model + m),
                      placement=placement, method=method,
                      n_trials_ecr=n_trials_ecr)
             for m in range(n_model)]
            for d in range(n_data)]
        return PUDFleetSession(sessions, mesh=mesh,
                               arch=sessions[0][0].arch)

    # -- calibration --------------------------------------------------------

    @property
    def calibration(self) -> CalibrationState | None:
        return self._state

    @property
    def n_fracs(self) -> int:
        return sum(self.fleet_cfg.frac_counts)

    @property
    def ladder(self):
        return self.fleet_cfg.ladder(self.physics)

    def calibrate(self, force: bool = False) -> CalibrationState:
        """Load the device's persisted table, or identify + persist it.

        A cache hit costs a file read; a miss runs the fleet Algorithm 1 +
        ECR/mask measurement and (with a cache) persists the table.
        """
        if self._state is not None and not force:
            return self._state
        t0 = time.perf_counter()
        levels, ecr, masks, hit = load_or_calibrate(
            self.cache if self.cache is not None else _NullCache(),
            self.device_id, self.key, self.fleet_cfg, self.physics,
            config=self.calib_cfg, method=self.method,
            n_trials_ecr=self.n_trials_ecr)
        self._state = CalibrationState(
            levels=levels, ecr=ecr, masks=masks,
            cache_hit=bool(hit), wall_s=time.perf_counter() - t0)
        return self._state

    def baseline_ecr(self, n_trials: int | None = None) -> float:
        """Mean fleet ECR of the uncalibrated B_{3,0,0} baseline on this
        device's manufactured offsets (the before-picture of Table I)."""
        from repro.core.ecr import measure_ecr_fleet
        from repro.core.offsets import baseline_charges
        cfg = self.fleet_cfg
        offsets = manufacture_fleet(self.key, cfg, self.physics)
        base = jnp.broadcast_to(
            baseline_charges(3, cfg.n_cols, self.physics)[None],
            (cfg.n_subarrays_total, 3, cfg.n_cols))
        ecr, _ = measure_ecr_fleet(
            jax.random.fold_in(self.key, 0x0ECB), offsets, base,
            self.physics, 3, n_trials=n_trials or self.n_trials_ecr)
        return float(np.asarray(ecr).mean())

    # -- canaries + live recalibration --------------------------------------

    @property
    def canaries(self):
        """The reserved ``core/canary.CanarySet``, or None."""
        return self._canaries

    def reserve_canaries(self, n_per_subarray: int = 16):
        """Reserve per-subarray canary columns for the drift monitor.

        Canaries come out of the calibration-time error-free set (evenly
        spread over each subarray) and are OR-ed into the planning masks,
        so no tensor is ever placed on them — the monitor can hammer them
        with probe patterns while decode runs on the rest of the grid.
        Call after ``calibrate`` and before ``pack``; the reservation also
        keys persisted placement names, so a canary-less cached plan is
        never reused for a canary-reserving session.
        """
        if self._state is None:
            raise RuntimeError("reserve_canaries requires calibrate() first")
        from repro.core.canary import CanarySet, reserve_canaries
        cols = reserve_canaries(self._state.masks, n_per_subarray)
        self._canaries = CanarySet(cols=cols, n_cols=self.fleet_cfg.n_cols)
        return self._canaries

    def recalibrate_subarrays(self, subarrays, sense_offsets, *,
                              assumed_temp_c: float | None = None
                              ) -> CalibrationState:
        """Partial live recalibration against the device's *current* offsets.

        The background half of drift recovery: re-runs ladder
        identification for ``subarrays`` only (per-subarray RNG streams,
        so the result is independent of how drift events were batched),
        re-measures their ECR + masks against the drifted offsets, merges
        the refreshed rows into the session state, and persists the merged
        table as a new cache version.  The cache save replaces the whole
        entry directory, which drops its persisted placements — exactly
        right, since plans made from the stale masks may sit on columns
        that went bad; the next ``pack`` re-plans from the merged masks.
        """
        if self._state is None:
            raise RuntimeError(
                "recalibrate_subarrays requires calibrate() first")
        from repro.core.ecr import measure_ecr_fleet
        from repro.core.fleet import fleet_calib_charges, recalibrate_subarrays
        t0 = time.perf_counter()
        idx = sorted(int(s) for s in subarrays)
        offs = jnp.asarray(sense_offsets)
        sub_levels = recalibrate_subarrays(
            self.key, offs, idx, self.fleet_cfg, self.physics,
            self.calib_cfg, method=self.method)
        charges = fleet_calib_charges(self.ladder, sub_levels, self.physics)
        sub_ecr, sub_masks = measure_ecr_fleet(
            jax.random.fold_in(self.key, 0x0EC5), offs[jnp.asarray(idx)],
            charges, self.physics, self.n_fracs,
            n_trials=self.n_trials_ecr)
        levels = np.asarray(self._state.levels).copy()
        ecr = np.asarray(self._state.ecr).copy()
        masks = np.asarray(self._state.masks).copy()
        levels[idx] = np.asarray(sub_levels)
        ecr[idx] = np.asarray(sub_ecr)
        masks[idx] = np.asarray(sub_masks)
        self._state = CalibrationState(
            levels=jnp.asarray(levels), ecr=jnp.asarray(ecr),
            masks=jnp.asarray(masks), cache_hit=False,
            wall_s=time.perf_counter() - t0)
        if self.cache is not None:
            self.cache.save(
                self.device_id, self.fleet_cfg, self.physics, levels,
                ecr=ecr, masks=masks,
                metadata={"method": self.method,
                          "recalibrated_subarrays": idx},
                assumed_temp_c=(self.physics.temp_nominal_c
                                if assumed_temp_c is None
                                else assumed_temp_c))
        return self._state

    def calibration_age(self) -> dict | None:
        """Age metadata of the persisted table (staleness for the drift
        monitor), or None without a cache / persisted entry."""
        if self.cache is None or isinstance(self.cache, _NullCache):
            return None
        table = self.cache.load(self.device_id, self.fleet_cfg, self.physics)
        if table is None:
            return None
        return {"calibrated_at": table.calibrated_at,
                "age_days": table.age_days(),
                "assumed_temp_c": table.assumed_temp_c,
                "params_fingerprint": table.params_fingerprint}

    # -- placement + packing ------------------------------------------------

    @property
    def placement(self) -> Placement | None:
        return self._placement

    @property
    def placement_status(self) -> str | None:
        """After ``pack``: "hit" | "planned" | "skipped" | None (placement
        not attempted — disabled or uncalibrated)."""
        return self._placement_status

    @property
    def placement_error(self) -> str | None:
        return self._placement_error

    @property
    def placement_name(self) -> str | None:
        return self._placement_name

    @property
    def packed(self) -> PackedModel | None:
        return self._packed

    def _plan_requests(self, reqs, base_name: str) -> Placement | None:
        """Cache-aware placement planning for an explicit request list.

        The shard-slicing entry used by ``PUDFleetSession``: each model
        shard plans its *own column slice* of every request against its own
        masks and persists under its own fingerprinted name. ``_plan``
        feeds it the whole-model requests.
        """
        pname = f"{base_name}-{requests_fingerprint(reqs)}"
        masks = self._state.masks
        if self._canaries is not None:
            # Reserved canaries plan as unusable despite being error-free,
            # and the reservation hash keys the persisted plan.
            masks = np.asarray(masks, bool) | self._canaries.mask()
            pname += f"-c{self._canaries.fingerprint()}"
        self._placement_name = pname
        placement = None
        if self.cache is not None:
            placement = self.cache.load_placement(
                self.device_id, self.fleet_cfg, self.physics, pname)
        if placement is not None:
            self._placement_status = "hit"
            self._placement = placement
            return placement
        try:
            placement = plan_for_grid(
                masks, reqs, self.fleet_cfg.grid_shape)
        except PlacementError as e:
            self._placement_status, self._placement_error = "skipped", str(e)
            return None
        if self.cache is not None:
            self.cache.save_placement(self.device_id, self.fleet_cfg,
                                      self.physics, pname, placement)
        self._placement_status = "planned"
        self._placement = placement
        return placement

    def _plan(self, params: dict, cfg: PUDGemvConfig,
              name: str | None) -> Placement | None:
        return self._plan_requests(packing_requests(params, cfg),
                                   name or self.arch or "model")

    def pack(self, params: dict, cfg: PUDGemvConfig | None = None, *,
             name: str | None = None,
             include_unembed: bool = True) -> PackedModel:
        """Pack a parameter tree for this device.

        With placement enabled and a calibrated session, every packable
        projection's columns are planned onto error-free physical columns
        (loaded from the cache when a plan for the same request fingerprint
        is already persisted, planned + persisted otherwise) and the packs
        come out in the placed physical layout.  ``name`` labels the
        persisted placement (default: the session's arch).

        The packs are stamped with the session backend (unless the config
        names its own), so model forwards dispatch them through it too.
        """
        if cfg is None:
            cfg = PUDGemvConfig(backend=self.backend)
        elif cfg.backend is None:
            cfg = dataclasses.replace(cfg, backend=self.backend)
        self._placement_status = self._placement_error = None
        self._placement = None
        if (self.placement_enabled and self._state is not None
                and self._state.masks is not None):
            self._placement = self._plan(params, cfg, name)
        pm = pack_model(params, cfg, include_unembed=include_unembed,
                        placement=self._placement)
        self._packed, self._pack_cfg = pm, cfg
        return pm

    # -- kernel autotuning ---------------------------------------------------

    def _tuning_cache(self):
        """The persistent tuning cache riding alongside the calibration
        cache (``<cache_dir>/tuning``), or None for cache-less sessions
        (tuning still runs; winners live only in the stamped packs)."""
        if self.cache is None:
            return None
        from repro.runtime.tune import TuningCache
        return TuningCache(self.cache.directory / "tuning")

    def tune(self, names=None, *, batches=(1, 8), force: bool = False,
             warmup: int = 1, reps: int = 3,
             max_candidates: int = 12) -> dict:
        """Autotune the packed projections and stamp the winners.

        For every pack (restricted to ``names`` — report names or unique
        path suffixes — when given) and every batch size in ``batches``
        (1 exercises the decode-shaped GeMV entry, >1 the batch-tiled
        GEMM), the persisted plan is loaded from the tuning cache; on a
        miss (or ``force=True``) the search runs (kernels/autotune.py:
        contract-filtered candidates, warmup + median timing, bit-exactness
        cross-check) and the winner is persisted.  Winning plans are
        stamped onto the packs, so every subsequent ``linear`` /
        ``serving_engine`` call — and any ``save_packed_npz`` — carries
        them; cold-start without plans falls back to the divisor heuristic.

        Returns the tuning report (also via :meth:`tuning_report`).
        """
        if self._packed is None:
            raise RuntimeError("no packed model: call session.pack() first")
        from repro.kernels.autotune import tune_kernel, tuning_key
        cache = self._tuning_cache()
        cfg = self._pack_cfg or PUDGemvConfig()
        mode = cfg.mode
        tensors = self._packed.tensors
        if names is not None:
            wanted = {}
            for name in names:
                hits = ([name] if name in tensors
                        else [k for k in tensors if k.endswith(name)])
                if len(hits) != 1:
                    raise KeyError(f"packed tensor {name!r} "
                                   + ("is ambiguous" if hits
                                      else "not found"))
                wanted[hits[0]] = tensors[hits[0]]
            tensors = wanted

        report: dict = {"fingerprint": (cache.fingerprint if cache
                                        else None),
                        "cache_dir": (str(cache.directory) if cache
                                      else None),
                        "keys": {}}
        stamped: dict[str, object] = {}
        for name, pt in tensors.items():
            planes = pt.planes[0] if pt.planes.ndim == 4 else pt.planes
            col_ids = None
            if pt.col_ids is not None:
                col_ids = (pt.col_ids[0] if pt.col_ids.ndim == 2
                           else pt.col_ids)
            plans: dict[str, object] = {}
            for batch in batches:
                entry = "gemm" if batch > 1 else "gemv"
                key = tuning_key(entry, int(batch), pt.k, pt.n, pt.n_bits,
                                 pt.layout, pt.placed)
                plan = None if (force or cache is None) else cache.load(key)
                row = {"name": name, "entry": entry}
                if plan is not None:
                    row["status"] = "hit"
                else:
                    # Deterministic int8 probe covering the full operand
                    # range; tuning is timing-only, values are irrelevant
                    # beyond exercising the same dtype/shape as serving.
                    x = ((jnp.arange(int(batch) * pt.k) % 255) - 127) \
                        .astype(jnp.int8).reshape(int(batch), pt.k)
                    res = tune_kernel(
                        entry, x, planes, col_ids=col_ids,
                        window_block=pt.window_block, layout=pt.layout,
                        logical_k=pt.logical_k, mode=mode,
                        backend=self.backend, warmup=warmup, reps=reps,
                        max_candidates=max_candidates)
                    plan = res.plan
                    row.update(status="tuned", **res.to_stats())
                    if cache is not None:
                        cache.save(key, plan, res.to_stats())
                row["plan"] = plan.to_dict()
                report["keys"][key] = row
                plans[entry] = plan
            stamped[name] = pt.replace(
                tile_plan=tuple(sorted(plans.items())))
        self._restamp_packs(stamped)
        self._tuning_report = report
        return report

    def _restamp_packs(self, stamped: dict) -> None:
        """Swap tuned packs into the packed tree (new ``PackedModel``,
        same aux metadata — the stamp is trace-static pytree aux)."""
        self._packed = _restamp_model(self._packed, stamped)

    def tuning_report(self) -> dict | None:
        """The last :meth:`tune` report (per-key status, plans, measured
        speedups), or None when the session never tuned."""
        return self._tuning_report

    # -- execution ----------------------------------------------------------

    def linear(self, x: jax.Array, name: str, *,
               backend: str | None = None) -> jax.Array:
        """Run one packed projection: x [..., K] -> [..., N] float32.

        ``name`` is the pack's report name or a unique path suffix
        ("unembed/w", "mixer/wi").  ``backend`` overrides the session
        backend for this call; all backends are bit-exact.
        """
        if self._packed is None:
            raise RuntimeError("no packed model: call session.pack() first")
        pt = self._packed.tensor(name)
        cfg = self._pack_cfg or PUDGemvConfig()
        return pud_linear(x, pt, cfg, backend=backend or self.backend)

    # -- reporting ----------------------------------------------------------

    def baseline_perf_model(self) -> PUDPerfModel:
        """The uncalibrated B_{3,0,0} Table-I operating point."""
        return PUDPerfModel(error_free_frac=1 - ECR_BASELINE_B300)

    def tuned_perf_model(self) -> "FleetPerfModel | PUDPerfModel":
        """The calibrated device's rate model: the measured per-subarray
        table when calibrated, the pinned operating point for
        ``at_operating_point`` sessions, the Table-I T_{2,1,0} constant
        otherwise."""
        if self._operating_point is not None:
            return PUDPerfModel(error_free_frac=1 - self._operating_point)
        if self._state is not None:
            return FleetPerfModel.from_table(
                self._state.ecr, n_fracs=self.n_fracs)
        return PUDPerfModel(error_free_frac=1 - ECR_PUDTUNE_T210)

    def placement_perf_model(self) -> FleetPerfModel | None:
        """Rate from the actual column placement (occupied-subarray waves),
        None when serving on the logical layout.  An *empty* placement
        (a zero-width model shard serving pure padding) also yields None —
        the device executes no placed columns, so the table-derived model
        is the honest rate."""
        if self._placement is None or not self._placement.entries:
            return None
        return FleetPerfModel.from_placement(
            self._placement, n_fracs=self.n_fracs)

    def flops_per_token(self) -> float | None:
        """2 x active params of the session's arch (one MAC = 2 flops)."""
        if self.arch is None:
            return None
        from repro.configs import get
        return 2.0 * get(self.arch).n_active_params

    def tokens_per_second(self, flops_per_token: float | None = None) -> float:
        flops = flops_per_token or self.flops_per_token()
        if flops is None:
            raise ValueError("no arch on this session: pass flops_per_token")
        return self.tuned_perf_model().tokens_per_second(flops)

    def optimal_batch_size(self, max_batch: int | None = None) -> int:
        """Occupancy-derived serving batch: the placement-derived rate
        model's optimum (weight replicas x operand residency), 1 when the
        session has no fleet-shaped model to derive it from."""
        pm = self.placement_perf_model() or self.tuned_perf_model()
        if isinstance(pm, FleetPerfModel):
            return pm.optimal_batch_size(max_batch)
        return 1

    def serving_engine(self, model, *, max_len: int,
                       batch_size: int | None = None,
                       chunk_prefill: int | None = None,
                       prefix_cache=False, slo=None, **kw):
        """A continuous-batching ``ServingEngine`` over this session's
        packed model (``pack`` must have run).  ``batch_size`` defaults to
        ``optimal_batch_size()``.

        Scheduler extensions (see ``runtime/engine.py``): ``chunk_prefill``
        interleaves fixed-size prefill chunks with decode waves,
        ``prefix_cache`` reuses completed prefills across requests
        (invalidated on every drift hot swap), and ``slo`` enables
        deadline-aware admission priced by this session's placement perf
        model (``step_seconds``).
        """
        from repro.runtime.engine import ServingEngine
        if self._packed is None:
            raise RuntimeError("no packed model: call session.pack() first")
        return ServingEngine(model, self._packed.params, session=self,
                             max_len=max_len, batch_size=batch_size,
                             chunk_prefill=chunk_prefill,
                             prefix_cache=prefix_cache, slo=slo, **kw)

    def perf_report(self, flops_per_token: float | None = None,
                    batch_size: int | None = None) -> dict:
        """Everything the serving driver prints: calibration status, Eq.-1
        rate models, the placement occupancy report and — when
        ``batch_size`` is given — the batch-aware aggregate rates."""
        base, tune = self.baseline_perf_model(), self.tuned_perf_model()
        rep: dict = {
            "device_id": self.device_id,
            "backend": self.backend,
            "n_subarrays": self.fleet_cfg.n_subarrays_total,
            "n_fracs": self.n_fracs,
            "calibrated": self._state is not None,
            "cache_hit": (self._state.cache_hit if self._state else None),
            "mean_ecr": (self._state.mean_ecr if self._state
                         else self._operating_point),
            "baseline_model": base,
            "tuned_model": tune,
            "gain": tune.speedup_vs(base),
            "placement": (self._placement.capacity_report()
                          if self._placement is not None else None),
            "placement_status": self._placement_status,
            "placement_model": self.placement_perf_model(),
        }
        flops = flops_per_token or self.flops_per_token()
        if flops is not None:
            rep["flops_per_token"] = flops
            rep["baseline_tok_s"] = base.tokens_per_second(flops)
            rep["tuned_tok_s"] = tune.tokens_per_second(flops)
            if rep["placement_model"] is not None:
                rep["placed_tok_s"] = \
                    rep["placement_model"].tokens_per_second(flops)
            # Weight-traffic terms of the last pack: the staging-bandwidth
            # ceiling and the rate under both (compute + traffic) limits.
            if self._packed is not None and isinstance(tune, FleetPerfModel):
                stored = packed_bytes(self._packed)["stored_bytes"]
                rep["weight_bytes_per_token"] = stored
                rep["staging_bound_tok_s"] = \
                    tune.staging_bound_tokens_per_second(stored)
                rep["traffic_aware_tok_s"] = \
                    tune.traffic_aware_tokens_per_second(flops, stored)
        if batch_size is not None:
            rep["batch_size"] = int(batch_size)
            rep["optimal_batch"] = self.optimal_batch_size()
            pm = self.placement_perf_model() or self.tuned_perf_model()
            if isinstance(pm, FleetPerfModel):
                rep["batch_speedup"] = pm.batch_speedup(batch_size)
                if flops is not None:
                    rep["batched_tok_s"] = pm.batched_tokens_per_second(
                        flops, batch_size)
        return rep

    def decode_extras(self) -> dict:
        """Decode-path diagnostics of the last ``pack``: layout, byte
        accounting (stored vs dense-equivalent — the bit-packing win), the
        per-token weight-traffic terms, and the packing report."""
        if self._packed is None:
            raise RuntimeError("no packed model: call session.pack() first")
        from repro.pud.gemv import weight_traffic
        return {
            "backend": self.backend,
            "layout": ("placed physical" if self._placed_layout
                       else "logical"),
            "weight_bits": self._packed.weight_bits,
            "n_packed": len(self._packed.packed_names),
            "report": self._packed.report,
            **packed_bytes(self._packed),
            **weight_traffic(self._packed),
        }

    @property
    def _placed_layout(self) -> bool:
        return self._packed is not None and self._packed.placed


def _pack_source(params: dict, packed: dict) -> dict:
    """What a fleet repacks from: ``params`` with every leaf the pack left
    unpacked swapped for its placed copy in ``packed``, and the packable
    leaves as given (host arrays and ``LayerSource``s stay off the
    devices)."""
    out = {}
    for key, sub in params.items():
        if isinstance(sub, dict):
            out[key] = _pack_source(sub, packed.get(key, {}))
        else:
            out[key] = packed.get(key, sub)
    return out


class PUDFleetSession:
    """A mesh-shaped grid of ``PUDSession``s serving one sharded model.

    ``sessions[d][m]`` is the device at data lane ``d``, model shard ``m``
    — each with its own device id, and therefore its own calibration-cache
    entry, placement plans, canary reservation and drift state.

    The "model" axis carries tensor parallelism: every packable
    projection's N columns split on *full-tensor* window-block boundaries
    (``pud.placement.shard_column_slices``, verified by
    ``analysis.contracts.check_shard_slices``) so shard ``m`` owns whole
    placement windows, plans them on its own masks, and executes its slice
    through ``shard_map`` (``kernels.ops.pud_matmul_sharded`` — bit-exact
    against the unsharded path).  The "data" axis carries independent
    serving lanes: :meth:`pack` builds one ``PackedModel`` of
    ``ShardedPackedTensor``s per lane and :meth:`serving_engine` runs one
    ``ServingEngine`` per lane over a round-robin split of the request
    queue (``runtime.engine.FleetServingEngine``).

    Build one with :meth:`PUDSession.open_fleet`.
    """

    def __init__(self, sessions, *, mesh=None, axis: str = "model",
                 arch: str | None = None):
        if not sessions or not sessions[0]:
            raise ValueError("open_fleet needs at least one session")
        self.sessions = sessions
        self.mesh = mesh
        self.axis = axis
        self.arch = arch
        self.n_data = len(sessions)
        self.n_model = len(sessions[0])
        self._packs: "list[PackedModel] | None" = None
        self._pack_cfg: PUDGemvConfig | None = None
        self._pack_args = None          # (source, name, include_unembed)
        self._pack_stats: PackStats | None = None
        self._shard_widths: tuple[int, ...] | None = None
        self._tuning_report: dict | None = None

    # -- grid views ----------------------------------------------------------

    @property
    def n_devices(self) -> int:
        return self.n_data * self.n_model

    @property
    def device_ids(self) -> list:
        return [[s.device_id for s in row] for row in self.sessions]

    @property
    def shard_widths(self) -> "tuple[int, ...] | None":
        """Total N columns each model shard owns (set by :meth:`pack`)."""
        return self._shard_widths

    @property
    def packs(self) -> "list[PackedModel] | None":
        return self._packs

    def shard(self, data_lane: int, model_shard: int) -> PUDSession:
        return self.sessions[data_lane][model_shard]

    # -- lifecycle -----------------------------------------------------------

    def calibrate(self, force: bool = False) -> list:
        """Calibrate every device; returns the [n_data][n_model] states."""
        return [[s.calibrate(force) for s in row] for row in self.sessions]

    def reserve_canaries(self, n_per_subarray: int = 16) -> list:
        return [[s.reserve_canaries(n_per_subarray) for s in row]
                for row in self.sessions]

    def flops_per_token(self) -> float | None:
        return self.sessions[0][0].flops_per_token()

    def optimal_batch_size(self, max_batch: int | None = None) -> int:
        """Worst-case (min over devices) so every lane's engine sustains it."""
        return min(s.optimal_batch_size(max_batch)
                   for row in self.sessions for s in row)

    # -- placement + packing -------------------------------------------------

    def _shard_requests(self, reqs):
        """Per-model-shard sliced request lists + per-shard total widths.

        Every request splits on the boundaries of its *own* full-tensor
        window block (``shard_column_slices``), so no placement window ever
        straddles a shard — ``check_shard_slices`` enforces it before any
        planning happens.  Zero-width shards (more shards than blocks)
        simply receive no request for that tensor.
        """
        from repro.analysis.contracts import check_shard_slices
        sliced = [[] for _ in range(self.n_model)]
        widths = [0] * self.n_model
        for r in reqs:
            spans, bc = shard_column_slices(r.n_cols, self.n_model)
            check_shard_slices(spans, r.n_cols, bc)
            for m, (lo, hi) in enumerate(spans):
                widths[m] += hi - lo
                if hi > lo:
                    sliced[m].append(dataclasses.replace(
                        r, n_cols=hi - lo, block_cols=bc))
        return sliced, tuple(widths)

    def _plan_shard(self, data_lane: int, model_shard: int, sliced,
                    name: str | None) -> Placement | None:
        s = self.sessions[data_lane][model_shard]
        s._placement_status = s._placement_error = None
        s._placement = None
        if not (s.placement_enabled and s._state is not None):
            return None
        base = (f"{name or self.arch or 'model'}"
                f"-shard{model_shard}of{self.n_model}")
        return s._plan_requests(sliced, base)

    def pack(self, params: dict, cfg: PUDGemvConfig | None = None, *,
             name: str | None = None,
             include_unembed: bool = True) -> "list[PackedModel]":
        """Pack one sharded ``PackedModel`` per data lane.

        Each lane's model shards plan their own column slice of every
        request on their own calibration masks.  If any live shard of a
        lane cannot place (uncalibrated, or planning fails), the whole
        lane falls back to the logical sharded layout — shards of one lane
        always share a layout, which the stacked-children representation
        requires.

        Packable leaves may be given on the host (numpy) or layer by layer
        (``pud.packer.LayerSource``) as well as on a device: the pack
        quantises one layer slice at a time and puts each shard's arrays
        on that shard's devices, and every other leaf replicated over the
        mesh (``pack_model_sharded``).  The packable leaves as given, with
        the placed replicated leaves, are what :meth:`repack_lane` packs
        from; :meth:`pack_report` gives the pack's counters.
        """
        if cfg is None:
            cfg = PUDGemvConfig(backend=self.sessions[0][0].backend)
        elif cfg.backend is None:
            cfg = dataclasses.replace(
                cfg, backend=self.sessions[0][0].backend)
        reqs = packing_requests(params, cfg, include_unembed)
        sliced, self._shard_widths = self._shard_requests(reqs)
        packs = []
        stats = PackStats()
        for d in range(self.n_data):
            placements = [self._plan_shard(d, m, sliced[m], name)
                          for m in range(self.n_model)]
            if any(placements[m] is None
                   for m in range(self.n_model) if sliced[m]):
                placements = None     # logical fallback, lane-consistent
            packs.append(pack_model_sharded(
                params, cfg, n_shards=self.n_model, placements=placements,
                include_unembed=include_unembed, mesh=self.mesh,
                axis=self.axis, stats=stats))
        self._packs, self._pack_cfg, self._pack_stats = packs, cfg, stats
        self._pack_args = (_pack_source(params, packs[0].params), name,
                           include_unembed)
        return packs

    def pack_report(self) -> dict | None:
        """Counters of the last :meth:`pack` or :meth:`repack_lane`
        (``pud.packer.PackStats``): layers and slices packed, bytes placed
        on each device, and the most unpacked weight bytes held on one
        device during the pack (``max_weight_bytes``)."""
        if self._pack_stats is None:
            return None
        return dict(self._pack_stats.report(),
                    shard_widths=self._shard_widths)

    def repack_lane(self, data_lane: int, *,
                    changed_model: int | None = None) -> PackedModel:
        """Rebuild one lane's sharded pack from its shards' current state.

        With ``changed_model`` given (the drift-recovery path), only that
        shard re-plans; every other shard of the lane reuses its existing
        ``Placement`` object untouched — the isolation guarantee per-shard
        recalibration rests on.
        """
        if self._packs is None or self._pack_args is None:
            raise RuntimeError("no packed fleet: call pack() first")
        params, name, include_unembed = self._pack_args
        cfg = self._pack_cfg
        reqs = packing_requests(params, cfg, include_unembed)
        sliced, self._shard_widths = self._shard_requests(reqs)
        placements = []
        for m in range(self.n_model):
            s = self.sessions[data_lane][m]
            if (changed_model is not None and m != changed_model
                    and s._placement is not None):
                placements.append(s._placement)   # untouched shard: reuse
            else:
                placements.append(
                    self._plan_shard(data_lane, m, sliced[m], name))
        if any(placements[m] is None
               for m in range(self.n_model) if sliced[m]):
            placements = None
        self._pack_stats = PackStats()
        pm = pack_model_sharded(
            params, cfg, n_shards=self.n_model, placements=placements,
            include_unembed=include_unembed, mesh=self.mesh, axis=self.axis,
            stats=self._pack_stats)
        self._packs[data_lane] = pm
        return pm

    def recalibrate_shard(self, model_shard: int, subarrays, sense_offsets,
                          *, data_lane: int = 0,
                          assumed_temp_c: float | None = None):
        """Route a drift event to the owning shard only.

        Re-runs partial recalibration on ``sessions[data_lane]
        [model_shard]``, re-plans that shard's slice of the last pack and
        rebuilds the lane's sharded ``PackedModel``.  Every other shard's
        table, placement and canaries are untouched — their ``PUDSession``
        state objects are not even read.  Returns the refreshed lane pack
        (also swapped into :attr:`packs`), or the refreshed
        ``CalibrationState`` when the fleet has not packed yet.
        """
        s = self.sessions[data_lane][model_shard]
        state = s.recalibrate_subarrays(subarrays, sense_offsets,
                                        assumed_temp_c=assumed_temp_c)
        if self._packs is None:
            return state
        return self.repack_lane(data_lane, changed_model=model_shard)

    # -- kernel autotuning ---------------------------------------------------

    def tune(self, *, batches=(1, 8), force: bool = False, warmup: int = 1,
             reps: int = 3, max_candidates: int = 12) -> dict:
        """Autotune the common per-shard kernel geometry, stamp every lane.

        All shards of a pack share one padded per-device shape by
        construction (``pack_linear_sharded`` pads every shard to the
        widest), so a single search per (pack, batch) — run on shard
        (0, 0)'s slice — covers the whole mesh.  Keys differ from the
        unsharded session's because N is the padded per-shard width.
        Winners persist in shard (0, 0)'s tuning cache.  Never routes
        through ``PUDSession.tune`` (whose stacked-layer slicing would
        mis-read the [S, WB, Kw, R] shard axis as a layer axis).
        """
        if self._packs is None:
            raise RuntimeError("no packed fleet: call pack() first")
        from repro.kernels.autotune import tune_kernel, tuning_key
        s0 = self.sessions[0][0]
        cache = s0._tuning_cache()
        cfg = self._pack_cfg or PUDGemvConfig()
        report: dict = {"fingerprint": (cache.fingerprint if cache
                                        else None),
                        "cache_dir": (str(cache.directory) if cache
                                      else None),
                        "keys": {}}
        ref = self._packs[0]
        tile_plans: dict[str, tuple] = {}
        for pname in ref.packed_names:
            st = ref.tensor(pname)
            if st.planes.ndim == 5:            # stacked layers: [L, S, ...]
                planes = st.planes[0, 0]
                col_ids = (st.col_ids[0, 0] if st.col_ids is not None
                           else None)
            else:                              # [S, WB, Kw, R]
                planes = st.planes[0]
                col_ids = st.col_ids[0] if st.col_ids is not None else None
            plans: dict[str, object] = {}
            for batch in batches:
                entry = "gemm" if batch > 1 else "gemv"
                key = tuning_key(entry, int(batch), st.k, st.padded_n,
                                 st.n_bits, st.layout, st.placed)
                plan = None if (force or cache is None) else cache.load(key)
                row = {"name": pname, "entry": entry}
                if plan is not None:
                    row["status"] = "hit"
                else:
                    x = ((jnp.arange(int(batch) * st.k) % 255) - 127) \
                        .astype(jnp.int8).reshape(int(batch), st.k)
                    res = tune_kernel(
                        entry, x, planes, col_ids=col_ids,
                        window_block=st.window_block, layout=st.layout,
                        logical_k=st.logical_k, mode=cfg.mode,
                        backend=s0.backend, warmup=warmup, reps=reps,
                        max_candidates=max_candidates)
                    plan = res.plan
                    row.update(status="tuned", **res.to_stats())
                    if cache is not None:
                        cache.save(key, plan, res.to_stats())
                row["plan"] = plan.to_dict()
                report["keys"][key] = row
                plans[entry] = plan
            tile_plans[pname] = tuple(sorted(plans.items()))
        for d, pm in enumerate(self._packs):
            stamped = {n: pm.tensor(n).replace(tile_plan=tile_plans[n])
                       for n in tile_plans}
            self._packs[d] = _restamp_model(pm, stamped)
        self._tuning_report = report
        return report

    def tuning_report(self) -> dict | None:
        return self._tuning_report

    # -- execution + reporting -----------------------------------------------

    def serving_engine(self, model, *, max_len: int,
                       batch_size: int | None = None,
                       chunk_prefill: int | None = None,
                       prefix_cache=False, slo=None, **kw):
        """A ``FleetServingEngine``: one continuous-batching lane per
        "data"-axis row, tensor parallelism inside each lane's packs.

        ``chunk_prefill`` / ``prefix_cache`` / ``slo`` pass through to
        every lane (``prefix_cache=True`` builds one per-lane LRU, and
        submit routes by cache affinity before round-robin)."""
        from repro.runtime.engine import FleetServingEngine
        if self._packs is None:
            raise RuntimeError("no packed fleet: call pack() first")
        return FleetServingEngine(
            model, [pm.params for pm in self._packs], fleet=self,
            max_len=max_len, batch_size=batch_size,
            chunk_prefill=chunk_prefill, prefix_cache=prefix_cache,
            slo=slo, **kw)

    def fleet_perf_model(self) -> FleetPerfAggregate:
        """Aggregate Eq.-1 rate model: the slowest device of each model
        shard bounds that shard, the slowest shard bounds every lane, and
        data lanes multiply (``pud.gemv.FleetPerfAggregate``)."""
        shards = []
        for m in range(self.n_model):
            worst = None
            for row in self.sessions:
                s = row[m]
                pm = s.placement_perf_model() or s.tuned_perf_model()
                if not isinstance(pm, FleetPerfModel):
                    pm = FleetPerfModel.from_table(
                        [1.0 - pm.error_free_frac])
                if worst is None or \
                        pm.macs_per_second < worst.macs_per_second:
                    worst = pm
            shards.append(worst)
        return FleetPerfAggregate(shards=tuple(shards), n_data=self.n_data,
                                  shard_widths=self._shard_widths)

    def perf_report(self, flops_per_token: float | None = None,
                    batch_size: int | None = None) -> dict:
        """Mesh shape, per-device reports, and the aggregate rates the
        serving driver prints (tokens/s over the whole fleet + scaling
        efficiency vs ``n_devices`` copies of shard (0, 0))."""
        agg = self.fleet_perf_model()
        flops = flops_per_token or self.flops_per_token()
        rep: dict = {
            "n_data": self.n_data,
            "n_model": self.n_model,
            "n_devices": self.n_devices,
            "device_ids": self.device_ids,
            "shard_widths": self._shard_widths,
            "shard_fraction": agg.shard_fraction,
            "aggregate_model": agg,
            "shards": [[s.perf_report(flops) for s in row]
                       for row in self.sessions],
        }
        if flops is not None:
            rep["flops_per_token"] = flops
            rep["aggregate_tok_s"] = agg.tokens_per_second(flops)
            rep["scaling_efficiency"] = agg.scaling_efficiency(flops)
            if batch_size is not None:
                rep["batch_size"] = int(batch_size)
                rep["aggregate_batched_tok_s"] = \
                    agg.batched_tokens_per_second(flops, batch_size)
        return rep
