"""Fleet-scale calibration engine: Algorithm 1 across a whole device.

A real module is not one 65 536-column subarray — it exposes a
``(channels, banks, subarrays)`` grid of them, and sense-amp offsets (hence
error patterns and the calibration data that fixes them) vary per subarray.
This module runs the paper's Algorithm 1 over the whole grid in ONE jitted
call:

  * ``manufacture_fleet``      — per-subarray sense offsets [G, C], derived
    by ``fold_in(key, subarray_index)`` so any single subarray of the fleet
    is bit-identical to manufacturing it alone with that folded key.
  * ``calibrate_fleet``        — three interchangeable engines:
      - ``per_subarray``: ``vmap`` of the unjitted single-subarray
        Algorithm 1 (bit-identical to N independent ``identify_calibration``
        calls — the equivalence oracle);
      - ``reference``:    vmapped pure-jnp fused iteration (kernels/ref.py);
      - ``fused``:        Pallas kernel (kernels/majx.calib_iter_fused), the
        subarray axis part of its grid, that does SiMRA sensing + bias
        accumulation + ladder level-step in a single pass instead of three
        jitted stages.
    With a ``mesh``, the subarray axis is ``shard_map``-ped over every mesh
    axis (launch/mesh.py meshes compose directly), one RNG stream per shard.
    The jitted program is built once per configuration and run with each
    call's key (``calib_build_report`` counts builds and memo hits).
  * ``fleet_calib_charges``    — levels -> per-subarray calibration-row
    charges for downstream ECR / arithmetic measurement.

Persistence lives in ``repro.runtime.calib_cache`` (versioned per-device
tables); ``load_or_calibrate`` glues the two so serving starts from a cached
table instead of recalibrating.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.kernels.ops import calib_iter_fused
from repro.kernels.ref import calib_iter_ref
from repro.pud.physics import NEUTRAL, PhysicsParams
from repro.runtime.tracing import span
from .calibrate import CalibrationConfig, identify_calibration_fn
from .offsets import (OffsetLadder, levels_to_charges, make_ladder,
                      neutral_level)

METHODS = ("fused", "reference", "per_subarray")


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Shape of one device's subarray grid."""

    n_channels: int = 1
    n_banks: int = 4
    n_subarrays: int = 4          # per bank
    n_cols: int = 4096            # per subarray (65 536 on real DDR4)
    frac_counts: tuple[int, ...] = (2, 1, 0)

    @property
    def grid_shape(self) -> tuple[int, int, int]:
        return (self.n_channels, self.n_banks, self.n_subarrays)

    @property
    def n_subarrays_total(self) -> int:
        return self.n_channels * self.n_banks * self.n_subarrays

    @property
    def n_cols_total(self) -> int:
        return self.n_subarrays_total * self.n_cols

    def ladder(self, params: PhysicsParams) -> OffsetLadder:
        return make_ladder(self.frac_counts, params)


@dataclasses.dataclass
class FleetCalibration:
    """Result of one fleet calibration run."""

    levels: jax.Array                  # [G, C] int32 ladder level per column
    mean_abs_bias: jax.Array | None    # [n_iterations] (None: per_subarray)
    config: FleetConfig
    method: str

    @property
    def levels_grid(self) -> jax.Array:
        """[channels, banks, subarrays, cols] view."""
        return self.levels.reshape(self.config.grid_shape
                                   + (self.config.n_cols,))


def subarray_key(key: jax.Array, index: int | jax.Array) -> jax.Array:
    """RNG key of subarray ``index`` — the fleet/single-subarray contract."""
    return jax.random.fold_in(key, index)


def manufacture_fleet(
    key: jax.Array, cfg: FleetConfig, params: PhysicsParams
) -> jax.Array:
    """Per-subarray sense offsets [G, C]; row g == single-subarray draw g."""
    def one(g):
        return params.sigma_static * jax.random.normal(
            subarray_key(key, g), (cfg.n_cols,), jnp.float32)
    return jax.vmap(one)(jnp.arange(cfg.n_subarrays_total))


def sense_tables(
    ladder: OffsetLadder, params: PhysicsParams,
    config: CalibrationConfig = CalibrationConfig(),
) -> tuple[tuple[tuple[float, ...], ...], tuple[float, ...]]:
    """Static sensing tables of one calibration MAJX: (bitline voltage
    [level][ones among the operands], noise sigma [level]).

    Operand bits are 0/1, so a sensing's charge sum is ones + the level's
    calibration-row sum + the constant rows, and its swing^2 sum is the
    operand count + the level's calibration-row swing + the constant rows.
    Both calibration engines read these tables, so neither recomputes the
    physics in its own float order.
    """
    rc = ladder.row_charges(params)                        # [L, n_rows]
    qsum = rc.sum(axis=1)
    swing = ((2.0 * (rc - NEUTRAL)) ** 2).sum(axis=1)
    ones = np.arange(config.maj_inputs + 1, dtype=np.float32)
    charge = ones[None, :] + qsum[:, None] + config.const_charge_sum
    v = np.asarray(params.bitline_voltage(charge, params.n_simra_rows),
                   np.float32)
    sigma = np.asarray(params.sensing_sigma(
        np.float32(ladder.n_fracs),
        swing + config.maj_inputs + config.const_swing_sq), np.float32)
    return (tuple(tuple(float(x) for x in row) for row in v),
            tuple(float(x) for x in sigma))


def _block_calibrate(ladder: OffsetLadder, params: PhysicsParams,
                     config: CalibrationConfig, method: str):
    """Returns f(key, offsets [Gl, C]) -> (levels [Gl, C], |bias| history)."""
    level_v, level_sigma = sense_tables(ladder, params, config)

    def block_iter(inputs, noise, levels, offs):
        """One iteration over the block's [Gl, ...] operands."""
        if method == "fused":   # the subarray axis is part of the grid
            return calib_iter_fused(
                inputs, noise, levels, offs, level_v, level_sigma,
                config.threshold, config.maj_inputs)
        return jax.vmap(lambda i, n, lv, o: calib_iter_ref(
            i, n, lv, o, level_v, level_sigma, config.threshold,
            config.maj_inputs))(inputs, noise, levels, offs)

    def run(key, offs):
        gl, c = offs.shape
        init = jnp.full((gl, c), neutral_level(ladder), jnp.int32)

        def iteration(levels, it_key):
            k_in, k_noise = jax.random.split(it_key)
            inputs = jax.random.bernoulli(
                k_in, 0.5, (gl, config.n_samples, config.maj_inputs, c)
            ).astype(jnp.float32)
            noise = jax.random.normal(
                k_noise, (gl, config.n_samples, c), jnp.float32)
            new, bias = block_iter(inputs, noise, levels, offs)
            return new, jnp.abs(bias).mean()

        keys = jax.random.split(key, config.n_iterations)
        return jax.lax.scan(iteration, init, keys)

    return run


def calibrate_fleet(
    key: jax.Array,
    sense_offsets: jax.Array,             # [G, C]
    cfg: FleetConfig,
    params: PhysicsParams,
    config: CalibrationConfig = CalibrationConfig(),
    *,
    mesh: Mesh | None = None,
    method: str = "fused",
) -> FleetCalibration:
    """Run Algorithm 1 over the whole subarray grid.

    ``mesh``: shard the subarray axis over every mesh axis (G must divide
    the device count evenly); without one, the grid runs vmapped on the
    local device.  ``method="per_subarray"`` is the bit-exact oracle.

    The jitted program is built once per configuration and reused with
    each call's key (``_build_and_run``).  Spans (``runtime/tracing``):
    ``repro.calib.call`` around the call, and inside it
    ``repro.calib.build`` then ``repro.calib.run``.
    """
    if method not in METHODS:
        raise ValueError(f"method {method!r} not in {METHODS}")
    g, _ = sense_offsets.shape
    if mesh is not None and mesh.size == 1:
        mesh = None
    with span("calib.call", method=method, subarrays=int(g)):
        if method == "per_subarray":
            levels = _build_and_run(method, True, cfg, params, config, None,
                                    key, jnp.arange(g), sense_offsets)
            return FleetCalibration(levels, None, cfg, method)
        if mesh is not None and g % mesh.size != 0:
            raise ValueError(
                f"{g} subarrays not divisible over {mesh.size} devices")
        levels, hist = _build_and_run(method, False, cfg, params, config,
                                      mesh, key, sense_offsets)
        return FleetCalibration(levels, hist, cfg, method)


def _make_program(method: str, subset: bool, cfg: FleetConfig,
                  params: PhysicsParams, config: CalibrationConfig,
                  mesh: Mesh | None):
    """The calibration program of one configuration, not yet jitted.

    ``subset`` (always, for ``per_subarray``): f(key, idx [n], offsets
    [n, C]) -> levels [n, C], row i on its own stream
    ``subarray_key(key, idx[i])``.  Otherwise f(key, offsets [G, C]) ->
    (levels, |bias| history) on one stream for the grid, or one per shard
    with a ``mesh``.
    """
    ladder = cfg.ladder(params)
    if method == "per_subarray":
        def one(key, idx, offs):
            return identify_calibration_fn(
                subarray_key(key, idx), offs, ladder, params, config)
        return jax.vmap(one, in_axes=(None, 0, 0))

    run = _block_calibrate(ladder, params, config, method)
    if subset:
        def one(key, idx, offs):
            levels, _ = run(subarray_key(key, idx), offs[None])
            return levels[0]
        return jax.vmap(one, in_axes=(None, 0, 0))
    if mesh is None:
        return run

    axes = tuple(mesh.axis_names)
    spec = P(axes)

    def sharded(key_block, offs):
        idx = jnp.int32(0)
        for name in axes:
            idx = idx * mesh.shape[name] + jax.lax.axis_index(name)
        levels, hist = run(jax.random.fold_in(key_block[0], idx), offs)
        return levels, jax.lax.pmean(hist, axes)

    grid = jax.shard_map(sharded, mesh=mesh, in_specs=(P(), spec),
                         out_specs=(spec, P()), check_vma=False)
    return lambda key, offs: grid(key[None], offs)


_MEMO_SIZE = 16
_memo: OrderedDict = OrderedDict()     # memo key -> jitted program, LRU
_report = {"builds": 0, "hits": 0}


def calib_build_report() -> dict:
    """``{"builds", "hits"}``: calibration programs built, and calls served
    from a program the memo held, since the process started."""
    return dict(_report)


def _build_and_run(method: str, subset: bool, cfg: FleetConfig,
                   params: PhysicsParams, config: CalibrationConfig,
                   mesh: Mesh | None, *args):
    """Run the configuration's jitted program (``_make_program``) on
    ``args``, building it only when the memo does not hold it.

    The memo key is everything the program closes over, the two
    calibration kernels included, so a kernel swapped at run time gets a
    program of its own.  Only the program is kept: every call runs all its
    iterations on its own key.  Past ``_MEMO_SIZE`` configurations the
    least recently used program is dropped.

    Spans: ``repro.calib.build`` (``hit``: the memo held the program; on a
    miss, jit, trace, lower and compile, or load from the persistent
    cache) then ``repro.calib.run`` (the dispatch; the result is not waited
    for).  On a hit, shapes or shardings new to the program compile in
    ``calib.run``, through jit's own cache.
    """
    memo_key = (method, subset, cfg, params, config, mesh,
                calib_iter_fused, calib_iter_ref)
    prog = _memo.get(memo_key)
    with span("calib.build", hit=prog is not None):
        if prog is None:
            prog = jax.jit(_make_program(method, subset, cfg, params, config,
                                         mesh))
            prog.lower(*args).compile()
            _memo[memo_key] = prog
            if len(_memo) > _MEMO_SIZE:
                _memo.popitem(last=False)
            _report["builds"] += 1
        else:
            _memo.move_to_end(memo_key)
            _report["hits"] += 1
    with span("calib.run"):
        return prog(*args)


def recalibrate_subarrays(
    key: jax.Array,
    sense_offsets: jax.Array,             # [G, C] full fleet, as sensed NOW
    subarrays,                            # iterable of subarray indices
    cfg: FleetConfig,
    params: PhysicsParams,
    config: CalibrationConfig = CalibrationConfig(),
    *,
    method: str = "reference",
) -> jax.Array:
    """Re-run Algorithm 1 for a subset of subarrays against current offsets.

    The background-recalibration primitive behind the drift monitor: on a
    drift event only the flagged subarrays re-identify, against the fleet's
    *currently sensed* (drifted) offsets, while the rest of the table is
    left untouched.  Every subarray keeps its own RNG stream
    (``subarray_key(key, g)``), so the result is independent of how drift
    events were batched — recalibrating {3} then {5} yields exactly the rows
    a joint {3, 5} pass would.  (Block methods run per subarray here rather
    than sharing one iteration stream across the block like
    ``calibrate_fleet``; for a *partial* pass, batching-independence is the
    contract that matters.)  The program comes from the same memo as
    ``calibrate_fleet``'s.

    Returns refreshed levels ``[len(subarrays), C]`` in ascending-index
    order; merging them into the full table is the caller's job
    (``PUDSession.recalibrate_subarrays``).
    """
    if method not in METHODS:
        raise ValueError(f"method {method!r} not in {METHODS}")
    idx = jnp.asarray(sorted(int(s) for s in subarrays), jnp.int32)
    offs = jnp.asarray(sense_offsets)[idx]
    return _build_and_run(method, True, cfg, params, config, None,
                          key, idx, offs)


def fleet_calib_charges(
    ladder: OffsetLadder, levels: jax.Array, params: PhysicsParams
) -> jax.Array:
    """[G, C] levels -> [G, n_rows, C] calibration-row charges."""
    return jax.vmap(lambda lv: levels_to_charges(ladder, lv, params))(levels)


# ---------------------------------------------------------------------------
# Cache glue: serve/gemv start from a table instead of recalibrating.
# ---------------------------------------------------------------------------


def load_or_calibrate(
    cache,                               # runtime.calib_cache.CalibrationTableCache
    device_id: str,
    key: jax.Array,
    cfg: FleetConfig,
    params: PhysicsParams = PhysicsParams(),
    config: CalibrationConfig = CalibrationConfig(),
    *,
    mesh: Mesh | None = None,
    # "reference" is bit-identical to the fused Pallas kernel (enforced by
    # tests/test_fleet.py) and much faster under the CPU interpreter;
    # "fused" lowers natively on a TPU.
    method: str = "reference",
    n_trials_ecr: int = 1024,
):
    """Return (levels [G, C], ecr [G], masks [G, C], cache_hit).

    ``masks`` is the per-column error-prone mask (True = faulty) that
    column placement (repro/pud/placement.py) consumes.  On a cache hit
    nothing is recalibrated or re-measured; on a miss the fleet is
    manufactured from ``fold_in(key, .)``, calibrated, its ECR + masks
    measured, and the table persisted for the next startup.
    """
    from .ecr import measure_ecr_fleet

    hit = cache.load(device_id, cfg, params)
    # A table without its ECR measurement or masks can't drive the perf
    # model / placement — treat it as a miss and re-identify rather than
    # hand back None.
    if hit is not None and hit.ecr is not None and hit.masks is not None:
        return hit.levels, hit.ecr, hit.masks, True

    offsets = manufacture_fleet(key, cfg, params)
    cal = calibrate_fleet(key, offsets, cfg, params, config,
                          mesh=mesh, method=method)
    ladder = cfg.ladder(params)
    charges = fleet_calib_charges(ladder, cal.levels, params)
    ecr, masks = measure_ecr_fleet(
        jax.random.fold_in(key, 0x0ECD), offsets, charges, params,
        ladder.n_fracs, n_trials=n_trials_ecr)
    cache.save(device_id, cfg, params, np.asarray(cal.levels),
               ecr=np.asarray(ecr), masks=np.asarray(masks),
               metadata={"method": cal.method,
                         "n_iterations": config.n_iterations},
               assumed_temp_c=params.temp_nominal_c)
    return cal.levels, ecr, masks, False
