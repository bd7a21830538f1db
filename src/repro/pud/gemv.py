"""PUD GeMV serving path: low-bit linear layers computed "in DRAM".

This is the MVDRAM [4] application layer that PUDTune's calibration makes
viable: serving-time projections of a quantized LLM execute as bit-plane
GeMV over the DRAM subarray's columns, and the usable throughput is set by
the calibrated error-free column fraction (paper Eq. 1).

Two coupled halves:

  * **Numerics** (`pack_linear`, `pud_linear`) — exact low-bit integer GeMV
    via the Pallas bit-plane kernel (kernels/bitplane_gemv.py). The weight
    layout IS the PUD layout: WB bit-planes over columns. On TPU the kernel
    computes it on the MXU; in real PUD the same planes sit in subarray rows.
  * **Performance model** (`PUDPerfModel`) — what a real 4-channel DDR4
    system would sustain for those GeMVs, derived from the bit-serial
    MAC command schedule (mul + add graphs of pud/bitserial.py) priced on
    the DDR4 timing model, scaled by the measured error-free fraction.
    ``speedup_vs_baseline`` is then PUDTune's end-to-end serving claim.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import pud_gemv, pud_matmul_sharded, round_to_dtype
from repro.kernels.ref import pack_bitplanes, pack_plane_words

from .bitserial import add8_counts, mul8_counts
from .packed import (LAYOUT_BITPACK, PackedTensor, ShardedPackedTensor,
                     as_packed_tensor, packed_bytes)
from .timing import OpCounts, SystemConfig, wave_latency_ns

# Default packable set: FFN projections (dominant decode GeMV flops).
# Entries are "scope.name" (scope = any path component) or a bare name.
FFN_PACKABLE = ("mixer.wi", "mixer.wg", "mixer.wo")
# Attention projections (2-D case: head axes flattened to one column axis).
ATTN_PACKABLE = ("attn.wq", "attn.wk", "attn.wv", "attn.wo")

# Table-I operating points: ECR of the uncalibrated B_{3,0,0} baseline vs
# the calibrated T_{2,1,0} ladder (the paper's headline 1.81x comes from
# the ratio of the error-free fractions these leave).
ECR_BASELINE_B300 = 0.466
ECR_PUDTUNE_T210 = 0.033


@dataclasses.dataclass(frozen=True)
class PUDGemvConfig:
    weight_bits: int = 4
    mode: str = "folded"         # "planes" (faithful) | "folded" (optimized)
    # Which projections pack_for_serving swaps onto the PUD path.
    packable: tuple[str, ...] = FFN_PACKABLE
    # Named execution backend (kernels/backends.py); None defers to the
    # pack's stamp, then to the default backend.
    backend: str | None = None


def pack_linear(w: jax.Array, n_bits: int = 4,
                backend: str | None = None,
                bitpack: bool = True) -> PackedTensor:
    """[K, N] float weights -> per-output-channel-quantized bit-planes.

    Returns a ``PackedTensor`` — by default in the *bit-packed* storage
    layout (planes [WB, ceil(K/8), N] uint8 words, eight K rows per byte;
    ``layout="bitpack8"``), the format whose HBM footprint actually matches
    the bits the PUD layout stores.  ``bitpack=False`` keeps the legacy
    dense one-byte-per-bit planes [WB, K, N] int8 in {0,1}; both are
    bit-exact through every kernel entry.  The legacy ``pack["planes"]``
    mapping access still works.  Symmetric per-channel: w ~ scale * q,
    q in [-2^{b-1}, 2^{b-1}).  ``backend`` stamps the pack with the
    execution backend model forwards should dispatch it through.
    """
    planes, scale = quantize_planes(w, n_bits, bitpack)
    if not bitpack:
        return PackedTensor(planes=planes, scale=scale, backend=backend)
    return PackedTensor(planes=planes, scale=scale, backend=backend,
                        layout=LAYOUT_BITPACK, logical_k=w.shape[0])


@functools.partial(jax.jit, static_argnames=("n_bits", "bitpack"))
def quantize_planes(w: jax.Array, n_bits: int,
                    bitpack: bool) -> tuple[jax.Array, jax.Array]:
    """[K, N] weights -> (planes, scale [N] f32) of ``pack_linear``.

    One compiled program, so the int32 bit-plane intermediates fuse into
    the int8/uint8 result instead of each materializing at full size (a
    vocabulary-wide unembed would otherwise need several GiB of scratch).
    """
    qmax = (1 << (n_bits - 1)) - 1
    scale = jnp.maximum(jnp.abs(w).max(axis=0), 1e-8) / qmax       # [N]
    q = jnp.clip(jnp.round(w / scale[None, :]), -qmax - 1, qmax)
    planes = pack_bitplanes(q.astype(jnp.int32), n_bits)
    if bitpack:
        planes = pack_plane_words(planes)
    return planes, scale.astype(jnp.float32)


def pud_linear(x: jax.Array, packed: "PackedTensor | dict",
               cfg: PUDGemvConfig = PUDGemvConfig(),
               backend: str | None = None) -> jax.Array:
    """x: [..., K] float -> [..., N] float32 through the bit-plane GeMV,
    rounded to x's precision (``kernels.ops.round_to_dtype``), so casting
    it to x's dtype is exact and a consumer fused with it sees the same
    values at every batch size.

    ``packed`` is a ``PackedTensor`` (or a legacy pack dict, coerced).
    The pack's layout metadata (dense vs bit-packed words, placed window
    stride) rides into the kernel dispatch.  Backend resolution: explicit
    ``backend`` arg > ``cfg.backend`` > the backend stamped on the pack
    (how a session's choice reaches model forwards, which call this with
    the default config) > ``DEFAULT_BACKEND``.
    """
    if isinstance(packed, ShardedPackedTensor):
        lead = x.shape[:-1]
        x2 = x.reshape((-1, x.shape[-1]))
        y = pud_matmul_sharded(x2, packed, mode=cfg.mode,
                               backend=backend or cfg.backend)
    else:
        pt = as_packed_tensor(packed)
        lead = x.shape[:-1]
        x2 = x.reshape((-1, x.shape[-1]))
        y = pud_gemv(x2, pt.planes, pt.scale,
                     mode=cfg.mode, col_ids=pt.col_ids,
                     backend=backend or cfg.backend or pt.backend,
                     layout=pt.layout, logical_k=pt.logical_k,
                     window_block=pt.window_block, tile_plan=pt.tile_plan)
    y = round_to_dtype(y, x.dtype)
    return y.reshape(lead + (y.shape[-1],))


def pud_linear_ref(x: jax.Array, w: jax.Array, n_bits: int = 4) -> jax.Array:
    """Oracle: quantize w the same way, do the float matmul on dequantized q."""
    qmax = (1 << (n_bits - 1)) - 1
    scale = jnp.maximum(jnp.abs(w).max(axis=0), 1e-8) / qmax
    q = jnp.clip(jnp.round(w / scale[None, :]), -qmax - 1, qmax)
    from repro.kernels.ops import quantize_activations
    xq, x_scale = quantize_activations(x.reshape((-1, x.shape[-1])))
    y = (xq.astype(jnp.float32) @ q.astype(jnp.float32))
    y = y * x_scale * scale[None, :]
    return y.reshape(x.shape[:-1] + (w.shape[-1],))


# ---------------------------------------------------------------------------
# Weight-byte traffic (the memory side of the serving hot path)
# ---------------------------------------------------------------------------

# Peak weight-staging bandwidth of the paper's 4-channel DDR4-2133 system:
# 8 B/transfer x 2133 MT/s per channel.  Every decoded token streams each
# packed projection once (GeMV is weight-bound), so bytes/token is simply
# the pack's stored footprint — which the bit-packed layout cuts ~8x.
WEIGHT_STAGING_BW_BYTES_S = 4 * 8 * 2133e6


def weight_traffic(packed) -> dict:
    """Per-token weight-traffic terms of a packed serving tree.

    Accepts a ``PackedModel`` or raw serving params (either pack format).
    ``stored_bytes_per_token`` is what the new bit-packed layout actually
    streams; ``dense_equiv_bytes_per_token`` is what the same packs cost in
    the legacy one-byte-per-bit layout; ``traffic_reduction`` is their
    ratio (~8x for bit-packed packs, 1x for dense ones).
    """
    stats = packed_bytes(packed)
    stored = stats["stored_bytes"]
    dense = stats["dense_equiv_bytes"]
    return {
        "stored_bytes_per_token": stored,
        "dense_equiv_bytes_per_token": dense,
        "traffic_reduction": dense / max(1, stored),
        "staging_bound_tok_s": WEIGHT_STAGING_BW_BYTES_S / max(1, stored),
    }


# ---------------------------------------------------------------------------
# DRAM-side performance model (Eq. 1 applied to GeMV).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PUDPerfModel:
    """Sustained GeMV rate of the PUD system for one calibrated device.

    A [K, N] GeMV with b-bit weights and 8-bit activations maps each of the
    K*N MACs onto one column's bit-serial MUL8 + accumulate-ADD8 graphs; the
    65 536-column wave executes error_free_frac*65 536 MACs per sequence.
    """

    error_free_frac: float
    n_fracs: int = 3                  # T_{2,1,0}
    sys: SystemConfig = dataclasses.field(default_factory=SystemConfig)

    @property
    def macs_per_second(self) -> float:
        mac_counts = mul8_counts(self.n_fracs) + add8_counts(self.n_fracs)
        lat_s = wave_latency_ns(mac_counts, self.sys) * 1e-9
        cols = self.error_free_frac * self.sys.n_cols_per_subarray
        return cols * self.sys.n_banks_parallel * self.sys.n_channels / lat_s

    def gemv_latency_s(self, k: int, n: int) -> float:
        return (k * n) / self.macs_per_second

    def tokens_per_second(self, flops_per_token: float) -> float:
        """flops_per_token = 2 * active params (one MAC = 2 flops)."""
        return self.macs_per_second / (flops_per_token / 2.0)

    def speedup_vs(self, baseline: "PUDPerfModel") -> float:
        return self.macs_per_second / baseline.macs_per_second

    def step_seconds(self, flops_per_token: float, batch: int = 1) -> float:
        """Modeled wall seconds of one batched decode wave (``batch``
        tokens emitted per step; no batching gain on a single device)."""
        return max(1, int(batch)) / self.tokens_per_second(flops_per_token)


@dataclasses.dataclass(frozen=True)
class FleetPerfModel:
    """Serving-rate model for a whole calibrated device grid.

    Built from the per-subarray ECR distribution of a persisted calibration
    table (runtime/calib_cache.py) rather than a single point estimate: the
    sustained rate prices waves rotating uniformly over the grid (mean
    error-free fraction), and the distribution bounds what a worst-case
    subarray placement would cost.

    The batched extension models multi-request (continuous-batching) decode:

      * **Replication** — a placement that occupies ``occupied_subarrays``
        of ``total_subarrays`` leaves idle subarrays that can hold replicas
        of the same placed weights; up to ``n_replicas`` requests execute
        fully in parallel.
      * **Operand amortization** — within one replica, the weight bit
        columns are static across the batch, so the weight-side staging row
        copies of each MAC's MUL8 partial-product ops are paid once per
        wave instead of once per request; only operand staging + the MAJ
        graph itself scale with the per-replica batch.
      * **Operand residency** — a subarray stages at most ``operand_slots``
        operand vectors per wave; past ``n_replicas * operand_slots``
        requests serialize into extra wave groups and aggregate throughput
        stops improving.  That bound is the occupancy-derived optimal
        batch size (``optimal_batch_size``).
    """

    error_free_fracs: tuple[float, ...]      # per subarray
    n_fracs: int = 3
    sys: SystemConfig = dataclasses.field(default_factory=SystemConfig)
    # Batched-serving shape of the device: how many copies of the placed
    # weights fit (from placement occupancy), and how many operand vectors
    # a subarray can stage per wave.
    occupied_subarrays: int | None = None
    total_subarrays: int | None = None
    operand_slots: int = 4

    @classmethod
    def from_table(cls, ecr_per_subarray, n_fracs: int = 3,
                   sys: SystemConfig | None = None) -> "FleetPerfModel":
        fracs = tuple(float(1.0 - e) for e in ecr_per_subarray)
        return cls(error_free_fracs=fracs, n_fracs=n_fracs,
                   sys=sys or SystemConfig())

    @classmethod
    def from_placement(cls, placement, n_fracs: int = 3,
                       sys: SystemConfig | None = None) -> "FleetPerfModel":
        """Rate from the *actual* column placement, not a mean fraction.

        Waves rotate over the subarrays the placement occupies; each wave
        executes exactly the columns placed there (repro/pud/placement.py),
        so the per-wave usable fraction is used/total per occupied
        subarray rather than the device-mean error-free fraction.
        """
        used = np.asarray(placement.used_per_subarray, np.float64)
        occupied = used[used > 0]
        if occupied.size == 0:
            raise ValueError("placement occupies no subarray")
        fracs = tuple(float(u / placement.n_cols_per_subarray)
                      for u in occupied)
        return cls(error_free_fracs=fracs, n_fracs=n_fracs,
                   sys=sys or SystemConfig(),
                   occupied_subarrays=int(occupied.size),
                   total_subarrays=int(placement.n_subarrays))

    def _point(self, frac: float) -> PUDPerfModel:
        return PUDPerfModel(error_free_frac=frac, n_fracs=self.n_fracs,
                            sys=self.sys)

    @property
    def mean_error_free_frac(self) -> float:
        return sum(self.error_free_fracs) / len(self.error_free_fracs)

    @property
    def macs_per_second(self) -> float:
        return self._point(self.mean_error_free_frac).macs_per_second

    @property
    def worst_subarray_macs_per_second(self) -> float:
        return self._point(min(self.error_free_fracs)).macs_per_second

    def tokens_per_second(self, flops_per_token: float) -> float:
        return self.macs_per_second / (flops_per_token / 2.0)

    def speedup_vs(self, baseline: "PUDPerfModel | FleetPerfModel") -> float:
        return self.macs_per_second / baseline.macs_per_second

    # -- weight-byte traffic ------------------------------------------------

    def staging_bound_tokens_per_second(self, weight_bytes: float) -> float:
        """Weight-staging bandwidth ceiling: each decoded token restages
        every packed projection's stored bytes once, so the DDR4 channels
        bound decode at BW / bytes-per-token.  With the bit-packed plane
        layout ``weight_bytes`` is ~8x smaller than the legacy dense
        layout's, which lifts this ceiling 8x (see ``weight_traffic``)."""
        return WEIGHT_STAGING_BW_BYTES_S / max(1.0, float(weight_bytes))

    def traffic_aware_tokens_per_second(self, flops_per_token: float,
                                        weight_bytes: float) -> float:
        """Sustained decode rate under both limits: the Eq.-1 compute rate
        and the weight-staging bandwidth bound."""
        return min(self.tokens_per_second(flops_per_token),
                   self.staging_bound_tokens_per_second(weight_bytes))

    # -- batched serving ----------------------------------------------------

    @property
    def n_replicas(self) -> int:
        """Independent weight copies the grid can hold in parallel."""
        if self.occupied_subarrays and self.total_subarrays:
            return max(1, self.total_subarrays // self.occupied_subarrays)
        return 1

    def _mac_counts_split(self) -> tuple[OpCounts, OpCounts]:
        """(shared, per-operand) command counts of one MAC's MUL8+ADD8 graph.

        Shared across a batched wave: the weight-bit constant copy of each
        of the 72 AND/OR partial-product ops (the weight columns are static
        for the whole batch).  Everything else — operand staging, calib-row
        copies, Fracs, SiMRAs — executes once per in-flight request.
        """
        total = mul8_counts(self.n_fracs) + add8_counts(self.n_fracs)
        n_andor = sum(2 * (8 - j) for j in range(8))
        shared = OpCounts(rowcopies=n_andor)
        per_op = OpCounts(rowcopies=total.rowcopies - n_andor,
                          fracs=total.fracs, simras=total.simras)
        return shared, per_op

    def batch_speedup(self, batch: int) -> float:
        """Aggregate-throughput gain of serving ``batch`` requests vs one.

        Strictly increasing up to ``optimal_batch_size()`` (replication is
        linear, amortization sub-linear), flat beyond it (operand residency
        exhausted: extra requests serialize into additional wave groups).
        """
        b = max(1, int(batch))
        b_eff = min(b, self.optimal_batch_size())
        active = min(self.n_replicas, b_eff)
        per_rep = b_eff / active
        shared, per_op = self._mac_counts_split()
        lat1 = wave_latency_ns(shared + per_op, self.sys)
        lat_b = wave_latency_ns(shared + per_rep * per_op, self.sys)
        return b_eff * lat1 / lat_b

    def batched_macs_per_second(self, batch: int) -> float:
        return self.macs_per_second * self.batch_speedup(batch)

    def batched_tokens_per_second(self, flops_per_token: float,
                                  batch: int) -> float:
        """Aggregate decode rate (all requests summed) at ``batch``."""
        return self.batched_macs_per_second(batch) / (flops_per_token / 2.0)

    def optimal_batch_size(self, max_batch: int | None = None) -> int:
        """Occupancy-derived optimum: replicas x per-subarray operand slots.

        Aggregate tokens/s increases monotonically up to this batch and is
        flat beyond it, so it is the smallest batch reaching peak rate.
        """
        opt = self.n_replicas * self.operand_slots
        return min(opt, max_batch) if max_batch else opt

    def step_seconds(self, flops_per_token: float, batch: int = 1) -> float:
        """Modeled wall seconds of one batched decode wave: the engine's
        SLO admission prices a step as ``batch`` tokens at the batched
        aggregate rate (runtime/engine.py's virtual clock)."""
        b = max(1, int(batch))
        return b / self.batched_tokens_per_second(flops_per_token, b)


@dataclasses.dataclass(frozen=True)
class FleetPerfAggregate:
    """Cross-shard serving-rate model of a sharded mesh deployment.

    ``shards`` are the per-model-shard :class:`FleetPerfModel`s (one per
    "model"-axis device — each built from that device's own calibration
    table/placement); ``n_data`` counts the data-parallel engine lanes.

    A decoded token needs *every* model shard's partial GEMM, so the
    per-lane token rate is bound by the slowest shard evaluated at the
    slowest shard's work share: with the N axis split on window-block
    boundaries the largest shard owns ``shard_fraction`` of the columns
    (> 1/S when the block count does not divide the shard count — the
    padding/imbalance cost the scaling-efficiency column measures).
    Aggregate throughput then scales linearly with the independent data
    lanes.
    """

    shards: tuple[FleetPerfModel, ...]
    n_data: int = 1
    shard_widths: tuple[int, ...] | None = None   # logical columns per shard

    @property
    def n_model(self) -> int:
        return len(self.shards)

    @property
    def n_devices(self) -> int:
        return self.n_model * self.n_data

    @property
    def shard_fraction(self) -> float:
        """Work share of the slowest (widest) model shard."""
        if self.shard_widths:
            total = sum(self.shard_widths)
            return max(self.shard_widths) / max(1, total)
        return 1.0 / self.n_model

    def _working_shards(self):
        """Shards that own columns — a zero-width shard (more shards than
        window blocks) executes no GEMM work and never bounds the lane."""
        if self.shard_widths:
            live = [m for m, w in zip(self.shards, self.shard_widths) if w]
            if live:
                return live
        return list(self.shards)

    def tokens_per_second(self, flops_per_token: float) -> float:
        lane = min(m.tokens_per_second(flops_per_token * self.shard_fraction)
                   for m in self._working_shards())
        return self.n_data * lane

    def batched_tokens_per_second(self, flops_per_token: float,
                                  batch: int) -> float:
        """Aggregate decode rate across all lanes at per-lane ``batch``."""
        lane = min(
            m.batched_tokens_per_second(
                flops_per_token * self.shard_fraction, batch)
            for m in self._working_shards())
        return self.n_data * lane

    def step_seconds(self, flops_per_token: float, batch: int = 1) -> float:
        """Modeled seconds of one decode wave on a single lane (the slowest
        shard bounds it; lanes step independently)."""
        b = max(1, int(batch))
        per_lane = self.batched_tokens_per_second(flops_per_token, b) \
            / self.n_data
        return b / per_lane

    def scaling_efficiency(self, flops_per_token: float,
                           batch: int = 1) -> float:
        """Aggregate rate vs ``n_devices`` ideal copies of shard 0 alone."""
        single = self.shards[0].batched_tokens_per_second(
            flops_per_token, batch)
        agg = self.batched_tokens_per_second(flops_per_token, batch)
        return agg / (self.n_devices * single)

    def report(self, flops_per_token: float, batch: int = 1) -> dict:
        return {
            "n_model": self.n_model,
            "n_data": self.n_data,
            "shard_fraction": self.shard_fraction,
            "agg_tok_s": self.batched_tokens_per_second(
                flops_per_token, batch),
            "scaling_efficiency": self.scaling_efficiency(
                flops_per_token, batch),
        }
