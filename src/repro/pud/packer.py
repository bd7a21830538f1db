"""Serving-time weight packer: swap model projections onto the PUD path.

``pack_model`` walks a trained/initialized parameter tree and replaces
selected projections with ``PackedTensor`` bit-plane packs (repro/pud/
packed.py), which ``models.layers.linear`` / ``models.attention`` dispatch
to the Pallas bit-plane GeMV.  This is how the paper's technique becomes a
first-class serving feature: any arch config can be served with
``--pud-gemv`` and its projections execute in the (simulated) DRAM layout.
``pack_for_serving`` is the legacy tuple-returning shim over it.

Which projections pack is configured by ``PUDGemvConfig.packable`` — entries
are either a bare key name ("wi") or scoped "component.key" ("mixer.wi",
matching when "mixer" appears on the tree path).  The default covers FFN
wi/wg/wo; add ``ATTN_PACKABLE`` for attention wq/wk/wv/wo, whose 3-D
``[D, H, Dh]`` weights pack as the flattened 2-D ``[D, H*Dh]`` case (the
head split is a view — the GeMV columns are the same either way).  MoE
routed expert banks keep the bf16 path (the expert dim adds a leading axis
the serving kernel does not tile yet).

Packs come out in the *bit-packed* storage layout by default (eight K rows
per uint8 word — ``pud/packed.py`` ``LAYOUT_BITPACK``), so the HBM bytes a
pack occupies finally match the bits the PUD format stores.  Stacked
(scanned) layers pack per-slice: [L, K, N] -> [L, WB, ceil(K/8), N]; under
the layer ``lax.scan`` each iteration sees one [WB, ceil(K/8), N] pack.

With a ``Placement`` (repro/pud/placement.py) the packer emits
*physically-permuted* planes in the block-aligned window layout: each
slice's bit-planes are scattered into the per-N-block physical windows its
logical columns were placed on, then bit-packed, plus the ``col_ids``
gather map the placed kernel consumes.  Faulty physical columns inside a
window hold zeros and are never addressed.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.ref import pack_plane_words
from repro.runtime.tracing import span

from .gemv import (ATTN_PACKABLE, FFN_PACKABLE, PUDGemvConfig, pack_linear,
                   quantize_planes)
from .packed import (LAYOUT_BITPACK, PackedModel, PackedTensor,
                     ShardedPackedTensor)
from .packed import packed_bytes  # noqa: F401  (legacy import location)
from .placement import (Placement, PlacementRequest, TensorPlacement,
                        shard_column_slices)


@dataclasses.dataclass(frozen=True, eq=False)
class LayerSource:
    """A stacked packable leaf [L, K, N] given one layer at a time.

    ``fn(l)`` returns layer ``l``'s [K, N] slice, on any device or on the
    host.  ``pack_model_sharded`` packs such a leaf slice by slice, so the
    whole stack never exists at once; ``PUDFleetSession`` keeps the source,
    not the weights, to repack from after a recalibration.
    """

    shape: tuple[int, ...]
    dtype: object
    fn: Callable[[int], object]

    @property
    def ndim(self) -> int:
        return len(self.shape)

    def layer(self, layer: int):
        return self.fn(layer)


def _weight(x) -> bool:
    """A leaf the packers read as a weight: a device array, a host
    (numpy) array or a ``LayerSource``."""
    return isinstance(x, (jax.Array, np.ndarray, LayerSource))


def _match(packable: tuple[str, ...], key: str, path: tuple[str, ...]) -> bool:
    """Does ``key`` at ``path`` belong to the packable set?

    "scope.name" entries require ``scope`` somewhere on the path; bare
    entries match the key in any context.
    """
    for entry in packable:
        if "." in entry:
            scope, name = entry.rsplit(".", 1)
            if key == name and scope in path:
                return True
        elif key == entry:
            return True
    return False


def _canonical(key: str, path: tuple[str, ...], w: jax.Array):
    """Matched projection -> canonical [K, N] / [L, K, N] view, or None.

    Attention weights carry explicit head axes; the PUD column layout does
    not care about the split, so wq/wk/wv flatten the trailing (H, Dh) axes
    and wo the leading ones.  Everything else (FFN, unembed) must already be
    2-D, optionally with a stacked-layer axis in front.
    """
    if "attn" in path:
        if isinstance(w, LayerSource):
            raise TypeError(f"{'/'.join(path + (key,))}: an attention "
                            "weight's head axes need the whole array, not "
                            "a LayerSource")
        if key in ("wq", "wk", "wv"):
            if w.ndim == 3:       # [D, H, Dh]
                return w.reshape(w.shape[0], -1)
            if w.ndim == 4:       # [L, D, H, Dh]
                return w.reshape(w.shape[0], w.shape[1], -1)
        elif key == "wo":
            if w.ndim == 3:       # [H, Dh, D]
                return w.reshape(-1, w.shape[-1])
            if w.ndim == 4:       # [L, H, Dh, D]
                return w.reshape(w.shape[0], -1, w.shape[-1])
        return None
    if w.ndim in (2, 3):
        return w
    return None


def _pack_stacked(w: jax.Array, n_bits: int,
                  backend: str | None) -> PackedTensor:
    """[L, K, N] (or [K, N]) weights -> stacked ``PackedTensor``."""
    if w.ndim == 2:
        return pack_linear(w, n_bits, backend)
    packs = [pack_linear(w[i], n_bits) for i in range(w.shape[0])]
    return PackedTensor(planes=jnp.stack([p.planes for p in packs]),
                        scale=jnp.stack([p.scale for p in packs]),
                        backend=backend, layout=packs[0].layout,
                        logical_k=packs[0].logical_k)


def _pack_placed(w: jax.Array, n_bits: int, tp: TensorPlacement,
                 backend: str | None) -> PackedTensor:
    """Physically-placed pack: planes scattered into the column window.

    The window is the *block-aligned* layout (repro/pud/placement.py):
    logical N-block j's columns sit inside window slice
    ``[j*tp.window_block, (j+1)*tp.window_block)``, so the placed kernels
    block the window axis per N-tile.  The scatter happens on dense planes
    (the window axis is the column axis, untouched by bit-packing), then
    the whole window bit-packs along K.  Returns a ``PackedTensor`` with
    planes [L?, WB, ceil(K/8), W] uint8 words, scale [L?, N], col_ids
    [L?, N] (absolute window positions) and ``window_block`` aux, where
    W = tp.region_size.
    """
    local = np.asarray(tp.local_cols)

    def one(w2, loc):
        idx = jnp.asarray(loc, jnp.int32)
        planes, scale = _placed_words(w2, idx, n_bits, tp.region_size)
        return PackedTensor(planes=planes, scale=scale, col_ids=idx)

    kw = dict(backend=backend, layout=LAYOUT_BITPACK,
              logical_k=w.shape[-2], window_block=tp.window_block)
    if w.ndim == 2:
        return dataclasses.replace(one(w, local), **kw)
    packs = [one(w[i], local[i]) for i in range(w.shape[0])]
    return PackedTensor(
        planes=jnp.stack([p.planes for p in packs]),
        scale=jnp.stack([p.scale for p in packs]),
        col_ids=jnp.stack([p.col_ids for p in packs]),
        **kw)


@functools.partial(jax.jit, static_argnames=("n_bits", "region"))
def _placed_words(w: jax.Array, idx: jax.Array, n_bits: int,
                  region: int) -> tuple[jax.Array, jax.Array]:
    """Quantize, scatter the dense planes to window positions ``idx`` and
    bit-pack the window, in one compiled program (bounded scratch)."""
    planes, scale = quantize_planes(w, n_bits, bitpack=False)
    window = jnp.zeros(planes.shape[:2] + (region,), jnp.int8)
    return pack_plane_words(window.at[:, :, idx].set(planes)), scale


def _pad_axis(a: jax.Array, axis: int, target: int, value=0) -> jax.Array:
    """Zero-risk trailing pad of one axis up to ``target`` columns."""
    grow = target - a.shape[axis]
    if grow <= 0:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, grow)
    return jnp.pad(a, pad, constant_values=value)


def _pad_col_ids(ids: jax.Array, n_max: int, block_cols: int,
                 window_block: int) -> jax.Array:
    """Extend a col_ids map to ``n_max`` columns of the padded geometry.

    Padded logical columns must still satisfy the block-slice invariant the
    placed kernels check (``analysis/contracts.check_col_ids``), so each
    points at the start of its *own* padded window block — those blocks
    hold zero planes, so the gathered contribution is zero and the padded
    output columns are sliced away after the shard GEMM anyway.
    """
    n_i = ids.shape[-1]
    if n_max <= n_i:
        return ids
    pad = (jnp.arange(n_i, n_max, dtype=jnp.int32) // block_cols) \
        * window_block
    pad = jnp.broadcast_to(pad, ids.shape[:-1] + (n_max - n_i,))
    return jnp.concatenate([ids, pad], axis=-1)


@functools.partial(jax.jit, static_argnames=("wb_i", "bc", "w_common",
                                             "nb_max", "n_max"))
def _normalize_placed_shard(planes, scale, col_ids, wb_i: int, bc: int,
                            w_common: int, nb_max: int, n_max: int):
    """Re-window one shard's placed pack onto the common padded geometry.

    The shard packed at its own ``window_block`` (wb_i, the max physical
    span of *its* blocks); the fleet needs every shard at the common stride
    ``w_common = max_i wb_i`` with ``nb_max`` blocks.  The window axis is
    the plane's trailing axis (untouched by bit-packing), so re-windowing
    is a reshape/pad: block j's columns move from ``j*wb_i + t`` to
    ``j*w_common + t``.
    """
    nb_i = col_ids.shape[-1] // bc
    pl = planes.reshape(planes.shape[:-1] + (nb_i, wb_i))
    pl = _pad_axis(_pad_axis(pl, -1, w_common), -2, nb_max)
    pl = pl.reshape(planes.shape[:-1] + (nb_max * w_common,))
    j = col_ids // wb_i
    ids = (j * w_common + col_ids - j * wb_i).astype(jnp.int32)
    ids = _pad_col_ids(ids, n_max, bc, w_common)
    scale = _pad_axis(scale, -1, n_max, value=1.0)
    return pl, scale, ids


@dataclasses.dataclass
class PackStats:
    """Counters of one sharded pack (``PUDFleetSession.pack_report``).

    ``layers``: stacked-layer indices packed; ``slices``: projection slices
    quantised (one per projection and layer, one per unstacked
    projection); ``device_bytes``: bytes of the packed model's arrays on
    each device (packs and the replicated leaves); ``peak_weight_bytes``:
    per device, the most bytes of unpacked projection weights the pack held
    there at one time — the leaves given on a device for the whole pack,
    plus the layer slice and the shard's columns in flight.
    """

    layers: int = 0
    slices: int = 0
    device_bytes: dict = dataclasses.field(default_factory=dict)
    peak_weight_bytes: dict = dataclasses.field(default_factory=dict)
    _held: dict = dataclasses.field(default_factory=dict, repr=False)

    def hold(self, a, sign: int = 1) -> None:
        """Count (``sign`` -1: uncount) a weight array where it lives."""
        if not isinstance(a, jax.Array):
            return                                  # host memory
        for sh in a.addressable_shards:
            d = str(sh.device)
            self._held[d] = self._held.get(d, 0) + sign * sh.data.nbytes
            self.peak_weight_bytes[d] = max(
                self.peak_weight_bytes.get(d, 0), self._held[d])

    def count_placed(self, tree) -> None:
        """Add every device array of ``tree`` to ``device_bytes`` once."""
        seen = set()
        for a in jax.tree.leaves(tree):
            if isinstance(a, jax.Array) and id(a) not in seen:
                seen.add(id(a))
                for sh in a.addressable_shards:
                    d = str(sh.device)
                    self.device_bytes[d] = (self.device_bytes.get(d, 0)
                                            + sh.data.nbytes)

    def report(self) -> dict:
        return {"layers": self.layers, "slices": self.slices,
                "device_bytes": dict(self.device_bytes),
                "peak_weight_bytes": dict(self.peak_weight_bytes),
                "max_weight_bytes": max(self.peak_weight_bytes.values(),
                                        default=0)}


@dataclasses.dataclass
class _ShardPlan:
    """How one projection splits, packs and pads over the model shards:
    shard ``m`` owns logical columns ``spans[m]`` and (placed) packs them
    on ``local[m]`` window positions; every shard pads to ``n_max``
    columns and (placed) ``nb_max`` window blocks of ``w_common``."""

    name: str
    w: object                   # [K, N] / [L, K, N]: device, host or source
    spans: list
    block_cols: int
    n_bits: int
    tps: list | None            # per-shard TensorPlacement, None: logical
    local: list | None          # per-shard tp.local_cols
    n_max: int
    w_common: int | None

    @property
    def n_layers(self) -> int | None:
        return self.w.shape[0] if len(self.w.shape) == 3 else None

    @property
    def placed(self) -> bool:
        return self.tps is not None

    @property
    def nb_max(self) -> int:
        return self.n_max // self.block_cols

    def layer(self, layer: int | None):
        if layer is None:
            return self.w
        if isinstance(self.w, LayerSource):
            return self.w.layer(layer)
        return self.w[layer]


def _shard_plan(w, n_shards: int, n_bits: int, placements, name: str
                ) -> _ShardPlan:
    spans, bc = shard_column_slices(w.shape[-1], n_shards)
    tps = local = w_common = None
    if placements is not None:
        tps, local = [], []
        for m, (lo, hi) in enumerate(spans):
            tp = placements[m].entries[name] if hi > lo else None
            if tp is not None and tp.block_cols != bc:
                raise ValueError(
                    f"shard {m} placement of {name!r} planned block_cols="
                    f"{tp.block_cols}, the sharded split uses {bc} — plan "
                    "per-shard placements with the forced block width")
            tps.append(tp)
            local.append(None if tp is None else np.asarray(tp.local_cols))
        w_common = max(tp.window_block for tp in tps if tp is not None)
    return _ShardPlan(name=name, w=w, spans=spans, block_cols=bc,
                      n_bits=n_bits, tps=tps, local=local,
                      n_max=max(hi - lo for lo, hi in spans),
                      w_common=w_common)


def _pack_shard_slice(plan: _ShardPlan, m: int, w2, layer: int | None,
                      device, stats: PackStats):
    """Shard ``m``'s columns of one [K, N] slice ``w2`` (host or any
    device), quantised and bit-packed on ``device`` in the plan's common
    padded geometry: (planes [WB, Kw, R], scale [Np], col_ids [Np] or
    None)."""
    lo, hi = plan.spans[m]
    k = w2.shape[0]
    if hi == lo:                 # zero-width shard: all padding
        wb, kw = plan.n_bits, -(-k // 8)
        if plan.placed:
            ids = (jnp.arange(plan.n_max, dtype=jnp.int32)
                   // plan.block_cols) * plan.w_common
            planes = jnp.zeros((wb, kw, plan.nb_max * plan.w_common),
                               jnp.uint8)
        else:
            ids = None
            planes = jnp.zeros((wb, kw, plan.n_max), jnp.uint8)
        return jax.device_put(
            (planes, jnp.ones((plan.n_max,), jnp.float32), ids), device)
    part = w2[:, lo:hi]
    stats.hold(part)
    wi = jax.device_put(part, device)
    moved = not (isinstance(part, jax.Array)
                 and part.devices() == wi.devices())   # else one buffer
    if moved:
        stats.hold(wi)
    if plan.placed:
        tp = plan.tps[m]
        loc = plan.local[m] if layer is None else plan.local[m][layer]
        idx = jax.device_put(np.asarray(loc, np.int32), device)
        planes, scale = _placed_words(wi, idx, plan.n_bits, tp.region_size)
        out = _normalize_placed_shard(
            planes, scale, idx, wb_i=tp.window_block, bc=plan.block_cols,
            w_common=plan.w_common, nb_max=plan.nb_max, n_max=plan.n_max)
    else:
        planes, scale = quantize_planes(wi, plan.n_bits, True)
        out = (_pad_axis(planes, -1, plan.n_max),
               _pad_axis(scale, -1, plan.n_max, value=1.0), None)
    stats.hold(part, -1)
    if moved:
        stats.hold(wi, -1)
    return out


def _shard_devices(mesh, axis: str, n_shards: int) -> list:
    """The device each model shard packs on: the first along ``axis``'s
    other mesh axes (None off a mesh: the default device)."""
    if mesh is None:
        return [None] * n_shards
    grid = np.moveaxis(np.asarray(mesh.devices),
                       list(mesh.axis_names).index(axis), 0)
    return [grid[m].reshape(-1)[0] for m in range(n_shards)]


def _place_shards(pieces: list, pos: int, mesh, axis: str) -> jax.Array:
    """Stack per-shard arrays on a new axis ``pos``; on a mesh, as one
    array sharded over ``axis`` there, each shard's piece on the devices
    that hold that shard (and replicated over the other mesh axes)."""
    if mesh is None:
        return jnp.stack(pieces, axis=pos)
    shape = pieces[0].shape[:pos] + (len(pieces),) + pieces[0].shape[pos:]
    spec = [None] * len(shape)
    spec[pos] = axis
    sharding = NamedSharding(mesh, P(*spec))
    arrays = [jax.device_put(jnp.expand_dims(pieces[idx[pos].start], pos),
                             dev)
              for dev, idx in
              sharding.addressable_devices_indices_map(shape).items()]
    return jax.make_array_from_single_device_arrays(shape, sharding, arrays)


def _assemble(plan: _ShardPlan, pieces: list, backend: str | None, mesh,
              axis: str) -> ShardedPackedTensor:
    """One projection's ``ShardedPackedTensor`` from its per-shard, per-
    layer (planes, scale, col_ids) pieces."""
    stacked = plan.n_layers is not None
    lead = 1 if stacked else 0

    def child(i):
        per = [jnp.stack([p[i] for p in ps]) if stacked else ps[0][i]
               for ps in pieces]
        return _place_shards(per, lead, mesh, axis)

    k = plan.w.shape[-2]
    return ShardedPackedTensor(
        planes=child(0), scale=child(1),
        col_ids=child(2) if plan.placed else None,
        shard_widths=tuple(hi - lo for lo, hi in plan.spans),
        block_cols=plan.block_cols, backend=backend, layout=LAYOUT_BITPACK,
        logical_k=k, window_block=plan.w_common, axis=axis, mesh=mesh)


def _pack_plans(plans: list[_ShardPlan], n_shards: int, backend, mesh,
                axis: str, stats: PackStats) -> dict:
    """Quantise, bit-pack and place every plan, one layer at a time: layer
    ``l`` of every stacked projection, then each unstacked projection.
    Each shard's columns go to that shard's device before they are packed,
    so no device holds more than the layer slice in flight."""
    devices = _shard_devices(mesh, axis, n_shards)
    pieces = {p.name: [[] for _ in range(n_shards)] for p in plans}
    given = [p.w for p in plans if isinstance(p.w, jax.Array)]
    for w in given:
        stats.hold(w)

    def pack_slice(plan, layer):
        w2 = plan.layer(layer)
        fresh = w2 is not plan.w
        if fresh:
            stats.hold(w2)
        for m in range(n_shards):
            pieces[plan.name][m].append(_pack_shard_slice(
                plan, m, w2, layer, devices[m], stats))
        if fresh:
            stats.hold(w2, -1)
        stats.slices += 1

    n_layers = max((p.n_layers or 0) for p in plans) if plans else 0
    with span("fleet.pack", shards=n_shards):
        for layer in range(n_layers):
            with span("fleet.pack.layer", layer=layer):
                for plan in plans:
                    if plan.n_layers is not None and layer < plan.n_layers:
                        pack_slice(plan, layer)
            stats.layers += 1
        for plan in plans:
            if plan.n_layers is None:
                with span("fleet.pack.layer", tensor=plan.name):
                    pack_slice(plan, None)
        out = {p.name: _assemble(p, pieces.pop(p.name), backend, mesh, axis)
               for p in plans}
    for w in given:
        stats.hold(w, -1)
    return out


def pack_linear_sharded(w, n_shards: int, *, n_bits: int = 4,
                        placements: list[Placement | None] | None = None,
                        name: str | None = None, backend: str | None = None,
                        mesh=None, axis: str = "model",
                        ) -> ShardedPackedTensor:
    """Pack one canonical [K, N] / [L, K, N] projection across model shards.

    The N axis splits on the full tensor's window-block boundaries
    (``shard_column_slices``) so every shard owns whole placement windows;
    each shard's slice packs independently — with that shard's own
    ``Placement`` when ``placements`` is given (placed layout; entries are
    looked up under ``name``), logically otherwise — then all shards pad
    to a common per-device shape and stack on the shard axis.  ``w`` may
    live on a device or the host, or be a ``LayerSource``.
    """
    plan = _shard_plan(w, n_shards, n_bits, placements, name)
    return _pack_plans([plan], n_shards, backend, mesh, axis,
                       PackStats())[name]


def pack_model_sharded(params: dict, cfg: PUDGemvConfig = PUDGemvConfig(),
                       *, n_shards: int,
                       placements: list[Placement | None] | None = None,
                       include_unembed: bool = True, mesh=None,
                       axis: str = "model",
                       stats: PackStats | None = None) -> PackedModel:
    """Tensor-parallel ``pack_model``: every pack is a ShardedPackedTensor.

    ``placements`` gives one per-shard ``Placement`` (planned on that
    shard's own calibration masks over its column slice of every request —
    see ``PUDFleetSession.pack``); None packs the logical layout.  The
    returned tree drops fp weights from packed projections exactly like
    ``pack_model``, so the single-device model code serves it unchanged —
    ``pud_linear`` dispatches on the pack type.

    Packable leaves may be device arrays, host (numpy) arrays or
    ``LayerSource``s; they are packed one layer slice at a time
    (``_pack_plans``).  With a ``mesh``, each shard's arrays are placed on
    the devices of its ``axis`` index and every other leaf is placed
    replicated over the mesh, so a lane's programs compile as one SPMD
    program.  ``stats`` (a ``PackStats``) collects the pack's counters.
    """
    stats = PackStats() if stats is None else stats
    packed_names: list[str] = []
    skipped: list[str] = []
    plans: list[_ShardPlan] = []
    rep = None if mesh is None else NamedSharding(mesh, P())

    def plan(w, name):
        plans.append(_shard_plan(w, n_shards, cfg.weight_bits, placements,
                                 name))
        packed_names.append(name)
        return name                    # a placeholder, swapped below

    unembed = []

    def walk(tree, path):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for key, sub in tree.items():
            p = path + (key,)
            if isinstance(sub, dict):
                out[key] = walk(sub, p)
                continue
            if include_unembed and p == ("unembed", "w"):
                unembed.append(sub)            # packed last, as pack_model
                continue
            if _weight(sub) and _match(cfg.packable, key, path):
                w = _canonical(key, path, sub)
                if w is not None:
                    out[key + "_pud"] = plan(w, "/".join(p))
                    continue
                skipped.append("/".join(p))
            if isinstance(sub, LayerSource):
                raise TypeError(f"{'/'.join(p)} is given by layer but is "
                                "not packed: give it as an array")
            out[key] = sub if rep is None else jax.device_put(sub, rep)
        return out

    tree = walk(params, ())
    if unembed:
        tree["unembed"]["w_pud"] = plan(unembed[0], "unembed/w")
    packs = _pack_plans(plans, n_shards, cfg.backend, mesh, axis, stats)

    def swap(t):
        return {k: (swap(v) if isinstance(v, dict)
                    else packs[v] if k.endswith("_pud") else v)
                for k, v in t.items()}

    tree = swap(tree)
    stats.count_placed(tree)
    return PackedModel(params=tree,
                       packed_names=tuple(packed_names),
                       skipped_names=tuple(skipped),
                       weight_bits=cfg.weight_bits,
                       placed=placements is not None)


def _pack_any(w, n_bits: int, name: str, placement: Placement | None,
              backend: str | None) -> PackedTensor:
    if placement is None:
        return _pack_stacked(w, n_bits, backend)
    tp = placement.entries.get(name)
    if tp is None:
        raise KeyError(
            f"placement has no entry for packed tensor {name!r}; plan it "
            "from packing_requests() of the same params/config "
            f"(have: {sorted(placement.entries)})")
    return _pack_placed(w, n_bits, tp, backend)


def packing_requests(params: dict, cfg: PUDGemvConfig = PUDGemvConfig(),
                     include_unembed: bool = True) -> list[PlacementRequest]:
    """Column demand of every projection ``pack_for_serving`` would pack.

    Feed this to ``placement.plan_placement`` — the request names match the
    report/placement keys the packer uses.
    """
    reqs: list[PlacementRequest] = []

    def walk(tree, path):
        for key, sub in tree.items():
            p = path + (key,)
            if isinstance(sub, dict):
                walk(sub, p)
            elif _weight(sub) and _match(cfg.packable, key, path):
                w = _canonical(key, path, sub)
                if w is None:
                    continue
                if w.ndim == 2:
                    reqs.append(PlacementRequest("/".join(p), w.shape[1], 0))
                else:
                    reqs.append(PlacementRequest(
                        "/".join(p), w.shape[2], w.shape[0]))

    walk(params, ())
    if include_unembed and "w" in params.get("unembed", {}):
        reqs.append(PlacementRequest(
            "unembed/w", params["unembed"]["w"].shape[1], 0))
    return reqs


def pack_model(params: dict, cfg: PUDGemvConfig = PUDGemvConfig(),
               include_unembed: bool = True,
               placement: Placement | None = None) -> PackedModel:
    """Pack a parameter tree for PUD serving; returns a ``PackedModel``.

    Original fp weights are dropped from packed projections (the bit-planes
    ARE the stored layout).  With ``placement``, every pack is emitted in
    its physical column layout (see ``_pack_placed``); the placement must
    cover exactly the tensors this config packs — build it from
    ``packing_requests(params, cfg)``.
    """
    packed_names: list[str] = []
    skipped: list[str] = []

    def walk(tree, path):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for key, sub in tree.items():
            p = path + (key,)
            if isinstance(sub, dict):
                out[key] = walk(sub, p)
                continue
            if isinstance(sub, jax.Array) and _match(cfg.packable, key, path):
                w = _canonical(key, path, sub)
                if w is not None:
                    name = "/".join(p)
                    out[key + "_pud"] = _pack_any(
                        w, cfg.weight_bits, name, placement, cfg.backend)
                    packed_names.append(name)
                    continue
                skipped.append("/".join(p))
            out[key] = sub
        return out

    packed = walk(params, ())
    if include_unembed and "unembed" in packed:
        w = packed["unembed"].pop("w")
        packed["unembed"]["w_pud"] = _pack_any(
            w, cfg.weight_bits, "unembed/w", placement, cfg.backend)
        packed_names.append("unembed/w")
    return PackedModel(params=packed,
                       packed_names=tuple(packed_names),
                       skipped_names=tuple(skipped),
                       weight_bits=cfg.weight_bits,
                       placed=placement is not None)


def pack_for_serving(params: dict, cfg: PUDGemvConfig = PUDGemvConfig(),
                     include_unembed: bool = True,
                     placement: Placement | None = None) -> tuple[dict, dict]:
    """Legacy entry point: returns (serving params tree, report dict).

    Thin shim over ``pack_model`` — new code should use that (or
    ``PUDSession.pack``, which also owns calibration + placement) and work
    with the typed ``PackedModel`` instead of the loose tuple.
    """
    pm = pack_model(params, cfg, include_unembed=include_unembed,
                    placement=placement)
    return pm.params, pm.report
