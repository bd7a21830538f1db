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

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ref import pack_plane_words

from .gemv import (ATTN_PACKABLE, FFN_PACKABLE, PUDGemvConfig, pack_linear,
                   quantize_planes)
from .packed import (LAYOUT_BITPACK, PackedModel, PackedTensor,
                     ShardedPackedTensor)
from .packed import packed_bytes  # noqa: F401  (legacy import location)
from .placement import (Placement, PlacementRequest, TensorPlacement,
                        shard_column_slices)


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


def _normalize_placed_shard(pk: PackedTensor, bc: int, w_common: int,
                            nb_max: int, n_max: int):
    """Re-window one shard's placed pack onto the common padded geometry.

    The shard packed at its own ``window_block`` (wb_i, the max physical
    span of *its* blocks); the fleet needs every shard at the common stride
    ``w_common = max_i wb_i`` with ``nb_max`` blocks.  The window axis is
    the plane's trailing axis (untouched by bit-packing), so re-windowing
    is a reshape/pad: block j's columns move from ``j*wb_i + t`` to
    ``j*w_common + t``.
    """
    wb_i = pk.window_block
    n_i = pk.col_ids.shape[-1]
    nb_i = n_i // bc
    planes = pk.planes                       # [L?, WB, Kw, nb_i*wb_i]
    pl = planes.reshape(planes.shape[:-1] + (nb_i, wb_i))
    pl = _pad_axis(_pad_axis(pl, -1, w_common), -2, nb_max)
    pl = pl.reshape(planes.shape[:-1] + (nb_max * w_common,))
    j = pk.col_ids // wb_i
    ids = (j * w_common + pk.col_ids - j * wb_i).astype(jnp.int32)
    ids = _pad_col_ids(ids, n_max, bc, w_common)
    scale = _pad_axis(pk.scale, -1, n_max, value=1.0)
    return pl, scale, ids


def pack_linear_sharded(w: jax.Array, n_shards: int, *, n_bits: int = 4,
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
    to a common per-device shape and stack on the shard axis.
    """
    n = w.shape[-1]
    spans, bc = shard_column_slices(n, n_shards)
    widths = tuple(hi - lo for lo, hi in spans)
    placed = placements is not None
    lead = w.shape[:-2]                          # () or (L,)

    packs: list[PackedTensor | None] = []
    for m, (lo, hi) in enumerate(spans):
        if hi == lo:
            packs.append(None)
            continue
        wi = w[..., lo:hi]
        if placed:
            tp = placements[m].entries[name]
            if tp.block_cols != bc:
                raise ValueError(
                    f"shard {m} placement of {name!r} planned block_cols="
                    f"{tp.block_cols}, the sharded split uses {bc} — plan "
                    "per-shard placements with the forced block width")
            packs.append(_pack_placed(wi, n_bits, tp, backend))
        else:
            packs.append(_pack_stacked(wi, n_bits, backend))

    live = [p for p in packs if p is not None]
    ref = live[0]
    logical_k = ref.logical_k
    kw_words = ref.planes.shape[-2]
    wb = ref.planes.shape[-3]
    n_max = max(widths)

    if placed:
        w_common = max(p.window_block for p in live)
        nb_max = n_max // bc
        region = nb_max * w_common
        norm = []
        pad_ids = jnp.broadcast_to(
            (jnp.arange(n_max, dtype=jnp.int32) // bc) * w_common,
            lead + (n_max,))
        for p in packs:
            if p is None:
                norm.append((jnp.zeros(lead + (wb, kw_words, region),
                                       jnp.uint8),
                             jnp.ones(lead + (n_max,), jnp.float32),
                             pad_ids))
            else:
                norm.append(_normalize_placed_shard(p, bc, w_common,
                                                    nb_max, n_max))
        planes = jnp.stack([t[0] for t in norm], axis=-4)
        scale = jnp.stack([t[1] for t in norm], axis=-2)
        col_ids = jnp.stack([t[2] for t in norm], axis=-2)
    else:
        w_common = None
        planes = jnp.stack(
            [_pad_axis(p.planes, -1, n_max) if p is not None
             else jnp.zeros(lead + (wb, kw_words, n_max), jnp.uint8)
             for p in packs], axis=-4)
        scale = jnp.stack(
            [_pad_axis(p.scale, -1, n_max, value=1.0) if p is not None
             else jnp.ones(lead + (n_max,), jnp.float32)
             for p in packs], axis=-2)
        col_ids = None

    return ShardedPackedTensor(
        planes=planes, scale=scale, col_ids=col_ids, shard_widths=widths,
        block_cols=bc, backend=backend, layout=LAYOUT_BITPACK,
        logical_k=logical_k, window_block=w_common, axis=axis, mesh=mesh)


def pack_model_sharded(params: dict, cfg: PUDGemvConfig = PUDGemvConfig(),
                       *, n_shards: int,
                       placements: list[Placement | None] | None = None,
                       include_unembed: bool = True, mesh=None,
                       axis: str = "model") -> PackedModel:
    """Tensor-parallel ``pack_model``: every pack is a ShardedPackedTensor.

    ``placements`` gives one per-shard ``Placement`` (planned on that
    shard's own calibration masks over its column slice of every request —
    see ``PUDFleetSession.pack``); None packs the logical layout.  The
    returned tree drops fp weights from packed projections exactly like
    ``pack_model``, so the single-device model code serves it unchanged —
    ``pud_linear`` dispatches on the pack type.
    """
    packed_names: list[str] = []
    skipped: list[str] = []

    def one(w, name):
        return pack_linear_sharded(
            w, n_shards, n_bits=cfg.weight_bits, placements=placements,
            name=name, backend=cfg.backend, mesh=mesh, axis=axis)

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
                    out[key + "_pud"] = one(w, name)
                    packed_names.append(name)
                    continue
                skipped.append("/".join(p))
            out[key] = sub
        return out

    packed = walk(params, ())
    if include_unembed and "unembed" in packed:
        w = packed["unembed"].pop("w")
        packed["unembed"]["w_pud"] = one(w, "unembed/w")
        packed_names.append("unembed/w")
    return PackedModel(params=packed,
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
            elif (isinstance(sub, jax.Array)
                  and _match(cfg.packable, key, path)):
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
