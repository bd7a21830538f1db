"""Public jit'd entry points for the Pallas kernels.

Every entry lowers natively on a TPU and runs in the Pallas interpreter
elsewhere (``kernels/platform.interpret_mode``); a backend name
(kernels/backends.py) picks the lowering per call.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .autotune import TunedTile, plan_for_entry, tune_kernel, tuning_key
from .backends import DEFAULT_BACKEND, get_backend
from .bitplane_gemm import B_BLOCK, bitplane_gemm, bitplane_gemm_placed
from .bitplane_gemv import (DEGENERATE_TILE_FLOOR, K_BLOCK, N_BLOCK,
                            bitplane_gemv, bitplane_gemv_placed)
from .bitplane_gemv import _heuristic_block as heuristic_block
from .bitplane_gemv import _largest_divisor as largest_divisor
from .majx import calib_iter_fused, majx_sense

__all__ = [
    "majx_sense", "calib_iter_fused", "bitplane_gemv",
    "bitplane_gemv_placed", "bitplane_gemm", "bitplane_gemm_placed",
    "pud_matmul", "pud_matmul_sharded", "pud_gemv", "quantize_activations",
    "round_to_dtype",
    # Autotuner surface (kernels/autotune.py): plans ride packs and the
    # tuning cache through these names.
    "TunedTile", "plan_for_entry", "tune_kernel", "tuning_key",
    # Tiling facts re-exported for non-kernel consumers (pud/placement.py,
    # analysis/contracts.py): the kernel implementation modules are private
    # to this package — the repo lint enforces that — so the block
    # constants and the divisor rule travel through this public surface.
    "B_BLOCK", "K_BLOCK", "N_BLOCK", "DEGENERATE_TILE_FLOOR",
    "largest_divisor", "heuristic_block",
]


def round_to_dtype(x: jax.Array, dtype) -> jax.Array:
    """float32 ``x`` rounded to the precision of the float ``dtype``.

    XLA may compute a bf16 value at f32 precision when it fuses the op
    that produces it into its consumer, and on a TPU whether it fuses
    depends on the shapes.  An explicit ``reduce_precision`` is a rounding
    it keeps, so what follows sees the same value at every batch size.
    Integer and 32-bit or wider dtypes leave ``x`` as it is.
    """
    if not jnp.issubdtype(dtype, jnp.floating):
        return x
    fi = jnp.finfo(dtype)
    if fi.bits >= 32:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def quantize_activations(x: jax.Array, clip: float = 4.0) -> tuple[jax.Array, jax.Array]:
    """Per-row symmetric int8 quantization for the PUD GeMV input.

    Row-independent by construction (per-row scale), so batched and
    per-request execution quantize each request identically — the property
    the batched-vs-sequential bit-exactness guarantee rests on.  It runs in
    float32 on ``x`` rounded to its own dtype (``round_to_dtype``), so the
    batch size cannot change how much precision the input carries.
    """
    x = round_to_dtype(x.astype(jnp.float32), x.dtype)
    scale = jnp.maximum(jnp.abs(x).max(axis=-1, keepdims=True), 1e-6) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def pud_matmul(
    x: jax.Array,          # [B, K] float activations
    planes: jax.Array,     # [WB, K(/8), N] bit-planes / bit-words
    w_scale: jax.Array,    # [N] or scalar dequant scale
    mode: str = "folded",
    col_ids: jax.Array | None = None,   # [N] window map -> placed kernel
    backend: str | None = None,         # named backend (kernels/backends.py)
    layout: str = "dense",              # plane storage (repro/pud/packed.py)
    logical_k: int | None = None,       # un-padded K of a bit-packed pack
    window_block: int | None = None,    # placed window stride (block-aligned)
    check_contracts: bool = False,      # pre-flight analysis/contracts.py
    tile_plan=None,                     # TunedTile | ((entry, TunedTile), ...)
) -> jax.Array:
    """Quantize -> bit-plane GEMM -> dequantize. Returns [B, N] float32.

    The batched primary entry of the PUD execution path: B = 1 runs the
    decode-shaped GeMV kernel (whole batch in one block, the faithful
    single-vector schedule), B > 1 the batch-tiled GEMM kernel — bit-exact
    against each other, so the dispatch is purely a tiling decision.

    With ``col_ids`` the planes are the physically-placed window layout
    (repro/pud/placement.py) and the column gather runs fused in the kernel.
    ``layout``/``logical_k``/``window_block`` carry the pack-format
    metadata of a ``PackedTensor`` (bit-packed words unpack inside the
    kernel).  ``backend`` names a registered lowering (default
    ``DEFAULT_BACKEND``).  All backends are bit-exact against each other.

    ``check_contracts=True`` runs the static kernel-contract checker
    (repro/analysis/contracts.py) over the resolved entry point before
    dispatch — tile selection, layout metadata consistency, placed-window
    bounds, VMEM budget — raising ``ContractViolation`` instead of letting
    a mis-built pack fail deep inside the kernel (the ``interpret``
    backend runs the same check unconditionally).

    ``tile_plan`` is the autotuner hook: a :class:`TunedTile` (or a tuple
    of ``(entry, TunedTile)`` pairs, resolved after the gemv/gemm dispatch)
    overriding block sizes / window stride / unpack mode.  Plans are
    execution choices only — every plan computes the identical result
    (kernels/autotune.py enforces it at tuning time); cold-start (no plan)
    falls back to the divisor heuristic unchanged.
    """
    xq, x_scale = quantize_activations(x)
    be = get_backend(backend or DEFAULT_BACKEND)
    batched = xq.shape[0] > 1
    entry = "gemm" if batched else "gemv"
    plan = plan_for_entry(tile_plan, entry)
    eff_mode = (plan.mode or mode) if plan is not None else mode
    eff_window_block = window_block
    if plan is not None and plan.window_block is not None:
        eff_window_block = plan.window_block
    if check_contracts:
        from repro.analysis.contracts import (check_kernel_args,
                                              check_tile_plan)

        if plan is not None:
            check_tile_plan(
                plan, entry, xq.shape, planes.shape, layout=layout,
                logical_k=logical_k, col_ids=col_ids,
                window_block=window_block, mode=mode)
        else:
            check_kernel_args(
                entry, xq.shape, planes.shape,
                layout=layout, logical_k=logical_k, col_ids=col_ids,
                window_block=window_block, mode=mode)
    # Layout kwargs only travel when they carry information: a legacy dense
    # pack dispatches through the pre-refactor 3-arg entry signature, so
    # custom backends registered against it keep working (bit-packed packs
    # genuinely require the layout-aware signature).
    kw = {}
    if layout != "dense":
        kw = {"layout": layout, "logical_k": logical_k}
    if plan is not None:
        if plan.n_block is not None:
            kw["n_block"] = plan.n_block
        if plan.k_block is not None:
            kw["k_block"] = plan.k_block
        if batched and plan.b_block is not None:
            kw["b_block"] = plan.b_block
    if col_ids is not None:
        if eff_window_block is not None:
            kw["window_block"] = eff_window_block
        acc = (be.matmul_placed(xq, planes, col_ids, eff_mode, **kw)
               if batched
               else be.gemv_placed(xq, planes, col_ids, eff_mode, **kw))
    else:
        acc = (be.matmul(xq, planes, eff_mode, **kw) if batched
               else be.gemv(xq, planes, eff_mode, **kw))
    return acc.astype(jnp.float32) * x_scale * w_scale


def pud_matmul_sharded(
    x: jax.Array,          # [B, K] float activations (replicated per device)
    st,                    # ShardedPackedTensor: children stacked [S, ...]
    mode: str = "folded",
    backend: str | None = None,
    check_contracts: bool = False,
) -> jax.Array:
    """Tensor-parallel ``pud_matmul`` over the pack's mesh "model" axis.

    ``st`` is a ``pud.packed.ShardedPackedTensor`` (duck-typed here so the
    kernel layer stays import-free of ``pud``): per-shard packs padded to a
    common per-device shape and stacked on a leading shard axis S that maps
    onto ``st.axis`` of ``st.mesh``.  Each device runs the ordinary
    ``pud_matmul`` on its own shard — its own planes, dequant scales and
    (placed layout) ``col_ids`` — with ``x`` replicated in; every device
    then gathers the per-shard outputs, which reassemble, replicated, by
    static column slices.

    Bit-exact against the unsharded path by construction: activation
    quantization is per-row (identical on every replica), the integer
    accumulation per output column touches exactly the same K values, and
    the dequant multiply order ``acc * x_scale * w_scale`` is the same
    expression ``pud_matmul`` computes — float columns never cross a shard
    boundary, so no re-association happens anywhere.
    """
    from jax.sharding import PartitionSpec as P

    if st.mesh is None:
        raise ValueError(
            "sharded pack carries no mesh — build it through "
            "PUDFleetSession.pack / pack_model_sharded(mesh=...)")
    axis = st.axis
    placed = st.col_ids is not None
    kw = dict(mode=mode, backend=backend or st.backend, layout=st.layout,
              logical_k=st.logical_k, window_block=st.window_block,
              check_contracts=check_contracts, tile_plan=st.tile_plan)

    # Every device gathers all shards' columns inside the shard_map, so the
    # result leaves it replicated.  Left sharded, the compiler may keep the
    # model's next ops (residual, norms) split over the hidden axis and sum
    # partial reductions across devices, re-associating float sums the
    # one-device path adds in another order.
    def gather(y):
        with jax.named_scope("repro.tp.gather"):
            return jax.lax.all_gather(y, axis)

    if placed:
        def body(xr, planes, scale, col_ids):
            return gather(pud_matmul(xr, planes[0], scale[0],
                                     col_ids=col_ids[0], **kw))

        f = jax.shard_map(body, mesh=st.mesh,
                          in_specs=(P(), P(axis), P(axis), P(axis)),
                          out_specs=P(), check_vma=False)
        y = f(x, st.planes, st.scale, st.col_ids)
    else:
        def body(xr, planes, scale):
            return gather(pud_matmul(xr, planes[0], scale[0], **kw))

        f = jax.shard_map(body, mesh=st.mesh,
                          in_specs=(P(), P(axis), P(axis)),
                          out_specs=P(), check_vma=False)
        y = f(x, st.planes, st.scale)
    # [S, B, Np] -> [B, N]: drop per-shard padding columns, concatenate in
    # logical order (shards own contiguous column ranges by construction).
    parts = [y[i, :, :w] for i, w in enumerate(st.shard_widths) if w]
    return jnp.concatenate(parts, axis=-1)


def pud_gemv(
    x: jax.Array,          # [K] or [B, K] float activations
    planes: jax.Array,
    w_scale: jax.Array,
    mode: str = "folded",
    col_ids: jax.Array | None = None,
    backend: str | None = None,
    layout: str = "dense",
    logical_k: int | None = None,
    window_block: int | None = None,
    check_contracts: bool = False,
    tile_plan=None,
) -> jax.Array:
    """Rank-dispatching shim over ``pud_matmul``.

    Kept as the legacy single-request entry: a 1-D ``x`` [K] returns [N],
    a 2-D ``x`` [B, K] behaves exactly like ``pud_matmul``.
    """
    kw = dict(mode=mode, col_ids=col_ids,
              backend=backend, layout=layout, logical_k=logical_k,
              window_block=window_block, check_contracts=check_contracts,
              tile_plan=tile_plan)
    if x.ndim == 1:
        return pud_matmul(x[None, :], planes, w_scale, **kw)[0]
    return pud_matmul(x, planes, w_scale, **kw)


def pud_gemv_ref(x, planes, w_scale, col_ids=None):
    xq, x_scale = quantize_activations(x)
    if col_ids is not None:
        acc = ref.bitplane_gemv_placed_ref(xq, planes, col_ids)
    else:
        acc = ref.bitplane_gemv_ref(xq, planes)
    return acc.astype(jnp.float32) * x_scale * w_scale
