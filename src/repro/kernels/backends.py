"""Named execution backends for the bit-plane GeMV.

The serving stack picks *where* a packed GeMV executes by name instead of
threading ``interpret``/oracle flags through every call site:

  * ``pallas``    — the Pallas TPU kernel; lowers natively on a TPU and runs
    in the interpreter elsewhere (``kernels/platform.interpret_mode``).
  * ``interpret`` — the same Pallas kernel forced through the interpreter,
    regardless of platform.  Useful for debugging kernel changes on TPU.
  * ``reference`` — the pure-jnp oracle (kernels/ref.py).

Every backend implements the same entry points — ``gemv``/``gemv_placed``
for the single-block GeMV and ``gemm``/``gemm_placed`` for the batch-tiled
GEMM the serving engine feeds — and all are bit-exact against each other,
enforced by tests/test_session.py and tests/test_bitplane_gemm.py across
placed and unplaced packs, dense and bit-packed plane layouts.  Layout
metadata (``layout``/``logical_k``/``window_block`` — see
repro/pud/packed.py) arrives as keyword arguments; the Pallas backends
hand them to the kernel wrappers, the reference backend densifies the
words first and runs the unchanged jnp oracle.  ``PUDSession`` selects a
backend per session and per call; register custom ones (e.g. a future GPU
lowering) with ``register_backend`` (backends without GEMM lowerings fall
back to their GeMV entry, which already accepts a [B, K] operand block).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from . import ref
from .bitplane_gemm import bitplane_gemm, bitplane_gemm_placed
from .bitplane_gemv import bitplane_gemv, bitplane_gemv_placed

DEFAULT_BACKEND = "pallas"


@dataclasses.dataclass(frozen=True)
class Backend:
    """One named lowering of the bit-plane GeMV/GEMM.

    ``gemv(x, planes, mode, **layout_kw)``: [B, K] int8 x [WB, K(/8), N]
    planes -> [B, N] int32 with the whole B in one block (decode-shaped).
    ``gemv_placed(x, planes, col_ids, mode, **layout_kw)``: same, with
    planes in the physical-window layout and the logical->window gather
    map.  ``gemm``/``gemm_placed``: identical signatures and numerics with
    the batch axis tiled into the kernel grid (serving-engine-shaped);
    None falls back to the GeMV entry.  ``layout_kw`` is the pack-format
    metadata: ``layout`` ("dense" | "bitpack8"), ``logical_k`` (un-padded
    K of a bit-packed pack), ``window_block`` (placed entries only).
    """

    name: str
    gemv: Callable[..., jax.Array]
    gemv_placed: Callable[..., jax.Array]
    gemm: Callable[..., jax.Array] | None = None
    gemm_placed: Callable[..., jax.Array] | None = None

    def matmul(self, x, planes, mode="folded", **kw):
        """Batch-tiled entry, falling back to the one-block GeMV."""
        return (self.gemm or self.gemv)(x, planes, mode, **kw)

    def matmul_placed(self, x, planes, col_ids, mode="folded", **kw):
        return (self.gemm_placed or self.gemv_placed)(x, planes, col_ids,
                                                      mode, **kw)


_REGISTRY: dict[str, Backend] = {}


def register_backend(backend: Backend) -> Backend:
    if backend.name in _REGISTRY:
        raise ValueError(f"backend {backend.name!r} already registered")
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown backend {name!r}; registered: "
                       f"{backend_names()}") from None


def backend_names() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def _pallas_entries(interpret: bool | None, check=False):
    """The four kernel entries at a fixed interpret policy (None lets the
    kernels resolve it from the platform when they trace).

    ``check=True`` runs the static contract checker
    (repro.analysis.contracts) before every launch; the ``interpret``
    backend enables it unconditionally, so the debugging backend also
    validates tiling/layout/VMEM invariants the hardware path assumes.
    """

    def preflight(entry, x, planes, **kw):
        if check:
            # Deferred: analysis.contracts imports kernels.ops, which
            # imports this module at its own top level.
            from repro.analysis.contracts import check_kernel_args
            check_kernel_args(entry, x.shape, planes.shape, **kw)

    def gemv(x, planes, mode="folded", *, layout="dense", logical_k=None,
             n_block=None, k_block=None):
        preflight("gemv", x, planes, layout=layout, logical_k=logical_k,
                  mode=mode, n_block=n_block, k_block=k_block)
        return bitplane_gemv(x, planes, mode=mode, interpret=interpret,
                             layout=layout, logical_k=logical_k,
                             n_block=n_block, k_block=k_block)

    def gemv_placed(x, planes, col_ids, mode="folded", *, layout="dense",
                    logical_k=None, window_block=None, n_block=None,
                    k_block=None):
        preflight("gemv", x, planes, layout=layout, logical_k=logical_k,
                  col_ids=col_ids, window_block=window_block, mode=mode,
                  n_block=n_block, k_block=k_block)
        return bitplane_gemv_placed(
            x, planes, col_ids, mode=mode, interpret=interpret,
            layout=layout, logical_k=logical_k, window_block=window_block,
            n_block=n_block, k_block=k_block)

    def gemm(x, planes, mode="folded", *, layout="dense", logical_k=None,
             b_block=None, n_block=None, k_block=None):
        preflight("gemm", x, planes, layout=layout, logical_k=logical_k,
                  mode=mode, b_block=b_block, n_block=n_block,
                  k_block=k_block)
        return bitplane_gemm(x, planes, mode=mode, interpret=interpret,
                             layout=layout, logical_k=logical_k,
                             b_block=b_block, n_block=n_block,
                             k_block=k_block)

    def gemm_placed(x, planes, col_ids, mode="folded", *, layout="dense",
                    logical_k=None, window_block=None, b_block=None,
                    n_block=None, k_block=None):
        preflight("gemm", x, planes, layout=layout, logical_k=logical_k,
                  col_ids=col_ids, window_block=window_block, mode=mode,
                  b_block=b_block, n_block=n_block, k_block=k_block)
        return bitplane_gemm_placed(
            x, planes, col_ids, mode=mode, interpret=interpret,
            layout=layout, logical_k=logical_k, window_block=window_block,
            b_block=b_block, n_block=n_block, k_block=k_block)

    return gemv, gemv_placed, gemm, gemm_placed


def _densify(planes, layout, logical_k):
    """Reference-backend adapter: bit-words -> dense planes (jnp oracle
    input); dense planes pass through untouched."""
    if layout == "bitpack8":
        return ref.unpack_plane_words(planes, logical_k)
    return planes


def _ref_gemv(x, planes, mode="folded", *, layout="dense", logical_k=None,
              b_block=None, n_block=None, k_block=None):
    # Tile overrides are execution hints; the oracle's numerics ignore them
    # (bit-exactness across tuned and heuristic tiles rests on this).
    planes = _densify(planes, layout, logical_k)
    if layout == "bitpack8" and planes.shape[1] != x.shape[1]:
        x = jnp.pad(x, ((0, 0), (0, planes.shape[1] - x.shape[1])))
    return ref.bitplane_gemv_ref(x, planes)


def _ref_gemv_placed(x, planes, col_ids, mode="folded", *, layout="dense",
                     logical_k=None, window_block=None, b_block=None,
                     n_block=None, k_block=None):
    # Gather the logical columns before unpacking: a column's words unpack
    # to that column's bits, so the order changes nothing but the scratch
    # (a placed window can be twice the logical width).
    planes = jnp.take(planes, col_ids, axis=2)
    return _ref_gemv(x, planes, mode, layout=layout, logical_k=logical_k)


_pl = _pallas_entries(None)
register_backend(Backend(
    name="pallas",
    gemv=_pl[0], gemv_placed=_pl[1], gemm=_pl[2], gemm_placed=_pl[3],
))

_it = _pallas_entries(True, check=True)
register_backend(Backend(
    name="interpret",
    gemv=_it[0], gemv_placed=_it[1], gemm=_it[2], gemm_placed=_it[3],
))

register_backend(Backend(
    name="reference",
    # The jnp oracle is already batch-shaped: the same entry serves both.
    gemv=_ref_gemv,
    gemv_placed=_ref_gemv_placed,
    gemm=_ref_gemv,
    gemm_placed=_ref_gemv_placed,
))
