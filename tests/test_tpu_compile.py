"""Ahead-of-time compiles of the main path's kernels for a TPU v5e.

The chip's compiler is installed here and compiles for a described, not
attached, ``v5e:2x2`` topology: these tests catch what interpret mode
cannot (operand dtypes the MXU refuses, gathers Mosaic cannot lower,
blocks not tiled by the (8, 128) layout) at the widths qwen3-1.7b serves.
Nothing runs, so they say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports every
test file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.fleet import FleetConfig, sense_tables
from repro.kernels.ops import (bitplane_gemm, bitplane_gemm_placed,
                               bitplane_gemv, bitplane_gemv_placed,
                               calib_iter_fused)
from repro.pud.physics import PhysicsParams
from repro.pud.placement import (PlacementRequest, WINDOW_ALIGN,
                                 plan_placement)

WB = 4
SHAPES = [(2048, 6144), (6144, 2048)]        # qwen3-1.7b FFN in / out
ENTRIES = {
    "gemv": bitplane_gemv,
    "gemm": bitplane_gemm,
    "gemv_placed": bitplane_gemv_placed,
    "gemm_placed": bitplane_gemm_placed,
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _placer_window_block(n: int) -> tuple[int, int]:
    """(window_block, n_blocks) the placer emits for an N-column tensor on
    a grid at the calibrated ~3.6% error-prone column rate."""
    masks = np.random.default_rng(0).random((4, 65536)) < 0.036
    tp = plan_placement(masks, [PlacementRequest("w", n, 0)]).entries["w"]
    return tp.window_block, tp.n_blocks


def _compile(fn, *args, **kw):
    compiled = jax.jit(lambda *a: fn(*a, interpret=False, **kw)) \
        .lower(*args).compile()
    return compiled.as_text()


@pytest.mark.parametrize("b", [1, 8])
@pytest.mark.parametrize("k,n", SHAPES)
@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_bitplane_entry_compiles_for_v5e(one_chip, entry, k, n, b):
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    x = spec((b, k), jnp.int8)
    kw = dict(mode="folded", layout="bitpack8", logical_k=k)
    if entry.endswith("placed"):
        window_block, n_blocks = _placer_window_block(n)
        assert window_block % WINDOW_ALIGN == 0
        args = (x, spec((WB, k // 8, n_blocks * window_block), jnp.uint8),
                spec((n,), jnp.int32))
        kw["window_block"] = window_block
    else:
        args = (x, spec((WB, k // 8, n), jnp.uint8))
    assert "tpu_custom_call" in _compile(ENTRIES[entry], *args, **kw)


@pytest.mark.parametrize("g", [None, 2])
def test_calib_iter_fused_compiles_for_v5e(one_chip, g):
    """The fused calibration kernel at paper scale (65,536 columns), alone
    and with a subarray axis in its grid (how the fleet engine calls it)."""
    params = PhysicsParams()
    ladder = FleetConfig().ladder(params)
    level_v, level_sigma = sense_tables(ladder, params)
    s, m, c = 256, 5, 65536
    lead = () if g is None else (g,)
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        lead + shape, dtype, sharding=one_chip)
    args = (spec((s, m, c), jnp.float32), spec((s, c), jnp.float32),
            spec((c,), jnp.int32), spec((c,), jnp.float32))
    hlo = _compile(calib_iter_fused, *args, level_v=level_v,
                   level_sigma=level_sigma, threshold=0.0009)
    assert "tpu_custom_call" in hlo
