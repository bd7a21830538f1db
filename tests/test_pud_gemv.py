"""PUD GeMV serving path: packing, kernel numerics, model integration,
performance model coupling (Eq. 1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.analysis.errors import ContractViolation
from repro.kernels import ref
from repro.kernels.ops import bitplane_gemv, quantize_activations
from repro.pud.gemv import (PUDGemvConfig, PUDPerfModel, pack_linear,
                            pud_linear, pud_linear_ref)
from repro.pud.packer import pack_for_serving, packed_bytes


# ---------------------------------------------------------------------------
# Bit-plane packing + kernel numerics (shape/dtype sweeps vs ref oracle)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,k,n,wb", [
    (1, 64, 128, 2), (4, 256, 256, 4), (8, 512, 256, 4), (2, 128, 512, 8),
    (3, 64, 64, 3),
])
@pytest.mark.parametrize("mode", ["planes", "folded"])
def test_bitplane_gemv_matches_ref(b, k, n, wb, mode):
    kx, kw = jax.random.split(jax.random.key(b * 1000 + k + n + wb))
    x = jax.random.randint(kx, (b, k), -127, 128, jnp.int32).astype(jnp.int8)
    w = jax.random.randint(kw, (k, n), -(1 << (wb - 1)), 1 << (wb - 1),
                           jnp.int32)
    planes = ref.pack_bitplanes(w, wb)
    want = ref.bitplane_gemv_ref(x, planes)
    if mode == "folded" and wb > 7:
        # A folded 8-bit weight does not fit the int8 MXU operand.
        with pytest.raises(ContractViolation, match="folded-weight-bits"):
            bitplane_gemv(x, planes, mode=mode)
        got = bitplane_gemv(x, planes, mode="planes")
    else:
        got = bitplane_gemv(x, planes, mode=mode)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and the oracle equals the plain integer matmul
    direct = x.astype(jnp.int32) @ w
    np.testing.assert_array_equal(np.asarray(want), np.asarray(direct))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), wb=st.integers(2, 8))
def test_pack_bitplanes_roundtrip(seed, wb):
    w = jax.random.randint(jax.random.key(seed), (32, 64),
                           -(1 << (wb - 1)), 1 << (wb - 1), jnp.int32)
    planes = ref.pack_bitplanes(w, wb)
    assert planes.shape == (wb, 32, 64)
    assert set(np.unique(np.asarray(planes))) <= {0, 1}
    recon = sum((planes[b].astype(np.int32) << b) for b in range(wb)) \
        - (1 << (wb - 1))
    np.testing.assert_array_equal(np.asarray(recon), np.asarray(w))


def test_pud_gemv_dequant_close_to_float():
    """Float-in/float-out wrapper: error bounded by int8 x int4 quantization."""
    kx, kw = jax.random.split(jax.random.key(0))
    x = jax.random.normal(kx, (4, 256), jnp.float32)
    w = 0.05 * jax.random.normal(kw, (256, 128), jnp.float32)
    packed = pack_linear(w, 4)
    y = pud_linear(x, packed)
    y_ref = pud_linear_ref(x, w, 4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    # against the exact float matmul: bounded relative error
    exact = x @ w
    rel = float(jnp.abs(y - exact).mean() / jnp.abs(exact).mean())
    assert rel < 0.2, rel


def test_quantize_activations_bounds():
    x = jax.random.normal(jax.random.key(1), (8, 64)) * 5
    q, scale = quantize_activations(x)
    assert q.dtype == jnp.int8
    np.testing.assert_allclose(
        np.asarray(q.astype(jnp.float32) * scale), np.asarray(x),
        atol=float(scale.max()) * 0.51)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16, jnp.float32])
def test_round_to_dtype_matches_a_cast(dtype):
    """The explicit rounding the PUD path puts on its input and output
    equals a round trip through ``dtype``, and leaves float32 alone."""
    from repro.kernels.ops import round_to_dtype
    x = jax.random.normal(jax.random.key(2), (4, 256), jnp.float32) * 3
    got = round_to_dtype(x, dtype)
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(x.astype(dtype).astype(jnp.float32)))


def test_pud_linear_output_is_at_input_precision():
    """A bf16 input gives a float32 output already at bf16 precision, so
    the model's cast back to bf16 changes nothing."""
    x = jax.random.normal(jax.random.key(3), (3, 64), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(4), (64, 96), jnp.float32) * 0.1
    y = pud_linear(x, pack_linear(w, 4))
    assert y.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(y.astype(jnp.bfloat16).astype(jnp.float32)))


# ---------------------------------------------------------------------------
# Model integration: pack_for_serving + layers.linear dispatch
# ---------------------------------------------------------------------------

def test_pack_for_serving_swaps_ffn_and_unembed():
    from repro.configs import get
    from repro.models.params import init_params
    model = get("granite-8b").make_smoke()
    params = init_params(model.param_defs(), jax.random.key(0))
    packed, report = pack_for_serving(params, PUDGemvConfig(weight_bits=4))
    assert "unembed/w" in report["packed"]
    assert any("mixer" in p for p in report["packed"])
    layer_key = next(k for k in packed if k.startswith("layers_"))
    assert "wi_pud" in packed[layer_key]["mixer"]
    assert "wi" not in packed[layer_key]["mixer"]
    sizes = packed_bytes(packed)
    assert sizes["pud_bytes"] > 0

    # decode through the packed path stays close to the bf16 path
    toks = jax.random.randint(jax.random.key(2), (2, 8), 0,
                              model.cfg.vocab, jnp.int32)
    logits_ref, cache_ref = model.prefill(params, toks, max_len=12)
    logits_pud, cache_pud = model.prefill(packed, toks, max_len=12)
    assert logits_pud.shape == logits_ref.shape
    assert not bool(jnp.isnan(logits_pud).any())
    # greedy tokens mostly agree (4-bit quantization of random weights)
    agree = float((jnp.argmax(logits_pud, -1)
                   == jnp.argmax(logits_ref, -1)).mean())
    assert agree >= 0.5, agree

    nxt = jnp.argmax(logits_pud, -1).astype(jnp.int32)[:, None]
    step_logits, _ = model.decode_step(packed, cache_pud, nxt, jnp.int32(8))
    assert step_logits.shape == (2, model.cfg.vocab)
    assert not bool(jnp.isnan(step_logits).any())


def test_moe_experts_left_unpacked():
    from repro.configs import get
    from repro.models.params import init_params
    model = get("deepseek-v2-lite-16b").make_smoke()
    params = init_params(model.param_defs(), jax.random.key(0))
    packed, report = pack_for_serving(params)
    moe_key = next(k for k in packed if k.endswith("_moe"))
    # routed expert banks keep the bf16 path (documented scope)
    assert "wi" in packed[moe_key]["mixer"]
    assert any("mixer/shared" in p or "mixer" in p for p in report["packed"])


# ---------------------------------------------------------------------------
# Performance model (Eq. 1 coupling)
# ---------------------------------------------------------------------------

def test_perf_model_scales_with_error_free_fraction():
    base = PUDPerfModel(error_free_frac=0.534)
    tune = PUDPerfModel(error_free_frac=0.967)
    assert tune.speedup_vs(base) == pytest.approx(0.967 / 0.534)
    assert tune.gemv_latency_s(4096, 4096) > 0
    # tokens/s inversely proportional to model size
    assert (tune.tokens_per_second(2 * 1e9)
            == pytest.approx(10 * tune.tokens_per_second(2 * 1e10)))
