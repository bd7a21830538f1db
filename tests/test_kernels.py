"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.analysis.errors import ContractViolation
from repro.kernels import ref
from repro.kernels.bitplane_gemv import bitplane_gemv
from repro.kernels.majx import majx_sense
from repro.kernels.ops import pud_gemv, pud_gemv_ref
from repro.pud.physics import PhysicsParams


# ---------------------------------------------------------------------------
# majx kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,r,c", [(8, 8, 1024), (16, 8, 2048), (8, 4, 1024),
                                   (32, 8, 1024)])
@pytest.mark.parametrize("n_fracs", [0, 3])
def test_majx_matches_ref(t, r, c, n_fracs):
    key = jax.random.key(42)
    k1, k2, k3 = jax.random.split(key, 3)
    charge = jax.random.uniform(k1, (t, r, c), jnp.float32)
    offs = 0.03 * jax.random.normal(k2, (c,), jnp.float32)
    noise = jax.random.normal(k3, (t, c), jnp.float32)
    params = PhysicsParams()
    got = majx_sense(charge, offs, noise, params, n_fracs, interpret=True)
    want = ref.majx_sense_ref(charge, offs, noise, params, n_fracs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_majx_majority_semantics():
    """With zero offsets/noise, SiMRA of k full + (8-k) neutral rows is a
    majority vote over the 5 'data' positions."""
    c = 1024
    params = PhysicsParams(sigma_dynamic=0.0, sigma_frac=0.0,
                           sigma_transfer=0.0)
    rows = []
    for k in range(6):
        data = [1.0] * k + [0.0] * (5 - k)
        rows.append(data + [0.5] * 3)
    charge = jnp.tile(jnp.array(rows, jnp.float32)[:, :, None], (1, 1, c))
    charge = jnp.concatenate([charge] * 2, axis=0)[:8]  # pad trials to block
    out = majx_sense(charge, jnp.zeros((c,)), jnp.zeros((8, c)), params, 0)
    expect = jnp.array([0, 0, 0, 1, 1, 1, 0, 0], jnp.float32)  # k>=3 -> 1
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(expect[:8]))


# ---------------------------------------------------------------------------
# bitplane gemv kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,k,n", [(1, 256, 256), (4, 512, 256),
                                   (8, 256, 512), (2, 1024, 1024)])
@pytest.mark.parametrize("wb", [2, 4, 8])
@pytest.mark.parametrize("mode", ["planes", "folded"])
def test_bitplane_gemv_matches_ref(b, k, n, wb, mode):
    key = jax.random.key(0)
    k1, k2 = jax.random.split(key)
    x = jax.random.randint(k1, (b, k), -127, 128, jnp.int32).astype(jnp.int8)
    w = jax.random.randint(k2, (k, n), -(1 << (wb - 1)), 1 << (wb - 1),
                           jnp.int32)
    planes = ref.pack_bitplanes(w, wb)
    if mode == "folded" and wb > 7:
        # A folded 8-bit weight does not fit the int8 MXU operand.
        with pytest.raises(ContractViolation, match="folded-weight-bits"):
            bitplane_gemv(x, planes, mode=mode, interpret=True)
        mode = "planes"
    got = bitplane_gemv(x, planes, mode=mode, interpret=True)
    want = ref.bitplane_gemv_ref(x, planes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the oracle itself must equal the plain integer matmul
    direct = x.astype(jnp.int32) @ w
    np.testing.assert_array_equal(np.asarray(want), np.asarray(direct))


def test_modes_bit_identical():
    key = jax.random.key(7)
    x = jax.random.randint(key, (4, 512), -127, 128, jnp.int32).astype(jnp.int8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (512, 256), -8, 8,
                           jnp.int32)
    planes = ref.pack_bitplanes(w, 4)
    a = bitplane_gemv(x, planes, mode="planes", interpret=True)
    b = bitplane_gemv(x, planes, mode="folded", interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pud_gemv_dequant_close_to_float():
    key = jax.random.key(3)
    x = jax.random.normal(key, (2, 512), jnp.float32)
    w = jax.random.randint(jax.random.fold_in(key, 1), (512, 256), -8, 8,
                           jnp.int32)
    planes = ref.pack_bitplanes(w, 4)
    got = pud_gemv(x, planes, w_scale=jnp.float32(1.0))
    want = pud_gemv_ref(x, planes, w_scale=jnp.float32(1.0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    # dequantized result approximates the float matmul
    exact = x @ w.astype(jnp.float32)
    err = np.abs(np.asarray(got) - np.asarray(exact))
    assert err.mean() < 0.05 * np.abs(np.asarray(exact)).mean()


# ---------------------------------------------------------------------------
# property tests
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(wb=st.integers(2, 8), seed=st.integers(0, 2**31 - 1))
def test_pack_bitplanes_roundtrip(wb, seed):
    key = jax.random.key(seed)
    w = jax.random.randint(key, (32, 16), -(1 << (wb - 1)), 1 << (wb - 1),
                           jnp.int32)
    planes = ref.pack_bitplanes(w, wb)
    assert set(np.unique(np.asarray(planes))) <= {0, 1}
    rebuilt = sum((planes[b].astype(jnp.int32) << b) for b in range(wb))
    rebuilt = rebuilt - (1 << (wb - 1))
    np.testing.assert_array_equal(np.asarray(rebuilt), np.asarray(w))
