"""The decode step's stacked KV cache: carried through the layer scan and
written one row per slot in place.

``TransformerLM.decode_step`` is checked bit for bit against the
formulation that passes each layer's cache slice through the scan's
``xs`` and restacks it from ``ys`` (kept here as the reference), and the
compiled, cache-donating ``ServingEngine._step`` is checked to update the
cache in place: no copy of a whole cache leaf, and every cache leaf
aliased from input to output.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ServingEngine
from repro.configs import get
from repro.models import attention as attn_mod
from repro.models.layers import rmsnorm
from repro.models.params import init_params
from repro.models.transformer import LMConfig, TransformerLM

MAX_LEN = 12
PROMPT = 8
BATCH = 3
STEPS = 3


def _xs_ys_decode_step(model, params, cache, tokens, cur_len):
    """Reference: each layer's cache slice in through ``xs``, the updated
    slice out through ``ys``, with the per-layer decode attention."""
    cfg = model.cfg
    h = model._embed_tokens(params, tokens)
    new_cache = {}
    for gi, (kind, _) in enumerate(cfg.groups()):
        name = f"layers_{gi}_{kind}"

        def body(h, xs, kind=kind):
            lp, lcache = xs
            with jax.named_scope("attention"):
                hn = rmsnorm(lp["ln1"], h,
                             zero_centered=cfg.zero_centered_norm)
                if cfg.attention == "mla":
                    q_nope, q_rope, c_new, kr_new = attn_mod.mla_decode_qkv(
                        lp["attn"], cfg.mla_config(), hn, cur_len)
                    upd = {"ckv": attn_mod.write_row(lcache["ckv"], c_new,
                                                     cur_len),
                           "kr": attn_mod.write_row(lcache["kr"], kr_new,
                                                    cur_len)}
                    a = attn_mod.mla_decode_attend(
                        lp["attn"], cfg.mla_config(), q_nope, q_rope,
                        upd["ckv"], upd["kr"], jnp.asarray(cur_len) + 1)
                else:
                    a, k, v = attn_mod.gqa_decode(
                        lp["attn"], cfg.attn_config(), hn, lcache["k"],
                        lcache["v"], cur_len)
                    upd = {"k": k, "v": v}
                h = h + a
            h, _ = model._mix_residual(kind, lp, h)
            return h, upd

        h, new_cache[name] = jax.lax.scan(body, h,
                                          (params[name], cache[name]))
    return model._unembed_last(params, h), new_cache


def _gqa_moe():
    return TransformerLM(LMConfig(
        name="gqa-moe", n_layers=3, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab=256, head_dim=16, qk_norm=True, n_experts=4,
        top_k=2, moe_d_ff=32, n_shared_experts=1, first_dense_layers=1,
        loss_chunk=32))


MODELS = {
    "gqa-qknorm": lambda: get("qwen3-1.7b").make_smoke(),
    "mla-dense-moe": lambda: get("deepseek-v2-lite-16b").make_smoke(),
    "gqa-qknorm-dense-moe": _gqa_moe,
}


@pytest.fixture(scope="module", params=sorted(MODELS))
def prefilled(request):
    model = MODELS[request.param]()
    params = init_params(model.param_defs(), jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (BATCH, PROMPT), 0,
                              model.cfg.vocab, jnp.int32)
    logits, cache = model.prefill(params, toks, max_len=MAX_LEN)
    # distinct rows past the prompt, so a misplaced write shows
    cache = jax.tree.map(
        lambda c: c + jax.random.normal(jax.random.key(2), c.shape,
                                        c.dtype), cache)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    return model, params, cache, nxt


def test_moe_models_have_two_layer_groups(prefilled):
    groups = [kind for kind, _ in prefilled[0].cfg.groups()]
    assert groups == (["dense", "moe"] if prefilled[0].cfg.is_moe
                      else ["dense"])


@pytest.mark.parametrize("lens", ["scalar", "per_slot"])
def test_carried_cache_matches_xs_ys(prefilled, lens):
    """Logits and every cache leaf, bit for bit, over several steps; the
    per-slot lengths put one slot at max_len - 1 and one at max_len, whose
    write is dropped."""
    model, params, cache, nxt = prefilled
    if lens == "scalar":
        cur = jnp.int32(PROMPT)
    else:
        cur = jnp.array([PROMPT, MAX_LEN - 1, MAX_LEN], jnp.int32)
    new = jax.jit(model.decode_step)
    ref = jax.jit(lambda p, c, t, n: _xs_ys_decode_step(model, p, c, t, n))
    c_new = c_ref = cache
    for _ in range(STEPS):
        l_new, c_new = new(params, c_new, nxt, cur)
        l_ref, c_ref = ref(params, c_ref, nxt, cur)
        np.testing.assert_array_equal(np.asarray(l_new), np.asarray(l_ref))
        assert jax.tree.structure(c_new) == jax.tree.structure(c_ref)
        for a, b in zip(jax.tree.leaves(c_new), jax.tree.leaves(c_ref)):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        nxt = jnp.argmax(l_new, axis=-1).astype(jnp.int32)[:, None]
        cur = jnp.minimum(cur + 1, MAX_LEN)
    if lens == "per_slot":
        for a, b in zip(jax.tree.leaves(c_new), jax.tree.leaves(cache)):
            np.testing.assert_array_equal(np.asarray(a[:, 2]),
                                          np.asarray(b[:, 2]))


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "deepseek-v2-lite-16b"])
def test_engine_step_updates_the_donated_cache_in_place(arch):
    """The compiled decode step neither copies a whole cache leaf nor
    leaves a donated cache leaf unaliased."""
    model = get(arch).make_smoke()
    params = init_params(model.param_defs(), jax.random.key(0))
    b, max_len = 4, 32
    eng = ServingEngine(model, params, max_len=max_len, batch_size=b)
    cache = init_params(model.cache_defs(b, max_len), jax.random.key(1))
    text = eng._step.lower(
        params, cache, jnp.zeros((b, 1), jnp.int32),
        jnp.zeros((b,), jnp.int32)).compile().as_text()
    for leaf in jax.tree.leaves(cache):
        dims = ",".join(map(str, leaf.shape))
        whole_copy = re.compile(
            rf"= \w+\[{dims}\]\S* copy(-start)?\(")
        assert not [ln for ln in text.splitlines() if whole_copy.search(ln)]
    header = next(ln for ln in text.splitlines() if ln.startswith("HloModule"))
    aliased = {int(i) for i in re.findall(r"\((\d+), \{\}, \w+-alias\)",
                                           header)}
    n_params = len(jax.tree.leaves(params))
    n_cache = len(jax.tree.leaves(cache))
    assert set(range(n_params, n_params + n_cache)) <= aliased
