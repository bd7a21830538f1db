"""Attention: GQA (flash-style chunked), decode with KV cache, MLA (DeepSeek),
and cross-attention for the enc-dec architecture.

Training/prefill attention scans over KV chunks with a running
(max, denominator, accumulator) — the flash pattern in pure JAX — so the
[B, H, S, S] score matrix is never materialized (required at seq 32k).
Decode computes one query row against the cache directly.

Sharding: heads ("heads"/"kv_heads" -> model axis), batch -> data axis.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .layers import rmsnorm, rmsnorm_defs, rope
from .params import ParamDef
from .sharding_ctx import hint, padded_head_count


def head_proj(p, name: str, x, heads: int, hdim: int):
    """x [..., D] @ [D, H, Dh] -> [..., H, Dh], PUD-packed aware.

    ``pud.packer.pack_model`` (via ``PUDSession.pack``) with attention
    packing replaces ``<name>`` by a ``<name>_pud`` ``PackedTensor``
    holding bit-planes of the flattened [D, H*Dh] projection; the head
    split is restored by reshape.
    """
    packed = p.get(name + "_pud")
    if packed is not None:
        from repro.pud.gemv import pud_linear
        y = pud_linear(x, packed).astype(x.dtype)
        return y.reshape(y.shape[:-1] + (heads, hdim))
    return jnp.einsum("...d,dhk->...hk", x, p[name].astype(x.dtype))


def merge_proj(p, name: str, x):
    """x [..., H, Dh] @ [H, Dh, D] -> [..., D], PUD-packed aware."""
    packed = p.get(name + "_pud")
    if packed is not None:
        from repro.pud.gemv import pud_linear
        flat = x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))
        return pud_linear(flat, packed).astype(x.dtype)
    return jnp.einsum("...hk,hkd->...d", x, p[name].astype(x.dtype))


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    qk_norm: bool = False
    causal: bool = True
    kv_chunk: int = 1024


def gqa_defs(cfg: AttnConfig, dtype=jnp.bfloat16) -> dict:
    h, kv, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    defs = {
        "wq": ParamDef((d, h, hd), ("embed", "heads", "head_dim"),
                       dtype=dtype, init="scaled"),
        "wk": ParamDef((d, kv, hd), ("embed", "kv_heads", "head_dim"),
                       dtype=dtype, init="scaled"),
        "wv": ParamDef((d, kv, hd), ("embed", "kv_heads", "head_dim"),
                       dtype=dtype, init="scaled"),
        "wo": ParamDef((h, hd, d), ("heads", "head_dim", "embed"),
                       dtype=dtype, init="scaled"),
    }
    if cfg.qk_norm:
        defs["q_norm"] = rmsnorm_defs(hd)
        defs["k_norm"] = rmsnorm_defs(hd)
    return defs


def _flash(q, k, v, *, causal: bool, kv_chunk: int, q_offset: int = 0,
           bias=None):
    """Chunked softmax attention.

    q: [B, Sq, H, D]; k,v: [B, Skv, KV, D] with H = KV * G.
    Returns [B, Sq, H, D].  q_offset: absolute position of q[0] (causal).

    GQA grouping is realized by REPEATING kv to the full head count rather
    than reshaping q to [B, S, KV, G, D]: a grouped reshape splits the
    "heads"-sharded dim (e.g. 16-way model sharding into KV=8 x G=2), which
    GSPMD cannot partition and resolves by replicating the whole attention
    (measured: 6.1x model flops on qwen3/train_4k — EXPERIMENTS.md §Perf
    iteration 2).  The repeat is a broadcast: with kv replicated and heads
    sharded, each device materializes only its own heads' kv slice.
    """
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = d ** -0.5
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    q = hint(q, "batch", None, "heads_act", None)
    k = hint(k, "batch", None, "heads_act", None)
    v = hint(v, "batch", None, "heads_act", None)
    n_chunks = max(1, skv // kv_chunk)
    assert skv % n_chunks == 0
    kc = k.reshape(b, n_chunks, skv // n_chunks, h, d).swapaxes(0, 1)
    vc = v.reshape(b, n_chunks, skv // n_chunks, h, d).swapaxes(0, 1)

    def body(carry, xs):
        m, l, acc, idx = carry
        kb, vb = xs
        kb = hint(kb, "batch", None, "heads_act", None)
        s = jnp.einsum("bqhd,bphd->bhqp", q, kb,
                       preferred_element_type=jnp.float32) * scale
        s = hint(s, "batch", "heads_act", None, None)
        if causal:
            qpos = q_offset + jnp.arange(sq)
            kpos = idx * (skv // n_chunks) + jnp.arange(skv // n_chunks)
            s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhqp,bphd->bhqd", p.astype(vb.dtype), vb,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new, idx + 1), None

    m0 = hint(jnp.full((b, h, sq), -jnp.inf, jnp.float32),
              "batch", "heads_act", None)
    l0 = hint(jnp.zeros((b, h, sq), jnp.float32), "batch", "heads_act", None)
    a0 = hint(jnp.zeros((b, h, sq, d), jnp.float32),
              "batch", "heads_act", None, None)
    (m, l, acc, _), _ = jax.lax.scan(body, (m0, l0, a0, 0), (kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def gqa_attention(p, cfg: AttnConfig, x, positions, kv_override=None):
    """Full-sequence attention (train / prefill). x: [B, S, D].

    Returns (out [B,S,D], (k, v) for cache seeding).
    kv_override: (k, v) from an encoder for cross-attention (no causal).
    """
    q = head_proj(p, "wq", x, cfg.n_heads, cfg.head_dim)
    if kv_override is None:
        k = head_proj(p, "wk", x, cfg.n_kv_heads, cfg.head_dim)
        v = head_proj(p, "wv", x, cfg.n_kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = rmsnorm(p["q_norm"], q)
            k = rmsnorm(p["k_norm"], k)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        causal = cfg.causal
    else:
        k, v = kv_override
        if cfg.qk_norm:
            q = rmsnorm(p["q_norm"], q)
        causal = False
    # Activation-level head padding: archs whose head count does not divide
    # the TP degree (llama4: 40, whisper: 20 on a 16-way axis) would run the
    # flash loop replicated.  Expand kv to the full head count (the GQA
    # grouping, done eagerly), pad q/k/v with zero heads to the next
    # multiple, shard over "model", trim before wo — numerically exact.
    cache_kv = (k, v)
    h_true = q.shape[2]
    hp = padded_head_count(h_true)
    if hp != h_true:
        g = h_true // k.shape[2]
        if g > 1:
            k = jnp.repeat(k, g, axis=2)
            v = jnp.repeat(v, g, axis=2)
        pad = ((0, 0), (0, 0), (0, hp - h_true), (0, 0))
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    out = _flash(q, k, v, causal=causal,
                 kv_chunk=min(cfg.kv_chunk, k.shape[1]))
    if hp != h_true:
        out = out[:, :, :h_true]
    out = merge_proj(p, "wo", out)
    return out, cache_kv


def gqa_prefill_chunk(p, cfg: AttnConfig, x, cache_k, cache_v, start,
                      kv_len: int):
    """Chunked-prefill attention: resume a prompt into an existing KV cache.

    x: [B, C, D] — the chunk's hidden states for absolute positions
    [start, start+C); cache_k/v: [B, Smax, KV, Dh] holding every earlier
    chunk's keys/values at their absolute positions.  The chunk's k/v are
    written at ``start`` (traced scalar) and attention runs over the first
    ``kv_len`` cache rows (static: the prompt's pow2 bucket), with the
    causal mask anchored at ``q_offset=start``.  Row ``p`` of the output
    sees exactly the keys ``0..p`` a whole-bucket prefill would show it, so
    chunked prefill is bit-identical to whole prefill row by row (the
    masked-tail length is the same ``kv_len`` in both).

    Returns (out [B,C,D], new_cache_k, new_cache_v).
    """
    b, c, _ = x.shape
    positions = start + jnp.broadcast_to(jnp.arange(c), (b, c))
    q = head_proj(p, "wq", x, cfg.n_heads, cfg.head_dim)
    k_new = head_proj(p, "wk", x, cfg.n_kv_heads, cfg.head_dim)
    v_new = head_proj(p, "wv", x, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k_new = rmsnorm(p["k_norm"], k_new)
    q = rope(q, positions, cfg.rope_theta)
    k_new = rope(k_new, positions, cfg.rope_theta)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k_new.astype(cache_k.dtype), start, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v_new.astype(cache_v.dtype), start, axis=1)
    k = cache_k[:, :kv_len].astype(x.dtype)
    v = cache_v[:, :kv_len].astype(x.dtype)
    # Same activation-level head padding as gqa_attention (no-op without a
    # model-sharded mesh context); per-head rows are independent, so padded
    # heads never perturb real heads' values.
    h_true = q.shape[2]
    hp = padded_head_count(h_true)
    if hp != h_true:
        g = h_true // k.shape[2]
        if g > 1:
            k = jnp.repeat(k, g, axis=2)
            v = jnp.repeat(v, g, axis=2)
        pad = ((0, 0), (0, 0), (0, hp - h_true), (0, 0))
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    out = _flash(q, k, v, causal=True, kv_chunk=min(cfg.kv_chunk, kv_len),
                 q_offset=start)
    if hp != h_true:
        out = out[:, :, :h_true]
    out = merge_proj(p, "wo", out)
    return out, cache_k, cache_v


def encoder_kv(p, cfg: AttnConfig, memory):
    """Precompute cross-attention K/V from encoder output."""
    k = head_proj(p, "wk", memory, cfg.n_kv_heads, cfg.head_dim)
    v = head_proj(p, "wv", memory, cfg.n_kv_heads, cfg.head_dim)
    return k, v


def _decode_positions(x, cur_len):
    """[B, 1] rope positions of one decode token per row: ``cur_len`` is a
    scalar (every row at one position) or a [B] vector of per-slot
    lengths (continuous batching)."""
    lens = jnp.asarray(cur_len)
    if lens.ndim == 1:
        return lens[:, None]
    return jnp.full((x.shape[0], 1), lens)


def write_row(cache, new, cur_len, layer=None):
    """Write one decode token's row into a KV cache at ``cur_len``.

    new: [B, 1, ...].  cache: [B, Smax, ...], or with ``layer`` (a traced
    index) the layer-stacked [L, B, Smax, ...] cache, written in place at
    that layer.  A scalar ``cur_len`` writes every row at one position (a
    dynamic-update-slice); a [B] vector writes row ``b`` at ``cur_len[b]``
    (a scatter), and a row whose length is already ``Smax`` is dropped.
    """
    new = new.astype(cache.dtype)
    lead = () if layer is None else (layer,)
    lens = jnp.asarray(cur_len)
    if lens.ndim == 1:
        rows = jnp.arange(new.shape[0])
        return cache.at[lead + (rows, lens)].set(new[:, 0], mode="drop")
    start = lead + (0, lens) + (0,) * (new.ndim - 2)
    return jax.lax.dynamic_update_slice(cache, new[(None,) * len(lead)],
                                        start)


def _length_mask(s, valid_len):
    """Mask scores s [B, ..., Smax] to the first ``valid_len`` rows (a
    scalar, or [B] per row)."""
    smax = s.shape[-1]
    valid = jnp.reshape(valid_len, (-1,) + (1,) * (s.ndim - 1))
    return jnp.where(jnp.arange(smax) < valid, s, -jnp.inf)


def gqa_decode_qkv(p, cfg: AttnConfig, x, cur_len):
    """One decode token's projections. x: [B, 1, D].

    Returns (q [B,1,H,Dh], k_new [B,1,KV,Dh], v_new [B,1,KV,Dh]): q and k
    qk-normed (if the config says so) and rotated at ``cur_len``.
    """
    q = head_proj(p, "wq", x, cfg.n_heads, cfg.head_dim)
    k_new = head_proj(p, "wk", x, cfg.n_kv_heads, cfg.head_dim)
    v_new = head_proj(p, "wv", x, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k_new = rmsnorm(p["k_norm"], k_new)
    pos = _decode_positions(x, cur_len)
    q = rope(q, pos, cfg.rope_theta)
    k_new = rope(k_new, pos, cfg.rope_theta)
    return q, k_new, v_new


def gqa_decode_attend(p, q, cache_k, cache_v, valid_len):
    """One query row per batch row against a KV cache, then ``wo``.

    q: [B, 1, H, Dh]; cache_k/v: [B, Smax, KV, Dh]; rows at or past
    ``valid_len`` (scalar or [B]) are masked.  Returns out [B, 1, D].
    """
    b = q.shape[0]
    h, kvh, d = q.shape[2], cache_k.shape[2], q.shape[3]
    g = h // kvh
    qr = q.reshape(b, kvh, g, d)
    s = jnp.einsum("bkgd,bpkd->bkgp", qr, cache_k,
                   preferred_element_type=jnp.float32) * d ** -0.5
    w = jax.nn.softmax(_length_mask(s, valid_len), axis=-1)
    o = jnp.einsum("bkgp,bpkd->bkgd", w.astype(cache_v.dtype), cache_v)
    return merge_proj(p, "wo", o.reshape(b, 1, h, d))


def gqa_decode(p, cfg: AttnConfig, x, cache_k, cache_v, cur_len,
               cross: bool = False):
    """One-token decode over one layer's cache. x: [B, 1, D];
    cache_k/v: [B, Smax, KV, D].

    ``cur_len`` is the current cache fill: a scalar (all rows at the same
    position — the classic single-sequence/batched-prompt decode) or a [B]
    vector of per-slot lengths (continuous batching: every slot sits at its
    own position; the cache write becomes a per-row scatter and the causal
    mask goes per-row).  Rows are independent either way, so the vector
    path is bit-identical per row to the scalar path at that row's length.

    Returns (out [B,1,D], new_cache_k, new_cache_v).
    For cross-attention the cache holds encoder K/V and is not updated.
    """
    if cross:
        q = head_proj(p, "wq", x, cfg.n_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = rmsnorm(p["q_norm"], q)
        valid_len = cache_k.shape[1]
    else:
        q, k_new, v_new = gqa_decode_qkv(p, cfg, x, cur_len)
        cache_k = write_row(cache_k, k_new, cur_len)
        cache_v = write_row(cache_v, v_new, cur_len)
        valid_len = jnp.asarray(cur_len) + 1
    out = gqa_decode_attend(p, q, cache_k, cache_v, valid_len)
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# MLA (Multi-head Latent Attention, DeepSeek-V2)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    kv_chunk: int = 1024

    @property
    def qk_dim(self):
        return self.qk_nope_dim + self.qk_rope_dim


def mla_defs(cfg: MLAConfig, dtype=jnp.bfloat16) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    return {
        "wq": ParamDef((d, h, cfg.qk_dim), ("embed", "heads", None),
                       dtype=dtype, init="scaled"),
        "wdkv": ParamDef((d, cfg.kv_lora_rank), ("embed", None), dtype=dtype,
                         init="scaled"),
        "kv_norm": rmsnorm_defs(cfg.kv_lora_rank),
        "wkr": ParamDef((d, cfg.qk_rope_dim), ("embed", None), dtype=dtype,
                        init="scaled"),
        "wuk": ParamDef((cfg.kv_lora_rank, h, cfg.qk_nope_dim),
                        (None, "heads", None), dtype=dtype, init="scaled"),
        "wuv": ParamDef((cfg.kv_lora_rank, h, cfg.v_head_dim),
                        (None, "heads", None), dtype=dtype, init="scaled"),
        "wo": ParamDef((h, cfg.v_head_dim, d), ("heads", None, "embed"),
                       dtype=dtype, init="scaled"),
    }


def mla_attention(p, cfg: MLAConfig, x, positions):
    """Training/prefill MLA. Returns (out, (c_kv, k_rope)) for cache seeding."""
    q = head_proj(p, "wq", x, cfg.n_heads, cfg.qk_dim)
    q_nope, q_rope = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)

    c_kv = rmsnorm(p["kv_norm"], x @ p["wdkv"].astype(x.dtype))  # [B,S,R]
    k_rope = rope((x @ p["wkr"].astype(x.dtype))[:, :, None, :], positions,
                  cfg.rope_theta)                                # [B,S,1,dr]
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wuk"].astype(x.dtype))
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["wuv"].astype(x.dtype))

    h = cfg.n_heads
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_rope.shape[:2] + (h,) +
                                  k_rope.shape[3:])], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    # pad v to qk_dim so _flash can share the accumulator, then trim
    vpad = jnp.pad(v, ((0, 0), (0, 0), (0, 0),
                       (0, cfg.qk_dim - cfg.v_head_dim)))
    out = _flash(q_full, k, vpad, causal=True,
                 kv_chunk=min(cfg.kv_chunk, x.shape[1]))
    out = out[..., : cfg.v_head_dim]
    out = merge_proj(p, "wo", out)
    return out, (c_kv, k_rope[:, :, 0, :])


def mla_prefill_chunk(p, cfg: MLAConfig, x, cache_ckv, cache_kr, start,
                      kv_len: int):
    """Chunked-prefill MLA: resume a prompt into the latent KV cache.

    x: [B, C, D] for absolute positions [start, start+C); cache_ckv:
    [B, Smax, R]; cache_kr: [B, Smax, dr].  The chunk's latents land at
    ``start`` and k_nope/v are re-expanded from the cached latents over the
    first ``kv_len`` rows — the same up-projection a whole-bucket prefill
    applies, so the rows are bit-identical (see gqa_prefill_chunk).

    Returns (out [B,C,D], new_cache_ckv, new_cache_kr).
    """
    b, c, _ = x.shape
    positions = start + jnp.broadcast_to(jnp.arange(c), (b, c))
    q = head_proj(p, "wq", x, cfg.n_heads, cfg.qk_dim)
    q_nope, q_rope = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)

    c_new = rmsnorm(p["kv_norm"], x @ p["wdkv"].astype(x.dtype))  # [B,C,R]
    kr_new = rope((x @ p["wkr"].astype(x.dtype))[:, :, None, :], positions,
                  cfg.rope_theta)[:, :, 0, :]                     # [B,C,dr]
    cache_ckv = jax.lax.dynamic_update_slice_in_dim(
        cache_ckv, c_new.astype(cache_ckv.dtype), start, axis=1)
    cache_kr = jax.lax.dynamic_update_slice_in_dim(
        cache_kr, kr_new.astype(cache_kr.dtype), start, axis=1)

    ckv = cache_ckv[:, :kv_len].astype(x.dtype)
    kr = cache_kr[:, :kv_len].astype(x.dtype)
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["wuk"].astype(x.dtype))
    v = jnp.einsum("bsr,rhk->bshk", ckv, p["wuv"].astype(x.dtype))
    h = cfg.n_heads
    kr_b = kr[:, :, None, :]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_b, kr_b.shape[:2] + (h,) +
                                  kr_b.shape[3:])], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    vpad = jnp.pad(v, ((0, 0), (0, 0), (0, 0),
                       (0, cfg.qk_dim - cfg.v_head_dim)))
    out = _flash(q_full, k, vpad, causal=True,
                 kv_chunk=min(cfg.kv_chunk, kv_len), q_offset=start)
    out = out[..., : cfg.v_head_dim]
    out = merge_proj(p, "wo", out)
    return out, cache_ckv, cache_kr


def mla_decode_qkv(p, cfg: MLAConfig, x, cur_len):
    """One decode token's MLA projections. x: [B, 1, D].

    Returns (q_nope [B,H,dn], q_rope [B,H,dr] rotated at ``cur_len``,
    c_new [B,1,R] the normed latent, kr_new [B,1,dr] the rotated rope key).
    """
    q = head_proj(p, "wq", x, cfg.n_heads, cfg.qk_dim)[:, 0]
    q_nope, q_rope = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    pos = _decode_positions(x, cur_len)
    q_rope = rope(q_rope[:, None], pos, cfg.rope_theta)[:, 0]
    c_new = rmsnorm(p["kv_norm"], x @ p["wdkv"].astype(x.dtype))  # [B,1,R]
    kr_new = rope((x @ p["wkr"].astype(x.dtype))[:, :, None, :], pos,
                  cfg.rope_theta)[:, :, 0, :]
    return q_nope, q_rope, c_new, kr_new


def mla_decode_attend(p, cfg: MLAConfig, q_nope, q_rope, cache_ckv,
                      cache_kr, valid_len):
    """Absorbed-matmul MLA attention over one layer's latent cache, then
    ``wo``: the per-step cost is O(S * (R + dr)) instead of
    O(S * H * head_dim).

    cache_ckv: [B, Smax, R]; cache_kr: [B, Smax, dr]; rows at or past
    ``valid_len`` (scalar or [B]) are masked.  Returns out [B, 1, D].
    """
    # absorb W_uk into the query: scores in latent space.  bf16 inputs with
    # f32 accumulation (preferred_element_type) — an .astype(f32) on the
    # score made XLA hoist an f32 convert of the ENTIRE stacked cache out of
    # the layer loop (1.3 GB/step materialization on deepseek-v2-lite
    # decode_32k; EXPERIMENTS.md §Perf iteration 7).
    cache_ckv = hint(cache_ckv, "batch", "kv_seq", None)
    cache_kr = hint(cache_kr, "batch", "kv_seq", None)
    dtype = q_nope.dtype
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope, p["wuk"].astype(dtype))
    s = (jnp.einsum("bhr,bpr->bhp", q_lat, cache_ckv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhk,bpk->bhp", q_rope, cache_kr,
                      preferred_element_type=jnp.float32))
    s = hint(s, "batch", None, "kv_seq")
    s = s * (cfg.qk_dim ** -0.5)
    w = jax.nn.softmax(_length_mask(s, valid_len), axis=-1)
    o_lat = jnp.einsum("bhp,bpr->bhr", w.astype(cache_ckv.dtype), cache_ckv)
    o = jnp.einsum("bhr,rhk->bhk", o_lat, p["wuv"].astype(dtype))
    return merge_proj(p, "wo", o)[:, None]

