"""Decoder-only transformer LM covering the dense / MoE / MLA families.

One config class (`LMConfig`) describes all six dense archs, the two MoE
archs (incl. DeepSeek-MLA) and the text backbones of the VLM.  Layers are
grouped into homogeneous runs (e.g. DeepSeek-V2-Lite = 1 dense + 26 MoE
layers) and each run is a `lax.scan` over stacked parameters with
`jax.checkpoint` on the body — compile time and activation memory stay
bounded at 95-layer scale.

Entry points (the Model protocol used by launch/ and configs/):
  * train_loss(params, batch, rng)      -> (loss, metrics)
  * prefill(params, tokens)             -> (last-position logits, cache)
  * decode_step(params, cache, token, cur_len) -> (logits, cache)
plus param_defs() / cache_defs() metadata for init, sharding and the dry-run.

Compiled programs carry ``jax.named_scope`` regions, so a profiler trace
can attribute device time by op name: ``embed``, ``attention`` (ln1, the
attention call and its residual add), ``ffn`` (ln2, the mixer and its
residual add) and ``unembed`` (final norm and logits).  What lies outside
them is the layer scan's own plumbing and whatever the caller adds; the
decode step's KV row write and read sit inside ``attention``.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from .attention import AttnConfig, MLAConfig
from .layers import (chunked_softmax_xent, embed, embed_defs, ffn, ffn_defs,
                     logits_last, rmsnorm, rmsnorm_defs, unembed_defs)
from .moe import MoEConfig, moe_defs, moe_ffn
from .params import ParamDef, stack_defs


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None
    activation: str = "silu"
    gated_ffn: bool = True
    qk_norm: bool = False
    rope_theta: float = 10000.0
    embed_scale: bool = False            # gemma-style sqrt(d) embed scaling
    zero_centered_norm: bool = False     # gemma-style (1 + scale) RMSNorm
    # attention family
    attention: str = "gqa"               # "gqa" | "mla"
    mla_kv_rank: int = 512
    mla_qk_nope: int = 128
    mla_qk_rope: int = 64
    mla_v_dim: int = 128
    # MoE (None -> dense)
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    first_dense_layers: int = 0
    # execution
    dtype: Any = jnp.bfloat16
    remat: bool = True
    loss_chunk: int = 512
    kv_chunk: int = 1024
    aux_loss_weight: float = 0.01

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    def attn_config(self) -> AttnConfig:
        return AttnConfig(self.d_model, self.n_heads, self.n_kv_heads,
                          self.hd, self.rope_theta, self.qk_norm,
                          kv_chunk=self.kv_chunk)

    def mla_config(self) -> MLAConfig:
        return MLAConfig(self.d_model, self.n_heads, self.mla_kv_rank,
                         self.mla_qk_nope, self.mla_qk_rope, self.mla_v_dim,
                         self.rope_theta, kv_chunk=self.kv_chunk)

    def moe_config(self) -> MoEConfig:
        return MoEConfig(self.d_model, self.n_experts, self.top_k,
                         self.moe_d_ff or self.d_ff,
                         self.n_shared_experts,
                         self.n_shared_experts * (self.moe_d_ff or self.d_ff),
                         activation=self.activation)

    def groups(self) -> list[tuple[str, int]]:
        """Homogeneous layer runs: [(kind, count)]."""
        if not self.is_moe:
            return [("dense", self.n_layers)]
        out = []
        if self.first_dense_layers:
            out.append(("dense", self.first_dense_layers))
        out.append(("moe", self.n_layers - self.first_dense_layers))
        return out


class TransformerLM:
    def __init__(self, cfg: LMConfig):
        self.cfg = cfg

    # -- parameter / cache metadata -----------------------------------------

    def _layer_defs(self, kind: str) -> dict:
        cfg = self.cfg
        if cfg.attention == "mla":
            attn = attn_mod.mla_defs(cfg.mla_config(), cfg.dtype)
        else:
            attn = attn_mod.gqa_defs(cfg.attn_config(), cfg.dtype)
        if kind == "moe":
            mixer = moe_defs(cfg.moe_config(), cfg.dtype)
        else:
            mixer = ffn_defs(cfg.d_model, cfg.d_ff, cfg.gated_ffn, cfg.dtype)
        return {
            "ln1": rmsnorm_defs(cfg.d_model),
            "attn": attn,
            "ln2": rmsnorm_defs(cfg.d_model),
            "mixer": mixer,
        }

    def param_defs(self):
        cfg = self.cfg
        defs = {
            "embed": embed_defs(cfg.vocab, cfg.d_model, cfg.dtype),
            "final_norm": rmsnorm_defs(cfg.d_model),
            "unembed": unembed_defs(cfg.d_model, cfg.vocab, cfg.dtype),
        }
        for gi, (kind, count) in enumerate(cfg.groups()):
            defs[f"layers_{gi}_{kind}"] = stack_defs(
                self._layer_defs(kind), count)
        return defs

    def cache_defs(self, batch: int, max_len: int):
        """ParamDef pytree for the decode cache (dry-run + serving init)."""
        cfg = self.cfg
        caches = {}
        for gi, (kind, count) in enumerate(cfg.groups()):
            if cfg.attention == "mla":
                caches[f"layers_{gi}_{kind}"] = {
                    "ckv": ParamDef((count, batch, max_len, cfg.mla_kv_rank),
                                    ("stack", "batch", "kv_seq", None),
                                    dtype=cfg.dtype, init="zeros"),
                    "kr": ParamDef((count, batch, max_len, cfg.mla_qk_rope),
                                   ("stack", "batch", "kv_seq", None),
                                   dtype=cfg.dtype, init="zeros"),
                }
            else:
                kv_shape = (count, batch, max_len, cfg.n_kv_heads, cfg.hd)
                axes = ("stack", "batch", "kv_seq", "kv_heads", "head_dim")
                caches[f"layers_{gi}_{kind}"] = {
                    "k": ParamDef(kv_shape, axes, dtype=cfg.dtype,
                                  init="zeros"),
                    "v": ParamDef(kv_shape, axes, dtype=cfg.dtype,
                                  init="zeros"),
                }
        return caches

    # -- forward -------------------------------------------------------------

    def _mix(self, kind, p, h_norm):
        cfg = self.cfg
        if kind == "moe":
            return moe_ffn(p, cfg.moe_config(), h_norm)
        return ffn(p, h_norm, cfg.activation), 0.0

    def _mix_residual(self, kind, p, h):
        """h + mixer(ln2(h)), in the ``ffn`` scope; returns (h, aux)."""
        with jax.named_scope("ffn"):
            hn = rmsnorm(p["ln2"], h,
                         zero_centered=self.cfg.zero_centered_norm)
            f, aux = self._mix(kind, p["mixer"], hn)
            return h + f, aux

    def _layer_full(self, kind, p, h, positions):
        cfg = self.cfg
        with jax.named_scope("attention"):
            hn = rmsnorm(p["ln1"], h, zero_centered=cfg.zero_centered_norm)
            if cfg.attention == "mla":
                a, kv = attn_mod.mla_attention(p["attn"], cfg.mla_config(),
                                               hn, positions)
            else:
                a, kv = attn_mod.gqa_attention(p["attn"], cfg.attn_config(),
                                               hn, positions)
            h = h + a
        h, aux = self._mix_residual(kind, p, h)
        return h, kv, aux

    def _embed_tokens(self, params, tokens):
        cfg = self.cfg
        with jax.named_scope("embed"):
            h = embed(params["embed"], tokens).astype(cfg.dtype)
            if cfg.embed_scale:
                h = h * jnp.asarray(cfg.d_model ** 0.5, cfg.dtype)
            return h

    def _unembed_last(self, params, h, last_idx=None):
        """Logits [B, V] of row ``last_idx`` (default: the last) of the
        final-normed ``h``, in the ``unembed`` scope."""
        with jax.named_scope("unembed"):
            h = rmsnorm(params["final_norm"], h,
                        zero_centered=self.cfg.zero_centered_norm)
            if last_idx is None:
                h_last = h[:, -1]
            else:
                h_last = jax.lax.dynamic_slice_in_dim(
                    h, last_idx, 1, axis=1)[:, 0]
            return logits_last(params["unembed"], h_last)

    def _backbone(self, params, h, positions, collect_cache=False):
        """Run all layer groups. Returns (h, caches, aux_total)."""
        cfg = self.cfg
        caches, aux_total = {}, 0.0
        for gi, (kind, count) in enumerate(cfg.groups()):
            name = f"layers_{gi}_{kind}"

            def body(carry, lp, kind=kind):
                h, aux = carry
                h, kv, aux_l = self._layer_full(kind, lp, h, positions)
                ys = kv if collect_cache else None
                return (h, aux + aux_l), ys

            scan_body = jax.checkpoint(body) if cfg.remat else body
            (h, aux_total), ys = jax.lax.scan(
                scan_body, (h, aux_total), params[name])
            if collect_cache:
                caches[name] = ys
        return h, caches, aux_total

    def apply_backbone(self, params, h, positions):
        """Expose hidden-state pipeline for wrappers (VLM)."""
        h, _, aux = self._backbone(params, h, positions)
        h = rmsnorm(params["final_norm"], h,
                    zero_centered=self.cfg.zero_centered_norm)
        return h, aux

    def train_loss(self, params, batch, rng=None):
        """batch: {tokens [B,S], labels [B,S], (mask [B,S])}."""
        cfg = self.cfg
        tokens = batch["tokens"]
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]),
                                     tokens.shape)
        h = self._embed_tokens(params, tokens)
        h, _, aux = self._backbone(params, h, positions)
        h = rmsnorm(params["final_norm"], h,
                    zero_centered=cfg.zero_centered_norm)
        loss, _ = chunked_softmax_xent(
            params["unembed"], h, batch["labels"], batch.get("mask"),
            chunk=min(cfg.loss_chunk, tokens.shape[1]))
        metrics = {"xent": loss, "aux": aux}
        return loss + cfg.aux_loss_weight * aux, metrics

    @property
    def supports_chunked_prefill(self) -> bool:
        """Whether bucketed / chunked prefill is bit-exact for this config.

        MoE routing is sequence-global (expert capacity is a function of
        the sequence length and top-C token selection competes across all
        positions), so padded or chunked prefill changes MoE outputs — MoE
        models keep the exact-length whole-prompt path.
        """
        return not self.cfg.is_moe

    def prefill(self, params, tokens, max_len: int | None = None,
                last_idx=None):
        """Process a full prompt; returns (last logits [B,V], cache).

        ``last_idx``: optional (traced) index of the row to read logits
        from — the true last prompt position when ``tokens`` is zero-padded
        to a length bucket.  Defaults to the final row, matching the
        unpadded behaviour.
        """
        cfg = self.cfg
        b, s = tokens.shape
        max_len = max_len or s
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        h = self._embed_tokens(params, tokens)
        h, raw, _ = self._backbone(params, h, positions, collect_cache=True)
        cache = {}
        for name, kv in raw.items():
            if cfg.attention == "mla":
                ckv, kr = kv
                pad = ((0, 0), (0, 0), (0, max_len - s), (0, 0))
                cache[name] = {"ckv": jnp.pad(ckv, pad),
                               "kr": jnp.pad(kr, pad)}
            else:
                k, v = kv
                pad = ((0, 0), (0, 0), (0, max_len - s), (0, 0), (0, 0))
                cache[name] = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
        return self._unembed_last(params, h, last_idx), cache

    def prefill_chunk(self, params, tokens, cache, start, *, kv_len: int,
                      last_idx=None):
        """Resume a prompt into an existing KV cache: one prefill chunk.

        tokens: [B, C] — prompt positions [start, start+C); ``cache`` is a
        full decode-cache pytree (``cache_defs`` layout, leaves
        [L, B, Smax, ...]) holding earlier chunks at their absolute
        positions.  ``start`` is traced (one jit variant per (C, kv_len),
        not per offset); ``kv_len`` is static — attention reads the first
        ``kv_len`` cache rows, the prompt's pow2 length bucket, so every
        row is bit-identical to a whole-bucket prefill (see
        attention.gqa_prefill_chunk).  ``last_idx``: chunk-local index of
        the final prompt token; when given, returns its logits row
        (otherwise the chunk's last row).

        Returns (logits [B, V], updated cache).  MoE configs are rejected
        — see ``supports_chunked_prefill``.
        """
        cfg = self.cfg
        if not self.supports_chunked_prefill:
            raise NotImplementedError(
                "chunked prefill is not bit-exact for MoE configs "
                "(sequence-global router capacity); use whole-prompt "
                "prefill")
        h = self._embed_tokens(params, tokens)
        new_cache = {}
        for gi, (kind, count) in enumerate(cfg.groups()):
            name = f"layers_{gi}_{kind}"

            def body(h, xs, kind=kind):
                lp, lcache = xs
                with jax.named_scope("attention"):
                    hn = rmsnorm(lp["ln1"], h,
                                 zero_centered=cfg.zero_centered_norm)
                    if cfg.attention == "mla":
                        a, ckv, kr = attn_mod.mla_prefill_chunk(
                            lp["attn"], cfg.mla_config(), hn, lcache["ckv"],
                            lcache["kr"], start, kv_len)
                        upd = {"ckv": ckv, "kr": kr}
                    else:
                        a, k, v = attn_mod.gqa_prefill_chunk(
                            lp["attn"], cfg.attn_config(), hn, lcache["k"],
                            lcache["v"], start, kv_len)
                        upd = {"k": k, "v": v}
                    h = h + a
                h, _ = self._mix_residual(kind, lp, h)
                return h, upd

            h, upd = jax.lax.scan(body, h, (params[name], cache[name]))
            new_cache[name] = upd
        return self._unembed_last(params, h, last_idx), new_cache

    def decode_step(self, params, cache, tokens, cur_len):
        """tokens: [B, 1]; cur_len: current cache fill — a scalar int, or a
        [B] int vector of per-slot lengths for continuous batching (each
        batch slot decodes at its own position; see runtime/engine.py).

        Each group's stacked [L, B, max_len, ...] cache leaves ride the
        layer scan's carry: layer ``li`` writes its new row in place at
        ``[li, row, cur_len[row]]`` and attends over its own rows, read
        from the carried buffer.  Through the scan's ``xs`` and ``ys``
        instead, the cache could not alias the caller's donated buffer
        (``xs`` are still read while ``ys`` are written): XLA would stack
        a second whole cache and copy both back every step
        (docs/serving.md).

        Returns (logits [B, V], new cache).
        """
        cfg = self.cfg
        mla = cfg.attention == "mla"
        valid_len = jnp.asarray(cur_len) + 1
        h = self._embed_tokens(params, tokens)
        new_cache = {}
        for gi, (kind, count) in enumerate(cfg.groups()):
            name = f"layers_{gi}_{kind}"

            def body(carry, xs, kind=kind):
                h, kv = carry
                lp, li = xs
                with jax.named_scope("attention"):
                    hn = rmsnorm(lp["ln1"], h,
                                 zero_centered=cfg.zero_centered_norm)
                    if mla:
                        q_nope, q_rope, c_new, kr_new = \
                            attn_mod.mla_decode_qkv(lp["attn"],
                                                    cfg.mla_config(), hn,
                                                    cur_len)
                        new = {"ckv": c_new, "kr": kr_new}
                    else:
                        q, k_new, v_new = attn_mod.gqa_decode_qkv(
                            lp["attn"], cfg.attn_config(), hn, cur_len)
                        new = {"k": k_new, "v": v_new}
                    kv = {n: attn_mod.write_row(c, new[n], cur_len, layer=li)
                          for n, c in kv.items()}
                    rows = {n: jax.lax.dynamic_index_in_dim(
                                c, li, axis=0, keepdims=False)
                            for n, c in kv.items()}
                    if mla:
                        a = attn_mod.mla_decode_attend(
                            lp["attn"], cfg.mla_config(), q_nope, q_rope,
                            rows["ckv"], rows["kr"], valid_len)
                    else:
                        a = attn_mod.gqa_decode_attend(
                            lp["attn"], q, rows["k"], rows["v"], valid_len)
                    h = h + a
                h, _ = self._mix_residual(kind, lp, h)
                return (h, kv), None

            (h, new_cache[name]), _ = jax.lax.scan(
                body, (h, cache[name]), (params[name], jnp.arange(count)))
        return self._unembed_last(params, h), new_cache
