"""Plain float32 reference of a dense GQA transformer served on PUD packs.

It follows the published architecture (Qwen3 / Llama family): pre-norm
RMSNorm blocks; grouped-query attention with rotary embeddings (rotate-half
form) and, where the configuration says so, RMSNorm on each head's queries
and keys before the rotation; a SwiGLU FFN ``down(silu(gate(x)) * up(x))``;
a final RMSNorm and the unembedding.  The projections the configuration
puts on the PUD path (``pud.packable`` and the unembed) compute what a
calibrated, error-free DRAM array computes: the input quantized per row to
``activation_bits`` (symmetric, scale = max|x| / 127), the weight per
output column to ``weight_bits`` (symmetric, scale = max|w| / 7, codes
clipped to [-8, 7]), an exact integer product, then both scales.  All other
arithmetic is float32 at the highest matmul precision.

It imports nothing of the program: the weights are regenerated from the
seed by ``bench/weights.py``, one layer at a time, and it runs over whole
sequences (prompt plus served tokens) with no cache and no batching
across requests.  The program's parameter tree gives the leaf names only.

The control (``precision="low"``) is the same computation with every
precision the configuration states taken one step lower: what it keeps in
bfloat16 (the embeddings, the attention projections' weights and inputs,
and the attention's query, key, probability and value operands) is rounded
to float8 e4m3 with a per-row (per-column for weights) scale, and the PUD
projections' 8-bit inputs are quantized to 4 bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x [T, H, D], rotate-half over the whole head."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def quantize_columns(w, bits):
    """[K, N] -> (integer codes as float32, per-column scale [N])."""
    qmax = (1 << (bits - 1)) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-8) / qmax
    q = jnp.clip(jnp.round(w / scale[None, :]), -qmax - 1, qmax)
    return q, scale


def pud_project(x, w, weight_bits, act_bits):
    """The PUD projection of x [..., K] by w [K, N]."""
    amax = (1 << (act_bits - 1)) - 1
    sx = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-6) / amax
    xq = jnp.clip(jnp.round(x / sx), -amax, amax)
    q, sw = quantize_columns(w, weight_bits)
    return _mm(xq, q) * sx * sw


class Reference:
    """The reference for one configuration and one seed."""

    def __init__(self, cfg: dict, seed: int, leaf_shapes: dict,
                 precision: str = "f32"):
        if precision not in ("f32", "low"):
            raise ValueError(f"unknown precision {precision!r}")
        self.cfg = cfg
        self.root = weights.seed_key(seed)
        self.shapes = leaf_shapes           # path -> full shape
        self.low = precision == "low"
        pud = cfg["pud"]
        self.packable = set(pud["packable"])
        self.wb, self.ab = pud["weight_bits"], pud["activation_bits"]
        if self.low:
            self.ab //= 2
        self.groups = sorted({p.split("/")[0] for p in leaf_shapes
                              if weights.is_stacked(p)})
        self._unembed = None

    # -- weights -----------------------------------------------------------

    def _leaf(self, path, layer=None):
        return _leaf_fn(self.root, path, self.shapes[path], layer)

    def layer_weights(self, group: str, layer: int) -> dict:
        pre = group + "/"
        return weights.nest({p[len(pre):]: self._leaf(p, layer)
                      for p in self.shapes if p.startswith(pre)})

    # -- forward -----------------------------------------------------------

    def hidden(self, tokens: np.ndarray) -> jax.Array:
        """Final-norm hidden states [S, T, d] of token rows [S, T]."""
        table = self._leaf("embed/table")
        h = jnp.take(table, jnp.asarray(tokens), axis=0)
        if self.low:
            h = _fp8(h, -1)
        for g in self.groups:
            n = self.shapes[f"{g}/ln1/scale"][0]
            for l in range(n):
                h = _layer(h, self.layer_weights(g, l), self._static())
        return _rms(h, self._leaf("final_norm/scale"),
                    self.cfg["rms_norm_eps"])

    def _static(self):
        c = self.cfg
        return (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"], float(c["rope_theta"]),
                float(c["rms_norm_eps"]), bool(c.get("qk_norm", False)),
                self.low, self.wb, self.ab,
                tuple(sorted(self.packable)))

    def logits_rows(self, h_rows: jax.Array) -> jax.Array:
        """Logits [R, V] of final hidden rows [R, d]."""
        if self._unembed is None:
            self._unembed = self._leaf("unembed/w")
        w = self._unembed
        if self.cfg["pud"].get("unembed"):
            return _unembed_pud(h_rows, w, self.wb, self.ab)
        return _mm(h_rows, w)


@functools.partial(jax.jit, static_argnames=("path", "shape"))
def _layer_leaf_jit(root, layer, path, shape):
    return weights.leaf(root, path, shape, jnp.float32, layer=layer)


@functools.partial(jax.jit, static_argnames=("path", "shape"))
def _whole_leaf_jit(root, path, shape):
    return weights.leaf(root, path, shape, jnp.float32)


def _leaf_fn(root, path, shape, layer):
    if layer is None or not weights.is_stacked(path):
        return _whole_leaf_jit(root, path, tuple(shape))
    return _layer_leaf_jit(root, jnp.uint32(layer), path, tuple(shape))


@functools.partial(jax.jit, static_argnames=("wb", "ab"))
def _unembed_pud(h, w, wb, ab):
    return pud_project(h, w, wb, ab)


@functools.partial(jax.jit, static_argnums=(2,))
def _layer(h, w, static):
    (nh, nkv, hd, theta, eps, qk_norm, low_precision, wb, ab,
     packable) = static
    s, t, d = h.shape
    positions = jnp.arange(t)
    low = _fp8 if low_precision else (lambda x, axis: x)

    def attn_one(x):                                  # x [T, d]
        xa = low(x, -1)
        q = _mm(xa, low(w["attn"]["wq"].reshape(d, nh * hd), 0))
        k = _mm(xa, low(w["attn"]["wk"].reshape(d, nkv * hd), 0))
        v = _mm(xa, low(w["attn"]["wv"].reshape(d, nkv * hd), 0))
        q, k, v = (q.reshape(t, nh, hd), k.reshape(t, nkv, hd),
                   v.reshape(t, nkv, hd))
        if qk_norm:
            q = _rms(q, w["attn"]["q_norm"]["scale"], eps)
            k = _rms(k, w["attn"]["k_norm"]["scale"], eps)
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        g = nh // nkv
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", low(q, -1), low(k, -1),
                        precision=HI) / jnp.sqrt(jnp.float32(hd))
        causal = positions[:, None] >= positions[None, :]
        sc = jnp.where(causal[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", low(p, -1), low(v, 0), precision=HI)
        o = low(o.reshape(t, nh * hd), -1)
        return _mm(o, low(w["attn"]["wo"].reshape(nh * hd, d), 0))

    def proj(x, name):
        wt = w["mixer"][name]
        if f"mixer.{name}" in packable:
            return pud_project(x, wt, wb, ab)
        return _mm(low(x, -1), low(wt, 0))

    x = _rms(h, w["ln1"]["scale"], eps)
    h = h + jax.lax.map(attn_one, x)
    x = _rms(h, w["ln2"]["scale"], eps)
    f = proj(jax.nn.silu(proj(x, "wg")) * proj(x, "wi"), "wo")
    return h + f


def gaps(ref: Reference, rows: list[np.ndarray], n_prompt: list[int],
         served: list[np.ndarray], pad_to: int, other=None,
         chunk: int = 256):
    """Teacher-forced comparison over sequences (prompt + served tokens).

    For each served token, the gap by which the reference's logit for it
    lies below the reference's best logit at that position.  ``other``
    (optional, one int array per sequence) are tokens some other
    computation put first at the same positions; their gaps are returned
    too.  Returns (served gaps, other gaps or None, predicted argmax per
    sequence).
    """
    seqs = np.zeros((len(rows), pad_to), np.int32)
    for i, r in enumerate(rows):
        seqs[i, :len(r)] = r
    h = ref.hidden(seqs)
    idx_s, idx_t, tgt, oth, owner = [], [], [], [], []
    for i, (p, sv) in enumerate(zip(n_prompt, served)):
        pos = np.arange(p - 1, p - 1 + len(sv))
        idx_s.append(np.full(len(sv), i))
        idx_t.append(pos)
        tgt.append(np.asarray(sv))
        owner.append(np.full(len(sv), i))
        if other is not None:
            oth.append(np.asarray(other[i]))
    idx_s, idx_t = np.concatenate(idx_s), np.concatenate(idx_t)
    tgt, owner = np.concatenate(tgt), np.concatenate(owner)
    oth = np.concatenate(oth) if other is not None else None
    g_served, g_other, best = [], [], []
    for a in range(0, len(tgt), chunk):
        sl = slice(a, a + chunk)
        rows_h = h[jnp.asarray(idx_s[sl]), jnp.asarray(idx_t[sl])]
        lg = ref.logits_rows(rows_h)
        top = jnp.max(lg, axis=-1)
        take = lambda ids: np.asarray(  # noqa: E731
            top - jnp.take_along_axis(lg, jnp.asarray(ids)[:, None],
                                      axis=-1)[:, 0])
        g_served.append(take(tgt[sl]))
        if oth is not None:
            g_other.append(take(oth[sl]))
        best.append(np.asarray(jnp.argmax(lg, axis=-1)))
    best = np.concatenate(best)
    per_seq = [best[owner == i] for i in range(len(rows))]
    return (np.concatenate(g_served),
            np.concatenate(g_other) if oth is not None else None, per_seq)
