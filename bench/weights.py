"""Model weights made on the device from ``--seed``, in one jitted call.

Every matrix entry is an integer in [-128, 128) times a power of two near
1/sqrt(fan_in) (embeddings near unit variance), and every norm scale is 1.
Such values are exact in bfloat16 and in float32, and each stacked layer's
slice comes from its own key, so the reference regenerates any one leaf or
layer bit for bit, on its own, without taking anything the program made.
The leaves follow the dense GQA parameter tree: a path this module does not
know is an error.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

INT_STD = 73.9          # std of an integer uniform on [-128, 128)


def seed_key(seed: int) -> jax.Array:
    """Key of a seed of up to 64 bits (``jax.random.key`` keeps only the
    low 32 without 64-bit mode)."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not a 64-bit unsigned integer")
    k = jax.random.fold_in(jax.random.key(0), np.uint32(seed >> 32))
    return jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))


def fan_in(path: str, shape: tuple[int, ...]) -> int | None:
    """Input width of a matrix leaf; None for a unit-variance embedding."""
    if path.endswith("embed/table"):
        return None
    if path.endswith(("attn/wq", "attn/wk", "attn/wv")):
        return shape[-3]
    if path.endswith("attn/wo"):
        return shape[-3] * shape[-2]
    if path.endswith(("mixer/wi", "mixer/wg", "mixer/wo", "unembed/w")):
        return shape[-2]
    raise KeyError(f"no weight rule for leaf {path!r}")


def exponent(path: str, shape: tuple[int, ...]) -> int:
    """The power of two a leaf's integers are scaled by: 2**-e."""
    n = fan_in(path, shape)
    target = INT_STD * (1.0 if n is None else math.sqrt(n))
    return int(math.floor(math.log2(target) + 0.5))


def is_stacked(path: str) -> bool:
    return path.startswith("layers_")


def _leaf_key(root: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(root, np.uint32(zlib.crc32(path.encode())))


def _ints(key, shape, e: int, dtype):
    q = jax.random.randint(key, shape, -128, 128, jnp.int32)
    return (q.astype(jnp.float32) * (2.0 ** -e)).astype(dtype)


def leaf(root: jax.Array, path: str, shape, dtype, layer=None) -> jax.Array:
    """One leaf (or, with ``layer``, one stacked layer's slice) of the
    weights of ``root``; ``shape`` is the leaf's own, stack axis included."""
    shape = tuple(shape)
    if path.endswith("/scale"):
        s = shape[1:] if layer is not None else shape
        return jnp.ones(s, dtype)
    e = exponent(path, shape)
    k = _leaf_key(root, path)
    if not is_stacked(path):
        return _ints(k, shape, e, dtype)
    if layer is not None:
        return _ints(jax.random.fold_in(k, layer), shape[1:], e, dtype)
    keys = jax.vmap(lambda l: jax.random.fold_in(k, l))(
        jnp.arange(shape[0], dtype=jnp.uint32))
    return jax.vmap(lambda kk: _ints(kk, shape[1:], e, dtype))(keys)


def leaves(defs):
    """[(path, shape, dtype)] of a parameter-definition tree (dicts of
    leaves that carry ``shape`` and ``dtype``)."""
    out = []

    def walk(tree, prefix):
        for k, v in tree.items():
            p = f"{prefix}/{k}" if prefix else k
            if isinstance(v, dict):
                walk(v, p)
            else:
                out.append((p, tuple(v.shape), v.dtype))
    walk(defs, "")
    return out


def nest(flat: dict) -> dict:
    """{"a/b/c": v} -> {"a": {"b": {"c": v}}}."""
    tree: dict = {}
    for p, v in flat.items():
        node = tree
        *head, last = p.split("/")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    return tree


def make(defs, seed: int) -> dict:
    """The whole parameter tree of ``defs`` for ``seed``, made on the
    default device in one jitted call, each leaf in its stated dtype."""
    paths = leaves(defs)

    def build(root):
        return nest({p: leaf(root, p, s, d) for p, s, d in paths})

    return jax.jit(build)(seed_key(seed))
