"""Sharding rules: logical axes -> mesh axes, input/cache/state PartitionSpecs.

Mesh axes (launch/mesh.py): ("data", "model") single-pod, ("pod", "data",
"model") multi-pod.  Policy (DESIGN.md §5):

  batch                 -> ("pod", "data")   (DP across pods, DP/FSDP inside)
  params "embed" dim    -> "data"            (FSDP / ZeRO-3: all-gathered per
                                              layer by XLA SPMD)
  params TP dims        -> "model"           (heads / mlp / experts / vocab)
  optimizer state       -> same as params    (ZeRO)
  sequence dim          -> None by default; "model" for long-prefill SP
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.params import DEFAULT_RULES, param_pspecs


def mesh_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


def make_rules(mesh: Mesh, overrides: dict | None = None) -> dict:
    rules = dict(DEFAULT_RULES)
    rules["batch"] = batch_axes(mesh)
    rules.update(overrides or {})
    return rules


def model_param_pspecs(model, mesh: Mesh, overrides: dict | None = None):
    return param_pspecs(model.param_defs(), make_rules(mesh, overrides))


def cache_pspecs(model, mesh: Mesh, batch: int, max_len: int,
                 overrides: dict | None = None):
    return param_pspecs(model.cache_defs(batch, max_len),
                        make_rules(mesh, overrides))


def input_pspecs(specs: dict, mesh: Mesh) -> dict:
    """PartitionSpec per input: leading dim = batch, rest replicated.

    Scalars (cur_len) replicate.
    """
    b = batch_axes(mesh)
    out = {}
    for name, s in specs.items():
        if s.ndim == 0:
            out[name] = P()
        else:
            out[name] = P(*((b,) + (None,) * (s.ndim - 1)))
    return out


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
