"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before first init).

Target: TPU v5e pods — 256 chips/pod as (data=16, model=16); multi-pod adds a
leading "pod" axis (2 pods = 512 chips).  "model" is the TP/EP axis (fast ICI
within a pod slice); "data" carries DP + FSDP; "pod" carries cross-pod DP
(gradient all-reduce over DCN/optical links).

Every mesh here has ``Auto`` axis types: the compiler propagates shardings
and ``with_sharding_constraint`` is a hint, which is what the model code's
sharding hints and the fleet's ``shard_map`` regions are written for.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2):
    """Small ("data", "model") mesh: CPU multi-device tests (forced host
    device count) and the serving launcher's ``--mesh``."""
    return _mesh((data, model), ("data", "model"))


def make_mesh_for_devices():
    """Largest ("data", "model") factorization of the visible devices —
    model axis capped at 8 — the launcher default without an explicit mesh.
    """
    n = len(jax.devices())
    model = 1
    for m in (8, 4, 2, 1):
        if n % m == 0 and m <= n:
            model = m
            break
    return _mesh((n // model, model), ("data", "model"))


def parse_mesh_spec(spec: str):
    """``"D,M"`` (e.g. ``--mesh 2,4``) -> a ("data", "model") host mesh.

    The one place a CLI mesh request turns into a ``Mesh`` — device-mesh
    construction is confined to this module (analysis/lint.py:
    no-mesh-outside-launch-mesh).
    """
    try:
        data, model = (int(p) for p in spec.split(","))
    except ValueError:
        raise ValueError(
            f"mesh spec {spec!r} is not 'DATA,MODEL' (e.g. '2,4')") from None
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got {data}x{model}")
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(
            f"mesh {data}x{model} needs {data * model} devices but only "
            f"{n} {jax.devices()[0].platform} device(s) are visible")
    return make_host_mesh(data, model)


def use_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh
    (``jax.set_mesh``); launchers and mesh tests enter meshes here."""
    return jax.set_mesh(mesh)
