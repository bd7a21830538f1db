"""Where the Pallas kernels run: the one native-vs-interpret decision."""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Pallas ``interpret`` flag: an explicit value wins; otherwise the
    kernels lower natively on a TPU and run in the interpreter elsewhere
    (their BlockSpecs target the TPU's tiling)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
