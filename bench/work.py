"""Operations and bytes of the work, from shapes alone.

The yardstick for rooflines and utilisation: nothing here asks the program
how much work it did.  (Copied in spirit from the logical-versus-placed
byte accounting of ``pud/gemv.weight_traffic`` and
``benchmarks/kernel_microbench.py``, kept here so no program change can
move it.)
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class Work:
    ops: float          # multiply-adds count two
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.ops + other.ops, self.bytes + other.bytes)

    def scale(self, n: float) -> "Work":
        return Work(self.ops * n, self.bytes * n)


def bitplane_gemm(b: int, k: int, n: int, weight_bits: int) -> Work:
    """One bit-plane GEMM x[b, k] int8 @ W[k, n] of ``weight_bits``-bit
    weights: the logical bit-packed planes (weight_bits x ceil(k/8) bytes
    per column), the int8 input and the int32 output."""
    planes = weight_bits * math.ceil(k / 8) * n
    return Work(ops=2.0 * b * k * n, bytes=float(planes + b * k + 4 * b * n))


def placed_bitplane_gemm(b: int, k: int, n: int, weight_bits: int) -> Work:
    """The same product through the placed kernel.  Counted at the logical
    bytes: the window's padding columns are not work, so a smaller pad
    reads as a higher roofline share."""
    return bitplane_gemm(b, k, n, weight_bits)


def least_seconds(w: Work, peak_ops_s: float, peak_bytes_s: float) -> float:
    """The least time the chip could take: ops or bytes, whichever binds."""
    return max(w.ops / peak_ops_s, w.bytes / peak_bytes_s)


def pud_projections(cfg: dict) -> list[tuple[str, int, int, int]]:
    """(name, k, n, count per forward) of every PUD-packed projection of a
    dense GQA configuration."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    out = []
    for name in cfg["pud"]["packable"]:
        if name in ("mixer.wi", "mixer.wg"):
            out.append((name, d, ff, cfg["num_hidden_layers"]))
        elif name == "mixer.wo":
            out.append((name, ff, d, cfg["num_hidden_layers"]))
        else:
            raise KeyError(f"no shape rule for packed projection {name!r}")
    if cfg["pud"].get("unembed"):
        out.append(("unembed", d, cfg["vocab_size"], 1))
    return out


def pud_decode_step(cfg: dict, batch: int) -> Work:
    """Logical work of the bit-plane kernels in one decode step of
    ``batch`` slots."""
    wb = cfg["pud"]["weight_bits"]
    total = Work(0.0, 0.0)
    for _, k, n, count in pud_projections(cfg):
        total = total + placed_bitplane_gemm(batch, k, n, wb).scale(count)
    return total


def logical_pud_bytes(cfg: dict) -> int:
    """Bytes of every packed projection's logical bit-planes."""
    wb = cfg["pud"]["weight_bits"]
    return sum(wb * math.ceil(k / 8) * n * count
               for _, k, n, count in pud_projections(cfg))


def forward_flops(cfg: dict, context: int) -> float:
    """FLOPs of the unquantized model for one token at position
    ``context - 1`` (it attends over ``context`` keys): every projection
    once, attention scores and values over the live context, and the
    unembed."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    proj = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    attn = 2 * h * hd * context
    return 2.0 * (cfg["num_hidden_layers"] * (proj + attn)
                  + d * cfg["vocab_size"])


def calib_iteration(g: int, s: int, m: int, c: int) -> Work:
    """One calibration iteration over a [g, c] grid, at the bytes the
    algorithm needs and no layout's: each column's level and offset in,
    its new level and bias out (4 bytes each), and the ``s`` samples of
    ``m`` operand bits at one bit each.  The sensing noise is not counted:
    a kernel can draw it where it uses it.  Its work is compares on the
    vector unit, which no MXU peak bounds, so only the bytes count."""
    return Work(ops=0.0, bytes=float(g * c * (16 + s * m / 8)))
