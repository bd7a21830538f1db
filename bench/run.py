#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload qwen3-1.7b.chat --seed 7 --seconds 10 --trace 0

From the root of a checkout.  Set-up (weights from the seed, calibration
table and placement from the checkout's ``bench/state/``, packing, warming
every shape the cell uses) is timed as ``setup_s``; then the cell measures
for ``--seconds`` and checks what the timed path produced against the plain
reference.  The last line of standard output is one JSON object: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiler trace of a shorter window.  The
compared numbers and their limits are the last lines of standard error and
the last key of that object.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for, and nonzero when the program is not in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
STATE = ROOT / "bench" / "state"


def _import_program() -> None:
    """The program is the checkout's ``src/repro``, never another copy."""
    sys.path.insert(0, str(ROOT / "src"))
    import repro
    where = [Path(p).resolve() for p in repro.__path__]
    if not all(ROOT.resolve() in w.parents for w in where):
        raise ImportError(f"repro comes from {where}, not this checkout")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from bench import harness, runtime

    cell = harness.resolve(args.workload, ROOT)
    runtime.configure(STATE)
    try:
        device = runtime.device_record(cell.chips)
    except runtime.NoChip as e:
        print(f"bench: {e}; this benchmark runs only on the chip",
              file=sys.stderr)
        return 2
    STATE.mkdir(parents=True, exist_ok=True)
    _import_program()
    peaks = harness.load_peaks(device["kind"], ROOT)
    out, _ = harness.runner(cell).run(
        cell, args.seed, args.seconds, bool(args.trace), state_dir=STATE,
        t_start=T_START)
    out.ctx["peaks"] = peaks
    line = harness.result_line(cell, out, device, bool(args.trace))
    print(json.dumps(line), flush=True)
    harness.print_checks(out.checks, out.failed, out.attempted)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
