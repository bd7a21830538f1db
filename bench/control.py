#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip.

    python3 bench/control.py --workload qwen3-1.7b.chat --seeds 1,2,...,12 \\
        --control-seeds 1,2,3 --seconds 4

For each seed, one run of the cell (a short window at the cell's own load)
and its compared number; for the control seeds also the control's number on
the same sample: the plain reference computed one precision below the one
the configuration states (fp8 for bf16 and int4 for int8 in serving,
bf16 for float32 calibration).  The limit goes between the largest program reading and the
smallest control reading.  Prints one JSON line per run and a summary; the
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def readings(workload: str, seeds, control_seeds, seconds: float) -> dict:
    """Run the cell once per seed; returns {"program": [...], "control":
    [...]} of the cell's compared number."""
    from bench import harness, runtime
    cell = harness.resolve(workload, ROOT)
    state_dir = ROOT / "bench" / "state"
    runtime.configure(state_dir)
    runtime.device_record(cell.chips)
    state_dir.mkdir(parents=True, exist_ok=True)
    name = next(iter(cell.limits))
    got = {"program": [], "control": []}
    for seed in seeds:
        t0 = time.perf_counter()
        _, r = harness.runner(cell).run(
            cell, seed, seconds, False, state_dir=state_dir, t_start=t0,
            control=seed in control_seeds)
        got["program"].append(r[name])
        if f"control_{name}" in r:
            got["control"].append(r[f"control_{name}"])
        print(json.dumps({"workload": workload, "seed": seed, **r}),
              flush=True)
    return got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    seeds = [int(s) for s in args.seeds.split(",")]
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    got = readings(args.workload, seeds, ctrl, args.seconds)
    print(json.dumps({"workload": args.workload,
                      "program_max": max(got["program"]),
                      "control_min": (min(got["control"]) if got["control"]
                                      else None),
                      **got}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
