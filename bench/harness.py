"""What one run of one cell is, found by name, and how its result prints.

Everything a cell needs comes from data found by the names in
``BENCHMARK.json``:

* the configuration's file (``configs[].file``), whose ``reference`` key
  names its architecture family: the program's builder
  ``bench/program/<family>.py`` and the plain reference
  ``bench/reference/<family>.py``;
* the traffic mix, ``bench/traffic/<traffic>.json``, whose ``kind`` names
  the runner module ``bench/<kind>.py`` that runs it;
* the limits that decide ``correct``, ``bench/limits/<workload>.json``;
* each per-layer metric's reader, ``bench/metrics/<metric>.py``, a module
  with ``read(ctx) -> float | None``.

So a later cell, mix, configuration or metric is new files plus entries in
``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    """One ``workloads`` entry with everything it names, resolved."""

    name: str
    chips: int
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: list[dict]      # BENCHMARK.json entries this cell reports
    per_layer: list[dict]
    root: Path

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload`` in ``root/BENCHMARK.json``."""
    spec = load_benchmark(root)
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(by_name)})")
    w = by_name[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    with open(root / "bench" / "limits" / f"{workload}.json") as f:
        limits = json.load(f)
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic_name=w["traffic"], traffic=traffic, limits=limits,
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, workload)],
        root=root)


def runner(cell: Cell):
    """The module that runs this cell's kind of traffic."""
    return importlib.import_module(f"bench.{cell.kind}")


def reader(name: str, root: Path = ROOT):
    """The ``read`` function of per-layer metric ``name``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(device_kind: str, root: Path = ROOT) -> dict:
    """The device's published peaks; a device not in the table is an
    error, never a default."""
    with open(root / "bench" / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]


# ---------------------------------------------------------------------------
# Correctness checks: each compared number beside its limit
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def check(cell: Cell, name: str, value: float) -> Check:
    return Check(name, float(value), float(cell.limits[name]))


# ---------------------------------------------------------------------------
# The result line
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Outcome:
    """What a runner hands back from one run."""

    attempted: int
    failed: int
    end_to_end: dict[str, float]          # metric name -> value
    checks: list[Check]
    ctx: dict                             # what per-layer readers read
    memory_peak_bytes: int
    busy_s: float | None = None           # traced runs only
    window_s: float | None = None
    breakdown: dict | None = None
    compiles_in_window: int = 0


def result_line(cell: Cell, out: Outcome, device: dict, trace: bool) -> dict:
    """The last line of standard output: the contract's keys, the checks
    last."""
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = reader(m["name"], cell.root)(out.ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        missing = [m["name"] for m in cell.end_to_end
                   if m["name"] not in out.end_to_end]
        if missing:
            raise RuntimeError(f"{cell.name} computed no {missing}")
        metrics = {m["name"]: {"value": float(out.end_to_end[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    dev = dict(device, memory_peak_bytes=int(out.memory_peak_bytes))
    if trace:
        dev["busy_s"] = out.busy_s
        dev["window_s"] = out.window_s
    line = {
        "correct": bool(out.checks) and all(c.ok for c in out.checks)
        and out.failed == 0,
        "attempted": int(out.attempted),
        "failed": int(out.failed),
        "metrics": metrics,
        "device": dev,
    }
    if trace and out.breakdown is not None:
        line["breakdown"] = out.breakdown
    line["compiles_in_window"] = int(out.compiles_in_window)
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


def print_checks(checks: list[Check], failed: int, attempted: int) -> None:
    """The compared numbers beside their limits, as the last lines of
    standard error."""
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    print(f"check failed_requests: {failed} of {attempted} (limit 0) "
          f"{'ok' if failed == 0 else 'FAILED'}", file=sys.stderr, flush=True)
