"""Shared helpers of the benchmark's CPU tests (imported by the test files).

``tiny_root`` is a benchmark tree of its own: the repository's
``BENCHMARK.json`` and ``bench/`` data, plus a smoke-size configuration
(``tiny``), a small closed-loop mix (``tinychat``) and their cells and
limits, all added as new files and entries the way a later change would
add them.  Runs in it go through the same runners as the chip's runs,
minus the look for a chip.
"""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_CONFIG = {
    "name": "tiny", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
}
TINY_CHAT = {
    "kind": "serve", "loop": "closed", "clients": 4, "max_len": 64,
    "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.6, "min": 4,
               "max": 32},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 2,
               "max": 24},
    "tokens": "uniform", "pool": 16, "pairing_seed": 0, "order_seed": 1,
    "lead_in_steps": 1,
    "trace_seconds": 1, "check_tokens": 100000, "check_max_requests": 1000,
}
# Limits of the tiny cells, between what sound CPU runs and the controls
# read at this size (tests/bench/test_bench_correctness.py).
TINY_LIMITS = {"tiny.chat": {"widest_gap": 0.5},
               "tiny.calib": {"level_mismatch": 0}}


def build_tiny_root(dest: Path) -> Path:
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("state", "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/qwen3-1.7b.json").read_text())
    cfg.update(TINY_CONFIG)
    cfg["grid"].update(n_subarrays=4, n_cols=2048)
    cfg["calibration"].update(n_iterations=4, n_samples=64)
    (dest / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    (dest / "bench/traffic/tinychat.json").write_text(json.dumps(TINY_CHAT))
    for name, lim in TINY_LIMITS.items():
        (dest / f"bench/limits/{name}.json").write_text(json.dumps(lim))
    spec["configs"].append({"name": "tiny", "source": "smoke size",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "CPU tests"})
    spec["workloads"] += [
        {"name": "tiny.chat", "config": "tiny", "traffic": "tinychat",
         "chips": 1, "why": "CPU tests"},
        {"name": "tiny.calib", "config": "tiny", "traffic": "calib",
         "chips": 1, "why": "CPU tests"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(
                "tiny.calib" if "qwen3-1.7b.calib" in m["workloads"]
                else "tiny.chat")
    (dest / "BENCHMARK.json").write_text(json.dumps(spec))
    return dest


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    return build_tiny_root(tmp_path_factory.mktemp("bench"))


def run_cell(root: Path, workload: str, seed: int, *, seconds: float = 1.0,
             backend: str = "reference", method: str = "reference",
             control: bool = False):
    """One run of a cell of ``root`` on the CPU: (outcome, readings).
    ``backend`` and ``method`` pick the PUD backend and calibration engine
    of the configuration (the interpreter-free ones by default)."""
    from bench import harness
    cell = harness.resolve(workload, root)
    cell.config["pud"]["backend"] = backend
    cell.config["calibration"]["method"] = method
    if cell.kind == "calib":
        cell.traffic["method"] = method
    state = root / "bench" / "state"
    state.mkdir(parents=True, exist_ok=True)
    return harness.runner(cell).run(cell, seed, seconds, False,
                                    state_dir=state,
                                    t_start=time.perf_counter(),
                                    control=control)
