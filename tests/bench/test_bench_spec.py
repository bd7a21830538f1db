"""BENCHMARK.json and the files its names resolve to.

Every workload resolves to a configuration file, a traffic mix with a
runner, its limits and a reader per per-layer metric; a new cell,
configuration, mix or metric is new files plus entries, with no file that
is already there edited; and off the chip the entry point prints no
result.
"""
import json
import os
import re
import subprocess
import sys

import pytest

from bench_tiny import ROOT, build_tiny_root
from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = harness.load_benchmark(ROOT)


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_workload_resolves_to_its_files(workload):
    cell = harness.resolve(workload, ROOT)
    runner = harness.runner(cell)
    assert callable(runner.run)
    if hasattr(runner, "family"):
        program, reference = runner.family(cell.config)
        assert callable(program.build_model)
        assert callable(reference.Reference) and callable(reference.gaps)
    assert cell.limits and all(isinstance(v, (int, float))
                               for v in cell.limits.values())
    names = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.reader(m["name"], ROOT))
        assert m["moves"] in names


def test_benchmark_json_keeps_its_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert all((ROOT / p).is_dir() for p in SPEC["paths"])
    assert 1 <= SPEC["run_seconds"] <= 51
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert NAME.match(c["name"])
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg.get("published", {}))
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in SPEC["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert harness.load_peaks("TPU v5 lite", ROOT)["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary", ROOT)


def test_a_new_cell_config_mix_and_metric_are_new_files_only(tmp_path):
    """The tiny cells arrive as new files plus entries; a new per-layer
    metric is one reader file plus its entry.  The tree's files that were
    there before are byte for byte the repository's."""
    root = build_tiny_root(tmp_path)
    (root / "bench/metrics/tiny_window_s.py").write_text(
        "def read(ctx):\n    return ctx.get('window_s')\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "tiny_window_s", "unit": "s", "better": "higher",
        "source": "host_clock", "layer": "engine", "moves": "output_tok_s",
        "workloads": ["tiny.chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    for dirpath, _, files in os.walk(ROOT / "bench"):
        if "state" in dirpath or "__pycache__" in dirpath:
            continue
        for f in files:
            src = os.path.join(dirpath, f)
            dst = root / os.path.relpath(src, ROOT)
            assert dst.read_bytes() == open(src, "rb").read()

    cell = harness.resolve("tiny.chat", root)
    assert cell.config["hidden_size"] == 64 and cell.traffic["clients"] == 4
    assert [m["name"] for m in cell.per_layer][-1] == "tiny_window_s"
    out = harness.Outcome(attempted=1, failed=0,
                          end_to_end={"setup_s": 1.0}, checks=[],
                          ctx={"window_s": 2.5}, memory_peak_bytes=0)
    line = harness.result_line(cell, out, {"platform": "tpu"}, trace=True)
    assert line["metrics"] == {"tiny_window_s": {"value": 2.5, "unit": "s"}}
    assert list(line)[-1] == "checks"


def test_off_the_chip_the_entry_point_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "qwen3-1.7b.chat", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "no TPU" in p.stderr
