"""The four-chip fleet cell: its files, its exit off the chip, its two
per-layer readers on a recorded two-device trace, and its comparison with
the plain reference at smoke size on a forced 1x4 CPU mesh.

The trace (``data/fleet_trace.json``), window [10, 11] s, two devices, two
decode steps each:

* device 0 runs a bit-plane kernel 0.2 s a step, then an ``all-gather``
  0.05 s, then (first step only) a 0.1 s fusion that reads the gather's
  result: busy 0.35 + 0.25 = 0.60 s;
* device 1 runs 0.3 s kernels, 0.1 s gathers and the same 0.1 s fusion:
  busy 0.5 + 0.4 = 0.90 s.

Averaged over devices: busy 0.75 s, gathers 0.15 s (20% of busy), kernels
0.5 s in 2 decode steps.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench_tiny import ROOT
from bench import harness, trace, work, work_fleet

CELL = "granite-8b-36L.fleet4-chat"
DATA = Path(__file__).parent / "data" / "fleet_trace.json"


def test_the_fleet_cell_resolves_to_its_files_on_four_chips():
    cell = harness.resolve(CELL, ROOT)
    assert cell.chips == 4 and cell.kind == "serve_fleet"
    mesh = cell.config["mesh"]
    assert mesh["data"] * mesh["model"] == cell.chips
    assert cell.config["num_hidden_layers"] == 36
    chat = json.loads((ROOT / "bench/traffic/chat.json").read_text())
    strip = ("kind", "assumed")
    assert {k: v for k, v in cell.traffic.items() if k not in strip} == \
        {k: v for k, v in chat.items() if k not in strip}
    names = {m["name"] for m in cell.per_layer}
    assert {"pud_roofline.fleet", "tp_gather_share"} <= names
    assert not names & {"pud_roofline.decode", "decode_mfu"}


def test_off_the_chip_the_fleet_cell_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "no TPU" in p.stderr


@pytest.fixture
def tr():
    return trace.Trace.from_json(json.loads(DATA.read_text()))


def test_gather_share_counts_gathers_by_their_own_name(tr):
    read = harness.reader("tp_gather_share", ROOT)
    assert read({"trace": tr}) == pytest.approx(100.0 * 0.15 / 0.75)


def test_gather_share_reads_zero_without_a_gather(tr):
    read = harness.reader("tp_gather_share", ROOT)
    tr.devices = [[e for e in d if not e.name.startswith("all-gather")]
                  for d in tr.devices]
    assert read({"trace": tr}) == 0.0
    tr.devices = []
    assert read({"trace": tr}) is None


def test_fleet_roofline_averages_the_shards_least_time(tr):
    read = harness.reader("pud_roofline.fleet", ROOT)
    peaks = {"int8_ops_s": 1e12, "hbm_bytes_s": 1e11}
    shards = [work.Work(ops=2e10, bytes=1e9),    # ops bind: 0.02 s
              work.Work(ops=1e10, bytes=3e9)]    # bytes bind: 0.03 s
    ctx = {"trace": tr, "peaks": peaks, "pud_decode_step_shards": shards}
    # 2 steps x mean 0.025 s over 0.5 s of kernels in the decode step
    assert read(ctx) == pytest.approx(10.0)
    # no split recorded: one chip, one shard with every column
    one = dict(ctx, pud_decode_step_shards=None, pud_decode_step=shards[1])
    assert read(one) == pytest.approx(2 * 0.03 / 0.5 * 100)
    assert read(dict(one, pud_decode_step=None)) is None


def test_shard_work_adds_up_to_the_whole_projection():
    cfg = json.loads((ROOT / "bench/configs/granite-8b-36L.json").read_text())
    widths = {"mixer.wi": (3584,) * 4, "mixer.wg": (3584,) * 4,
              "mixer.wo": (2048, 2048, 0, 0), "unembed": (12288,) * 4}
    shards = work_fleet.pud_decode_step_shards(cfg, 16, widths)
    whole = work.pud_decode_step(cfg, 16)
    assert len(shards) == 4
    assert sum(w.ops for w in shards) == pytest.approx(whole.ops)
    # every shard reads the whole input of every projection
    inputs = sum(16 * k * count for _, k, _, count
                 in work.pud_projections(cfg))
    assert sum(w.bytes for w in shards) == pytest.approx(
        whole.bytes + 3 * inputs)
    assert work_fleet.projection_key("layers_0_dense/mixer/wo") == "mixer.wo"
    assert work_fleet.projection_key("unembed/w") == "unembed"


# The fleet runner against the plain reference at smoke size: the tiny
# configuration with FFN and vocabulary wide enough that each of the four
# shards owns columns of the 1024-wide projections (the 64-wide wo sits on
# shard 0 alone), through ``bench/serve_fleet.run`` on a 1x4 CPU mesh.
# Limit 0.5, as the one-chip tiny cell's: sound runs read 0.13-0.23 over
# four seeds, the control (fp8 attention, 4-bit PUD inputs) 1.25-1.80.
FLEET_PROG = """
    import json, pathlib, sys, tempfile, time
    sys.path[:0] = ["tests/bench", "."]
    from bench_tiny import TINY_CHAT, build_tiny_root
    from bench import harness, serve_fleet

    root = build_tiny_root(pathlib.Path(tempfile.mkdtemp()))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/tiny.json").read_text())
    cfg.update(name="tiny-fleet", intermediate_size=1024, vocab_size=1024,
               mesh={"data": 1, "model": 4})
    cfg["pud"]["backend"] = "reference"
    cfg["calibration"]["method"] = "reference"
    (root / "bench/configs/tiny-fleet.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tinyfleet.json").write_text(
        json.dumps(dict(TINY_CHAT, kind="serve_fleet")))
    (root / "bench/limits/tiny-fleet.chat.json").write_text(
        json.dumps({"widest_gap": 0.5}))
    spec["configs"].append({"name": "tiny-fleet", "source": "smoke size",
                            "file": "bench/configs/tiny-fleet.json",
                            "reduced": [], "why": "CPU tests"})
    spec["workloads"].append({"name": "tiny-fleet.chat",
                              "config": "tiny-fleet", "traffic": "tinyfleet",
                              "chips": 4, "why": "CPU tests"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.resolve("tiny-fleet.chat", root)
    state = root / "bench" / "state"
    state.mkdir(parents=True)
    out, r = harness.runner(cell).run(
        cell, 2 ** 33 + 17, 1.0, False, state_dir=state,
        t_start=time.perf_counter(), control=True)
    print("FLEET_RUN " + json.dumps({
        "runner": harness.runner(cell) is serve_fleet,
        "attempted": out.attempted, "failed": out.failed,
        "checks": [[c.name, c.value, c.limit, c.ok] for c in out.checks],
        "shards": len(out.ctx["pud_decode_step_shards"]),
        "e2e": sorted(out.end_to_end), **r}))
"""


def test_fleet_serving_agrees_with_the_reference_and_its_control_fails():
    sys.path.insert(0, str(ROOT / "tests"))
    from conftest import run_forced_devices
    r = run_forced_devices(FLEET_PROG, marker="FLEET_RUN ", devices=4,
                           timeout=900)
    line = next(l for l in r.stdout.splitlines()
                if l.startswith("FLEET_RUN "))
    got = json.loads(line[len("FLEET_RUN "):])
    assert got["runner"] and got["shards"] == 4
    assert got["attempted"] > 0 and got["failed"] == 0
    assert got["sample_tokens"] >= 50
    (name, value, limit, ok), = got["checks"]
    assert name == "widest_gap" and ok and value <= limit == 0.5
    assert got["control_widest_gap"] > limit
    assert {"output_tok_s", "ttft_p95_ms", "itl_p95_ms", "setup_s"} \
        <= set(got["e2e"])
