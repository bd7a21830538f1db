"""The model's named scopes against the benchmark's readers: no scope name
matches an older reader's pattern.

The program puts its compiled ops under ``jax.named_scope`` regions
``embed``, ``attention``, ``ffn`` and ``unembed``.  On a v5e the scope
reaches each op's metadata (its framework op name,
``jit(_step_fn)/while/body/closed_call/attention/dot_general``), not the
op event's own stats, so ``bench/trace.py`` does not see it today.  A
trace reader that adds the op name to an op's text (the ``tf_op`` stat
that ``bench/trace._text`` already takes when present) would: this
recorded trace holds such text.  In its 1 s window two decode steps
(``jit__step_fn``) run 0.60 s of ops, a prefill 0.20 s and a calibration
kernel 0.03 s, with placed kernels under ``ffn`` and ``unembed``.  Every
per-layer reader reads the same value on it as on the same trace with the
scopes cut from its op text.
"""
import dataclasses
import json
from pathlib import Path

import pytest

from bench_tiny import ROOT
from bench import harness, trace, work

DATA = Path(__file__).parent / "data" / "program_trace.json"
SCOPES = ("embed", "attention", "ffn", "unembed")
READERS = [m["name"] for m in harness.load_benchmark(ROOT)["per_layer"]]


@pytest.fixture
def tr():
    return trace.Trace.from_json(json.loads(DATA.read_text()))


def _cut(text: str) -> str:
    for s in SCOPES:
        text = text.replace(f"/{s}/", "/")
    return text


def _unscoped(tr):
    return dataclasses.replace(tr, devices=[
        [dataclasses.replace(e, text=_cut(e.text)) for e in d]
        for d in tr.devices])


def _ctx(tr):
    cfg = json.loads((ROOT / "bench/configs/qwen3-1.7b.json").read_text())
    cal, g = cfg["calibration"], cfg["grid"]
    return {
        "trace": tr, "peaks": harness.load_peaks("TPU v5 lite", ROOT),
        "window_s": 1.0, "model_flops": 1e12,
        "sched": {"steps": 2, "slot_occupancy": 1.0, "decode_wall_s": 0.6},
        "packs": {"placed_bytes": 3, "logical_bytes": 2},
        "pud_decode_step": work.pud_decode_step(cfg, 16),
        "calib_iteration": work.calib_iteration(
            g["n_subarrays"], cal["n_samples"], cal["maj_inputs"],
            g["n_cols"]),
        "calib_iterations": cal["n_iterations"],
    }


def test_the_trace_carries_every_scope(tr):
    text = " ".join(e.text for d in tr.devices for e in d)
    assert all(f"/{s}/" in text for s in SCOPES)
    assert _cut(text) != text and all(f"/{s}/" not in _cut(text)
                                      for s in SCOPES)


@pytest.mark.parametrize("name", READERS)
def test_readers_ignore_the_scopes(tr, name):
    read = harness.reader(name, ROOT)
    with_scopes, without = read(_ctx(tr)), read(_ctx(_unscoped(tr)))
    assert with_scopes is not None
    assert with_scopes == without
