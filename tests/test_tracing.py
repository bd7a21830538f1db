"""Program spans and counters: the ``repro.*`` spans the serving engine and
``calibrate_fleet`` write into a profiler trace (names, nesting,
attributes), the always-on counters behind ``scheduler_report()``, and the
per-token timestamps of ``Completion``."""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.fleet as fleet
from repro.api import (CalibrationConfig, FleetConfig, PhysicsParams,
                       Request, ServingEngine)
from repro.configs import get
from repro.core.fleet import (_block_calibrate, calibrate_fleet,
                              manufacture_fleet)
from repro.models.params import init_params
from repro.runtime.engine import FleetServingEngine
from repro.runtime.tracing import span

MAX_LEN = 32
LENS = [5, 8, 3, 11, 16]
GEN = [3, 2, 4, 1, 3]


@pytest.fixture(scope="module")
def smoke():
    model = get("qwen3-1.7b").make_smoke()
    params = init_params(model.param_defs(), jax.random.key(0))
    return model, params


def _requests(model, lens=LENS, gen=GEN):
    k = jax.random.key(5)
    return [Request(request_id=i, tokens=jax.random.randint(
        jax.random.fold_in(k, i), (n,), 0, model.cfg.vocab, jnp.int32),
        max_new_tokens=g) for i, (n, g) in enumerate(zip(lens, gen))]


def _spans(trace_dir) -> list[dict]:
    """The ``repro.*`` host events of the newest trace under
    ``trace_dir``: name, start/end (ns), thread line and attributes."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append({"name": e.name[len("repro."):],
                                "start": e.start_ns,
                                "end": e.start_ns + e.duration_ns,
                                "line": line.name, "attrs": dict(e.stats)})
    return sorted(out, key=lambda s: (s["start"], -s["end"]))


def _parent(spans, s):
    """Innermost other span on the same thread that covers ``s``."""
    around = [p for p in spans if p is not s and p["line"] == s["line"]
              and p["start"] <= s["start"] and s["end"] <= p["end"]]
    return min(around, key=lambda p: p["end"] - p["start"], default=None)


def test_span_adds_to_totals():
    totals = {}
    for _ in range(3):
        with span("x.y", totals, a=1) as sp:
            pass
    assert sp.end >= sp.start
    assert totals["x.y"][1] == 3 and totals["x.y"][0] >= 0.0
    with span("x.z") as sp:            # no totals: the trace alone
        pass
    assert "x.z" not in totals


@pytest.mark.parametrize("chunk", [None, 8])
def test_engine_spans_nest_with_attributes(smoke, tmp_path, chunk):
    model, params = smoke
    eng = ServingEngine(model, params, max_len=MAX_LEN, batch_size=2,
                        chunk_prefill=chunk)
    with jax.profiler.trace(str(tmp_path)):
        done = eng.run(_requests(model))
    assert len(done) == len(LENS)
    spans = _spans(str(tmp_path))
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s)
    assert set(by) == {"engine.step", "engine.prefill", "engine.insert",
                       "engine.decode.dispatch", "engine.decode.readback"}
    rep = eng.scheduler_report()
    assert len(by["engine.step"]) == rep["span_n"]["engine.step"]
    assert [s["attrs"]["step"] for s in by["engine.step"]] == \
        sorted(s["attrs"]["step"] for s in by["engine.step"])
    for name in ("engine.prefill", "engine.insert", "engine.decode.dispatch",
                 "engine.decode.readback"):
        for s in by[name]:
            assert _parent(spans, s)["name"] == "engine.step", name
    # one request's spans share its id; the prompt attribute counts its
    # real tokens, the bucket or chunk the rows computed
    for rid, n in enumerate(LENS):
        pf = [s for s in by["engine.prefill"] if s["attrs"]["request"] == rid]
        assert sum(s["attrs"]["prompt"] for s in pf) == n
        rows = "chunk" if chunk else "bucket"
        assert all(s["attrs"]["prompt"] <= s["attrs"][rows] for s in pf)
        ins = [s for s in by["engine.insert"] if s["attrs"]["request"] == rid]
        assert len(ins) == 1 and ins[0]["attrs"]["slot"] in (0, 1)
        assert ins[0]["start"] >= pf[-1]["end"]
    assert all(1 <= s["attrs"]["live"] <= 2
               for s in by["engine.decode.dispatch"])
    assert len(by["engine.decode.dispatch"]) == rep["steps"]


def test_counters(smoke):
    model, params = smoke
    eng = ServingEngine(model, params, max_len=MAX_LEN, batch_size=2)
    eng.run(_requests(model))
    rep = eng.scheduler_report()
    assert rep["prompt_tokens"] == sum(LENS)
    assert rep["prefilled_tokens"] == sum(max(1, 1 << (n - 1).bit_length())
                                          for n in LENS)
    assert rep["prompt_tokens"] <= rep["prefilled_tokens"]
    assert rep["prefill_calls"] == len(LENS)       # one per admission
    assert "wall_tok_s" not in rep
    s, n = rep["span_s"], rep["span_n"]
    assert n["engine.prefill"] == len(LENS)
    assert n["engine.insert"] == len(LENS)
    assert n["engine.decode.dispatch"] == n["engine.decode.readback"] \
        == rep["steps"]
    assert rep["decode_wall_s"] == pytest.approx(
        s["engine.decode.dispatch"] + s["engine.decode.readback"])
    inner = sum(v for k, v in s.items() if k != "engine.step")
    assert 0 < inner <= s["engine.step"]
    assert eng.watchdog.ema_s is not None


def test_completion_timestamps(smoke):
    model, params = smoke
    eng = ServingEngine(model, params, max_len=MAX_LEN, batch_size=2)
    reqs = _requests(model)
    bounds = []                          # (start, end, step index after)
    eng.submit_all(reqs)
    while eng.n_pending or eng.n_active:
        t0 = time.perf_counter()
        eng.step()
        bounds.append((t0, time.perf_counter(),
                       eng.scheduler_report()["steps"]))
    done = {c.request_id: c for c in eng._completions}
    for r in reqs:
        c = done[r.request_id]
        t = [c.submit_t, c.prefill_t] + c.token_t
        assert t == sorted(t)
        assert len(c.token_t) == len(c.tokens) == r.max_new_tokens
        # the first token is read back in the step that admitted it, the
        # last in the step after which the engine's count is finished_step
        first = [b for b in bounds if b[0] <= c.token_t[0] <= b[1]]
        last = [b for b in bounds if b[0] <= c.token_t[-1] <= b[1]]
        assert bounds.index(first[0]) == c.admitted_step
        if len(c.tokens) > 1:
            assert last[0][2] == c.finished_step
        assert c.finished_step - c.admitted_step + 1 == len(c.tokens)


def test_fleet_sums_counters_and_tags_lanes(smoke, tmp_path):
    model, params = smoke
    fleet = FleetServingEngine(model, [params, params], max_len=MAX_LEN,
                               batch_size=2)
    with jax.profiler.trace(str(tmp_path)):
        fleet.run(_requests(model))
    rep = fleet.scheduler_report()
    lanes = rep["lanes"]
    for key in ("prompt_tokens", "prefill_calls", "prefilled_tokens",
                "decode_wall_s"):
        assert rep[key] == pytest.approx(sum(r[key] for r in lanes)), key
    assert rep["prompt_tokens"] == sum(LENS)
    for key in ("span_s", "span_n"):
        for name in lanes[0][key]:
            assert rep[key][name] == pytest.approx(
                sum(r[key].get(name, 0) for r in lanes))
    tagged = {s["attrs"].get("lane") for s in _spans(str(tmp_path))}
    assert tagged == {0, 1}


def test_calibrate_fleet_spans_and_levels(tmp_path):
    cfg = FleetConfig(n_channels=1, n_banks=1, n_subarrays=2, n_cols=256)
    physics = PhysicsParams()
    calib = CalibrationConfig(n_iterations=3, n_samples=32)
    key = jax.random.key(11)
    offs = manufacture_fleet(key, cfg, physics)
    fleet._memo.clear()
    with jax.profiler.trace(str(tmp_path)):
        cal = calibrate_fleet(key, offs, cfg, physics, calib,
                              method="reference")
        cal.levels.block_until_ready()
    spans = _spans(str(tmp_path))
    names = [s["name"] for s in spans]
    assert names == ["calib.call", "calib.build", "calib.run"]
    assert spans[0]["attrs"] == {"method": "reference", "subarrays": 2}
    assert _parent(spans, spans[1]) is spans[0]
    assert _parent(spans, spans[2]) is spans[0]
    assert spans[1]["end"] <= spans[2]["start"]
    assert not spans[1]["attrs"]["hit"]
    # a second call with another key is served the program the first built
    with jax.profiler.trace(str(tmp_path / "again")):
        calibrate_fleet(jax.random.key(12), offs, cfg, physics, calib,
                        method="reference").levels.block_until_ready()
    again = _spans(str(tmp_path / "again"))
    assert [s["name"] for s in again] == names
    assert again[1]["attrs"]["hit"]
    # the split into build and run computes what one jitted call did
    run = _block_calibrate(cfg.ladder(physics), physics, calib, "reference")
    levels, hist = jax.jit(run)(key, offs)
    np.testing.assert_array_equal(np.asarray(cal.levels), np.asarray(levels))
    np.testing.assert_array_equal(np.asarray(cal.mean_abs_bias),
                                  np.asarray(hist))
