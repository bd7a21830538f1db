"""Serving cells: closed-loop clients against ``ServingEngine`` on PUD packs.

One run: make the weights from the seed, open a ``PUDSession`` whose
calibration table and placement live in the benchmark's state directory
(only a checkout's first run calibrates and plans), pack and place, build
the engine, warm every prefill bucket the mix can draw plus insert and the
decode step, let the clients' first requests in (the lead-in), then measure
for the window.  Each client sends its next request when its last one
completes.  After the window no new request is sent and the engine drains,
so every request sent in the window is answered and checked.

Per-token times come from a host clock read at the return of every
``engine.step()`` (each step blocks on its tokens) and from each
``Completion``'s ``admitted_step`` and ``finished_step``: the request's
first token (from its prefill) and its second (from the same step's decode
wave) arrive when the step that admitted it returns, and every later token
one step after the one before.  The reconstruction is checked against the
engine's own step count at the end.

Correctness: once the window has closed and device memory has been read
and freed, a sample of finished requests drawn from the seed, the longest
among them, goes through the plain reference (``bench/reference``) over
prompt plus served tokens; the widest gap by which a served token's
reference logit lies below the reference's best is held to the cell's
limit.
"""
from __future__ import annotations

import gc
import importlib
import shutil
import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench import harness, work
from bench import trace as trace_mod
from bench import traffic as traffic_mod
from bench import weights
from bench.runtime import CompileCounter, memory_peak_bytes


def family(cfg: dict):
    """(program builder, plain reference) modules of the configuration's
    architecture family, named by its ``reference`` key:
    ``bench/program/<family>.py`` and ``bench/reference/<family>.py``."""
    name = cfg["reference"]
    return (importlib.import_module(f"bench.program.{name}"),
            importlib.import_module(f"bench.reference.{name}"))


def open_session(cfg: dict, state_dir):
    """``PUDSession.open`` on the configuration's grid, with its tables and
    placements cached under ``state_dir``."""
    from repro.core.calibrate import CalibrationConfig
    from repro.core.fleet import FleetConfig
    from repro.pud.physics import PhysicsParams
    from repro.runtime.session import PUDSession
    g = dict(cfg["grid"])
    g["frac_counts"] = tuple(g["frac_counts"])
    cal = dict(cfg["calibration"])
    method = cal.pop("method")
    return PUDSession.open(
        cfg["name"], grid=FleetConfig(**g), calib=CalibrationConfig(**cal),
        physics=PhysicsParams(**cfg["physics"]), key=int(cfg["device_key"]),
        method=method, backend=cfg["pud"]["backend"],
        cache_dir=str(state_dir / "pud" / cfg["name"]))


def pack(session, params, cfg: dict):
    from repro.pud.gemv import PUDGemvConfig
    p = cfg["pud"]
    pm = session.pack(params, PUDGemvConfig(
        weight_bits=p["weight_bits"], mode=p["mode"],
        packable=tuple(p["packable"]), backend=p["backend"]),
        include_unembed=bool(p["unembed"]))
    if p["placed"] and session.placement_status not in ("planned", "hit"):
        raise RuntimeError(f"placement {session.placement_status}: "
                           f"{session.placement_error}")
    return pm


def leaf_shapes(model) -> dict:
    return {p: s for p, s, _ in weights.leaves(model.param_defs())}


class ClosedLoop:
    """Clients against one engine, with a host-clock log per step."""

    def __init__(self, engine, stream, first_id: int):
        self.engine = engine
        self.stream = stream
        self.wave = engine.scheduler_report()["steps"]
        self.step_t: dict[int, float] = {}      # wave index -> return time
        self.submit_t: dict[int, float] = {}
        self.requests: dict[int, tuple[np.ndarray, int]] = {}
        self.done: dict = {}
        self.next_id = first_id

    def submit(self) -> None:
        from repro.runtime.engine import Request
        tokens, n_out = self.stream.next()
        rid = self.next_id
        self.next_id += 1
        with TraceAnnotation("bench.submit"):
            self.engine.submit(Request(request_id=rid, tokens=tokens,
                                       max_new_tokens=n_out))
        self.submit_t[rid] = time.perf_counter()
        self.requests[rid] = (tokens, n_out)

    def step(self) -> tuple[float, list]:
        """One engine step; returns (its return time, its completions)."""
        if not (self.engine.n_active or self.engine.n_pending):
            raise RuntimeError("a step with no request in the engine")
        with TraceAnnotation("bench.step"):
            done = self.engine.step()
        t = time.perf_counter()
        self.wave += 1
        self.step_t[self.wave] = t
        with TraceAnnotation("bench.record"):
            for c in done:
                self.done[c.request_id] = c
        return t, done

    def drain(self, limit: int) -> None:
        for _ in range(limit):
            if not (self.engine.n_active or self.engine.n_pending):
                return
            self.step()
        raise RuntimeError(f"engine did not drain in {limit} steps")

    def token_times(self, rid: int) -> list[float]:
        """Host times at which request ``rid``'s tokens became available."""
        c = self.done[rid]
        n = len(c.tokens)
        if c.finished_step - c.admitted_step + 1 != n:
            raise RuntimeError(f"request {rid}: {n} tokens between steps "
                               f"{c.admitted_step} and {c.finished_step}")
        a = c.admitted_step
        return [self.step_t[a + 1]] + [self.step_t[a + k - 1]
                                       for k in range(2, n + 1)]


def _occupancy_slots(rep: dict) -> float:
    return rep["slot_occupancy"] * rep["steps"] * rep["batch_size"]


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool, *,
        state_dir, t_start: float, control: bool = False):
    """One run of a serving cell.  Returns the ``harness.Outcome``, and with
    ``control`` also the control's readings on the same sample."""
    cfg, mix = cell.config, cell.traffic
    counter = CompileCounter()
    program, reference = family(cfg)
    model = program.build_model(cfg)
    shapes = leaf_shapes(model)
    params = weights.make(model.param_defs(), seed)
    session = open_session(cfg, state_dir)
    session.calibrate()
    pm = pack(session, params, cfg)
    del params
    placed_bytes = sum(int(t.planes.size) * t.planes.dtype.itemsize
                       for t in pm.tensors.values())
    engine = session.serving_engine(model, max_len=mix["max_len"],
                                    batch_size=mix["clients"])
    stream = traffic_mod.ClosedLoopTraffic(mix, seed, cfg["vocab_size"])

    # Warm-up: one request per prefill bucket the mix can draw, two tokens
    # each, compiles every prefill, insert and the decode step.
    from repro.runtime.engine import Request
    lens = {}
    for p, _ in stream.pool:
        lens.setdefault(min(mix["max_len"], traffic_mod.next_pow2(p)), p)
    rng = np.random.default_rng([int(seed), 0xB0C])
    warm = [Request(request_id=i, tokens=rng.integers(
        0, cfg["vocab_size"], p, dtype=np.int32), max_new_tokens=2)
        for i, p in enumerate(lens.values())]
    engine.run(warm)

    loop = ClosedLoop(engine, stream, first_id=len(warm))
    for _ in range(mix["clients"]):
        loop.submit()
    for _ in range(int(mix["lead_in_steps"])):
        loop.step()
    trace_dir = None
    if trace:
        import jax
        trace_dir = state_dir / "traces" / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
        jax.profiler.start_trace(str(trace_dir))
        seconds = min(seconds, float(mix["trace_seconds"]))
    rep0 = engine.scheduler_report()
    wave0 = loop.wave
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    counter.start()
    with TraceAnnotation("bench.window"):
        while True:
            t, done = loop.step()
            if t - t0 >= seconds:
                break
            for _ in done:
                loop.submit()
    t_end = t
    counter.stop()
    if trace:
        jax.profiler.stop_trace()
    rep1 = engine.scheduler_report()
    wave1 = loop.wave
    loop.drain(limit=4 * mix["max_len"])
    if loop.wave != engine.scheduler_report()["steps"]:
        raise RuntimeError("step log does not match the engine's step count")
    mem = memory_peak_bytes()

    # -- end-to-end metrics from the step log --------------------------------
    window = t_end - t0
    n_tok, flops, ttft, itl = 0, 0.0, [], []
    attempted = failed = 0
    for rid, (tokens, n_out) in loop.requests.items():
        c = loop.done.get(rid)
        in_window = t0 <= loop.submit_t[rid] <= t_end
        attempted += in_window
        if c is None or c.shed or len(c.tokens) != n_out:
            failed += in_window
            continue
        times = loop.token_times(rid)
        p = len(tokens)
        for k, tk in enumerate(times):
            if t0 < tk <= t_end:
                n_tok += 1
                flops += work.forward_flops(cfg, p + k)
                if k == 0:
                    ttft.append(tk - loop.submit_t[rid])
                else:
                    itl.append(tk - times[k - 1])
    e2e = {"setup_s": setup_s, "output_tok_s": n_tok / window}
    if ttft:
        e2e["ttft_p95_ms"] = 1e3 * float(np.percentile(ttft, 95))
    if itl:
        e2e["itl_p95_ms"] = 1e3 * float(np.percentile(itl, 95))

    waves = wave1 - wave0
    ctx = {
        "kind": "serve", "window_s": window, "e2e": e2e, "peaks": None,
        "sched": {
            "steps": waves,
            "slot_occupancy": ((_occupancy_slots(rep1) - _occupancy_slots(rep0))
                               / (waves * mix["clients"]) if waves else None),
            "decode_wall_s": rep1["decode_wall_s"] - rep0["decode_wall_s"],
        },
        "model_flops": flops,
        "packs": {"placed_bytes": placed_bytes,
                  "logical_bytes": work.logical_pud_bytes(cfg)},
        "pud_decode_step": work.pud_decode_step(cfg, mix["clients"]),
        "trace": None,
    }

    # -- correctness: the sample against the reference -----------------------
    sample = pick_sample(loop, t0, seed, mix)
    del engine, session, pm, loop.engine
    gc.collect()
    rows = [np.concatenate([loop.requests[r][0],
                            np.asarray(loop.done[r].tokens[:-1], np.int32)])
            for r in sample]
    n_prompt = [len(loop.requests[r][0]) for r in sample]
    served = [np.asarray(loop.done[r].tokens, np.int32) for r in sample]
    readings = {}
    other = None
    if control:
        low = reference.Reference(cfg, seed, shapes, precision="low")
        _, _, other = reference.gaps(low, rows, n_prompt, served,
                                     mix["max_len"])
    ref = reference.Reference(cfg, seed, shapes)
    g_served, g_other, _ = reference.gaps(ref, rows, n_prompt, served,
                                          mix["max_len"], other=other)
    checks = [harness.check(cell, "widest_gap", float(np.max(g_served)))]
    readings["widest_gap"] = float(np.max(g_served))
    readings["sample_tokens"] = int(sum(len(s) for s in served))
    if control:
        readings["control_widest_gap"] = float(np.max(g_other))

    out = harness.Outcome(
        attempted=attempted, failed=failed, end_to_end=e2e, checks=checks,
        ctx=ctx, memory_peak_bytes=mem,
        compiles_in_window=counter.count)
    if trace:
        tr = trace_mod.load(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = tr
        out.busy_s = trace_mod.busy_seconds(tr)
        out.window_s = tr.window_s
        out.breakdown = {"device_ops": trace_mod.top_ops(tr),
                         "idle_gaps": trace_mod.idle_gaps(tr)}
    return out, readings


def pick_sample(loop: ClosedLoop, t0: float, seed: int, mix: dict) -> list:
    """Requests sent in the window and finished: the longest, then others
    in an order drawn from the seed, up to ``check_tokens`` served tokens
    or ``check_max_requests`` requests."""
    ids = sorted(r for r in loop.done if loop.submit_t.get(r, -1) >= t0
                 and not loop.done[r].shed)
    if not ids:
        raise RuntimeError("no request sent in the window finished")
    longest = max(ids, key=lambda r: (len(loop.done[r].tokens),
                                      len(loop.requests[r][0]), -r))
    rest = [r for r in ids if r != longest]
    order = np.random.default_rng([int(seed), 0xC4EC]).permutation(len(rest))
    out = [longest]
    n = len(loop.done[longest].tokens)
    for i in order:
        if n >= mix["check_tokens"] or len(out) >= mix["check_max_requests"]:
            break
        out.append(rest[i])
        n += len(loop.done[rest[i]].tokens)
    return out
