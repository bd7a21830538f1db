"""Beyond-paper: continuous-batching serving engine — aggregate tokens/s vs
batch size, and the scheduler's slot occupancy on a ragged request trace.

Two rate surfaces per batch size:

  * **DRAM-side model** — the placement-derived ``FleetPerfModel`` batched
    rate (weight replication across idle subarrays + per-wave operand
    amortization, repro/pud/gemv.py).  The acceptance property lives here:
    aggregate tokens/s increases monotonically from batch 1 up to the
    occupancy-derived optimum (replicas x operand slots) and is flat past
    it — batching recovers throughput the calibrated columns would
    otherwise idle away between requests.
  * **Measured engine** — the actual ``ServingEngine`` decoding a queue of
    requests through the placed Pallas path on this container's CPU
    (interpret mode), reporting scheduler occupancy and wall tokens/s.
    CPU wall numbers are for the scheduler's health, not DRAM throughput.

A third section prices the tensor-parallel fleet: the same calibrated
device's rate model composed into a ``FleetPerfAggregate`` at 1/2/4 model
shards, with shard widths from the FULL arch geometry split on window-block
boundaries (``shard_column_slices`` — the same split ``PUDFleetSession``
executes).  Pure rate-model math: no forced multi-device runtime, so this
runs on the single-device CI container.

The fourth section is the **heavy-tail latency trace**: lognormal
inter-arrival gaps, mixed prompt lengths, and a realistic repeat mix
(repeated full prompts + a shared system prompt) replayed against the
baseline whole-request engine and the chunked+prefix-cached engine.
Latencies are **modeled**, on the same deterministic virtual clock the
SLO policy prices admission with: every decode wave costs one step, and
prefill work is priced per kv row actually computed that step
(``scheduler_report()["prefilled_tokens"]``), so a whole-request prefill
stalls the step for its full bucket while a chunk only adds a chunk's
worth — the queueing effect chunked prefill exists to remove, measured
where CPU wall time (dispatch-overhead-bound on the smoke model, noisy
in CI) cannot show it.  Per-request submit->completion p50/p99 (e2e and
per-token) and the prefix hit rate land in ``BENCH_serving.json``; the
run *raises* unless the chunked+cached p99 beats the baseline on the
identical trace, and ``--compare BENCH_serving.json --tolerance 0.15``
regression-gates the mode-relative scores against the committed baseline
(geomean-normalized — the CI job).
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import (CalibrationConfig, FleetConfig, FleetPerfAggregate,
                       FleetPerfModel, PUDGemvConfig, PUDSession, Request,
                       ServingEngine, shard_column_slices)
from repro.configs import get

from .common import emit

ROOT = pathlib.Path(__file__).resolve().parents[1]

ARCH = "qwen3-1.7b"
N_REQUESTS = 6
PROMPT_LEN = 8
GEN = 4
SHARD_COUNTS = (1, 2, 4)

# Heavy-tail trace shape
TRACE_REQUESTS = 24
TRACE_GEN = 6
TRACE_MAX_LEN = 48
TRACE_CHUNK = 8
STEP_MS = 5.0           # modeled decode-wave cost (ratios matter, not units)
TOLERANCE = 0.15


def _session() -> PUDSession:
    s = PUDSession.open(
        ARCH,
        grid=FleetConfig(n_channels=1, n_banks=1, n_subarrays=8,
                         n_cols=1024),
        calib=CalibrationConfig(n_iterations=6, n_samples=128),
        key=11, n_trials_ecr=256)
    s.calibrate()
    return s


def _full_arch_projections(spec) -> list[tuple[int, int]]:
    """(n_cols, n_slices) of every projection the packer would pack for the
    FULL arch config — the gated-FFN triplet per layer plus the unembed —
    without allocating any weights (the dry-run idiom)."""
    cfg = spec.make_model().cfg
    return [(cfg.d_ff, cfg.n_layers), (cfg.d_ff, cfg.n_layers),
            (cfg.d_model, cfg.n_layers), (cfg.vocab, 1)]


def shard_scaling_rows(pm, flops_tok: float, spec) -> list[dict]:
    """Aggregate modeled tokens/s at 1/2/4 model shards of one data lane.

    Widths come from splitting the full arch's projections exactly the way
    the fleet packs them: per-tensor window-block boundaries, remainder
    blocks to earlier shards.  Efficiency < 1 measures only that block
    raggedness (the slowest-shard bound of ``FleetPerfAggregate``).
    """
    if not isinstance(pm, FleetPerfModel):
        pm = FleetPerfModel.from_table([1.0 - pm.error_free_frac])
    projections = _full_arch_projections(spec)
    rows = []
    for n_shards in SHARD_COUNTS:
        widths = [0] * n_shards
        for n_cols, n_slices in projections:
            spans, _ = shard_column_slices(n_cols, n_shards)
            for m, (lo, hi) in enumerate(spans):
                widths[m] += (hi - lo) * n_slices
        agg = FleetPerfAggregate(shards=(pm,) * n_shards, n_data=1,
                                 shard_widths=tuple(widths))
        rows.append({
            "n_shards": n_shards,
            "shard_fraction": agg.shard_fraction,
            "aggregate_tok_s": agg.tokens_per_second(flops_tok),
            "scaling_efficiency": agg.scaling_efficiency(flops_tok),
        })
    return rows


# ---------------------------------------------------------------------------
# Heavy-tail latency trace
# ---------------------------------------------------------------------------


def build_trace(vocab: int, n: int = TRACE_REQUESTS, seed: int = 7):
    """A deterministic heavy-tail request trace.

    Lognormal inter-arrival gaps (in scheduler steps — bursts arrive
    inside one step, the tail waits many), mixed prompt lengths, and the
    repeat structure real serving has: ~1/4 exact repeats of a handful of
    popular prompts (full prefix hits) and ~1/3 fresh questions behind one
    shared 12-token system prompt (chunk-aligned partial hits).
    """
    rng = np.random.default_rng(seed)
    sysp = rng.integers(0, vocab, size=12).astype(np.int32)
    popular = [rng.integers(0, vocab, size=s).astype(np.int32)
               for s in (9, 14, 20)]
    trace, step = [], 0
    for i in range(n):
        step += int(rng.lognormal(mean=0.0, sigma=1.2))
        kind = rng.choice(["repeat", "shared", "cold"], p=[0.25, 0.35, 0.4])
        if kind == "repeat":
            tokens = popular[int(rng.integers(len(popular)))]
        elif kind == "shared":
            tail = rng.integers(0, vocab,
                                size=int(rng.integers(3, 9))).astype(np.int32)
            tokens = np.concatenate([sysp, tail])
        else:
            s = int(np.clip(rng.lognormal(mean=2.2, sigma=0.6), 3,
                            TRACE_MAX_LEN - TRACE_GEN - 1))
            tokens = rng.integers(0, vocab, size=s).astype(np.int32)
        trace.append((step, i, tokens))
    return trace


def _replay(engine, trace) -> dict:
    """Step-driven replay on the modeled clock.

    Each scheduling step costs one decode wave (``STEP_MS``) plus the
    prefill kv rows it actually computed, priced at one wave-token each
    (``STEP_MS / batch``): a whole-request admission stalls its step for
    the full prompt bucket, a chunk adds at most a chunk, a prefix full
    hit adds nothing.  Deterministic by construction — identical across
    machines and runs, so the committed-baseline gate cannot flake.
    """
    per_wave = STEP_MS / engine.batch_size
    rep = engine.scheduler_report()
    waves0, pt0 = rep["steps"], rep["prefilled_tokens"]
    pc0 = rep.get("prefix_cache", {"hits": 0, "misses": 0})
    submit_v, e2e, per_tok = {}, [], []
    i, step, vclock = 0, 0, 0.0
    while i < len(trace) or engine.n_pending or engine.n_active:
        while i < len(trace) and trace[i][0] <= step:
            _, rid, tokens = trace[i]
            submit_v[rid] = vclock
            engine.submit(Request(request_id=rid, tokens=tokens,
                                  max_new_tokens=TRACE_GEN))
            i += 1
        comps = engine.step()
        rep = engine.scheduler_report()
        cost = ((rep["steps"] - waves0) * STEP_MS
                + (rep["prefilled_tokens"] - pt0) * per_wave)
        waves0, pt0 = rep["steps"], rep["prefilled_tokens"]
        vclock += cost if cost > 0 else STEP_MS     # idle: time still passes
        for c in comps:
            lat = vclock - submit_v[c.request_id]
            e2e.append(lat)
            per_tok.append(lat / max(1, len(c.tokens)))
        step += 1
    pc1 = engine.scheduler_report().get("prefix_cache", pc0)
    hits = pc1["hits"] - pc0["hits"]
    misses = pc1["misses"] - pc0["misses"]
    e2e_ms = np.asarray(e2e)
    tok_ms = np.asarray(per_tok)
    return {
        "requests": len(e2e),
        "p50_e2e_ms": float(np.percentile(e2e_ms, 50)),
        "p99_e2e_ms": float(np.percentile(e2e_ms, 99)),
        "p50_tok_ms": float(np.percentile(tok_ms, 50)),
        "p99_tok_ms": float(np.percentile(tok_ms, 99)),
        "hit_rate": hits / (hits + misses) if hits + misses else 0.0,
    }


def run_trace(model, params) -> list[dict]:
    """Replay the identical heavy-tail trace on the whole-request baseline
    and on the chunked+cached engine; one row per mode."""
    trace = build_trace(model.cfg.vocab)
    modes = [
        ("baseline", {}),
        ("chunked_cached", {"chunk_prefill": TRACE_CHUNK,
                            "prefix_cache": True}),
    ]
    rows = []
    for mode, kw in modes:
        engine = ServingEngine(model, params, max_len=TRACE_MAX_LEN,
                               batch_size=4, **kw)
        m = _replay(engine, trace)
        m["mode"] = mode
        # gate score: inverse p99 e2e (higher is better), the number the
        # committed-baseline compare normalizes
        m["score"] = 1e3 / m["p99_e2e_ms"]
        rows.append(m)
    return rows


def compare_trace_rows(current: list[dict], baseline: list[dict], *,
                       tolerance: float = TOLERANCE) -> list[str]:
    """Regression-gate trace scores against the committed baseline.

    Geomean-normalized per run (kernel_microbench's compare idiom): a
    uniformly faster/slower machine cancels, only the *relative* standing
    of a mode can regress — e.g. chunked+cached losing its p99 edge.
    """
    cur = {r["mode"]: max(float(r["score"]), 1e-12) for r in current}
    base = {r["mode"]: max(float(r["score"]), 1e-12) for r in baseline}
    failures = [f"baseline mode {m} missing from this run"
                for m in sorted(set(base) - set(cur))]
    shared = sorted(set(base) & set(cur))
    if not shared:
        return failures + ["no modes shared with the baseline"]
    cur_gm = math.exp(sum(math.log(cur[m]) for m in shared) / len(shared))
    base_gm = math.exp(sum(math.log(base[m]) for m in shared) / len(shared))
    for m in shared:
        ratio = (cur[m] / cur_gm) / (base[m] / base_gm)
        if ratio < 1.0 - tolerance:
            failures.append(
                f"{m}: relative p99 score is {ratio:.3f} of the committed "
                f"baseline (gate: >= {1.0 - tolerance:.2f})")
    return failures


def run(scale=None) -> list[dict]:
    spec = get(ARCH)
    model = spec.make_smoke()
    from repro.models.params import init_params
    params = init_params(model.param_defs(), jax.random.key(0))

    session = _session()
    session.pack(params, PUDGemvConfig(weight_bits=4), name="engine-bench")
    flops_tok = 2 * spec.n_active_params
    pm = session.placement_perf_model() or session.tuned_perf_model()
    opt = session.optimal_batch_size()

    key = jax.random.key(3)
    prompts = [jax.random.randint(jax.random.fold_in(key, i), (PROMPT_LEN,),
                                  0, model.cfg.vocab, jnp.int32)
               for i in range(N_REQUESTS)]

    batches = sorted({1, 2, 4, opt} | {min(opt + 4, 2 * opt)})
    rows = []
    for bs in batches:
        engine = ServingEngine(model, session.packed.params,
                               session=session, max_len=PROMPT_LEN + GEN + 1,
                               batch_size=bs)
        engine.run([Request(request_id=i, tokens=p, max_new_tokens=GEN)
                    for i, p in enumerate(prompts)])
        sched = engine.scheduler_report()
        rows.append({
            "batch_size": bs,
            "is_optimum": bs == opt,
            "model_tok_s": pm.batched_tokens_per_second(flops_tok, bs)
            if hasattr(pm, "batched_tokens_per_second")
            else pm.tokens_per_second(flops_tok),
            "batch_speedup": (pm.batch_speedup(bs)
                              if hasattr(pm, "batch_speedup") else 1.0),
            "steps": sched["steps"],
            "slot_occupancy": sched["slot_occupancy"],
            "step_tok_s": (sched["generated_tokens"]
                           / sched["span_s"]["engine.step"]),
        })
    return rows, shard_scaling_rows(pm, flops_tok, spec)


def _parse_args(argv):
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.serving_engine",
        description="Serving-engine benchmark: batch sweep, shard scaling, "
                    "and the heavy-tail latency trace with a committed-"
                    "baseline regression gate.")
    ap.add_argument("--full", action="store_true",
                    help="accepted for benchmark-CLI symmetry")
    ap.add_argument("--compare", metavar="BASELINE.json",
                    help="gate the trace scores against a committed "
                         "BENCH_serving baseline; non-zero exit on "
                         "regression")
    ap.add_argument("--tolerance", type=float, default=TOLERANCE,
                    help="allowed relative score drop (default %(default)s)")
    return ap.parse_args(argv)


def main(scale=None, argv=None) -> None:
    # ``scale`` keeps the benchmarks.run entry point working (that path
    # never gates; run.py treats any exception as a benchmark failure).
    args = _parse_args([] if scale is not None else argv)
    rows, shard_rows = run(scale)
    emit("serving_engine", rows,
         header=f"{ARCH} smoke, {N_REQUESTS} requests x {GEN} tokens, "
                f"placed PUD path")
    print("Continuous-batching engine (DRAM-side model + measured "
          "scheduler):")
    for r in rows:
        tag = "  <- occupancy-derived optimum" if r["is_optimum"] else ""
        print(f"  batch {r['batch_size']:>3d}: "
              f"{r['model_tok_s']:8.2f} aggregate tok/s model "
              f"({r['batch_speedup']:5.2f}x), "
              f"{r['steps']:>3d} steps, "
              f"slot occupancy {r['slot_occupancy']:.1%}, "
              f"{r['step_tok_s']:6.1f} tok/s CPU wall{tag}")
    up_to_opt = [r["model_tok_s"] for r in rows if r["batch_size"]
                 <= max(r2["batch_size"] for r2 in rows if r2["is_optimum"])]
    mono = all(a < b for a, b in zip(up_to_opt, up_to_opt[1:]))
    print(f"  aggregate tokens/s monotone up to the optimum: "
          f"{'OK' if mono else 'VIOLATION'}")
    if not mono:
        raise AssertionError(
            "batched rate must increase monotonically up to the "
            f"occupancy-derived optimum; got {up_to_opt}")

    emit("serving_engine_sharded", shard_rows,
         header=f"{ARCH} FULL geometry, tensor-parallel model shards of "
                "one data lane (FleetPerfAggregate, device-free)")
    print("Tensor-parallel shard scaling (modeled, full arch geometry):")
    for r in shard_rows:
        print(f"  {r['n_shards']} shard(s): "
              f"{r['aggregate_tok_s']:8.2f} aggregate tok/s, "
              f"widest shard {r['shard_fraction']:.1%} of columns, "
              f"scaling efficiency {r['scaling_efficiency']:.1%}")
    agg1 = shard_rows[0]["aggregate_tok_s"]
    agg4 = shard_rows[-1]["aggregate_tok_s"]
    if agg4 < 2.0 * agg1:
        raise AssertionError(
            "4-shard aggregate modeled tokens/s must be at least 2x the "
            f"single-shard rate; got {agg4:.2f} vs {agg1:.2f}")
    print(f"  4-shard aggregate {agg4 / agg1:.2f}x single shard "
          f"(acceptance floor 2.0x): OK")

    # -- heavy-tail latency trace -------------------------------------------
    spec = get(ARCH)
    model = spec.make_smoke()
    from repro.models.params import init_params
    params = init_params(model.param_defs(), jax.random.key(0))
    trace_rows = run_trace(model, params)
    emit("serving_trace", trace_rows,
         header=f"{ARCH} smoke, {TRACE_REQUESTS}-request heavy-tail trace "
                f"(lognormal arrivals, repeat mix), chunk={TRACE_CHUNK}, "
                f"modeled-clock latencies")
    print("Heavy-tail latency trace (identical trace, both engines, "
          "modeled clock):")
    for r in trace_rows:
        print(f"  {r['mode']:>15s}: e2e p50 {r['p50_e2e_ms']:8.1f} ms, "
              f"p99 {r['p99_e2e_ms']:8.1f} ms | per-token p50 "
              f"{r['p50_tok_ms']:6.1f} ms, p99 {r['p99_tok_ms']:6.1f} ms | "
              f"hit rate {r['hit_rate']:.1%}")
    by_mode = {r["mode"]: r for r in trace_rows}
    base_p99 = by_mode["baseline"]["p99_e2e_ms"]
    chunk_p99 = by_mode["chunked_cached"]["p99_e2e_ms"]

    # Gate BEFORE overwriting the committed baseline, so a regressed run
    # cannot silently become the next run's baseline.
    if args.compare:
        baseline = json.loads(pathlib.Path(args.compare).read_text())
        failures = compare_trace_rows(trace_rows, baseline.get("rows", []),
                                      tolerance=args.tolerance)
        if failures:
            for f in failures:
                print(f"  REGRESSION {f}")
            raise SystemExit(
                f"serving_engine: {len(failures)} trace mode(s) regressed "
                f"beyond --tolerance {args.tolerance}")
        print(f"  compare: OK vs {args.compare} "
              f"(tolerance {args.tolerance})")

    payload = {
        "trace": {"requests": TRACE_REQUESTS, "gen": TRACE_GEN,
                  "chunk": TRACE_CHUNK, "max_len": TRACE_MAX_LEN},
        "rows": trace_rows,
    }
    (ROOT / "BENCH_serving.json").write_text(
        json.dumps(payload, indent=1, default=str))
    print(f"  wrote {ROOT / 'BENCH_serving.json'}")

    if by_mode["chunked_cached"]["hit_rate"] <= 0.0:
        raise AssertionError(
            "heavy-tail trace produced no prefix-cache hits — the repeat "
            "mix is broken")
    if chunk_p99 >= base_p99:
        raise AssertionError(
            "chunked+cached p99 e2e latency must beat the whole-request "
            f"baseline on the identical trace; got {chunk_p99:.1f} ms vs "
            f"{base_p99:.1f} ms")
    print(f"  chunked+cached p99 {chunk_p99:.1f} ms < baseline "
          f"{base_p99:.1f} ms ({base_p99 / chunk_p99:.2f}x better): OK")


if __name__ == "__main__":
    main()
