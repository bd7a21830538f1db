"""Batched serving driver: prefill + greedy decode, optional PUD GeMV path.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        --preset smoke --batch 4 --prompt-len 32 --gen 16 --pud-gemv

With ``--pud-gemv`` the FFN and unembed projections (plus attention with
``--pud-attention``) are packed into 4-bit bit-planes (the PUD/MVDRAM weight
layout) and every decode step executes them through the Pallas bit-plane
kernel. The driver reports:

  * numerics: max |logit delta| and token agreement vs the bf16 path,
  * the DRAM-side performance model: tokens/s a real 4-channel DDR4 PUD
    system would sustain for this model at the calibrated error-free column
    fraction — baseline B_{3,0,0} vs PUDTune T_{2,1,0} (the paper's Eq. 1
    applied end-to-end).

With ``--calib-cache`` the device's persisted per-subarray table drives the
whole chain: calibration masks -> column placement (error-free physical
columns only, repro/pud/placement.py) -> physically-permuted packs -> the
placed Pallas kernel, and the serving rate is derived from the actual
placement occupancy instead of a mean error-free fraction.

With ``--engine`` generation runs through the continuous-batching
``ServingEngine`` (runtime/engine.py): each prompt row becomes a queued
request, slots admit/evict at step granularity, and ``--batch-size``
(default: the session's occupancy-derived optimum) sets the padded decode
batch.  Batched decode is bit-identical per request to the lockstep loop.

All of that wiring lives behind ``repro.api.PUDSession`` (docs/api.md);
this driver is one consumer of the session, not the owner of the chain.
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get
from repro.launch.compile_cache import enable_compile_cache
from repro.models.params import init_params, param_count
from repro.pud.gemv import ATTN_PACKABLE, FFN_PACKABLE, PUDGemvConfig
from repro.runtime.steps import make_serve_step

#: Seed of the default serve-step key when the caller does not thread one
#: (greedy decode never consumes it; sampling steps derive from here).
DEFAULT_SEED = 0


@functools.lru_cache(maxsize=8)
def _jitted(model):
    """Per-model jitted (prefill, serve step) pair, cached so repeated
    greedy_generate calls (bf16 + pud legs, tests) reuse one trace cache."""
    return (jax.jit(model.prefill, static_argnames=("max_len",)),
            jax.jit(make_serve_step(model)))


def greedy_generate(model, params, tokens, gen: int, max_len: int,
                    extras: dict | None = None, prefix_len: int = 0,
                    key: jax.Array | None = None):
    """Prefill then ``gen`` greedy steps. Returns [B, gen] tokens.

    prefix_len: non-token positions preceding the prompt in the cache
    (VLM patch prefix) — decode positions start after prompt + prefix.
    key: explicit PRNG key threaded into the serve step (step ``i`` sees
    ``fold_in(key, i)``); defaults to ``jax.random.key(0)``, the former
    implicit constant.  Greedy decode never consumes it, but threading it
    explicitly keeps batched-vs-sequential comparisons (and any sampling
    serve step) reproducible from one seed.

    Prefill runs jitted (like the decode steps and the ServingEngine's
    per-request prefill), so per-request sequential decode and batched
    engine decode see bit-identical logits end to end.
    """
    prefill, step = _jitted(model)
    if extras:
        logits, cache = prefill(params, tokens, *extras.values(),
                                max_len=max_len)
    else:
        logits, cache = prefill(params, tokens, max_len=max_len)
    cur = tokens.shape[1] + prefix_len
    out = []
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    if key is None:
        key = jax.random.key(DEFAULT_SEED)
    all_logits = [logits]
    for i in range(gen):
        out.append(nxt)
        nxt, logits, cache = step(params, cache, nxt, jnp.int32(cur + i),
                                  jax.random.fold_in(key, i))
        all_logits.append(logits)
    return jnp.concatenate(out, axis=1), jnp.stack(all_logits, axis=1)


def _scheduler_kwargs(args) -> dict:
    """Engine scheduler extensions from the CLI flags (all default-off)."""
    return {
        "chunk_prefill": args.chunk_prefill,
        "prefix_cache": bool(args.prefix_cache),
        "slo": args.slo_ms,
    }


def _print_scheduler_extras(sched: dict, indent: str = "    ") -> None:
    """Prefix-cache / SLO telemetry lines (only when the features are on)."""
    pc = sched.get("prefix_cache")
    if pc:
        print(f"{indent}prefix cache: {pc['hits']} hits / "
              f"{pc['misses']} misses ({pc['hit_rate']:.0%}), "
              f"{pc['entries']} entries ({pc['bytes'] / 1024:.0f} KiB), "
              f"{pc['invalidations']} invalidations")
    slo = sched.get("slo")
    if slo:
        print(f"{indent}slo: {slo['met']} met / {slo['missed']} missed "
              f"(shed {slo['shed_on_admit']} at admission, "
              f"{slo['shed_admitted']} in flight; "
              f"modeled step {slo['step_ms']:.3f} ms)")


def _monitored_serve(args, session, engine, model, params, requests,
                     tokens, max_len) -> int:
    """Serve ``requests`` under the drift monitor (--monitor).

    With ``--drift-sim`` the simulator drifts the device's sense offsets at
    step ``--drift-at`` and the live pack is corrupted to match (faults
    re-derived from the drifted offsets, injected into the serving tree),
    so the canary probes are detecting a real numeric failure, not a flag.
    The controller then drives detection -> partial recalibration ->
    repack -> between-steps hot swap, and a post-swap spot check proves
    decode is bit-identical to a fresh pack on the recovered table.
    """
    from repro.core.canary import probe_ecr
    from repro.core.reliability import DriftSimulator
    from repro.pud.placement import inject_read_faults, refresh_fault_state
    from repro.runtime.drift import (DriftConfig, DriftController,
                                     DriftMonitor)
    from repro.runtime.engine import Request

    sim = DriftSimulator.for_session(session)
    mon = DriftMonitor(session, sim, config=DriftConfig(
        n_canary=args.n_canary, probe_every=args.probe_every))
    read_faults = None
    if args.drift_sim and session.placement is not None:
        def read_faults(packed_params):
            masks = np.asarray(session.calibration.masks, bool)
            pl = refresh_fault_state(session.placement, masks,
                                     np.asarray(sim.sense_offsets()))
            return inject_read_faults(packed_params, pl)
    ctl = DriftController(engine, mon, params,
                          pack_name=f"{args.arch}-{args.preset}",
                          read_faults=read_faults)
    print(f"  monitor: probing {args.n_canary} canaries/subarray every "
          f"{args.probe_every} steps "
          f"(amortized overhead {mon.probe_overhead():.2%} of decode)")

    engine.submit_all(requests)
    drifted, steps = False, 0
    while (engine.n_pending or engine.n_active
           or ctl.phase != "monitor" or engine.swap_pending):
        if args.drift_sim and not drifted and steps >= args.drift_at:
            subs = [int(s) for s in args.drift_subarrays.split(",") if s]
            sim.advance(temp_c=args.drift_temp, days=args.drift_days,
                        subarrays=subs)
            _, masks = probe_ecr(
                jax.random.fold_in(jax.random.key(args.seed), 0xD21F),
                sim.sense_offsets(), mon._charges(), session.physics,
                session.n_fracs, n_trials=128)
            if session.placement is not None:
                engine.params = inject_read_faults(
                    engine.params, refresh_fault_state(
                        session.placement, np.asarray(masks, bool),
                        np.asarray(sim.sense_offsets())))
            print(f"  drift-sim: offsets drifted at step {steps} "
                  f"(temp {args.drift_temp:.0f}C, {args.drift_days:g} "
                  f"days, subarrays {subs}); live pack corrupted")
            drifted = True
        ctl.step()
        steps += 1
        if steps > 64 * (len(requests) + 8):
            raise RuntimeError("monitor loop did not converge")

    rep = ctl.report()
    for ev in mon.detector.events:
        print(f"    drift event: subarray {ev.subarray} {ev.severity} "
              f"(canary ECR {ev.new_ecr:.3f}, probe round "
              f"{ev.probe_round})")
    for rec in rep["recoveries"]:
        ecr = ", ".join(f"{g}: {e:.3f}"
                        for g, e in rec["recalibrated_ecr"].items())
        print(f"    recovery: detected step {rec['detected_step']}, "
              f"recalibrated subarrays {rec['subarrays']} "
              f"(post-recal table ECR {{{ecr}}}), hot swap staged at "
              f"step {rec['swap_staged_step']}")
    print(f"    swaps at steps {rep['swap_steps']}, tokens on swap steps "
          f"{rep['swap_step_tokens']}, min tokens/step "
          f"{rep['min_tokens_per_step']} (zero-downtime: no stalled step)")
    sched = engine.scheduler_report()
    print(f"  engine: {sched['completed']} requests, "
          f"{sched['generated_tokens']} tokens in {sched['steps']} steps "
          f"({sched['batch_size']} slots, "
          f"occupancy {sched['slot_occupancy']:.1%})")

    if rep["recoveries"]:
        # Spot check: post-swap decode must equal a fresh decode on the
        # recovered pack (the bit-exactness contract, tests/test_drift.py).
        post = [Request(request_id=1000 + i,
                        tokens=tokens[i], max_new_tokens=args.gen)
                for i in range(min(2, len(requests)))]
        comps = {c.request_id: c for c in ctl.run(post)}
        fresh = session.packed.params
        n_ok = 0
        for r in post:
            want, _ = greedy_generate(
                model, fresh, jnp.asarray(r.tokens)[None, :],
                args.gen, max_len)
            n_ok += comps[r.request_id].tokens == list(np.asarray(want[0]))
        print(f"    post-swap spot check: {n_ok}/{len(post)} requests "
              "bit-identical to fresh decode on the recovered pack")
        if n_ok != len(post):
            raise RuntimeError("post-swap decode diverged from fresh pack")
    age = session.calibration_age()
    if age is not None:
        print(f"    table age: {age['age_days']:.4f} days "
              f"(assumed temp {age['assumed_temp_c']:.0f}C)")
    return 0


def _sharded_serve(args, spec, model, params, tokens, ref_toks,
                   max_len) -> int:
    """Serve through the tensor+data-parallel fleet (``--mesh D,M``).

    Opens one logical ``PUDSession`` per mesh device, calibrates and packs
    each lane's tensor-parallel shards (placement windows never straddle a
    shard), then drains the request queue through one ``ServingEngine``
    lane per data row — per-request decode stays bit-identical to the
    single-device engine, which the token-agreement print verifies against
    the bf16 reference exactly like the unsharded path.
    """
    from repro.core.calibrate import CalibrationConfig
    from repro.core.fleet import FleetConfig
    from repro.launch.mesh import parse_mesh_spec
    from repro.runtime.engine import Request
    from repro.runtime.session import PUDSession

    mesh = parse_mesh_spec(args.mesh)
    n_data, n_model = int(mesh.shape["data"]), int(mesh.shape["model"])
    packable = FFN_PACKABLE + (ATTN_PACKABLE if args.pud_attention else ())
    cfg = PUDGemvConfig(weight_bits=args.weight_bits, packable=packable)
    fleet = PUDSession.open_fleet(
        args.arch, mesh=mesh,
        grid=FleetConfig(n_channels=1, n_banks=1,
                         n_subarrays=args.fleet_subarrays,
                         n_cols=args.fleet_cols),
        cache_dir=args.calib_cache, device_id=args.device_id,
        calib=CalibrationConfig(n_iterations=12, n_samples=256),
        key=jax.random.key(args.seed + 2), placement=args.placement)
    print(f"[serve] fleet mesh {n_data}x{n_model} (data x model), "
          f"{fleet.n_devices} logical devices")
    t0 = time.time()
    fleet.calibrate()
    print(f"  calibration: {fleet.n_devices} devices in "
          f"{time.time() - t0:.2f}s")
    fleet.pack(params, cfg, name=f"{args.arch}-{args.preset}-fleet")
    statuses = sorted({s.placement_status or "logical"
                       for row in fleet.sessions for s in row})
    print(f"  placement per shard: {statuses}; "
          f"shard widths {list(fleet.shard_widths)} "
          f"(windows never straddle a shard)")
    if args.tune:
        trep = fleet.tune()
        n_hit = sum(1 for r in trep["keys"].values()
                    if r["status"] == "hit")
        print(f"  autotune: {len(trep['keys'])} per-shard keys "
              f"({n_hit} cache hits, {len(trep['keys']) - n_hit} searched)")

    engine = fleet.serving_engine(model, max_len=max_len,
                                  batch_size=args.batch_size,
                                  **_scheduler_kwargs(args))
    requests = [Request(request_id=i, tokens=tokens[i],
                        max_new_tokens=args.gen)
                for i in range(args.batch)]
    completions = engine.run(requests)
    sched = engine.scheduler_report()
    print(f"  fleet engine: {sched['completed']} requests over "
          f"{sched['n_lanes']} lanes in {sched['steps']} steps "
          f"({sched['batch_size']} slots/lane, "
          f"{sched['generated_tokens']} tokens)")
    _print_scheduler_extras(sched)
    agree = float(np.mean(
        [c.tokens == list(np.asarray(ref_toks[c.request_id]))
         for c in completions]))
    print(f"    token agreement vs bf16: {100 * agree:.1f}% "
          "(quantization only — sharded decode is bit-identical to the "
          "single-device engine)")
    perf = engine.perf_report(2 * spec.n_active_params)
    print(f"    aggregate DDR4-PUD model: {perf['aggregate_tok_s']:.2f} "
          f"tok/s over {perf['n_devices']} devices, scaling efficiency "
          f"{perf['scaling_efficiency']:.2f} "
          f"(slowest-shard work share {perf['shard_fraction']:.3f})")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--preset", default="smoke", choices=("smoke", "full"))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--pud-gemv", action="store_true")
    ap.add_argument("--engine", action="store_true",
                    help="also serve through the continuous-batching "
                         "ServingEngine (one request per batch row); "
                         "combine with --pud-gemv to feed it the packed "
                         "PUD path, alone it serves the bf16 tree")
    ap.add_argument("--chunk-prefill", type=int, default=None, metavar="N",
                    help="chunked prefill: admit prompts N tokens per step "
                         "interleaved with decode waves (pow2-rounded; "
                         "bit-identical to whole-request prefill)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="LRU reuse of completed prefills: repeated "
                         "prompts skip prefill, shared system prompts "
                         "resume after the cached prefix (invalidated on "
                         "every drift hot swap)")
    ap.add_argument("--slo-ms", type=float, default=None, metavar="X",
                    help="SLO-aware admission with an X ms default "
                         "deadline per request: earliest-deadline-first "
                         "admission priced by the placement perf model, "
                         "hopeless/expired requests shed")
    ap.add_argument("--batch-size", type=int, default=None,
                    help="engine decode slots; default = the session's "
                         "occupancy-derived optimal batch")
    ap.add_argument("--pud-attention", action="store_true",
                    help="also pack attention wq/wk/wv/wo onto the PUD path")
    ap.add_argument("--tune", action="store_true",
                    help="with --pud-gemv: autotune kernel tile plans at "
                         "startup (persisted under <calib-cache>/tuning; "
                         "cache hits cost a file read, cold start falls "
                         "back to the divisor heuristic)")
    ap.add_argument("--weight-bits", type=int, default=4)
    ap.add_argument("--no-placement", dest="placement",
                    action="store_false", default=True,
                    help="with --calib-cache: skip column placement and "
                         "pack onto logical columns (faulty ones included)")
    ap.add_argument("--monitor", action="store_true",
                    help="with --pud-gemv --engine: reserve canary columns, "
                         "probe them between decode steps (runtime/drift.py) "
                         "and recover from detected drift via partial "
                         "recalibration + a between-steps hot swap")
    ap.add_argument("--drift-sim", action="store_true",
                    help="with --monitor: inject simulated offset drift "
                         "(core/reliability.DriftSimulator) mid-serve and "
                         "demonstrate the full detect/recal/swap loop")
    ap.add_argument("--drift-at", type=int, default=3,
                    help="engine step at which --drift-sim injects drift")
    ap.add_argument("--drift-temp", type=float, default=3000.0,
                    help="simulated operating temperature in C; the default "
                         "is a deliberate stress far beyond the paper's "
                         "envelope so detection is certain in one round")
    ap.add_argument("--drift-days", type=float, default=0.0,
                    help="simulated days since calibration (time-drift leg)")
    ap.add_argument("--drift-subarrays", default="1,5",
                    help="comma-separated subarray ids hit by --drift-sim")
    ap.add_argument("--probe-every", type=int, default=4,
                    help="canary probe cadence in engine steps")
    ap.add_argument("--n-canary", type=int, default=16,
                    help="reserved canary columns per subarray")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="with --pud-gemv --engine: serve through a "
                         "tensor+data-parallel fleet on a DATAxMODEL host "
                         "mesh (PUDSession.open_fleet) — one calibrated "
                         "device per mesh position, packs sharded on "
                         "placement-window boundaries, one engine lane per "
                         "data row; requires XLA_FLAGS="
                         "--xla_force_host_platform_device_count>=DATA*MODEL")
    ap.add_argument("--calib-cache", default=None, metavar="DIR",
                    help="persistent calibration-table cache; serving "
                         "starts from the device's stored per-subarray "
                         "offset table instead of recalibrating")
    ap.add_argument("--device-id", default="dimm0")
    ap.add_argument("--fleet-subarrays", type=int, default=16,
                    help="subarray grid size used on a cache miss")
    ap.add_argument("--fleet-cols", type=int, default=2048,
                    help="columns per subarray used on a cache miss")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.monitor and not (args.pud_gemv and args.engine):
        ap.error("--monitor requires --pud-gemv and --engine")
    if args.drift_sim and not args.monitor:
        ap.error("--drift-sim requires --monitor")
    if args.mesh and not (args.pud_gemv and args.engine):
        ap.error("--mesh requires --pud-gemv and --engine")
    if args.mesh and args.monitor:
        ap.error("--mesh and --monitor are mutually exclusive (use "
                 "runtime.drift.FleetDriftMonitor programmatically)")
    enable_compile_cache()
    platform = jax.devices()[0].platform

    spec = get(args.arch)
    model = spec.make_smoke() if args.preset == "smoke" else spec.make_model()
    lm_cfg = getattr(model.cfg, "lm", None) or model.cfg
    params = init_params(model.param_defs(), jax.random.key(args.seed))
    print(f"[serve] {args.arch} ({args.preset}, "
          f"{param_count(model.param_defs()):,} params) "
          f"batch={args.batch} prompt={args.prompt_len} gen={args.gen}")

    key = jax.random.key(args.seed + 1)
    tokens = jax.random.randint(
        key, (args.batch, args.prompt_len), 0, lm_cfg.vocab, jnp.int32)
    max_len = args.prompt_len + args.gen + 1
    prefix_len = 0
    extras = {}
    if spec.family == "vlm":
        extras["patches"] = 0.1 * jax.random.normal(
            key, (args.batch, model.cfg.n_patches, model.cfg.d_vit),
            jnp.bfloat16)
        prefix_len = model.cfg.n_patches   # cache spans patches + text
        max_len += prefix_len
    elif spec.family == "encdec":
        extras["frames"] = 0.1 * jax.random.normal(
            key, (args.batch, model.cfg.n_frames, model.cfg.d_model),
            jnp.bfloat16)

    t0 = time.time()
    ref_toks, ref_logits = greedy_generate(
        model, params, tokens, args.gen, max_len, extras, prefix_len)
    dt = time.time() - t0
    print(f"  bf16 path: {args.batch * args.gen} tokens in {dt:.2f}s "
          f"({platform} wall, compilation included)")

    if args.mesh:
        if extras:
            print("  fleet: vlm/encdec families not supported yet "
                  "(extras require family-specific prefill); skipping")
            return 0
        return _sharded_serve(args, spec, model, params, tokens, ref_toks,
                              max_len)

    if args.pud_gemv:
        packable = FFN_PACKABLE + (ATTN_PACKABLE if args.pud_attention
                                   else ())
        cfg = PUDGemvConfig(weight_bits=args.weight_bits, packable=packable)

        # All PUD wiring (calibration table, persistence, placement,
        # packing, rate models) lives behind the session facade.
        from repro.core.calibrate import CalibrationConfig
        from repro.core.fleet import FleetConfig
        from repro.runtime.session import PUDSession
        session = PUDSession.open(
            args.arch,
            grid=FleetConfig(n_channels=1, n_banks=1,
                             n_subarrays=args.fleet_subarrays,
                             n_cols=args.fleet_cols),
            cache_dir=args.calib_cache, device_id=args.device_id,
            calib=CalibrationConfig(n_iterations=12, n_samples=256),
            key=jax.random.key(args.seed + 2), placement=args.placement)
        if args.calib_cache:
            # Device-specific model from the persisted per-subarray table:
            # a cache hit costs a file read, not an Algorithm-1 run.
            st = session.calibrate()
            status = ("HIT (no recalibration)" if st.cache_hit
                      else "MISS (identified + persisted)")
            mean_ecr = 1 - session.tuned_perf_model().mean_error_free_frac
            print(f"  calibration table [{args.device_id}] {status} "
                  f"in {st.wall_s:.2f}s: "
                  f"{session.fleet_cfg.n_subarrays_total} subarrays, "
                  f"mean ECR {mean_ecr:.3f}")

        if args.monitor:
            # Canaries must be carved out before packing so placement
            # avoids them; a cache-less session calibrates here.
            if session.calibration is None:
                st = session.calibrate()
                print(f"  calibration (for --monitor): identified "
                      f"{session.fleet_cfg.n_subarrays_total} subarrays "
                      f"in {st.wall_s:.2f}s")
            session.reserve_canaries(args.n_canary)
            print(f"  canaries: {args.n_canary}/subarray reserved "
                  f"(set {session.canaries.fingerprint()}), excluded "
                  "from placement")

        packed = session.pack(params, cfg,
                              name=f"{args.arch}-{args.preset}")
        if session.placement_status == "skipped":
            print(f"  placement: SKIPPED ({session.placement_error}); "
                  "serving on logical columns")
        elif session.placement is not None:
            rep = session.perf_report()["placement"]
            pstatus = ("HIT" if session.placement_status == "hit"
                       else "planned + persisted")
            print(f"  placement [{session.placement_name}] {pstatus}: "
                  f"{rep['used_cols']:,}/{rep['usable_cols']:,} "
                  "error-free columns used "
                  f"(occupancy {rep['occupancy']:.1%}, "
                  f"{rep['occupied_subarrays']}"
                  f"/{rep['n_subarrays']} subarrays, "
                  f"{len(rep['spilled_tensors'])} tensors spilled)")

        if args.tune:
            # Tile plans load from the persistent tuning cache (miss =
            # search + persist) and are stamped onto the packs, so the
            # greedy and engine paths below both decode on tuned tiles.
            trep = session.tune()
            n_hit = sum(1 for r in trep["keys"].values()
                        if r["status"] == "hit")
            n_tuned = len(trep["keys"]) - n_hit
            print(f"  autotune: {len(trep['keys'])} keys "
                  f"({n_hit} cache hits, {n_tuned} searched)")
            for tkey, row in sorted(trep["keys"].items()):
                speed = (f"  {row['speedup']:.2f}x vs heuristic"
                         if "speedup" in row else "")
                print(f"    {row['status']:<5s} {tkey}: "
                      f"{row['plan'] or 'heuristic'}{speed}")
            packed = session.packed   # re-fetch: packs now carry plans

        extras_rep = session.decode_extras()
        toks, logits = greedy_generate(
            model, packed.params, tokens, args.gen, max_len, extras,
            prefix_len)
        agree = float((toks == ref_toks).mean())
        delta = float(jnp.abs(logits - ref_logits).max())
        print(f"  pud-gemv path ({cfg.weight_bits}-bit planes, "
              f"{extras_rep['n_packed']} projections packed, "
              f"{extras_rep['layout']} columns, "
              f"{extras_rep['stored_bytes'] / 2**20:.1f} MiB bit-packed "
              f"vs {extras_rep['dense_equiv_bytes'] / 2**20:.1f} MiB dense "
              f"— {extras_rep['traffic_reduction']:.1f}x less weight "
              "traffic/token):")
        print(f"    token agreement vs bf16: {100 * agree:.1f}%   "
              f"max |logit delta|: {delta:.3f} "
              "(quantization, not error — the kernel is exact int math)")

        # DRAM-side throughput model: what the paper's system sustains.
        perf = session.perf_report(2 * spec.n_active_params)
        print(f"    DDR4-PUD serving model ({args.arch} full config, "
              f"{args.weight_bits}-bit): "
              f"baseline {perf['baseline_tok_s']:.2f} tok/s"
              f" -> PUDTune {perf['tuned_tok_s']:.2f}"
              f" tok/s ({perf['gain']:.2f}x, Eq. 1)")
        if session.placement is not None:
            print("    placement-derived rate (occupied-subarray waves): "
                  f"{perf['placed_tok_s']:.2f} "
                  f"tok/s at {session.placement.occupancy:.1%} occupancy")

    if args.engine:
        if extras:
            print("  engine: vlm/encdec families not supported yet "
                  "(extras require family-specific prefill); skipping")
            return 0
        from repro.runtime.engine import Request, ServingEngine
        serve_params = packed.params if args.pud_gemv else params
        engine = ServingEngine(
            model, serve_params,
            session=session if args.pud_gemv else None,
            max_len=max_len, batch_size=args.batch_size,
            **_scheduler_kwargs(args))
        requests = [Request(request_id=i, tokens=tokens[i],
                            max_new_tokens=args.gen)
                    for i in range(args.batch)]
        if args.monitor:
            return _monitored_serve(args, session, engine, model, params,
                                    requests, tokens, max_len)
        completions = engine.run(requests)
        sched = engine.scheduler_report()
        step_s = sched["span_s"].get("engine.step", 0.0)
        rate = sched["generated_tokens"] / step_s if step_s else 0.0
        print(f"  engine: {sched['completed']} requests, "
              f"{sched['generated_tokens']} tokens in {sched['steps']} steps "
              f"({sched['batch_size']} slots, "
              f"occupancy {sched['slot_occupancy']:.1%}, "
              f"{rate:.1f} tok/s {platform} wall over its steps)")
        if args.chunk_prefill:
            print(f"    chunked prefill: {sched['prefill_chunks']} chunks "
                  f"of {engine.chunk_prefill} tokens "
                  f"({sched['chunk_traces']} compiled variants)")
        _print_scheduler_extras(sched)
        # continuous batching must not change any request's tokens
        seq = ref_toks if not args.pud_gemv else toks
        agree = float(np.mean([c.tokens == list(np.asarray(seq[i]))
                               for i, c in enumerate(completions)]))
        print("    batched vs lockstep decode: "
              f"{100 * agree:.1f}% of requests bit-identical")
        if args.pud_gemv:
            perf = session.perf_report(2 * spec.n_active_params,
                                       batch_size=engine.batch_size)
            if "batched_tok_s" in perf:
                print("    DDR4-PUD batched rate: "
                      f"{perf['batched_tok_s']:.2f} aggregate tok/s at "
                      f"batch {perf['batch_size']} "
                      f"({perf['batch_speedup']:.2f}x over batch-1; "
                      f"occupancy-derived optimum {perf['optimal_batch']})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
