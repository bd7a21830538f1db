#!/usr/bin/env python3
"""Smoke test of the PUD serving path on a TPU: the quickest proof that the
system still starts on the chip.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # the 1x4 fleet path on four chips

One process runs every phase; any failure exits nonzero.  The one-chip run
drives the main path at the published widths of ``qwen3-1.7b`` (28 layers,
d_model 2048, d_ff 6144, vocab 151,936, random weights from ``--seed``):

  1. calibrate the device grid natively with the fused Pallas engine, and
     check its levels against the ``reference`` engine on a few subarrays;
  2. pack the FFN and unembed projections and place them on error-free
     columns (the placement must be planned or a cache hit, never skipped);
  3. run one real-width projection through the native ``pallas`` backend
     and require it to equal the ``reference`` backend bit for bit;
  4. serve 8 requests through ``ServingEngine`` and require their tokens to
     equal lockstep ``greedy_generate`` on the same packs, with the compiled
     decode step containing the Pallas kernels (``tpu_custom_call``).

``--four-chips`` runs only the tensor-parallel fleet (``PUDSession.open_fleet``
on a 1x4 mesh, ``FleetServingEngine``) and compares every request's tokens
with a one-chip engine on device 0 in the same process.

Timings printed on the way are wall times of this run on the chip, compile
included: a smoke check, not a benchmark.  The last line of standard output
is one JSON object naming the device, printed only when every check passed.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    arch: str = "qwen3-1.7b"
    preset: str = "full"               # "smoke" only for CPU rehearsals
    n_subarrays: int = 16              # one-chip grid: 16 x 65,536 columns
    n_cols: int = 65536
    fleet_subarrays: int = 4           # per fleet device (a quarter of the
                                       # demand each)
    n_check_subarrays: int = 2         # fused-vs-reference calibration check
    n_requests: int = 8
    prompt_len: int = 128
    gen: int = 32
    weight_bits: int = 4
    calib_iterations: int = 12
    calib_samples: int = 256
    seed: int = 0


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Checks:
    """The checks of one run.  A failed check is logged and the phases go
    on, so one call on the chip reports every failure; any failure fails
    the run."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, ok: bool, what: str) -> None:
        if not ok:
            self.failed.append(what)
        log(f"  {'ok' if ok else 'FAILED'}: {what}")


@contextlib.contextmanager
def phase(name: str, platform: str):
    t0 = time.perf_counter()
    log(f"phase {name} ...")
    yield
    log(f"phase {name}: {time.perf_counter() - t0:.2f} s {platform} wall "
        f"(compile included; not a benchmark){device_memory()}")


def device_memory() -> str:
    """Device 0's bytes in use and peak, where the backend reports them."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    if "bytes_in_use" not in stats:
        return ""
    gib = 1024 ** 3
    return (f"; device 0 holds {stats['bytes_in_use'] / gib:.2f} GiB, "
            f"peak {stats.get('peak_bytes_in_use', 0) / gib:.2f} GiB")


def device_record() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _grid(cfg: SmokeConfig, n_subarrays: int):
    from repro.core.fleet import FleetConfig
    return FleetConfig(n_channels=1, n_banks=1, n_subarrays=n_subarrays,
                       n_cols=cfg.n_cols)


def _calib(cfg: SmokeConfig):
    from repro.core.calibrate import CalibrationConfig
    return CalibrationConfig(n_iterations=cfg.calib_iterations,
                             n_samples=cfg.calib_samples)


def _model(cfg: SmokeConfig):
    from repro.configs import get
    spec = get(cfg.arch)
    return spec.make_model() if cfg.preset == "full" else spec.make_smoke()


def _params(cfg: SmokeConfig, model):
    import jax
    from repro.models.params import init_params
    return init_params(model.param_defs(), jax.random.key(cfg.seed))


def _requests(cfg: SmokeConfig, model):
    import jax
    import jax.numpy as jnp
    from repro.runtime.engine import Request
    tokens = jax.random.randint(
        jax.random.key(cfg.seed + 1), (cfg.n_requests, cfg.prompt_len), 0,
        model.cfg.vocab, jnp.int32)
    reqs = [Request(request_id=i, tokens=tokens[i], max_new_tokens=cfg.gen)
            for i in range(cfg.n_requests)]
    return tokens, reqs


def _max_len(cfg: SmokeConfig) -> int:
    return cfg.prompt_len + cfg.gen + 1


def _pack_cfg(cfg: SmokeConfig):
    from repro.pud.gemv import PUDGemvConfig
    return PUDGemvConfig(weight_bits=cfg.weight_bits, backend="pallas")


def check_fused_calibration(cfg: SmokeConfig, session, check) -> None:
    """The fused Pallas engine equals the jnp reference engine on the first
    subarrays of the session's grid (same key, same offsets)."""
    import numpy as np
    from repro.core.fleet import calibrate_fleet, manufacture_fleet
    g = cfg.n_check_subarrays
    grid = _grid(cfg, g)
    offsets = manufacture_fleet(session.key, session.fleet_cfg,
                                session.physics)[:g]
    levels = {m: np.asarray(calibrate_fleet(
        session.key, offsets, grid, session.physics, session.calib_cfg,
        method=m).levels) for m in ("fused", "reference")}
    differ = int((levels["fused"] != levels["reference"]).sum())
    log(f"  fused vs reference levels on {g} x {cfg.n_cols} columns: "
        f"{differ} differ")
    check(differ == 0, "fused calibration levels equal the reference "
          "engine's")


def open_session(cfg: SmokeConfig, n_subarrays: int, key: int, **kw):
    from repro.runtime.session import PUDSession
    return PUDSession.open(cfg.arch, grid=_grid(cfg, n_subarrays),
                           calib=_calib(cfg), key=key, method="fused",
                           backend="pallas", **kw)


def pack_placed(session, params, cfg: SmokeConfig, check):
    pm = session.pack(params, _pack_cfg(cfg))
    rep = session.perf_report()["placement"]
    log(f"  placement {session.placement_status}: {rep['used_cols']:,}/"
        f"{rep['usable_cols']:,} error-free columns used "
        f"({rep['occupied_subarrays']}/{rep['n_subarrays']} subarrays)"
        if rep else f"  placement {session.placement_status}: "
        f"{session.placement_error}")
    check(session.placement_status in ("planned", "hit"),
          f"placement is planned or a hit "
          f"(got {session.placement_status!r})")
    return pm


def check_kernel_parity(pm, cfg: SmokeConfig, check) -> None:
    """Native ``pallas`` == ``reference`` on real-width projections: the
    unembed at batch 1 (GeMV entry) and 8 (GEMM entry), and layer 0's FFN
    input projection."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.pud.gemv import pud_linear
    x = jax.random.normal(jax.random.key(cfg.seed + 3),
                          (cfg.n_requests, pm.tensor("unembed/w").k),
                          jnp.float32)
    cases = [("unembed/w", x[:1]), ("unembed/w", x)]
    wi = next(n for n in pm.tensors if n.endswith("mixer/wi"))
    cases.append((wi, x))
    for name, xb in cases:
        pt = pm.tensor(name)
        if pt.planes.ndim == 4:               # stacked layers: take layer 0
            pt = pt.replace(planes=pt.planes[0], scale=pt.scale[0],
                            col_ids=(None if pt.col_ids is None
                                     else pt.col_ids[0]))
        got, want = (np.asarray(jax.jit(functools.partial(
            pud_linear, backend=b))(xb, pt)) for b in ("pallas", "reference"))
        check(got.shape == want.shape and np.isfinite(got).all()
              and np.array_equal(got, want),
              f"pallas == reference bit for bit on {name} "
              f"[{xb.shape[0]}, {pt.k}] -> {got.shape[-1]} "
              f"(placed={pt.placed})")


def serve(engine, reqs) -> dict:
    return {c.request_id: list(c.tokens) for c in engine.run(reqs)}


def run_one_chip(cfg: SmokeConfig, platform: str) -> list[str]:
    """The one-chip phases; returns the failed checks."""
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.serve import greedy_generate

    check = Checks()
    session = open_session(cfg, cfg.n_subarrays, cfg.seed + 2)
    with phase("calibrate", platform):
        st = session.calibrate()
        log(f"  {session.fleet_cfg.n_subarrays_total} x {cfg.n_cols} "
            f"columns, mean ECR {st.mean_ecr:.4f}")
        check(np.isfinite(np.asarray(st.ecr)).all() and st.mean_ecr < 0.2,
              "calibrated ECR is finite and below 20%")
        check_fused_calibration(cfg, session, check)

    model = _model(cfg)
    with phase("pack + place", platform):
        params = _params(cfg, model)
        pm = pack_placed(session, params, cfg, check)
        del params                      # the packed tree is what serves
    with phase("kernel parity", platform):
        check_kernel_parity(pm, cfg, check)

    tokens, reqs = _requests(cfg, model)
    max_len = _max_len(cfg)
    with phase("serve", platform):
        engine = session.serving_engine(model, max_len=max_len,
                                        batch_size=cfg.n_requests)
        got = serve(engine, reqs)
        sched = engine.scheduler_report()
        log(f"  engine: {sched['completed']} requests, "
            f"{sched['generated_tokens']} tokens in {sched['steps']} steps")
    with phase("lockstep reference", platform):
        want, logits = greedy_generate(model, pm.params, tokens, cfg.gen,
                                       max_len)
        want = np.asarray(want)
        check(bool(jnp.isfinite(logits).all()), "lockstep logits are finite")
    same = sum(got[i] == list(want[i]) for i in range(cfg.n_requests))
    log(f"  {same}/{cfg.n_requests} requests bit-identical to lockstep")
    check(same == cfg.n_requests and all(
        len(t) == cfg.gen for t in got.values()),
        "engine tokens equal lockstep greedy_generate tokens")
    hlo = engine._step.lower(engine.params, engine._cache,
                             jnp.asarray(engine._tokens),
                             jnp.asarray(engine._lens)).compile().as_text()
    if platform == "tpu":
        check("tpu_custom_call" in hlo,
              "compiled decode step contains tpu_custom_call")
    return check.failed


def run_four_chips(cfg: SmokeConfig, platform: str) -> list[str]:
    """The 1x4 fleet against one chip; returns the failed checks."""
    import jax
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.session import PUDSession

    check = Checks()
    n_dev = len(jax.devices())
    check(n_dev >= 4, f"four devices visible (got {n_dev})")
    mesh = make_host_mesh(1, 4)
    fleet = PUDSession.open_fleet(
        cfg.arch, mesh=mesh, grid=_grid(cfg, cfg.fleet_subarrays),
        calib=_calib(cfg), key=cfg.seed + 2, method="fused",
        backend="pallas")
    single = open_session(cfg, cfg.n_subarrays, cfg.seed + 2)
    with phase("calibrate (4 fleet devices + 1 single)", platform):
        fleet.calibrate()
        single.calibrate()

    model = _model(cfg)
    with phase("pack + place", platform):
        params = _params(cfg, model)
        fleet.pack(params, _pack_cfg(cfg))
        statuses = [s.placement_status for row in fleet.sessions
                    for s in row]
        log(f"  fleet placement per shard: {statuses}, shard widths "
            f"{list(fleet.shard_widths)}")
        check(all(s in ("planned", "hit") for s in statuses),
              "every fleet shard's placement is planned or a hit")
        pack_placed(single, params, cfg, check)
        del params

    _, reqs = _requests(cfg, model)
    max_len = _max_len(cfg)
    with phase("serve on the 1x4 fleet", platform):
        fleet_engine = fleet.serving_engine(model, max_len=max_len,
                                            batch_size=cfg.n_requests)
        got = serve(fleet_engine, reqs)
    with phase("serve on device 0", platform):
        with jax.default_device(jax.devices()[0]):
            want = serve(single.serving_engine(
                model, max_len=max_len, batch_size=cfg.n_requests), reqs)
    same = sum(got[i] == want[i] for i in range(cfg.n_requests))
    log(f"  {same}/{cfg.n_requests} requests bit-identical between the "
        "fleet and the one-chip engine")
    check(same == cfg.n_requests and all(
        len(t) == cfg.gen for t in got.values()),
        "fleet tokens equal one-chip engine tokens")
    return check.failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 1x4 fleet path and its one-chip "
                         "comparison (needs four chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = device_record()
    log(f"devices: {dev}; compile cache {cache_dir}")
    if dev["platform"] != "tpu":
        log(f"no TPU found (platform {dev['platform']!r}); this check runs "
            "only on the chip")
        return 2
    cfg = SmokeConfig(seed=args.seed)
    run = run_four_chips if args.four_chips else run_one_chip
    failed = run(cfg, dev["platform"])
    jax.effects_barrier()
    if failed:
        log(f"{len(failed)} check(s) failed: {failed}")
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
