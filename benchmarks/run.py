"""Benchmark harness entry point: one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME] [--list]

Order: offset ladders (Fig. 3) -> Table I -> Frac sensitivity (Fig. 5) ->
reliability (Fig. 6) -> Algorithm-1 convergence -> fleet calibration ->
Pallas kernels -> serving -> serving engine (continuous batching) -> drift
recovery (canary detect + hot swap) -> MAJX generalization -> column
placement -> roofline summary (reads dry-run artifacts if present).

Benchmarks register in the ``BENCHES`` dict (name -> runner taking a
``BenchScale``); imports stay inside the runners so ``--only``/``--list``
never pay for modules they don't use.  A raising benchmark is reported,
the remaining ones still run, and the process exits nonzero.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback
from typing import Callable

from .common import BenchScale


def _fig3(scale):
    """Offset ladders (Fig. 3)."""
    from . import fig3_offsets
    fig3_offsets.main(scale)


def _table1(scale):
    """ECR + arithmetic throughput operating points (Table I)."""
    from . import table1
    table1.main(scale)


def _fig5(scale):
    """Frac-count sensitivity (Fig. 5)."""
    from . import fig5_frac_sensitivity
    fig5_frac_sensitivity.main(scale)


def _fig6(scale):
    """Temperature/retention reliability (Fig. 6)."""
    from . import fig6_reliability
    fig6_reliability.main(scale)


def _convergence(scale):
    """Algorithm-1 convergence trajectory."""
    from . import calibration_convergence
    calibration_convergence.main(scale)


def _fleet(scale):
    """Whole-grid fleet calibration engine + cached startup."""
    from . import fleet_calibration
    fleet_calibration.main(["--full"] if scale.full else [])


def _kernels(scale):
    """Pallas kernels vs jnp oracles."""
    from . import kernel_bench
    kernel_bench.main(scale)


def _kernel_microbench(scale):
    """Weight bytes/token + tokens/s: dense vs bit-packed, logical vs
    placed, planes vs folded (writes BENCH_kernels.json)."""
    from . import kernel_microbench
    kernel_microbench.main(scale)


def _serving(scale):
    """MVDRAM serving table (Eq. 1 per arch)."""
    from . import mvdram_serving
    mvdram_serving.main(scale)


def _serving_engine(scale):
    """Continuous-batching engine: tokens/s vs batch size + occupancy."""
    from . import serving_engine
    serving_engine.main(scale)


def _drift(scale):
    """Online drift recovery: detection latency, partial recal scope,
    zero-downtime hot swap (fails on any stalled step)."""
    from . import drift_recovery
    drift_recovery.main(scale)


def _majx(scale):
    """MAJX generalization (MAJ3/MAJ7)."""
    from . import majx_general
    majx_general.main(scale)


def _placement(scale):
    """Column placement: occupancy + tokens/s with/without placement."""
    from . import placement
    placement.main(scale)


def _roofline(scale):
    """Roofline summary from dry-run artifacts (if present)."""
    from . import roofline
    for mesh in ("single", "multi"):
        try:
            rows = roofline.load(mesh, "base")
        except FileNotFoundError:
            rows = []
        if rows:
            print(f"\n-- mesh: {mesh} ({len(rows)} cells)")
            print(roofline.fmt_table(rows))
        else:
            print(f"\n-- mesh: {mesh}: no dry-run artifacts yet")


BENCHES: dict[str, Callable[[BenchScale], None]] = {
    "fig3": _fig3,
    "table1": _table1,
    "fig5": _fig5,
    "fig6": _fig6,
    "convergence": _convergence,
    "fleet": _fleet,
    "kernels": _kernels,
    "kernel_microbench": _kernel_microbench,
    "serving": _serving,
    "serving_engine": _serving_engine,
    "drift": _drift,
    "majx": _majx,
    "placement": _placement,
    "roofline": _roofline,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale protocol (65536 columns; slower)")
    ap.add_argument("--only", default=None, choices=sorted(BENCHES))
    ap.add_argument("--list", action="store_true",
                    help="list registered benchmarks and exit")
    args = ap.parse_args(argv)
    if args.list:
        for name, fn in BENCHES.items():
            print(f"{name:<14s} {(fn.__doc__ or '').strip()}")
        return 0
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    scale = (BenchScale(n_cols=65536, n_cols_arith=4096, full=True)
             if args.full else BenchScale())

    t0 = time.time()
    names = [args.only] if args.only else list(BENCHES)
    failures: list[str] = []
    for name in names:
        print(f"\n{'=' * 72}\n== {name}\n{'=' * 72}", flush=True)
        # A raising benchmark must not take the rest of the suite down with
        # it — but it MUST fail the run: CI smoke jobs key off the exit
        # code, and a swallowed exception reads as a green pass.
        try:
            BENCHES[name](scale)
        except Exception:
            failures.append(name)
            traceback.print_exc()
            print(f"[run] benchmark {name!r} FAILED", flush=True)
    status = (f"{len(failures)} FAILED ({', '.join(failures)})" if failures
              else "all passed")
    print(f"\n{len(names)} benchmark(s) in {time.time() - t0:.0f}s: "
          f"{status}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
