"""From a profiler trace to the numbers the per-layer readers take.

A trace is reduced to a small plain form first (``Trace``): per device the
operations that ran on it and the compiled programs (XLA modules) they ran
in, plus the benchmark's own host spans (``bench.*`` annotations), all in
seconds on one clock.  Everything after that is arithmetic on intervals,
checked on a small recorded trace in ``tests/bench``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

HOST_PREFIX = "bench."
# Operations that hold others (a scan's loop); only what they hold counts.
_CONTAINER = re.compile(r"^%?(while|conditional|call)\b")
WINDOW_SPAN = "bench.window"
# Stats whose text helps recognise an operation (kernel or op names).
_TEXT_STATS = ("hlo_op", "long_name", "tf_op", "kernel_details", "name")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float          # seconds
    dur: float            # seconds
    module: str = ""      # XLA module (compiled program) it ran in
    text: str = ""        # name plus descriptive stats, for matching

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    devices: list[list[Event]]      # per device: operations
    modules: list[list[Event]]      # per device: module executions
    host: list[Event]               # bench.* host spans
    window: tuple[float, float]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        """A trace kept as plain lists (the recorded test trace)."""
        ev = lambda r: Event(*r)  # noqa: E731
        return cls(devices=[[ev(r) for r in dev] for dev in d["devices"]],
                   modules=[[ev(r) for r in dev] for dev in d["modules"]],
                   host=[ev(r) for r in d["host"]],
                   window=tuple(d["window"]))


# ---------------------------------------------------------------------------
# Reading the profiler's file
# ---------------------------------------------------------------------------


def _stats(e) -> dict:
    try:
        return dict(e.stats)
    except Exception:  # stats the reader cannot decode carry no names
        return {}


def _short(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _text(name: str, stats: dict) -> str:
    parts = [name] + [str(stats[k]) for k in _TEXT_STATS if k in stats]
    return " ".join(parts)


def _module_of(mods: list[Event], starts: list[float], t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and mods[i].start <= t <= mods[i].end:
        return mods[i].name
    return ""


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "SparseCore" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [Event(e.name, e.start_ns * 1e-9,
                                  e.duration_ns * 1e-9)
                            for e in line.events]
                elif line.name == "XLA Ops":
                    for e in line.events:
                        if _CONTAINER.match(e.name):
                            continue
                        st = _stats(e)
                        ops.append(Event(_short(e.name), e.start_ns * 1e-9,
                                         e.duration_ns * 1e-9,
                                         str(st.get("hlo_module", "")),
                                         _text(e.name, st)))
            mods.sort(key=lambda e: e.start)
            starts = [m.start for m in mods]
            ops = [e if e.module else dataclasses.replace(
                e, module=_module_of(mods, starts, e.start)) for e in ops]
            ops.sort(key=lambda e: e.start)
            devices.append(ops)
            modules.append(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append(Event(e.name, e.start_ns * 1e-9,
                                          e.duration_ns * 1e-9))
    win = [e for e in host if e.name == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    return Trace(devices, modules, host, (win[0].start, win[0].end))


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------


def _clipped(events, lo: float, hi: float) -> list[tuple[float, float]]:
    out = []
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            out.append((a, b))
    out.sort()
    return out


# Events are stamped in nanoseconds; nearer than this, two intervals touch
# (the difference is rounding of the seconds, not a gap).
_TOUCH = 1e-12


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in intervals:
        if merged and a <= merged[-1][1] + _TOUCH:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_seconds(trace: Trace) -> float:
    """Seconds of the window in which some operation ran, averaged over
    the devices."""
    lo, hi = trace.window
    per = [sum(b - a for a, b in _union(_clipped(d, lo, hi)))
           for d in trace.devices]
    return sum(per) / len(per) if per else 0.0


def idle_share(trace: Trace) -> float | None:
    """1 - busy / window, or None when the trace has no device or
    nothing ran."""
    busy = busy_seconds(trace)
    if not trace.devices or busy <= 0 or trace.window_s <= 0:
        return None
    return 1.0 - busy / trace.window_s


def op_seconds(trace: Trace, op: str, module: str | None = None) -> float:
    """Device seconds, in the window and averaged over devices, of the
    operations whose text matches regex ``op`` (inside modules matching
    ``module``, if given)."""
    lo, hi = trace.window
    rop = re.compile(op)
    rmod = re.compile(module) if module else None
    per = []
    for d in trace.devices:
        sel = [e for e in d if rop.search(e.text or e.name)
               and (rmod is None or rmod.search(e.module))]
        per.append(sum(b - a for a, b in _clipped(sel, lo, hi)))
    return sum(per) / len(per) if per else 0.0


def op_count(trace: Trace, op: str) -> float:
    """Executions of operations matching ``op`` that began in the window,
    averaged over devices."""
    lo, hi = trace.window
    r = re.compile(op)
    per = [sum(1 for e in d if r.search(e.text or e.name)
               and lo <= e.start < hi) for d in trace.devices]
    return sum(per) / len(per) if per else 0.0


def module_count(trace: Trace, module: str) -> float:
    """Executions of modules matching ``module`` that began in the window,
    averaged over devices."""
    lo, hi = trace.window
    r = re.compile(module)
    per = [sum(1 for m in mods if r.search(m.name) and lo <= m.start < hi)
           for mods in trace.modules]
    return sum(per) / len(per) if per else 0.0


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` operation names that took most device time in the window
    (seconds, averaged over devices)."""
    lo, hi = trace.window
    tot: dict[str, float] = {}
    for d in trace.devices:
        for e in d:
            a, b = max(e.start, lo), min(e.end, hi)
            if b > a:
                tot[e.name] = tot.get(e.name, 0.0) + (b - a)
    k = max(1, len(trace.devices))
    return [[name, s / k] for name, s in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` longest stretches of the window in which device 0 ran
    nothing, each named by the innermost benchmark host span around its
    middle (``idle`` when none)."""
    if not trace.devices:
        return []
    lo, hi = trace.window
    busy = _union(_clipped(trace.devices[0], lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t + _TOUCH:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t + _TOUCH:
        gaps.append((t, hi))
    spans = [e for e in trace.host if e.name != WINDOW_SPAN]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (a + b)
        around = [e for e in spans if e.start <= mid <= e.end]
        name = min(around, key=lambda e: e.dur).name if around else "idle"
        out.append([name[len(HOST_PREFIX):] if name.startswith(HOST_PREFIX)
                    else name, b - a])
    return out
