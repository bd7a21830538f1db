"""Straggler / hang mitigation for the training and serving loops.

``launch/train.py`` brackets its optimizer steps with these; ``ServingEngine``
brackets every decode step the same way and surfaces the counters
(stragglers, EMA step time, hangs) through ``perf_report``.

Two cooperating pieces, both host-side (the device program is SPMD and
lock-stepped — detection must happen at the host boundary):

  * ``StepWatchdog`` — per-step wall-time tracker with an EMA baseline.
    A step slower than ``slow_factor`` x EMA is flagged (straggler); a step
    exceeding ``hang_timeout_s`` triggers the ``on_hang`` callback from a
    monitor thread (at fleet scale: report the host to the coordinator so
    the job restarts without it; here: log + raise).
  * ``Heartbeat`` — writes ``heartbeat_<host>.json`` (step, wall time,
    monotonically increasing counter) so an external supervisor
    (launch/train.py --supervise, or the cluster manager) can distinguish
    "slow" from "dead" and act per host.

The counters feed EXPERIMENTS.md's fault-tolerance test: kill -9 mid-run,
restart, verify bit-identical continuation from the atomic checkpoint.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import threading
import time
from typing import Callable


@dataclasses.dataclass
class StepWatchdog:
    slow_factor: float = 2.5
    hang_timeout_s: float = 600.0
    ema_alpha: float = 0.1
    on_hang: Callable[[float], None] | None = None

    def __post_init__(self):
        self.ema_s: float | None = None
        self.stragglers: list[tuple[int, float]] = []
        self._step_start: float | None = None
        self._step_idx = 0
        self._stop = threading.Event()
        self._monitor: threading.Thread | None = None

    # -- step bracketing -------------------------------------------------------

    def start_step(self, step: int, now: float | None = None) -> None:
        """``now``: the caller's own ``time.perf_counter()`` reading of the
        step's start, so one clock read serves both."""
        self._step_idx = step
        self._step_start = time.perf_counter() if now is None else now
        if self._monitor is None and self.on_hang is not None:
            self._monitor = threading.Thread(target=self._watch, daemon=True)
            self._monitor.start()

    def end_step(self, now: float | None = None) -> dict:
        assert self._step_start is not None, "end_step before start_step"
        dt = (time.perf_counter() if now is None else now) - self._step_start
        self._step_start = None
        is_straggler = self.ema_s is not None and dt > self.slow_factor * \
            self.ema_s
        if is_straggler:
            self.stragglers.append((self._step_idx, dt))
        # EMA excludes flagged steps so one hiccup doesn't poison the baseline
        if not is_straggler:
            self.ema_s = dt if self.ema_s is None else (
                (1 - self.ema_alpha) * self.ema_s + self.ema_alpha * dt)
        return {"step_time_s": dt, "ema_s": self.ema_s,
                "straggler": is_straggler}

    def _watch(self) -> None:
        while not self._stop.wait(1.0):
            start = self._step_start
            if start is None:
                continue
            waited = time.perf_counter() - start
            if waited > self.hang_timeout_s:
                self.on_hang(waited)
                return

    def close(self) -> None:
        self._stop.set()


@dataclasses.dataclass
class Heartbeat:
    directory: str | os.PathLike
    host_id: int = 0

    def __post_init__(self):
        self.directory = pathlib.Path(self.directory)
        self.directory.mkdir(parents=True, exist_ok=True)
        self._count = 0

    @property
    def path(self) -> pathlib.Path:
        return self.directory / f"heartbeat_{self.host_id}.json"

    def beat(self, step: int, **extra) -> None:
        self._count += 1
        tmp = self.path.with_suffix(f".tmp-{os.getpid()}")
        tmp.write_text(json.dumps(
            {"host": self.host_id, "step": step, "count": self._count,
             "time": time.time(), **extra}))
        os.rename(tmp, self.path)

    @staticmethod
    def read_all(directory) -> list[dict]:
        out = []
        for p in pathlib.Path(directory).glob("heartbeat_*.json"):
            try:
                out.append(json.loads(p.read_text()))
            except (json.JSONDecodeError, OSError):
                pass  # torn read: supervisor retries next poll
        return sorted(out, key=lambda h: h["host"])

    @staticmethod
    def stale_hosts(directory, timeout_s: float = 120.0) -> list[int]:
        now = time.time()
        return [h["host"] for h in Heartbeat.read_all(directory)
                if now - h["time"] > timeout_s]
