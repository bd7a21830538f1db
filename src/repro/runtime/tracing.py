"""Program spans on the profiler's clock, plus always-on span totals.

``span("engine.prefill", totals, request=7)`` opens a
``jax.profiler.TraceAnnotation`` named ``repro.engine.prefill``: while a
profiler runs (``jax.profiler.trace``), the event lands in the trace's host
plane on the clock the device events use, with its attributes as stats.
With no profiler running the annotation costs about a microsecond.

When ``totals`` is given the span also adds its ``time.perf_counter``
duration and a count to ``totals[name]`` (``[seconds, count]``): the
counter an operator reads without a profiler (``scheduler_report()``).

Attribute values must be host ints or strings: a device array would force
a wait for the device.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

PREFIX = "repro."


class span:
    """Context manager: one program span.  ``start``/``end`` hold its
    ``perf_counter`` bounds once entered/exited."""

    __slots__ = ("name", "totals", "attrs", "start", "end", "_ann")

    def __init__(self, name: str, totals: dict | None = None, **attrs):
        self.name = name
        self.totals = totals
        self.attrs = attrs
        self.start = self.end = None

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(PREFIX + self.name, **self.attrs)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._ann.__exit__(*exc)
        if self.totals is not None:
            acc = self.totals.setdefault(self.name, [0.0, 0])
            acc[0] += self.end - self.start
            acc[1] += 1
