"""Share of the device time in the traced window spent in all-gathers:
the tensor-parallel fleet's gathers of each sharded projection's columns.

An operation counts by its own name (``all-gather``, ``all-gather-start``,
``all-gather-done``, numbered), never by an operand it reads.  Its time in
the window, averaged over the devices, over the devices' busy time (the
union of their operations' intervals, averaged alike); 0 where none
ran, as on one chip."""
import re

from bench import trace

GATHER = re.compile(r"^all-gather")


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr.devices:
        return None
    lo, hi = tr.window
    per = [sum(min(e.end, hi) - max(e.start, lo) for e in d
               if GATHER.match(e.name) and min(e.end, hi) > max(e.start, lo))
           for d in tr.devices]
    busy = trace.busy_seconds(tr)
    if busy <= 0:
        return None
    return 100.0 * (sum(per) / len(per)) / busy
