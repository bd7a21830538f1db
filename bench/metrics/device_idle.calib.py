"""Share of the traced window in which no operation ran on the device
(1 - union of device operation intervals / window)."""
from bench import trace


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    idle = trace.idle_share(tr)
    return None if idle is None else 100.0 * idle
