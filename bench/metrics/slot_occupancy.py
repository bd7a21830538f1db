"""Share of decode slots holding a request, over the window's decode
waves (``ServingEngine.scheduler_report`` counters, differenced)."""


def read(ctx):
    s = ctx.get("sched")
    if not s or not s["steps"] or s["slot_occupancy"] is None:
        return None
    return 100.0 * s["slot_occupancy"]
