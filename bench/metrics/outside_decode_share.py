"""Share of the window spent outside the engine's decode calls: prefill,
insert, and host time between waves (1 - decode_wall_s / window)."""


def read(ctx):
    s = ctx.get("sched")
    if not s or not s["steps"] or not ctx.get("window_s"):
        return None
    return 100.0 * (1.0 - s["decode_wall_s"] / ctx["window_s"])
