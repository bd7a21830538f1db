"""Host-clock time of one decode wave (the engine's ``decode_wall_s``,
which blocks on the wave's tokens, over the window's waves)."""


def read(ctx):
    s = ctx.get("sched")
    if not s or not s["steps"]:
        return None
    return 1e3 * s["decode_wall_s"] / s["steps"]
