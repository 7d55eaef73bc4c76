"""Device busy time in the traced window per planner call."""
UNIT = "ms"


def read(ctx):
    red = ctx.get("reduced")
    if red is None or not ctx.get("calls"):
        return None
    return 1e3 * red["busy_s"] / ctx["calls"]
