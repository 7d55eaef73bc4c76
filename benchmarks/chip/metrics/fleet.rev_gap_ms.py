"""Mean device idle across the boundary of two consecutive revolution
dispatches in the traced window: the telemetry sync, the ring flush and
the next dispatch."""
from chipbench import trace as tr

UNIT = "ms"


def read(ctx):
    trace = ctx.get("trace")
    if trace is None:
        return None
    gaps = tr.program_gaps(trace, *ctx["window"])
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e6
