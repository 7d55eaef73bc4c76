"""Planned steps over steps the device ran in the traced calls (masked
padding steps compute in full).  The steps run are counted in the trace:
each run of the main program's heaviest operation, which lives in the
step's body, is one step of every plane on that chip."""
from chipbench import trace as tr

UNIT = "%"


def read(ctx):
    trace, n_steps = ctx.get("trace"), ctx.get("n_steps")
    if trace is None or n_steps is None:
        return None
    runs = tr.op_runs(trace, *ctx["window"])
    if not runs:
        return None
    heaviest = max(runs, key=lambda name: runs[name][1])
    return 100.0 * int(n_steps.sum()) / (runs[heaviest][0] * ctx["planes"])
