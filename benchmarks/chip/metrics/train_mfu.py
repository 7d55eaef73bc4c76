"""Model FLOPs of the traced calls' useful images over the traced window,
the chips and their bf16 peak: the whole step's share of the peak."""
UNIT = "%"


def read(ctx):
    red, n_steps = ctx.get("reduced"), ctx.get("n_steps")
    if red is None or n_steps is None:
        return None
    flops = int(n_steps.sum()) * ctx["batch_size"] \
        * ctx["train_flops_per_image"]
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / (red["window_s"] * peak)
