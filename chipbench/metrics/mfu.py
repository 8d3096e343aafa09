"""The whole step's share of the chips' peak: the configuration's conv and
dense FLOPs per inference, times the inferences completed in the traced
window, over the window's seconds times chips times the peak of
``peaks.json``."""


def read(ctx):
    if ctx.trace is None or not ctx.traced_completed:
        return None
    done = ctx.flops_per_inference * ctx.traced_completed
    return 100.0 * done / (ctx.trace["window_s"] * len(ctx.devices) * ctx.peak_flops)
