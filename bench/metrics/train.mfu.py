"""The window's share of the chip's bf16 peak: the model operations of
the local steps completed in the window (counted from the configuration's
shapes by ``bench.work``) over the window's seconds times the peak."""


def read(rec, ctx):
    flops = rec.counts.get("model_flops_in_window")
    if not flops:
        return None
    return 100.0 * flops / (rec.window_s * ctx["peaks"]["bf16_flops_s"])
