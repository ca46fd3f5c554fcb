"""The whole step's share of the chips' peak: the operations the
forward and backward passes need (from shapes, nothing recomputed) times
steps, over the traced window, over chips times the published bf16
peak."""


def read(ctx):
    tr = ctx["trace"]
    flops = ctx["costs"]["model_flops"] * tr.steps
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["device"]["count"]
    return 100.0 * flops / tr.window_s() / peak
