"""Model step: operations the traced steps' served tokens need (2 per
matrix parameter per computed token, the LM head for each row whose
token is sampled, attention from each token's position) over the traced
window's wall time times the chip's peak bf16 rate, in percent."""
from bench.roofline import step_flops


def read(ctx):
    if not ctx.trace or not ctx.work:
        return None
    flops = sum(step_flops(w, ctx.m) for w in ctx.work)
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx.trace["window_s"] * ctx.peaks["bf16_flops"])
