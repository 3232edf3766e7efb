"""Model step: operations the traced steps' served tokens need (the
configuration's architecture module counts them: for a dense stack 2 per
matrix parameter per computed token, the LM head for each row whose
token is sampled, attention from each token's position) over the traced
window's wall time times the chip's peak bf16 rate, in percent."""


def read(ctx):
    if not ctx.trace or not ctx.work:
        return None
    flops = sum(ctx.arch.step_flops(w, ctx.m) for w in ctx.work)
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx.trace["window_s"] * ctx.peaks["bf16_flops"])
