"""Instance scheduler: the share of prompt tokens served from the prefix
cache over the window, from the engines' counters:
saved / (computed + saved), window deltas."""


def read(ctx):
    saved = ctx.delta("prefill_tokens_saved")
    computed = ctx.delta("prefill_tokens")
    total = saved + computed
    return saved / total if total > 0 else None
