"""Instance scheduler: the share of the KV pool held by running calls
(not by blocks the prefix cache keeps for reuse), averaged over the
window's steps, in percent."""


def read(ctx):
    s = ctx.pool_live
    return 100.0 * sum(s) / len(s) if s else None
