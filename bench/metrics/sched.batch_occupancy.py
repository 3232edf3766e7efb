"""Instance scheduler: mean rows per fused step (the engine's
``batch_occupancy`` observations) over the window."""


def read(ctx):
    n = ctx.delta("batch_occupancy.count")
    if n <= 0:
        return None
    total = ctx.delta_product("batch_occupancy.mean", "batch_occupancy.count")
    return total / n
