"""Engine (serving/engine.py, the fused step of ``_build_fused``): mean
device time of one execution of the fused-step program in the trace."""


def read(ctx):
    steps = ctx.trace["steps"] if ctx.trace else []
    if not steps:
        return None
    return 1e3 * sum(s["module_s"] for s in steps) / len(steps)
