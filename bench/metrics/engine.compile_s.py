"""Engine (serving/engine.py): seconds the engines spent on fused steps
issued on shapes new to them (tracing, lowering, compiling or loading a
program) inside the window: the window delta of their ``compile_s``
counters.  Nothing when the program keeps no such counter."""


def read(ctx):
    if not any(k.startswith("engine") and k.endswith(".compile_s") for k in ctx._s1):
        return None
    return ctx.delta("compile_s")
