"""Roofline and model-FLOP arithmetic shared by the per-layer readers."""
from __future__ import annotations

import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


def kernel_cost(name: str):
    path = HERE / "kernels" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_kernel_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.cost


def share(ctx, kernel: str):
    """Percent of the kernel's traced device time that the chip's peaks
    say its served work needs at the least; None when the trace holds no
    such kernel time or no such work."""
    if not ctx.trace:
        return None
    cost = kernel_cost(kernel)
    t_min = t_dev = 0.0
    for s in ctx.trace["steps"]:
        if s["work"] is None:
            continue
        flops, byts = cost(s["work"], ctx.m)
        t_min += max(flops / ctx.peaks["bf16_flops"],
                     byts / ctx.peaks["hbm_bytes_per_s"])
        t_dev += s["kernels"].get(kernel, 0.0)
    if t_dev <= 0 or t_min <= 0:
        return None
    return 100.0 * t_min / t_dev


def step_flops(work, m: dict) -> float:
    """Operations one step's served tokens need."""
    d, h, kv, hd, f, v, L = m["d"], m["h"], m["kv"], m["hd"], m["f"], m["v"], m["L"]
    per_token = 2.0 * L * (d * (h + 2 * kv) * hd + h * hd * d + 3 * d * f)
    attn = 4.0 * L * h * hd
    n_tok = len(work.decode_ctx) + sum(e - s for s, e in work.chunks)
    keys = sum(work.decode_ctx) + sum((e - s) * (s + e + 1) / 2.0
                                      for s, e in work.chunks)
    return per_token * n_tok + attn * keys + 2.0 * d * v * work.n_emit
