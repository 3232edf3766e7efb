"""Roofline arithmetic shared by the kernels' per-layer readers."""
from __future__ import annotations

from pathlib import Path

from bench.model import load_module

HERE = Path(__file__).resolve().parent


def kernel_cost(name: str):
    return load_module(HERE / "kernels" / f"{name}.py").cost


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
