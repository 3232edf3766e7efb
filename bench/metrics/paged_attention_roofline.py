"""Kernels (kernels/paged_attention.py): least time the chip needs for the
decode attention the traced steps served (bench/kernels/paged_attention.py
and the peaks), as a share of the kernel's device time in the trace."""
from bench.roofline import share


def read(ctx):
    return share(ctx, "paged_attention")
