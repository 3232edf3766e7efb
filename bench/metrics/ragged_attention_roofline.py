"""Kernels (kernels/ragged_attention.py): least time the chip needs for the
prefill-chunk attention the traced steps served
(bench/kernels/ragged_attention.py and the peaks), as a share of the
kernel's device time in the trace."""
from bench.roofline import share


def read(ctx):
    return share(ctx, "ragged_attention")
