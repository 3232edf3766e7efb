"""Work the ragged prefill kernel must do for one step, from the served
chunks' positions alone (not from the kernel's tiling).

Per chunk of new tokens at positions ``[start, end)``, per layer: the
token at position ``p`` attends ``p + 1`` keys with every query head
(4 * H * hd * (p + 1) operations, causal); every KV page that holds one
of the ``end`` tokens is read once for K and once for V per chunk; the
chunk's q comes in and its o goes out once.
"""
from __future__ import annotations


def cost(work, m: dict, itemsize: int = 2):
    """(operations, bytes) of one fused step's prefill attention, all layers."""
    flops = byts = 0.0
    h, kv, hd, bs, L = m["h"], m["kv"], m["hd"], m["block_size"], m["L"]
    for start, end in work.chunks:
        n = end - start
        keys = n * (start + end + 1) / 2.0          # sum of (p + 1)
        pages = -(-end // bs)
        flops += 4.0 * h * hd * keys
        byts += 2.0 * kv * pages * bs * hd * itemsize + 2.0 * n * h * hd * itemsize
    return flops * L, byts * L
