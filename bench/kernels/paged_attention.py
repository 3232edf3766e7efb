"""Work the paged decode kernel must do for one step, from the served
rows' context lengths alone (not from the kernel's tiling).

Per decode row with context ``c``, per layer: every query head takes a
``c``-long dot product with the keys and a ``c``-long weighted sum of
the values (4 * H * hd * c operations); every KV page that holds one of
the ``c`` tokens is read once for K and once for V; q comes in and o
goes out once.
"""
from __future__ import annotations


def cost(work, m: dict, itemsize: int = 2):
    """(operations, bytes) of one fused step's decode attention, all layers."""
    flops = byts = 0.0
    h, kv, hd, bs, L = m["h"], m["kv"], m["hd"], m["block_size"], m["L"]
    for c in work.decode_ctx:
        pages = -(-c // bs)
        flops += 4.0 * h * hd * c
        byts += 2.0 * kv * pages * bs * hd * itemsize + 2.0 * h * hd * itemsize
    return flops * L, byts * L
