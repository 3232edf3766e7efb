"""Work the paged decode kernel must do for one step, from the served
rows' context lengths alone (not from the kernel's tiling).

Per decode row with context ``c``, per attention layer: the row attends
``k = c`` keys, or the last ``k = min(c, window)`` where the layer has a
window; every query head takes a ``k``-long dot product with the keys
and a ``k``-long weighted sum of the values (4 * H * hd * k operations);
every KV page that holds one of those keys is read once for K and once
for V (at the least ``ceil(k / block_size)`` pages); q comes in and o
goes out once.  ``m["attn"]`` lists the attention layers in groups of
``n`` layers with like heads and window.
"""
from __future__ import annotations


def cost(work, m: dict, itemsize: int = 2):
    """(operations, bytes) of one fused step's decode attention, all layers."""
    bs = m["block_size"]
    flops_all = byts_all = 0.0
    for g in m["attn"]:
        flops = byts = 0.0
        h, kv, hd, w = g["h"], g["kv"], g["hd"], g["window"]
        for c in work.decode_ctx:
            k = c if w is None else min(c, w)
            pages = -(-k // bs)
            flops += 4.0 * h * hd * k
            byts += 2.0 * kv * pages * bs * hd * itemsize + 2.0 * h * hd * itemsize
        flops_all += flops * g["n"]
        byts_all += byts * g["n"]
    return flops_all, byts_all
