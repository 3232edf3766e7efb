"""Work the ragged prefill kernel must do for one step, from the served
chunks' positions alone (not from the kernel's tiling).

Per chunk of new tokens at positions ``[start, end)``, per attention
layer: the token at position ``p`` attends ``p + 1`` keys (causal), or
the last ``min(p + 1, window)`` where the layer has a window, with every
query head (4 * H * hd operations a key); every KV page that holds one
of the keys the chunk attends, positions ``[lo, end)``, is read once for
K and once for V per chunk (at the least ``ceil((end - lo) /
block_size)`` pages); the chunk's q comes in and its o goes out once.
``m["attn"]`` lists the attention layers in groups of ``n`` layers with
like heads and window.
"""
from __future__ import annotations


def _keys(start: int, end: int, window) -> float:
    """Sum over p in [start, end) of the keys position p attends."""
    if window is None:
        return (end - start) * (start + end + 1) / 2.0
    return float(sum(min(p + 1, window) for p in range(start, end)))


def cost(work, m: dict, itemsize: int = 2):
    """(operations, bytes) of one fused step's prefill attention, all layers."""
    bs = m["block_size"]
    flops_all = byts_all = 0.0
    for g in m["attn"]:
        flops = byts = 0.0
        h, kv, hd, w = g["h"], g["kv"], g["hd"], g["window"]
        for start, end in work.chunks:
            n = end - start
            lo = 0 if w is None else max(0, start + 1 - w)
            pages = -(-(end - lo) // bs)
            flops += 4.0 * h * hd * _keys(start, end, w)
            byts += 2.0 * kv * pages * bs * hd * itemsize + 2.0 * n * h * hd * itemsize
        flops_all += flops * g["n"]
        byts_all += byts * g["n"]
    return flops_all, byts_all
