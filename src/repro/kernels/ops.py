"""jit'd public wrappers around the Pallas kernels.

On TPU the Pallas path compiles natively; in this CPU container the kernel
body executes under ``interpret=True``.  ``backend="ref"`` selects the
pure-jnp oracle (used by the serving engine on CPU for speed — interpret
mode is a correctness tool, not fast).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.paged_attention import paged_attention as _paged_pallas
from repro.kernels.ragged_attention import (
    ragged_segment_attention as _ragged_pallas,
)


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


@functools.partial(jax.jit, static_argnames=("backend",))
def paged_attention(q, pool, layer, block_tables, context_lens, backend: str = "ref"):
    """Decode attention over layer ``layer`` of the whole paged KV pool
    (L, 2, N, KV, bs, hd).  See kernels/ref.py for the other shapes."""
    if backend in ("pallas", "interpret"):
        return _paged_pallas(q, pool, layer, block_tables, context_lens,
                             interpret=backend == "interpret")
    if backend != "ref":
        raise ValueError(f"unknown paged attention backend {backend!r}")
    return _ref.paged_attention_ref(q, pool[layer, 0], pool[layer, 1],
                                    block_tables, context_lens)


@functools.partial(jax.jit, static_argnames=("backend",))
def ragged_segment_attention(q, pool, layer, block_tables, positions,
                             backend: str = "ref"):
    """Segment-blocked causal attention over layer ``layer`` of the whole
    paged pool (L, 2, N, KV, bs, hd) for the prefill part of a fused
    :class:`~repro.serving.batch_scheduler.IterationBatch` — every chunk's
    tokens tiled to (S, L).  See ``kernels/ref.py`` for the other shapes
    and mask semantics.

    Backends
    --------
    ``"pallas"`` / ``"interpret"``
        The native segment-tiled kernel (``kernels/ragged_attention.py``):
        grid (segment, kv_head, kv_page), scalar-prefetched per-segment
        block tables, (L·G, hd) query tiles with online-softmax scratch,
        and per-segment page bounds so a segment only visits pages up to
        ``max(positions) // bs``.
    ``"flat"`` / ``"flat_interpret"`` / ``"flat_ref"``
        The PR 3 flatten-and-repeat lowering onto the single-query paged
        *decode* path — S·L query rows, block tables repeated per row,
        each row's context length set to ``position + 1`` — kept as the
        differential-testing reference for the native kernel (the suffix
        picks which decode backend executes it).
    ``"ref"``
        Pure-jnp oracle with the same segment-bounded page gather as the
        native kernel.
    """
    if q.size == 0:        # absent prefill part (decode-only iteration):
        return q           # every backend must no-op, not trace 0 rows
    if backend in ("pallas", "interpret"):
        return _ragged_pallas(q, pool, layer, block_tables, positions,
                              interpret=backend == "interpret")
    if backend in ("flat", "flat_interpret", "flat_ref"):
        s, lq, kv, g, hd = q.shape
        flat_q = q.reshape(s * lq, kv, g, hd)
        flat_bt = jnp.repeat(block_tables, lq, axis=0)
        flat_cl = positions.reshape(-1) + 1
        if backend == "flat_ref":
            out = _ref.paged_attention_ref(flat_q, pool[layer, 0],
                                           pool[layer, 1], flat_bt, flat_cl)
        else:
            out = _paged_pallas(flat_q, pool, layer, flat_bt, flat_cl,
                                interpret=backend == "flat_interpret")
        return out.reshape(s, lq, kv, g, hd)
    if backend != "ref":
        # a typo'd backend must not silently compile the dense jnp oracle
        # into a device hot loop (token-identical, so nothing else catches it)
        raise ValueError(f"unknown ragged attention backend {backend!r}")
    return _ref.ragged_segment_attention_ref(
        q, pool[layer, 0], pool[layer, 1], block_tables, positions)
