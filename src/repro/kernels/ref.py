"""Pure-jnp oracles for the Pallas kernels.

These are the correctness references: every kernel test sweeps shapes and
dtypes and asserts allclose against these functions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _tokens(pages: jnp.ndarray, s_max: int) -> jnp.ndarray:
    """Gathered pages (B, nb, KV, bs, hd) -> token-major (B, nb*bs, KV, hd)."""
    b, _, kv, _, hd = pages.shape
    return pages.transpose(0, 1, 3, 2, 4).reshape(b, s_max, kv, hd)


def paged_attention_ref(q: jnp.ndarray,
                        k_pool: jnp.ndarray,
                        v_pool: jnp.ndarray,
                        block_tables: jnp.ndarray,
                        context_lens: jnp.ndarray) -> jnp.ndarray:
    """Decode attention over a paged KV cache.

    q:            (B, KV, G, hd)   — grouped queries (H = KV*G)
    k_pool/v_pool:(N_blocks, KV, bs, hd) — head-major pages
    block_tables: (B, max_blocks)  int32 physical block ids
    context_lens: (B,)             int32 valid tokens per sequence
    returns:      (B, KV, G, hd)
    """
    b, kv, g, hd = q.shape
    bs = k_pool.shape[2]
    max_blocks = block_tables.shape[1]
    s_max = max_blocks * bs

    # gather pages -> (B, S_max, KV, hd)
    k = _tokens(k_pool[block_tables], s_max)
    v = _tokens(v_pool[block_tables], s_max)

    scores = jnp.einsum("bkgd,btkd->bkgt", q, k).astype(jnp.float32) / (hd ** 0.5)
    valid = jnp.arange(s_max)[None, :] < context_lens[:, None]          # (B, S)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


def ragged_segment_attention_ref(q: jnp.ndarray,
                                 k_pool: jnp.ndarray,
                                 v_pool: jnp.ndarray,
                                 block_tables: jnp.ndarray,
                                 positions: jnp.ndarray) -> jnp.ndarray:
    """Segment-blocked causal attention for a fused ragged iteration batch.

    A fused iteration's prefill chunks ("segments") are tiled into a
    dense (S, L) layout — L is the padded chunk length — so each
    segment's KV pages are gathered ONCE, not once per query token.
    Query (s, j) sits at absolute position ``positions[s, j]`` of its
    sequence and attends the pool-resident KV of *its own* sequence at
    positions ``<= positions[s, j]`` through its segment's block table —
    never across segments.  Fresh KV (this iteration's chunk tokens) must
    already be scattered into the pool: the fused runner writes before
    attending within each layer, so intra-chunk causality and
    same-iteration shared-prefix reads both resolve through the pool.
    Padding rows (j >= the chunk's real length) produce garbage that the
    caller discards.

    q:            (S, L, KV, G, hd) — grouped queries, tiled per segment
    k_pool/v_pool:(N_blocks, KV, bs, hd) — head-major pages
    block_tables: (S, max_blocks)   int32 — one table per segment
    positions:    (S, L)            int32 absolute position per token
    returns:      (S, L, KV, G, hd)

    The gather is *segment-bounded*, mirroring the native Pallas kernel
    (``kernels/ragged_attention.py``): pages past a segment's last
    attendable page (``max(positions) // bs``) are clamped to that bound
    page instead of dereferencing the table's padding entries, so a
    short chunk in a batch padded to a long table width re-reads one
    already-hot page rather than touching cold pool blocks it can never
    attend.  Bounded pages are fully masked either way — the output is
    bit-identical to an unbounded gather.
    """
    s, _, kv, g, hd = q.shape
    if q.size == 0:        # absent prefill part (decode-only iteration)
        return q
    bs = k_pool.shape[2]
    nb = block_tables.shape[1]
    s_max = nb * bs
    bounds = jnp.max(positions, axis=1) // bs                    # (S,)
    page_idx = jnp.minimum(jnp.arange(nb)[None, :], bounds[:, None])
    bt = jnp.take_along_axis(block_tables, page_idx, axis=1)
    k = _tokens(k_pool[bt], s_max)
    v = _tokens(v_pool[bt], s_max)
    scores = jnp.einsum("slkgd,stkd->skglt", q, k).astype(jnp.float32) / (hd ** 0.5)
    keep = positions[:, None, None, :, None] >= \
        jnp.arange(s_max)[None, None, None, None, :]
    scores = jnp.where(keep, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("skglt,stkd->slkgd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


def chunked_prefill_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                                  window: int | None = None) -> jnp.ndarray:
    """Causal (optionally sliding-window) attention oracle.

    q (B,S,KV,G,hd); k/v (B,S,KV,hd) -> (B,S,KV,G,hd)
    """
    s = q.shape[1]
    hd = q.shape[-1]
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32) / (hd ** 0.5)
    qi = jnp.arange(s)[:, None]
    kj = jnp.arange(s)[None, :]
    mask = qi >= kj
    if window is not None:
        mask &= (qi - kj) < window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v).astype(q.dtype)
