"""Pallas TPU paged-attention decode kernel (flash-decoding style).

The serving engine's hot loop: one query token per sequence attends to a
paged KV cache (vLLM-style block pool).  TPU adaptation (DESIGN.md §3):
instead of CUDA warp-level gathers, the block table is *scalar-prefetched*
into SMEM and fed to the BlockSpec index maps, so Pallas pipelines the
HBM->VMEM page copies double-buffered while the MXU reduces the previous
page.  Accumulation is the standard running-max/denominator (flash)
reduction in fp32 VMEM scratch.

Grid: (B, KV_heads, num_pages).  The kernel reads the serving engine's
whole pool, (L, 2, N, KV, bs, hd), at a traced ``layer``: the layer is
scalar-prefetched beside the tables and the K and V index maps point at
``(layer, 0, page, head)`` and ``(layer, 1, page, head)``, so the layer
scan hands every layer the same pool buffer and no per-layer slice is
ever materialized.  Pages are head-major, so one (kv_head, page) tile is
a contiguous (block_size, head_dim) slab: the last two dims of every k/v
block are (bs, hd), which is what Mosaic's TPU lowering asks of a block
(second-minor divisible by 8, minor by 128, or equal to the array's
dims).  A token-major page (bs, KV, hd) would make the block's second-
minor dim a single KV head out of KV, which the TPU compiler refuses.  A
bf16 page fills one native (16, 128) tile at bs=16; bs=8 compiles as well.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(block_tables_ref,   # (B, nb) SMEM (scalar prefetch)
                       context_lens_ref,   # (B,)   SMEM (scalar prefetch)
                       layer_ref,          # (1,)   SMEM (scalar prefetch)
                       q_ref,              # (1, 1, G, hd) VMEM
                       k_ref,              # (1, 1, 1, 1, bs, hd) VMEM (gathered page)
                       v_ref,              # (1, 1, 1, 1, bs, hd) VMEM
                       o_ref,              # (1, 1, G, hd) VMEM
                       acc_ref,            # (G, hd) f32 scratch
                       m_ref,              # (G, 1) f32 scratch
                       l_ref,              # (G, 1) f32 scratch
                       *, bs: int, nb: int, scale: float):
    b = pl.program_id(0)
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    cl = context_lens_ref[b]

    # a page whose first token is already past the context is fully
    # masked: it would contribute alpha=1, p=0 — skipping the dot and
    # accumulate is bit-identical, and short-context rows stop paying
    # MXU time for the padded max-blocks grid
    @pl.when(n * bs < cl)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # (G, hd)
        k = k_ref[0, 0, 0, 0].astype(jnp.float32)            # (bs, hd)
        v = v_ref[0, 0, 0, 0].astype(jnp.float32)

        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (G, bs)
        token_idx = n * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        scores = jnp.where(token_idx < cl, scores, NEG_INF)

        m_prev = m_ref[...]                                  # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)                          # (G, bs)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(n == nb - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q: jnp.ndarray,
                    pool: jnp.ndarray,
                    layer: jnp.ndarray,
                    block_tables: jnp.ndarray,
                    context_lens: jnp.ndarray,
                    interpret: bool = False) -> jnp.ndarray:
    """q (B,KV,G,hd); pool (L, 2, N, KV, bs, hd), K at [layer, 0] and V at
    [layer, 1]; layer an int32 scalar; tables (B, nb); lens (B,)."""
    b, kv, g, hd = q.shape
    if q.size == 0:        # absent decode part (prefill-only iteration)
        return q
    bs = pool.shape[4]
    nb = block_tables.shape[1]
    scale = hd ** -0.5

    kernel = functools.partial(_paged_attn_kernel, bs=bs, nb=nb, scale=scale)
    grid = (b, kv, nb)

    # page index map: clamp past the sequence's last in-context page
    # ((cl-1)//bs — exactly the pages the kernel's pl.when computes), so
    # grid steps over fully-masked pages revisit the bound page and issue
    # no new HBM->VMEM copy (same trick as the ragged kernel): short-
    # context rows stop paying bandwidth for the padded max-blocks grid,
    # and table padding entries are never dereferenced.  The outer
    # maximum makes the clamp total: cl=0 (every in-repo caller clamps
    # cl>=1, but this is a public entry point) pins page 0 instead of
    # feeding a negative SMEM index to the table
    def page(bb, n, bt, cl):
        return bt[bb, jnp.minimum(n, jnp.maximum(cl[bb] - 1, 0) // bs)]

    def k_map(bb, h, n, bt, cl, lay):
        return (lay[0], 0, page(bb, n, bt, cl), h, 0, 0)

    def v_map(bb, h, n, bt, cl, lay):
        return (lay[0], 1, page(bb, n, bt, cl), h, 0, 0)

    def row_map(bb, h, n, bt, cl, lay):
        return (bb, h, 0, 0)

    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, hd), row_map),
                # the pool goes in twice, once per index map
                pl.BlockSpec((1, 1, 1, 1, bs, hd), k_map),
                pl.BlockSpec((1, 1, 1, 1, bs, hd), v_map),
            ],
            out_specs=pl.BlockSpec((1, 1, g, hd), row_map),
            scratch_shapes=[
                pltpu.VMEM((g, hd), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=interpret,
    )(block_tables, context_lens, layer, q, pool, pool)
