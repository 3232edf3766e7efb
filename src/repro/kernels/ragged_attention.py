"""Pallas TPU ragged segment-attention kernel (native, segment-tiled).

The prefill half of a fused :class:`~repro.serving.batch_scheduler.
IterationBatch`: each prompt chunk ("segment") is a dense (L,) tile of
queries at arbitrary absolute positions, attending its own sequence's
pool-resident KV through a per-segment block table under a
segment-blocked causal mask.

PR 3 lowered this by flatten-and-repeat onto the single-query *decode*
kernel: S·L grid rows, the segment's block table repeated per query row,
and one (1, 1, G, hd) query tile per MXU step — every page of a chunk's
context re-gathered once per query token, and the MXU fed single-token
tiles exactly where Sarathi-style chunked prefill concentrates work.
This kernel is the native formulation:

* **grid (segment, kv_head, kv_page)** — one online-softmax pass per
  (segment, head) pair, pages innermost so each page of a segment's
  context is DMA'd into VMEM exactly ONCE and reduced against the whole
  query slab of that head: the wrapper lays q out as (S, KV, L·G, hd),
  so each step is one (L·G, bs) MXU product instead of L separate
  (G, bs) steps;
* **head-major pages** — pages are (KV, bs, hd), so a (kv_head, page)
  k/v block is a dense (bs, hd) tile, the block shape the TPU compiler
  accepts;
* **the whole pool at a traced layer** — the kernel takes the serving
  engine's pool (L, 2, N, KV, bs, hd) and a ``layer`` scalar, and its K
  and V index maps point at ``(layer, 0, page, head)`` and ``(layer, 1,
  page, head)``: the layer scan carries one pool buffer and never
  slices a layer out of it;
* **scalar-prefetched block tables** — like the decode kernel, the
  per-segment table, page bound and layer live in SMEM and feed the
  BlockSpec index maps, so Pallas double-buffers the HBM→VMEM page
  copies;
* **per-segment page bounds** — a segment only *visits* pages up to
  ``max(positions) // bs``: beyond its bound the k/v index maps clamp to
  the bound page, and consecutive grid steps with an unchanged block
  index issue no new copy (the standard Pallas revisit trick), while
  ``pl.when`` skips the compute.  Short chunks in a batch padded to a
  long table width stop paying bandwidth or MXU time for pages they can
  never attend;
* **online softmax** in fp32 VMEM scratch (running max / denominator),
  identical accumulation scheme to the decode kernel.

Padding query rows (j >= the chunk's real length) carry position 0,
attend token 0 of the (clamped) first page, and produce garbage the
caller discards — they can never NaN (token 0 is always unmasked).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ragged_attn_kernel(block_tables_ref,   # (S, nb) SMEM (scalar prefetch)
                        page_bounds_ref,    # (S,)    SMEM (scalar prefetch)
                        layer_ref,          # (1,)    SMEM (scalar prefetch)
                        q_ref,              # (1, 1, L*G, hd) VMEM
                        pos_ref,            # (1, L*G, 1) VMEM
                        k_ref,              # (1, 1, 1, 1, bs, hd) VMEM (gathered page)
                        v_ref,              # (1, 1, 1, 1, bs, hd) VMEM
                        o_ref,              # (1, 1, L*G, hd) VMEM
                        acc_ref,            # (L*G, hd) f32 scratch
                        m_ref,              # (L*G, 1) f32 scratch
                        l_ref,              # (L*G, 1) f32 scratch
                        *, bs: int, nb: int, scale: float):
    s = pl.program_id(0)
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # pages past the segment's bound are never gathered (the index map
    # clamps to the bound page — no new DMA) and never reduced
    @pl.when(n <= page_bounds_ref[s])
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                   # (L*G, hd)
        k = k_ref[0, 0, 0, 0].astype(jnp.float32)             # (bs, hd)
        v = v_ref[0, 0, 0, 0].astype(jnp.float32)

        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (L*G, bs)
        # segment-blocked causal mask: query row r at absolute position
        # pos[r] sees pool tokens of its own table at indices <= pos[r]
        token_idx = n * bs + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        scores = jnp.where(pos_ref[0] >= token_idx, scores, NEG_INF)

        m_prev = m_ref[...]                                   # (L*G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)                           # (L*G, bs)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(n == nb - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ragged_segment_attention(q: jnp.ndarray,
                             pool: jnp.ndarray,
                             layer: jnp.ndarray,
                             block_tables: jnp.ndarray,
                             positions: jnp.ndarray,
                             interpret: bool = False) -> jnp.ndarray:
    """q (S, L, KV, G, hd); pool (L_layers, 2, N, KV, bs, hd), K at
    [layer, 0] and V at [layer, 1]; layer an int32 scalar; tables (S, nb);
    positions (S, L) absolute position per query token.  Returns
    (S, L, KV, G, hd).  See ``kernels/ref.py`` for mask semantics."""
    s, lq, kv, g, hd = q.shape
    if q.size == 0:        # absent prefill part (decode-only iteration)
        return q
    bs = pool.shape[4]
    nb = block_tables.shape[1]
    scale = hd ** -0.5
    # last page each segment can attend: max position // bs (padding rows
    # sit at position 0 and never raise the bound)
    page_bounds = jnp.max(positions, axis=1) // bs            # (S,)
    # one (L*G, hd) query slab per (segment, kv head): row r = j*G + g is
    # token j's g-th grouped head, so the slab's last two dims are dense
    # tiles on the TPU and the MXU sees L*G rows per page
    rows = lq * g
    q_t = q.transpose(0, 2, 1, 3, 4).reshape(s, kv, rows, hd)
    pos_rows = jnp.repeat(positions, g, axis=1).reshape(s, rows, 1)

    kernel = functools.partial(_ragged_attn_kernel, bs=bs, nb=nb, scale=scale)
    grid = (s, kv, nb)

    def k_map(ss, h, n, bt, bounds, lay):
        return (lay[0], 0, bt[ss, jnp.minimum(n, bounds[ss])], h, 0, 0)

    def v_map(ss, h, n, bt, bounds, lay):
        return (lay[0], 1, bt[ss, jnp.minimum(n, bounds[ss])], h, 0, 0)

    def slab_map(ss, h, n, bt, bounds, lay):
        return (ss, h, 0, 0)

    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, rows, hd), slab_map),
                pl.BlockSpec((1, rows, 1),
                             lambda ss, h, n, bt, bounds, lay: (ss, 0, 0)),
                # the pool goes in twice, once per index map
                pl.BlockSpec((1, 1, 1, 1, bs, hd), k_map),
                pl.BlockSpec((1, 1, 1, 1, bs, hd), v_map),
            ],
            out_specs=pl.BlockSpec((1, 1, rows, hd), slab_map),
            scratch_shapes=[
                pltpu.VMEM((rows, hd), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s, kv, rows, hd), q.dtype),
        interpret=interpret,
    )(block_tables, page_bounds, layer, q_t, pos_rows, pool, pool)
    return out.reshape(s, kv, lq, g, hd).transpose(0, 2, 1, 3, 4)
