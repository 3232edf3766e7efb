"""LLM engine instance: paged-KV model runner + continuous batching.

``PagedModelRunner`` executes real tokens with the paged KV pool (the
Pallas kernel's layout; ref backend on CPU, pallas on TPU).
``LLMEngine`` implements vLLM-style continuous batching with dynamic
memory allocation and preemption-by-recompute — the behaviours the paper's
dispatcher is designed around (§2.2.3).

Engines expose the *status monitor* surface Kairos polls (§3 overview):
KV memory in use / capacity, running/waiting counts, preemption counter.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.scheduler import SchedulerPolicy
from repro.kernels import ops as kops
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_TRACER, Tracer, span
from repro.models import attention as attn_mod
from repro.models.layers import embed_tokens, lm_logits, rms_norm, swiglu
from repro.models.model import LanguageModel
from repro.models.moe import moe_ffn
from repro.models.sharding import (
    POOL_PSPEC,
    serving_param_specs,
    validate_serving_tp,
)
from repro.serving.batch_scheduler import (
    BatchScheduler,
    IterationBatch,
    SchedStats,
    TokenPrefixMatcher,
    flatten_plan,
)
from repro.serving.faults import InstanceCrashed
from repro.serving.kv_cache import BlockManager
from repro.serving.prefix_cache import PrefixCache
from repro.serving.request import Request


# =============================================================================
# Paged model runner (uniform-attention architectures)
# =============================================================================


def _layer_qkv(lp, xx, sin, cos, cfg):
    """Shared transformer-layer head for every runner path: pre-norm, QKV
    projection, RoPE on q/k.  The paths differ only in how the fresh KV is
    scattered and which attention kernel consumes it."""
    h = rms_norm(xx, lp["ln1"], cfg.norm_eps)
    q, k, v = attn_mod._project_qkv(lp["attn"], h, h, cfg)
    return attn_mod.apply_rope(q, sin, cos), attn_mod.apply_rope(k, sin, cos), v


def _layer_finish(xx, o, lp, cfg, axis: Optional[str] = None):
    """Shared transformer-layer tail: attention output projection and the
    FFN/MoE block, both residual.  ``o`` is (B, S, H*hd).

    ``axis`` names the tensor-parallel mesh axis when this body runs
    inside shard_map: ``o`` then holds the LOCAL head slice and ``wo``
    the matching row slice, so the projection yields a partial sum —
    the all-reduce here, plus the matching one after the row-sharded
    FFN down-projection, are the standard two megatron collectives per
    layer (the only ones on the sharded hot path).  Both partial sums
    are accumulated and psum'd in fp32, rounding to the activation
    dtype once AFTER the full contraction — the same rounding point as
    the unsharded einsum, which is what keeps tp>1 token streams
    bit-identical to the tp=1 differential baseline in bf16."""
    if axis is not None:
        attn_out = jnp.einsum("bse,ed->bsd", o, lp["attn"]["wo"],
                              preferred_element_type=jnp.float32)
        attn_out = jax.lax.psum(attn_out, axis).astype(xx.dtype)
    else:
        attn_out = jnp.einsum("bse,ed->bsd", o, lp["attn"]["wo"])
    xx = xx + attn_out
    h2 = rms_norm(xx, lp["ln2"], cfg.norm_eps)
    if "moe" in lp:
        f, _ = moe_ffn(lp["moe"], h2, cfg)
        if axis is not None:
            f = jax.lax.psum(f, axis)
    elif axis is not None:
        f = swiglu(h2, **lp["ffn"], preferred_element_type=jnp.float32)
        f = jax.lax.psum(f, axis).astype(xx.dtype)
    else:
        f = swiglu(h2, **lp["ffn"])
    return xx + f


def _write_tokens(pool, layer, slots, k, v, bs: int):
    """Write token rows ``k`` and ``v`` (T, n_kv, hd) into layer ``layer``
    of the whole pool (L, 2, N, n_kv, bs, hd) at flat slots
    ``block * bs + offset``; a slot past the pool (``N * bs``) drops its
    row.  Every (token, KV head) pair is indexed on its own, so each
    scatter window is one ``hd`` row, the pool's minor dim: a window of
    (n_kv, hd) would make XLA's layout assignment swap the KV-head and
    in-block dims of the carried pool, and convert the whole pool to and
    from the Pallas kernels' default layout around every step."""
    blk, off = (slots // bs)[:, None], (slots % bs)[:, None]
    heads = jnp.arange(k.shape[1])[None, :]
    pool = pool.at[layer, 0, blk, heads, off].set(k, mode="drop")
    return pool.at[layer, 1, blk, heads, off].set(v, mode="drop")


class PagedModelRunner:
    """Runs a :class:`LanguageModel` against a paged KV pool.

    Pool: (L, 2, num_blocks, n_kv, block_size, hd) — head-major pages,
    so one (kv_head, block) tile is the dense (block_size, hd) slab the
    Pallas kernels DMA.  Decode is batched across sequences at arbitrary
    positions via block tables.

    **In-place pool semantics** (``donate_pool``, default on): every
    jitted step function *donates* the pool argument, so XLA writes the
    updated pool into the very buffer it read — one resident pool buffer
    per runner for the lifetime of the process.  Donation holds the
    buffer at the jit boundary only; inside the step, the decode and
    fused steps' layer scan *carries* the whole pool (never a per-layer
    slice as a scan input or output), each layer scatters its fresh K/V
    per (token, KV head) into ``pool[layer, 0|1]``, and the attention
    kernels read ``pool`` at a traced ``layer``.  XLA then updates the
    pool in place through the scan and keeps the kernels' layout, so a
    step moves only the KV rows it writes and the pages it reads; a
    per-layer slice as a scan operand would be sliced, copied and
    re-stacked for every layer of every step.  Without donation each
    dispatch materializes a second full-size pool buffer just to change a
    few KV rows.  The donation invariant:
    a pool reference passed to a step function is DEAD on return — every
    call site here rebinds ``self.pool`` from the function's result in
    the same statement, and nothing else may retain a pool reference
    across a dispatch.  ``donate_pool=False`` keeps the copying
    behaviour as a differential baseline (token streams are identical;
    only buffer traffic changes).

    ``ragged_backend`` picks the lowering for the fused iteration's
    prefill attention (`kernels.ops.ragged_segment_attention`): the
    native segment-tiled kernel ("pallas"/"interpret"), the pure-jnp
    segment-bounded oracle ("ref"), or the legacy flatten-and-repeat
    lowering onto the decode kernel ("flat"/"flat_interpret"/"flat_ref",
    kept for differential tests).  Defaults to ``backend``.

    **Tensor parallelism** (``mesh``): given a ("data", "model") mesh
    slice, the runner shards megatron-style over the "model" axis —
    QKV/O and FFN weights per ``models.sharding.param_pspec``, the KV
    pool over KV heads (``POOL_PSPEC``; logical pool shape unchanged,
    the BlockManager stays head-agnostic) — and lowers every step
    function through ``shard_map``.  Each shard runs the SAME fused
    iteration body on its local KV-head slice (the attention kernels'
    kv_head grid dim is simply the local head count; block tables and
    ragged metadata are replicated), and the only collectives per layer
    are the two standard megatron all-reduces.  Donation survives
    sharding: jit aliases the pool shard-for-shard, so each device
    keeps ONE resident pool shard for the runner's lifetime
    (``pool_address()`` returns the per-shard address tuple).  A
    ``mesh`` whose "model" axis is 1 only *places* the arrays on that
    slice's device — the computation is the exact single-device
    baseline, which is what keeps tp=1 bit-identical for differential
    tests.
    """

    def __init__(self, model: LanguageModel, params, num_blocks: int,
                 block_size: int, max_batch: int = 8,
                 backend: Optional[str] = None,
                 ragged_backend: Optional[str] = None,
                 donate_pool: bool = True,
                 mesh: Optional[Mesh] = None):
        cfg = model.cfg
        assert model.uniform_kind == "attn", "paged runner serves attention archs"
        assert cfg.sliding_window is None, "windowed paged decode: see DESIGN.md"
        self.model, self.cfg = model, cfg
        self.block_size, self.num_blocks = block_size, num_blocks
        self.max_batch = max_batch
        self.backend = backend or kops.default_backend()
        self.ragged_backend = ragged_backend or self.backend
        self.donate_pool = donate_pool
        # ---- tensor-parallel mesh placement (tp=1 + mesh=None is the
        # exact single-device baseline: no shard_map, no collectives) ----
        self.mesh = mesh
        tp = (int(mesh.shape["model"])
              if mesh is not None and "model" in mesh.axis_names else 1)
        validate_serving_tp(cfg, tp)
        self.tp = tp
        self._tp_axis = "model" if tp > 1 else None
        hd = cfg.resolved_head_dim
        # local (per-shard) config: the step bodies reshape activations
        # by head counts, and under shard_map each shard owns 1/tp of
        # the KV heads plus their whole query-head groups (heads are
        # laid out group-contiguous, so the megatron column slice of
        # wq/wk/wv is exactly a KV-head-aligned slice).  head_dim is
        # pinned so resolved_head_dim can't drift with num_heads.
        self._lcfg = (dataclasses.replace(
            cfg, num_heads=cfg.num_heads // tp,
            num_kv_heads=cfg.num_kv_heads // tp, head_dim=hd)
            if tp > 1 else cfg)
        self._pool_pspec = POOL_PSPEC if tp > 1 else P()
        if mesh is not None:
            specs = (serving_param_specs(params, cfg, mesh)
                     if tp > 1 else jax.tree_util.tree_map(lambda _: P(),
                                                           params))
            self._param_specs = specs
            params = jax.device_put(params, jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), specs))
        else:
            self._param_specs = None
        self.params = params
        self.pool = self._new_pool()
        # perf counters now live on a metrics registry (obs.metrics);
        # n_dispatches is a property alias over it — device *op
        # dispatches* issued (jitted calls plus standalone ops like the
        # legacy path's per-chunk jnp.argmax — each is a separately
        # launched device computation).  Plain device->host transfers of
        # already-computed arrays (np.asarray on a result) execute no op
        # and are not counted on either path.
        self._new_metrics()
        # argument shapes of the fused step issued so far (shared with
        # clones, which share its compiled specializations)
        self._issued: set = set()
        if self._tp_axis is None:
            decode = self._build_decode()
            fused = self._build_fused()
            suffix = self._build_suffix_prefill()
            copy = self._build_copy_block()
        else:
            # lower every step body through shard_map: params enter with
            # their megatron specs, the pool with its KV-head shard, the
            # ragged batch metadata (tokens / positions / block tables /
            # scalar-prefetched scatter slots) replicated.  Outputs:
            # next-token ids are replicated (each shard computes the
            # identical argmax from the psum'ed activations and the
            # replicated LM head), the pool keeps its shard spec so jit
            # donation aliases shard-for-shard.
            rep = P()
            ppar, pspec = self._param_specs, self._pool_pspec
            decode = self._smap(self._build_decode(),
                                (ppar, pspec) + (rep,) * 4, (rep, pspec))
            fused = self._smap(self._build_fused(),
                               (ppar, pspec) + (rep,) * 10, (rep, pspec))
            copy = self._smap(self._build_copy_block(),
                              (pspec, rep, rep), pspec)
            raw_suffix = self._build_suffix_prefill()
            smap = self._smap

            def suffix(params, pool, tokens, ctx_bt, write_idx, n_cached):
                # n_cached is a static python int (jit static_argnames),
                # consumed by slicing inside the body — bind it BEFORE
                # shard_map so it never becomes a traced spec'd operand;
                # each n_cached specialization re-wraps at trace time.
                fn = smap(functools.partial(raw_suffix, n_cached=n_cached),
                          (ppar, pspec, rep, rep, rep), (rep, pspec))
                return fn(params, pool, tokens, ctx_bt, write_idx)
        write_blocks = self._build_write_blocks()
        if self._tp_axis is not None:
            write_blocks = self._smap(
                write_blocks,
                (self._pool_pspec, self._pool_pspec, P()), self._pool_pspec)
        self._decode_fn = self._jit_pool(decode)
        self._prefill_fn = jax.jit(self.model.prefill)
        self._suffix_fn = self._jit_pool(suffix,
                                         static_argnames=("n_cached",))
        self._fused_fn = self._jit_pool(fused)
        self._scatter_fn = self._jit_pool(self._build_scatter_prefill(),
                                          pool_argnum=0)
        self._copy_block_fn = self._jit_pool(copy, pool_argnum=0)
        self._write_blocks_fn = self._jit_pool(write_blocks, pool_argnum=0)

    def _new_pool(self) -> jnp.ndarray:
        """Fresh zeroed KV pool, placed on this runner's mesh slice with
        the KV-head shard spec (or the default device when meshless)."""
        cfg = self.cfg
        pool = jnp.zeros(
            (cfg.num_layers, 2, self.num_blocks, cfg.num_kv_heads,
             self.block_size, cfg.resolved_head_dim), self.model.dtype)
        if self.mesh is not None:
            pool = jax.device_put(pool,
                                  NamedSharding(self.mesh, self._pool_pspec))
        return pool

    def _smap(self, fn, in_specs, out_specs):
        """shard_map a step body over this runner's mesh slice.
        check_vma=False: the Pallas/interpret attention backends defeat
        replication inference, and every replicated output here is
        replicated by construction (psum'ed activations x replicated
        head)."""
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _new_metrics(self):
        self.metrics = MetricsRegistry()
        self.n_dispatches = 0
        # fused steps issued on a shape new to the runner (each traces,
        # lowers and compiles or loads it), and the seconds they took
        self.metrics.counter("compiles")
        self.metrics.counter("compile_s")

    @property
    def n_dispatches(self) -> int:
        """Alias over ``metrics.counter("n_dispatches")`` — kept
        read/write (``runner.n_dispatches += 1``) so pre-registry call
        sites and BENCH gates work unchanged."""
        return int(self.metrics.counter("n_dispatches").value)

    @n_dispatches.setter
    def n_dispatches(self, v: int):
        self.metrics.counter("n_dispatches").value = float(v)

    def metrics_snapshot(self) -> dict:
        """Registry snapshot with the derived gauges refreshed (compiled
        specializations, resident pool bytes)."""
        self.metrics.set("jit_cache_size", self.jit_cache_size())
        self.metrics.set("pool_bytes",
                         self.pool.size * self.pool.dtype.itemsize)
        return self.metrics.snapshot()

    def _jit_pool(self, fn, pool_argnum: int = 1, **kw):
        """jit a step function that threads the KV pool in and out; with
        ``donate_pool`` the pool argument's buffer is donated so the
        update happens in place (the returned pool aliases the input)."""
        if self.donate_pool:
            kw["donate_argnums"] = (pool_argnum,)
        return jax.jit(fn, **kw)

    def pool_address(self) -> Optional[int]:
        """Device buffer address of the pool, or None when the runtime
        doesn't expose one.  With donation active the address is stable
        across dispatches (the perf-guard test and the fusion benchmark's
        ``pool_bytes_copied_per_iter`` metric both watch it).  May block
        on an in-flight dispatch — call between synced iterations only.
        Only a *missing* API degrades to None: a RuntimeError (e.g. a
        deleted buffer — a stale reference surviving past its donation)
        must propagate, not masquerade as an unsupported probe.

        A sharded pool returns a TUPLE of per-shard addresses (one per
        addressable shard, shard-index order): donation under shard_map
        aliases shard-for-shard, so EVERY position must be stable across
        dispatches — the sharded perf tests and ``benchmarks/shard_scale``
        compare whole tuples."""
        try:
            shards = self.pool.addressable_shards
            if len(shards) > 1:
                return tuple(s.data.unsafe_buffer_pointer() for s in shards)
            return self.pool.unsafe_buffer_pointer()
        except (AttributeError, NotImplementedError):
            return None

    def jit_cache_size(self) -> int:
        """Total compiled specializations across the runner's jitted entry
        points — the recompile counter the fusion benchmark/CI tracks
        (``_cache_size`` of each jitted callable, JAX 0.9)."""
        return sum(f._cache_size() for f in
                   (self._decode_fn, self._prefill_fn, self._suffix_fn,
                    self._fused_fn, self._scatter_fn, self._copy_block_fn,
                    self._write_blocks_fn))

    # -- block-granular KV transfer (live request migration) ------------------
    def read_blocks(self, block_ids: Sequence[int]) -> np.ndarray:
        """Gather the KV of ``block_ids`` to host:
        (L, 2, n_blocks, n_kv, block_size, hd) numpy.  The gather is a
        fresh buffer — the pool itself is only *read*, never donated, so
        ``pool_address()`` is unchanged by this call (the migration tests
        witness exactly that).  Like every pool read it must run between
        synced iterations: an in-flight donated dispatch may be
        overwriting the pool concurrently."""
        self.n_dispatches += 1
        return np.asarray(self.pool[:, :, jnp.asarray(block_ids, jnp.int32)])

    def write_blocks(self, kv: np.ndarray, block_ids: Sequence[int]):
        """Scatter transferred KV into ``block_ids`` — the restore half of
        a live migration.  One jitted dispatch with the pool donated
        (``self.pool`` rebinds from the result in the same statement), so
        the target instance keeps its single resident pool buffer."""
        assert kv.shape[2] == len(block_ids)
        self.n_dispatches += 1
        self.pool = self._write_blocks_fn(
            self.pool, jnp.asarray(kv, self.pool.dtype),
            jnp.asarray(block_ids, jnp.int32))

    def _build_write_blocks(self):
        def write(pool, kv, bt):
            return pool.at[:, :, bt].set(kv)
        return write

    # -- prefill: run the model once, scatter its contiguous KV into pages ---
    def prefill(self, tokens: jnp.ndarray, block_table: List[int]):
        """tokens (S,) int32 -> last-token logits (V,). Fills the pool.

        Two dispatches: the model prefill and the (donated) pool scatter
        — the scatter used to be an out-of-jit ``at[].set`` that copied
        the entire pool to write one prompt's pages, and was not counted
        in ``n_dispatches`` at all.

        Tensor-parallel runners route through the shard_map'd suffix
        path with ``n_cached=0`` instead: the monolithic ``model.prefill``
        produces full-head contiguous KV, which has no per-shard scatter
        (tp=1 keeps the exact legacy two-dispatch lowering as the
        differential baseline)."""
        if self.tp > 1:
            return self.prefill_suffix(tokens, block_table, 0)
        nb = -(-tokens.shape[0] // self.block_size)
        self.n_dispatches += 2
        logits, cache = self._prefill_fn(self.params, tokens[None])
        bt = jnp.asarray(block_table[:nb], jnp.int32)
        self.pool = self._scatter_fn(self.pool, cache["kv"], bt)
        return logits[0]

    def _build_scatter_prefill(self):
        bs = self.block_size

        def scatter(pool, kv, bt):
            """kv (L,2,1,S,kv,hd) contiguous prefill KV -> the pages in
            ``bt``; pool donated, so the scatter is in place."""
            s = kv.shape[3]
            nb = bt.shape[0]
            kv = jnp.pad(kv, [(0, 0), (0, 0), (0, 0), (0, nb * bs - s),
                              (0, 0), (0, 0)])
            kv = kv.reshape(kv.shape[0], 2, nb, bs, *kv.shape[4:])
            return pool.at[:, :, bt].set(kv.swapaxes(3, 4))

        return scatter

    # -- chunk prefill: attend over resident KV, compute only new tokens ------
    def prefill_suffix(self, tokens: jnp.ndarray, block_table: List[int],
                       n_cached: int):
        """tokens (S,) = the next prompt chunk; block_table covers the
        whole prompt.  The chunk attends over the ``n_cached`` tokens
        already resident in the pool (shared cached prefix and/or earlier
        chunks of this prompt) plus itself; only the chunk's KV is
        written.  ``n_cached`` may be any value >= 0 — chunk boundaries
        need not align to blocks (the last resident block may be
        partially filled and is completed in place)."""
        s = tokens.shape[0]
        bs = self.block_size
        assert s > 0 and 0 <= n_cached
        n_ctx_blocks = -(-n_cached // bs)
        ctx_bt = jnp.asarray(block_table[:n_ctx_blocks], jnp.int32)
        write_idx = jnp.asarray(
            [block_table[p // bs] * bs + p % bs
             for p in range(n_cached, n_cached + s)], jnp.int32)
        self.n_dispatches += 1
        logits, self.pool = self._suffix_fn(
            self.params, self.pool, jnp.asarray(tokens, jnp.int32),
            ctx_bt, write_idx, n_cached)
        return logits

    def copy_block(self, src: int, dst: int):
        """Copy-on-write data path: duplicate one physical block.  One
        jitted (donated) dispatch moving exactly one block — the old
        out-of-jit ``at[].set`` rebuilt the whole pool per copy, and
        baked the block ids into the op (src/dst are traced scalars
        here, so every copy shares one compiled specialization)."""
        self.n_dispatches += 1
        self.pool = self._copy_block_fn(self.pool, src, dst)

    def _build_copy_block(self):
        def copy(pool, src, dst):
            return pool.at[:, :, dst].set(pool[:, :, src])
        return copy

    def _build_suffix_prefill(self):
        cfg = self._lcfg
        axis = self._tp_axis
        hd = cfg.resolved_head_dim
        bs = self.block_size

        def step(params, pool, tokens, ctx_bt, write_idx, n_cached):
            s = tokens.shape[0]
            positions = n_cached + jnp.arange(s, dtype=jnp.int32)
            sin, cos = attn_mod.rope_at(positions, hd, cfg.rope_theta)
            k_pos = jnp.arange(n_cached + s, dtype=jnp.int32)
            bias = jnp.where(positions[:, None] >= k_pos[None, :],
                             0.0, attn_mod.NEG_INF)[None, None, None]
            x = embed_tokens(params, tokens[None]).astype(pool.dtype)  # (1,S,d)

            def body(xx, xs):
                lp, pool_layer = xs
                q, k, v = _layer_qkv(lp, xx, sin, cos, cfg)
                # resident K/V: gather the covering pages (already rope'd
                # at write), keep the first n_cached rows — the last page
                # may be partially filled by an earlier chunk
                pk = pool_layer[0][ctx_bt].swapaxes(1, 2).reshape(
                    -1, cfg.num_kv_heads, hd)[:n_cached]
                pv = pool_layer[1][ctx_bt].swapaxes(1, 2).reshape(
                    -1, cfg.num_kv_heads, hd)[:n_cached]
                kf = jnp.concatenate([pk[None], k], axis=1)   # (1, P+S, kv, hd)
                vf = jnp.concatenate([pv[None], v], axis=1)
                scores = attn_mod._gqa_scores(q, kf)
                probs = jax.nn.softmax(scores + bias, axis=-1)
                o = attn_mod._gqa_out(probs, vf).reshape(1, s, -1)
                return _layer_finish(xx, o, lp, cfg, axis), \
                    jnp.stack([k[0], v[0]])                   # (2, S, kv, hd)

            x, kvs = jax.lax.scan(body, x, (params["layers"], pool))
            # scatter the chunk's KV at its exact token slots — per-token
            # (block, offset) indices, so chunks may start or end mid-block
            pool = pool.at[:, :, write_idx // bs, :, write_idx % bs].set(
                kvs.transpose(2, 0, 1, 3, 4))
            x = rms_norm(x, params["final_norm"], cfg.norm_eps)
            logits = lm_logits(params, x[:, -1], cfg)
            return logits[0], pool

        return step

    # -- fused ragged iteration: one dispatch per engine step -----------------
    def run_iteration(self, batch: IterationBatch) -> jnp.ndarray:
        """Execute a whole :class:`IterationBatch` — every prefill chunk,
        every decode token, and the plan's copy-on-write block copies — as
        ONE jitted device dispatch, returning next-token argmax ids (S,)
        for every segment row.  The result is a *device* array: jax async
        dispatch means this call returns before the compute finishes, so
        a cluster loop can issue the next engine's iteration while this
        one runs; the caller syncs (one transfer) only when it actually
        consumes the token values.  The per-chunk path pays K+1 dispatches
        and K blocking argmax syncs for the same work.

        The pool argument is donated: ``self.pool`` is rebound from the
        call's result in the same statement, so the dead input reference
        can never be observed, and the next-token output is a distinct
        (non-aliased) buffer — deferring its host sync via
        :class:`TokenBuffer` never touches donated storage.

        The call runs under the ``kairos.engine.compile`` span, and adds
        to the ``compiles`` / ``compile_s`` counters, when its argument
        shapes are new to the runner; under ``kairos.engine.dispatch``
        otherwise."""
        self.n_dispatches += 1
        # numpy arrays go straight to the jitted call: the C++ dispatch
        # path converts them far cheaper than 12 python-level jnp.asarray
        # round-trips (measured ~1.7 ms/iteration at smoke scale)
        args = (batch.tokens_p, batch.positions_p, batch.tables_p,
                batch.tokens_d, batch.positions_d, batch.tables_d,
                batch.write_slots, batch.sample_rows, batch.cow_src,
                batch.cow_dst)
        shapes = tuple(a.shape for a in args)
        new = shapes not in self._issued
        t0 = time.perf_counter()
        with span("kairos.engine.compile" if new else "kairos.engine.dispatch"):
            nxt, self.pool = self._fused_fn(self.params, self.pool, *args)
        if new:
            self._issued.add(shapes)
            self.metrics.inc("compiles")
            self.metrics.inc("compile_s", time.perf_counter() - t0)
        return nxt

    def _build_fused(self):
        cfg = self._lcfg
        axis = self._tp_axis
        hd = cfg.resolved_head_dim
        bs = self.block_size
        backend = self.backend
        ragged_backend = self.ragged_backend

        def step(params, pool, tokens_p, positions_p, tables_p,
                 tokens_d, positions_d, tables_d, write_slots, sample_rows,
                 cow_src, cow_dst):
            # copy-on-write first: decode rows write into the copies.
            # dst never aliases another pair's src (dsts come off the free
            # list, srcs are shared), so one vectorized copy is exact;
            # padding pairs point dst past the pool and drop
            pool = pool.at[:, :, cow_dst].set(pool[:, :, cow_src], mode="drop")
            sp, lmax = tokens_p.shape
            tp = sp * lmax
            tokens = jnp.concatenate([tokens_p.reshape(-1), tokens_d])
            positions = jnp.concatenate([positions_p.reshape(-1), positions_d])
            x = embed_tokens(params, tokens[None]).astype(pool.dtype)  # (1,T,d)
            sin, cos = attn_mod.rope_at(positions, hd, cfg.rope_theta)

            def body(carry, xs):
                xx, pool = carry
                lp, layer = xs
                q, k, v = _layer_qkv(lp, xx, sin, cos, cfg)
                # scatter every fresh K/V into its pool slot BEFORE
                # attending: a token then reads earlier same-iteration
                # tokens (its own chunk's prefix, or another chunk that
                # shares its cached-prefix blocks) straight from the pool;
                # padding rows carry an out-of-range slot and drop
                pool = _write_tokens(pool, layer, write_slots, k[0], v[0], bs)
                g = cfg.num_heads // cfg.num_kv_heads
                qg = q[0].reshape(-1, cfg.num_kv_heads, g, hd)
                # chunk rows attend as dense (Sp, L) tiles through the
                # short per-chunk tables (segment-blocked causal: pages
                # gathered once per chunk, not once per token); decode
                # rows through their full tables via the classic paged
                # kernel — chunk tokens never gather the longest decode
                # context
                op = kops.ragged_segment_attention(
                    qg[:tp].reshape(sp, lmax, cfg.num_kv_heads, g, hd),
                    pool, layer, tables_p, positions_p, backend=ragged_backend)
                od = kops.paged_attention(
                    qg[tp:], pool, layer, tables_d, positions_d + 1,
                    backend=backend)
                o = jnp.concatenate(
                    [op.reshape(tp, cfg.num_kv_heads, g, hd), od])
                o = o.reshape(1, -1, cfg.num_heads * hd)
                return (_layer_finish(xx, o, lp, cfg, axis), pool), None

            # the pool is carried, not scanned over: every layer writes
            # into and reads from the one buffer, which XLA updates in place
            layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)
            (x, pool), _ = jax.lax.scan(body, (x, pool),
                                        (params["layers"], layer_ids))
            x = rms_norm(x, params["final_norm"], cfg.norm_eps)
            rows = x[0][sample_rows]                       # (S, d)
            logits = lm_logits(params, rows, cfg)          # (S, V)
            return jnp.argmax(logits, -1).astype(jnp.int32), pool

        return step

    # -- batched paged decode --------------------------------------------------
    def _build_decode(self):
        cfg = self._lcfg
        axis = self._tp_axis
        hd = cfg.resolved_head_dim
        bs = self.block_size
        backend = self.backend

        def step(params, pool, tokens, positions, block_tables, live):
            """tokens (B,), positions (B,), block_tables (B, nbmax), live (B,) bool."""
            x = embed_tokens(params, tokens[:, None]).astype(pool.dtype)   # (B,1,d)
            ctx = jnp.where(live, positions + 1, 1).astype(jnp.int32)
            sin, cos = attn_mod.rope_at(positions[:, None], hd, cfg.rope_theta)

            # write k/v at (table[pos // bs], pos % bs); dead batch slots
            # point past the pool (mode="drop") so they can never stomp a
            # live page — block tables may now be shared across sequences
            flat = block_tables[jnp.arange(tokens.shape[0]), positions // bs] * bs \
                + positions % bs
            flat = jnp.where(live, flat, pool.shape[2] * bs)

            def body(carry, xs):
                xx, pool = carry
                lp, layer = xs
                q, k, v = _layer_qkv(lp, xx, sin, cos, cfg)
                pool = _write_tokens(pool, layer, flat, k[:, 0], v[:, 0], bs)
                g = cfg.num_heads // cfg.num_kv_heads
                qg = q.reshape(q.shape[0], cfg.num_kv_heads, g, hd)
                o = kops.paged_attention(qg, pool, layer, block_tables, ctx,
                                         backend=backend)
                o = o.reshape(q.shape[0], 1, cfg.num_heads * hd)
                return (_layer_finish(xx, o, lp, cfg, axis), pool), None

            layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)
            (x, pool), _ = jax.lax.scan(body, (x, pool),
                                        (params["layers"], layer_ids))
            x = rms_norm(x, params["final_norm"], cfg.norm_eps)
            logits = lm_logits(params, x[:, 0], cfg)
            return logits, pool

        return step

    def decode_batch(self, tokens: np.ndarray, positions: np.ndarray,
                     block_tables: np.ndarray, live: np.ndarray):
        """All inputs padded to a fixed batch; returns logits (B, V)."""
        self.n_dispatches += 1
        logits, self.pool = self._decode_fn(
            self.params, self.pool,
            jnp.asarray(tokens, jnp.int32), jnp.asarray(positions, jnp.int32),
            jnp.asarray(block_tables, jnp.int32), jnp.asarray(live, bool))
        return logits

    def clone(self) -> "PagedModelRunner":
        """A new runner over the same model/params with a fresh zeroed KV
        pool, *sharing* this runner's compiled step functions (the jitted
        callables close over config/backend only; params and pool are
        traced arguments).  A multi-instance cluster built from clones
        pays for one compile per shape bucket, not one per instance.

        Safe under donation: donation is per *call*, not per compiled
        function — each clone owns its own pool buffer and donates only
        that buffer when it dispatches, so instances never alias (and a
        shared jitted fn called concurrently from cluster worker threads
        donates each caller's pool independently).  The fresh pool is
        built from static shape/dtype, never by reading the source
        runner's buffer — cloning is legal even while the source has a
        dispatch in flight.

        Sharded runners clone the same way WITHIN a mesh slice: the
        clone shares the placed (sharded) params and the compiled
        shard_map'd step fns — compiled executables close over the
        slice's device set, so same-slice instances pay one compile.  A
        runner for a DIFFERENT slice cannot be cloned (its executables
        are bound to other devices); build it with
        ``PagedModelRunner(..., mesh=other_slice)`` instead."""
        c = object.__new__(PagedModelRunner)
        c.model, c.cfg, c.params = self.model, self.cfg, self.params
        c.block_size, c.num_blocks = self.block_size, self.num_blocks
        c.max_batch, c.backend = self.max_batch, self.backend
        c.ragged_backend = self.ragged_backend
        c.donate_pool = self.donate_pool
        c.mesh, c.tp = self.mesh, self.tp
        c._tp_axis, c._lcfg = self._tp_axis, self._lcfg
        c._pool_pspec = self._pool_pspec
        c._param_specs = self._param_specs
        c.pool = c._new_pool()
        c._new_metrics()
        c._issued = self._issued
        c._decode_fn = self._decode_fn
        c._prefill_fn = self._prefill_fn
        c._suffix_fn = self._suffix_fn
        c._fused_fn = self._fused_fn
        c._scatter_fn = self._scatter_fn
        c._copy_block_fn = self._copy_block_fn
        c._write_blocks_fn = self._write_blocks_fn
        return c

    @classmethod
    def from_config(cls, model: LanguageModel, params, config,
                    backend: Optional[str] = None,
                    mesh: Optional[Mesh] = None) -> "PagedModelRunner":
        """Build a runner from a :class:`~repro.serving.config.ServingConfig`
        (the mesh, being device placement rather than configuration, is
        supplied separately)."""
        return cls(model, params, backend=backend, mesh=mesh,
                   **config.runner_kwargs())


# =============================================================================
# Deferred host sync: lazy next-token references
# =============================================================================


class TokenBuffer:
    """The next-token ids of one fused dispatch, synced to host lazily.

    ``run_iteration`` returns a device array whose compute may still be
    in flight (jax async dispatch).  The buffer converts it to numpy
    exactly once, on first access — so the device->host round-trip (and
    the wait for the producing dispatch) happens only when a token value
    is actually consumed: fed into a later iteration's flatten, checked
    against ``eos_token``, or materialized at request finish.

    Donation audit: the held array is the dispatch's next-token *output*
    — a buffer XLA allocates fresh (outputs alias only donated inputs,
    and the pool's shape can't alias a token vector), so a deferred
    ``host()`` read is safe no matter how many further iterations have
    donated and overwritten the pool in the meantime.

    The first ``host()`` runs under the ``kairos.engine.sync`` span and
    then calls ``on_host`` once: the moment the tokens are on the host."""

    __slots__ = ("_dev", "_host", "_on_host")

    def __init__(self, dev, on_host: Optional[Callable[[], None]] = None):
        self._dev = dev
        self._host: Optional[np.ndarray] = None
        self._on_host = on_host

    def host(self) -> np.ndarray:
        if self._host is None:
            dev = self._dev               # local ref: a concurrent host()
            if dev is not None:           # call can never hand us None
                with span("kairos.engine.sync"):
                    self._host = np.asarray(dev)
                self._dev = None          # release the device buffer early
                on_host, self._on_host = self._on_host, None
                if on_host is not None:
                    on_host()
        return self._host


class TokenRef:
    """One row of a :class:`TokenBuffer`: a not-yet-synced token id.

    Converts to ``int`` on demand (``__int__``/``__index__``), so host
    code that stores pending tokens — the engine's next-token map — never
    blocks on the device until the value is observed.  Comparison syncs too:
    equality against a plain int is value equality."""

    __slots__ = ("buf", "row")

    def __init__(self, buf: TokenBuffer, row: int):
        self.buf = buf
        self.row = row

    def __int__(self) -> int:
        return int(self.buf.host()[self.row])

    __index__ = __int__

    def __eq__(self, other) -> bool:
        try:
            return int(self) == int(other)
        except (TypeError, ValueError):
            return NotImplemented

    def __hash__(self) -> int:
        return hash(int(self))

    def __repr__(self) -> str:
        return f"TokenRef({int(self)})"


# =============================================================================
# Continuous-batching engine
# =============================================================================

# back-compat alias: engine stats now live on the shared batch scheduler
EngineStats = SchedStats


class LLMEngine:
    """One LLM instance: a :class:`BatchScheduler` drives the runner.

    All scheduling decisions — admission order (``policy``, default
    FCFS), prefix-cache matching, block accounting, growth / eviction /
    preemption, and chunked-prefill batch composition
    (``prefill_chunk_tokens``: per-iteration prefill token budget,
    ``None`` = monolithic) — live in
    :class:`repro.serving.batch_scheduler.BatchScheduler`, shared verbatim
    with the discrete-event simulator's ``SimInstance``; this class only
    executes the plans with real tokens.

    Execution model (``fused_iteration``, default on): each composed
    :class:`IterationPlan` is flattened into one ragged
    :class:`IterationBatch` and executed by a single device dispatch
    (:meth:`PagedModelRunner.run_iteration`) returning every segment's
    next token in one transfer.  A request finishing its prefill starts
    decoding the *next* iteration (its first token is this dispatch's
    argmax), so generated tokens are identical to the legacy per-chunk
    path — kept behind ``fused_iteration=False`` for differential
    testing — which issues one jitted call per prefill chunk plus a
    decode dispatch, with a blocking argmax sync after every chunk."""

    def __init__(self, runner: PagedModelRunner, instance_id: int = 0,
                 max_batch: int = 8, eos_token: int = -1,
                 clock: Callable[[], float] = time.monotonic,
                 enable_prefix_cache: bool = False,
                 policy: Optional[SchedulerPolicy] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 fused_iteration: bool = True,
                 tracer: Tracer = NULL_TRACER,
                 role: str = "general"):
        self.runner = runner
        self.fused_iteration = fused_iteration
        self._pending: Optional[Tuple[IterationBatch, TokenBuffer]] = None
        self._pending_finished: Optional[List[Request]] = None
        self.bm = BlockManager(runner.num_blocks, runner.block_size)
        self.prefix_cache = (PrefixCache(runner.block_size)
                             if enable_prefix_cache else None)
        self.instance_id = instance_id
        self.max_batch = max_batch
        self.eos_token = eos_token
        self.clock = clock
        self.tracer = tracer
        self._next_tok: dict[int, int] = {}
        # fault plane (serving/faults.py): wired by the cluster; when set,
        # every composed iteration consults the injector mid-dispatch
        self.faults = None
        # wall seconds of the newest step whose tokens reached the host,
        # from the start of its dispatch_iteration: written by the thread
        # that synced them, read by recovery's step-deadline check
        self.last_step_wall = 0.0
        self.sched = BatchScheduler(
            self.bm, policy=policy, prefix_cache=self.prefix_cache,
            matcher=TokenPrefixMatcher(), max_running=max_batch,
            max_batch=runner.max_batch,
            prefill_chunk_tokens=prefill_chunk_tokens,
            on_preempt=lambda r: self._next_tok.pop(r.req_id, None),
            tracer=tracer, instance_id=instance_id, role=role)

    @classmethod
    def from_config(cls, runner: PagedModelRunner, config, *,
                    instance_id: int = 0, eos_token: int = -1,
                    clock: Callable[[], float] = time.monotonic,
                    policy: Optional[SchedulerPolicy] = None,
                    tracer: Tracer = NULL_TRACER,
                    role: Optional[str] = None) -> "LLMEngine":
        """Build an engine from a :class:`~repro.serving.config.ServingConfig`
        (identity, clock, policy object and tracer are runtime wiring, not
        configuration).  ``role`` overrides ``config.role_of(instance_id)``
        — the autoscaler uses it to mint instances for a specific pool."""
        if role is None:
            role = config.role_of(instance_id)
        return cls(runner, instance_id=instance_id, eos_token=eos_token,
                   clock=clock, policy=policy, tracer=tracer, role=role,
                   **config.engine_kwargs())

    @property
    def role(self) -> str:
        return self.sched.role

    @property
    def waiting(self) -> List[Request]:
        return self.sched.waiting

    @property
    def running(self) -> List[Request]:
        return self.sched.running

    # ------------------------------------------------- pending-token surface
    # (live migration moves a mid-decode request's sampled-but-not-yet-fed
    # token between engines; these keep serving/migration.py off _next_tok)
    def pending_token(self, req_id: int) -> Optional[int]:
        """The request's sampled-but-not-yet-fed next token, materialized
        to a plain int (syncs a deferred :class:`TokenRef`), or None for a
        request still mid-prefill."""
        tok = self._next_tok.get(req_id)
        return None if tok is None else int(tok)

    def set_pending_token(self, req_id: int, tok: int):
        self._next_tok[req_id] = int(tok)

    def drop_pending_token(self, req_id: int):
        self._next_tok.pop(req_id, None)

    @property
    def stats(self) -> SchedStats:
        return self.sched.stats

    # ---------------------------------------------------------------- monitor
    @property
    def kv_capacity_tokens(self) -> int:
        return self.bm.num_blocks * self.bm.block_size

    @property
    def kv_used_tokens(self) -> int:
        return sum(r.total_len for r in self.running)

    @property
    def kv_cached_tokens(self) -> int:
        """Tokens parked in zero-ref prefix-cache blocks (reclaimable)."""
        return self.bm.cached_blocks * self.bm.block_size

    def memory_free_fraction(self) -> float:
        return self.bm.free_blocks / self.bm.num_blocks

    def poll_oom(self) -> bool:
        oom, self.stats.recent_oom = self.stats.recent_oom, False
        return oom

    def metrics_snapshot(self) -> dict:
        """One flat dict of this instance's counters and gauges: the
        runner's registry (dispatches, recompiles, pool bytes, iteration
        histograms) plus scheduler occupancy and prefix-cache stats."""
        m = self.runner.metrics
        m.set("queue_depth", len(self.waiting))
        m.set("running", len(self.running))
        m.set("kv_used_tokens", self.kv_used_tokens)
        m.set("kv_cached_tokens", self.kv_cached_tokens)
        m.set("n_finished", self.stats.n_finished)
        m.set("n_preempted", self.stats.n_preempted)
        m.set("n_admitted", self.stats.n_admitted)
        m.set("prefill_tokens", self.stats.prefill_tokens)
        m.set("prefill_tokens_saved", self.stats.prefill_tokens_saved)
        if self.prefix_cache is not None:
            m.set("prefix_cache_hit_rate", self.prefix_cache.stats.hit_rate())
        return self.runner.metrics_snapshot()

    # ---------------------------------------------------------------- intake
    def submit(self, req: Request):
        req.instance_id = self.instance_id
        self.sched.submit(req)

    # ---------------------------------------------------------------- stepping
    def step(self) -> List[Request]:
        """One continuous-batching iteration; returns finished requests.

        The legacy serial entry point: dispatch + collect back-to-back,
        with the host sync forced — the engine blocks on the device
        result before returning, exactly the pre-pipelining behaviour.
        Cluster loops call :meth:`dispatch_iteration` / :meth:`collect`
        instead to overlap engines."""
        self.dispatch_iteration()
        return self.collect(force_sync=True)

    @property
    def has_pending(self) -> bool:
        """A dispatched-but-not-collected iteration is in flight."""
        return self._pending is not None or self._pending_finished is not None

    def dispatch_iteration(self) -> bool:
        """Compose this engine's next iteration and issue its device
        dispatch WITHOUT waiting for the result (jax async dispatch): the
        returned next-token ids stay on device until :meth:`collect` —
        or a later consumer — actually needs them.  Returns True iff an
        iteration was issued.  On the legacy per-chunk path there is no
        single dispatch to defer; the iteration executes synchronously
        here and ``collect`` just hands back its finishers."""
        assert not self.has_pending, "collect() the previous iteration first"
        t0 = time.monotonic()
        with span("kairos.sched.plan"):
            plan = self.sched.plan(self.clock())
        if plan is None:
            return False
        if self.faults is not None:
            # mid-dispatch fault point: the plan has already mutated
            # scheduler state (chunk bookkeeping, decode growth), which is
            # exactly what a real worker death leaves behind.  Non-crash
            # effects land first so a storm of ooms still fences.
            eff = self.faults.on_dispatch(self.instance_id)
            if eff.oom:
                self.sched.stats.recent_oom = True
            if eff.delay_s > 0.0:
                time.sleep(eff.delay_s)
            if eff.crash is not None:
                raise InstanceCrashed(self.instance_id, eff.crash.step)
        if not self.fused_iteration:
            self._pending_finished = self._execute_per_chunk(plan)
            self.last_step_wall = time.monotonic() - t0
            return True
        with span("kairos.sched.flatten"):
            batch = flatten_plan(plan, self.bm, self._next_tok)
        self.runner.metrics.observe("iteration_tokens", batch.n_tokens)
        self.runner.metrics.observe("batch_occupancy", len(batch.segments))
        # a final prefill chunk's argmax IS the call's first token
        firsts = [(seg.req, seg.req.n_preemptions) for seg in batch.segments
                  if seg.kind == "prefill" and seg.emits_token]
        toks = TokenBuffer(self.runner.run_iteration(batch),
                           functools.partial(self._on_host, t0, firsts))
        self._pending = (batch, toks)
        return True

    def _on_host(self, t0: float, firsts: List[Tuple[Request, int]]):
        """A step's tokens reached the host: its wall time runs from the
        start of its :meth:`dispatch_iteration` to now, and the first
        tokens it computed are stamped now (TTFT), unless the call was
        preempted since and its token dropped."""
        self.last_step_wall = time.monotonic() - t0
        now = self.clock()
        for r, n_preempted in firsts:
            if r.first_token_time >= 0 or r.n_preemptions != n_preempted:
                continue
            r.first_token_time = now
            if self.tracer.enabled:
                self.tracer.emit("first-token", req_id=r.req_id,
                                 instance_id=self.instance_id,
                                 agent=r.agent_name, msg_id=r.msg_id, ts=now)

    def sync(self):
        """Block until the in-flight iteration's next-token ids are
        host-resident (no-op when nothing is pending).  Cluster worker
        threads call this right after :meth:`dispatch_iteration` so the
        device wait lands on the worker — concurrently with the other
        engines' compute — and never on the control-plane thread."""
        if self._pending is not None:
            self._pending[1].host()

    def collect(self, force_sync: bool = False) -> List[Request]:
        """Book the dispatched iteration's results; returns finished
        requests.  All bookkeeping is host-side metadata: new tokens are
        recorded as :class:`TokenRef`s, so nothing blocks on the device
        unless EOS checking demands token values or ``force_sync`` asks
        for the legacy blocking behaviour.  A decode row books the token
        it was fed, which its flatten already brought to the host; a
        first token is stamped when its buffer syncs (:meth:`_on_host`)."""
        with span("kairos.engine.collect"):
            if self._pending_finished is not None:
                finished, self._pending_finished = self._pending_finished, None
                return finished
            if self._pending is None:
                return []
            (batch, toks), self._pending = self._pending, None
            if force_sync or self.eos_token >= 0:
                toks.host()
            finished = []
            now = self.clock()
            traced = self.tracer.enabled
            for j, seg in enumerate(batch.segments):
                r = seg.req
                if seg.kind == "prefill":
                    if seg.emits_token:
                        self._next_tok[r.req_id] = TokenRef(toks, j)
                    continue
                r.output_tokens.append(int(self._next_tok[r.req_id]))
                r.output_len += 1
                self._next_tok[r.req_id] = TokenRef(toks, j)
                if traced:
                    self.tracer.emit("decode", req_id=r.req_id,
                                     instance_id=self.instance_id,
                                     agent=r.agent_name, msg_id=r.msg_id,
                                     ts=now)
                done = (r.output_len >= r.max_new_tokens
                        or (self.eos_token >= 0
                            and int(toks.host()[j]) == self.eos_token))
                if done:
                    self.sched.finish(r, self.clock())
                    self._next_tok.pop(r.req_id, None)
                    finished.append(r)
            return finished

    def _execute_per_chunk(self, plan) -> List[Request]:
        """Legacy differential-testing path: one jitted dispatch per
        prefill chunk (plus a blocking argmax sync each) and a separate
        decode dispatch."""
        # prefill chunks, in plan order: a chunk may attend shared blocks
        # written by an earlier chunk of this very iteration
        for c in plan.chunks:
            toks = jnp.asarray(
                np.asarray(c.req.prompt_tokens)[c.start:c.end], jnp.int32)
            table = self.bm.block_table(c.req.req_id)
            if c.start == 0 and c.is_last:
                logits = self.runner.prefill(toks, table)
            else:
                logits = self.runner.prefill_suffix(toks, table, c.start)
            if c.is_last:
                # jnp.argmax is its own device op dispatch, and int()
                # blocks on it — one round-trip per completed chunk (the
                # fused path folds every argmax into the main dispatch
                # and returns them in one transfer instead)
                self.runner.n_dispatches += 1
                self._next_tok[c.req.req_id] = int(jnp.argmax(logits))
                if c.req.first_token_time < 0:
                    c.req.first_token_time = self.clock()
                if self.tracer.enabled:
                    self.tracer.emit("first-token", req_id=c.req.req_id,
                                     instance_id=self.instance_id,
                                     agent=c.req.agent_name,
                                     msg_id=c.req.msg_id,
                                     ts=c.req.first_token_time)
        for src, dst in plan.cow:
            self.runner.copy_block(src, dst)
        if not plan.decode:
            return []
        b = self.runner.max_batch
        batch = plan.decode
        nbmax = max(len(self.bm.block_table(r.req_id)) + 1 for r in batch)
        tokens = np.zeros((b,), np.int32)
        positions = np.zeros((b,), np.int32)
        tables = np.zeros((b, nbmax), np.int32)
        live = np.zeros((b,), bool)
        for i, r in enumerate(batch):
            t = self.bm.block_table(r.req_id)
            tables[i, :len(t)] = t
            tokens[i] = self._next_tok[r.req_id]
            positions[i] = r.total_len
            live[i] = True
        logits = self.runner.decode_batch(tokens, positions, tables, live)
        self.runner.n_dispatches += 1
        nxt = np.asarray(jnp.argmax(logits, -1))
        finished = []
        for i, r in enumerate(batch):
            r.output_tokens.append(int(tokens[i]))
            r.output_len += 1
            self._next_tok[r.req_id] = int(nxt[i])
            done = (r.output_len >= r.max_new_tokens
                    or (self.eos_token >= 0 and int(nxt[i]) == self.eos_token))
            if done:
                self.sched.finish(r, self.clock())
                self._next_tok.pop(r.req_id, None)
                finished.append(r)
        return finished

    def run_until_drained(self, max_steps: int = 10_000) -> List[Request]:
        out = []
        for _ in range(max_steps):
            out.extend(self.step())
            if not self.running and not self.waiting:
                break
        return out
