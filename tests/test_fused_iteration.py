"""Single-dispatch fused iteration execution (engine.run_iteration).

The fused path flattens a whole :class:`IterationPlan` — every prefill
chunk (arbitrary mid-block start/end, cached-prefix resident KV) plus
every decode token — into one ragged :class:`IterationBatch` executed by
ONE jitted device dispatch.  Covered here:

* token identity vs the legacy per-chunk path (with and without a cached
  shared prefix, mid-block chunk boundaries, mixed prefill+decode
  iterations, staggered arrivals under memory pressure);
* the donated in-place KV pool: buffer-address stability across fused
  AND legacy iterations (donation actually happened), the probe's
  ability to detect copies with donation off, token identity donated vs
  non-donated under preemption pressure, jitted prefill-scatter /
  copy-block helpers, and clone() pool ownership;
* the native ragged kernel vs the flatten-and-repeat lowering: token
  identity end to end (ref and Pallas-interpret backends);
* exactly one device dispatch per iteration (vs K+1 on the legacy path);
* a recompile-count guard: the bucketed static shapes bound `jax.jit`
  cache growth across a varied workload;
* the ragged segment-mask attention lowerings vs the ref oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.kernels.ref import ragged_segment_attention_ref
from repro.serving import (
    LLMEngine,
    PagedModelRunner,
    Request,
    flatten_plan,
    pad_bucket,
    reset_request_ids,
)


@pytest.fixture(scope="module")
def model_and_params():
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("qwen3-1.7b").reduced()
    model = build_model(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def _mixed_reqs(seed=11, sys_len=16, n=4, uniq=6, max_new=4):
    """Shared-prefix requests (full-block cached prefix when caching on)."""
    rng = np.random.default_rng(seed)
    sys_toks = rng.integers(0, 500, sys_len).astype(np.int32)
    reqs = []
    for i in range(n):
        toks = np.concatenate(
            [sys_toks, rng.integers(0, 500, uniq + i).astype(np.int32)])
        reqs.append(Request(agent_name="a", msg_id=f"m{i}", prompt_len=len(toks),
                            prompt_tokens=toks, max_new_tokens=max_new,
                            arrival_time=float(i)))
    return reqs


def _serve(model_and_params, *, fused, chunk, cache, reqs=None,
           staggered=False, num_blocks=64, **runner_kw):
    model, params = model_and_params
    reset_request_ids()
    runner = PagedModelRunner(model, params, num_blocks=num_blocks,
                              block_size=8, max_batch=4, **runner_kw)
    eng = LLMEngine(runner, max_batch=4, enable_prefix_cache=cache,
                    prefill_chunk_tokens=chunk, fused_iteration=fused)
    reqs = reqs if reqs is not None else _mixed_reqs()
    if staggered:
        # trickle arrivals so iterations genuinely mix chunks and decodes
        pending = list(reqs)
        done = []
        for _ in range(4000):
            if pending:
                eng.submit(pending.pop(0))
            done.extend(eng.step())
            if not pending and not eng.running and not eng.waiting:
                break
    else:
        for r in reqs:
            eng.submit(r)
        done = eng.run_until_drained(max_steps=4000)
    assert len(done) == len(reqs)
    assert eng.bm.free_blocks + eng.bm.cached_blocks == eng.bm.num_blocks
    return eng, sorted((d.msg_id, tuple(d.output_tokens)) for d in done)


# =============================================================================
# token identity vs the per-chunk path
# =============================================================================


@pytest.mark.parametrize("cache", [False, True])
@pytest.mark.parametrize("chunk", [None, 5, 8, 16])
def test_fused_token_identical(model_and_params, cache, chunk):
    """Fused execution generates exactly the legacy tokens at every chunk
    budget — including 5, which splits blocks mid-way — with and without
    a cached shared prefix."""
    _, legacy = _serve(model_and_params, fused=False, chunk=chunk, cache=cache)
    _, fused = _serve(model_and_params, fused=True, chunk=chunk, cache=cache)
    assert fused == legacy, f"chunk={chunk} cache={cache} diverged"


def test_fused_token_identical_staggered_mixed_iterations(model_and_params):
    """Staggered arrivals force iterations that mix mid-prompt chunks with
    running decodes; outputs must still match the legacy path."""
    reqs = lambda: _mixed_reqs(seed=7, sys_len=24, n=6, uniq=11, max_new=6)
    _, legacy = _serve(model_and_params, fused=False, chunk=16, cache=True,
                       reqs=reqs(), staggered=True)
    _, fused = _serve(model_and_params, fused=True, chunk=16, cache=True,
                      reqs=reqs(), staggered=True)
    assert fused == legacy


def test_fused_survives_preemption_pressure(model_and_params):
    """Tight pool: preemption-by-recompute still drains and matches the
    legacy path's generated tokens."""
    reqs = lambda: _mixed_reqs(seed=3, sys_len=8, n=5, uniq=19, max_new=10)
    _, legacy = _serve(model_and_params, fused=False, chunk=8, cache=False,
                       reqs=reqs(), num_blocks=24)
    eng, fused = _serve(model_and_params, fused=True, chunk=8, cache=False,
                        reqs=reqs(), num_blocks=24)
    assert fused == legacy


# =============================================================================
# donated in-place pool (zero-copy hot path)
# =============================================================================


def _drain_tracking_pool(model_and_params, *, donate, fused=True,
                         num_blocks=24, chunk=8):
    """Drain a preemption-pressure workload recording the pool's device
    buffer address after every iteration; returns (addresses, outputs)."""
    model, params = model_and_params
    reset_request_ids()
    runner = PagedModelRunner(model, params, num_blocks=num_blocks,
                              block_size=8, max_batch=4, donate_pool=donate)
    eng = LLMEngine(runner, max_batch=4, enable_prefix_cache=True,
                    prefill_chunk_tokens=chunk, fused_iteration=fused)
    for r in _mixed_reqs(seed=3, sys_len=8, n=5, uniq=19, max_new=6):
        eng.submit(r)
    addrs, done = [], []
    for _ in range(4000):
        done.extend(eng.step())
        addrs.append(runner.pool_address())
        if not eng.running and not eng.waiting:
            break
    assert len(done) == 5
    return addrs, sorted((d.msg_id, tuple(d.output_tokens)) for d in done)


@pytest.mark.parametrize("fused", [True, False])
def test_pool_buffer_address_stable_under_donation(model_and_params, fused):
    """Donation actually happened: every dispatch of a drain — fused
    iterations, and the legacy path's prefill-scatter / copy-block /
    suffix / decode helpers — updates the ONE pool buffer in place,
    including across preemption-by-recompute.  Skips cleanly where the
    runtime exposes no buffer address."""
    addrs, _ = _drain_tracking_pool(model_and_params, donate=True, fused=fused)
    if addrs[0] is None:
        pytest.skip("runtime exposes no unsafe_buffer_pointer")
    assert len(set(addrs)) == 1, \
        f"donated pool buffer moved: {len(set(addrs))} distinct addresses"


def test_pool_address_probe_detects_copies(model_and_params):
    """The guard above is meaningful: with donation off, the same drain
    materializes fresh pool buffers (the address moves) — if this ever
    stops detecting copies, the stability assertion proves nothing."""
    addrs, _ = _drain_tracking_pool(model_and_params, donate=False)
    if addrs[0] is None:
        pytest.skip("runtime exposes no unsafe_buffer_pointer")
    assert len(set(addrs)) > 1


def test_donated_vs_nondonated_token_identical(model_and_params):
    """Donation changes buffer traffic only: token streams are identical
    under prefix-cache + chunked-prefill + preemption pressure."""
    _, donated = _drain_tracking_pool(model_and_params, donate=True)
    _, plain = _drain_tracking_pool(model_and_params, donate=False)
    assert donated == plain


def test_prefill_and_copy_block_are_jitted_dispatches(model_and_params):
    """The legacy out-of-jit full-pool ``at[].set`` writes are gone:
    ``prefill`` is exactly two counted dispatches (model + donated
    scatter), ``copy_block`` exactly one, and neither moves the pool
    buffer."""
    model, params = model_and_params
    reset_request_ids()
    runner = PagedModelRunner(model, params, num_blocks=16, block_size=8,
                              max_batch=2)
    a0 = runner.pool_address()
    rng = np.random.default_rng(0)
    d0 = runner.n_dispatches
    runner.prefill(jnp.asarray(rng.integers(0, 500, 12), jnp.int32), [3, 4])
    assert runner.n_dispatches - d0 == 2
    d0 = runner.n_dispatches
    runner.copy_block(3, 7)
    assert runner.n_dispatches - d0 == 1
    np.testing.assert_array_equal(np.asarray(runner.pool[:, :, 7]),
                                  np.asarray(runner.pool[:, :, 3]))
    if a0 is not None:
        assert runner.pool_address() == a0
    # copy_block shares ONE compiled specialization across block ids
    d0 = runner.n_dispatches
    cache0 = runner.jit_cache_size()
    runner.copy_block(4, 8)
    runner.copy_block(7, 9)
    assert runner.n_dispatches - d0 == 2
    assert runner.jit_cache_size() == cache0


def test_clone_owns_pool_under_donation(model_and_params):
    """Clones share compiled (donating) step fns but never a pool
    buffer: dispatching one instance leaves the other's pool untouched
    and at its own stable address."""
    model, params = model_and_params
    reset_request_ids()
    r0 = PagedModelRunner(model, params, num_blocks=16, block_size=8,
                          max_batch=2)
    r1 = r0.clone()
    assert r0._fused_fn is r1._fused_fn
    a0, a1 = r0.pool_address(), r1.pool_address()
    rng = np.random.default_rng(1)
    r0.prefill(jnp.asarray(rng.integers(0, 500, 8), jnp.int32), [0])
    assert not np.asarray(r0.pool[:, :, 0] == 0).all()
    assert np.asarray(r1.pool == 0).all()
    if a1 is not None:
        assert r1.pool_address() == a1 and r0.pool_address() == a0
        assert a0 != a1


# =============================================================================
# native ragged kernel vs flatten-and-repeat, end to end
# =============================================================================


def test_native_vs_flat_ragged_token_identical_under_pressure(model_and_params):
    """The native segment-bounded ragged lowering generates exactly the
    flatten-and-repeat lowering's tokens under prefix-cache +
    chunked-prefill + preemption pressure (tight pool)."""
    reqs = lambda: _mixed_reqs(seed=9, sys_len=16, n=5, uniq=13, max_new=6)
    _, native = _serve(model_and_params, fused=True, chunk=8, cache=True,
                       reqs=reqs(), num_blocks=24, ragged_backend="ref")
    _, flat = _serve(model_and_params, fused=True, chunk=8, cache=True,
                     reqs=reqs(), num_blocks=24, ragged_backend="flat_ref")
    assert native == flat


def test_native_pallas_kernel_token_identical_in_engine(model_and_params):
    """The real Pallas kernel (interpret mode) inside the fused engine
    step produces the ref backend's exact tokens — small workload, the
    interpreted grid is slow."""
    reqs = lambda: _mixed_reqs(seed=5, sys_len=8, n=2, uniq=5, max_new=3)
    _, ref = _serve(model_and_params, fused=True, chunk=8, cache=True,
                    reqs=reqs(), num_blocks=32, ragged_backend="ref")
    _, native = _serve(model_and_params, fused=True, chunk=8, cache=True,
                       reqs=reqs(), num_blocks=32,
                       ragged_backend="interpret")
    assert native == ref


# =============================================================================
# dispatch counting
# =============================================================================


def test_fused_is_single_dispatch_per_iteration(model_and_params):
    """Every fused iteration — mixed, prefill-only, or decode-only —
    issues exactly one device dispatch; the legacy path issues K+1 plus
    an argmax round-trip per completed chunk."""
    model, params = model_and_params
    totals = {}
    for fused in (True, False):
        reset_request_ids()
        runner = PagedModelRunner(model, params, num_blocks=64, block_size=8,
                                  max_batch=4)
        eng = LLMEngine(runner, max_batch=4, prefill_chunk_tokens=8,
                        fused_iteration=fused)
        for r in _mixed_reqs(seed=5, sys_len=16, n=3, uniq=7, max_new=3):
            eng.submit(r)
        iters = 0
        for _ in range(4000):
            before = runner.n_dispatches
            eng.step()
            issued = runner.n_dispatches - before
            if issued == 0:
                break                      # idle: drained
            iters += 1
            if fused:
                assert issued == 1, f"fused iteration issued {issued} dispatches"
        assert iters > 0
        totals[fused] = (runner.n_dispatches, iters)
    n_fused, it_fused = totals[True]
    n_legacy, it_legacy = totals[False]
    assert n_fused == it_fused, "fused: exactly one dispatch per iteration"
    # legacy pays K+1 per mixed iteration plus argmax round-trips: strictly
    # more dispatches than iterations over any run that decodes
    assert n_legacy > it_legacy


# =============================================================================
# recompile guard
# =============================================================================


def test_bucketing_bounds_recompiles(model_and_params):
    """The IterationBatch's padded bucket shapes keep the fused jit cache
    logarithmic: a workload sweeping many prompt lengths, budgets, and
    batch mixes must compile at most one entry per distinct bucket tuple."""
    model, params = model_and_params
    runner = PagedModelRunner(model, params, num_blocks=64, block_size=8,
                              max_batch=4)
    shape_keys = set()
    rng = np.random.default_rng(0)
    for trial, chunk in enumerate((None, 8, 16, 5)):
        reset_request_ids()
        eng = LLMEngine(runner, max_batch=4, prefill_chunk_tokens=chunk,
                        fused_iteration=True)
        # shim: record every flattened shape the engine executes
        orig = runner.run_iteration

        def run(batch, _orig=orig):
            shape_keys.add(batch.shape_key)
            return _orig(batch)

        runner.run_iteration = run
        n = int(rng.integers(2, 5))
        for i in range(n):
            plen = int(rng.integers(3, 60))
            toks = rng.integers(0, 500, plen).astype(np.int32)
            eng.submit(Request(agent_name="a", msg_id=f"t{trial}-{i}",
                               prompt_len=plen, prompt_tokens=toks,
                               max_new_tokens=int(rng.integers(1, 6)),
                               arrival_time=float(i)))
        done = eng.run_until_drained(max_steps=4000)
        runner.run_iteration = orig
        assert len(done) == n
    compiled = runner.jit_cache_size()   # only the fused fn ran
    assert 0 < compiled <= len(shape_keys), \
        "fused jit must compile at most once per bucket shape"
    # and the bucket set itself stays small: every dim is floor * 2^k, so
    # this sweep (4 budgets x 14 requests x prompt lengths 3..59) lands on
    # a couple dozen tuples — unbucketed shapes would be in the hundreds
    assert compiled <= 24, f"bucket set exploded: {sorted(shape_keys)}"


def test_pad_bucket():
    assert pad_bucket(0, 4) == 0     # absent part: compiled away
    assert pad_bucket(1, 4) == 4
    assert pad_bucket(4, 4) == 4
    assert pad_bucket(5, 4) == 8
    assert pad_bucket(129, 4) == 256


# =============================================================================
# flatten_plan semantics
# =============================================================================


def test_flatten_defers_first_decode_of_completed_prefill():
    """A request whose final chunk is in this plan must NOT get a decode
    row this iteration — its first decode token is this dispatch's own
    argmax (data dependency) — while already-decoding requests do."""
    from repro.serving import BatchScheduler, BlockManager
    reset_request_ids()
    bm = BlockManager(num_blocks=32, block_size=8)
    sched = BatchScheduler(bm, max_running=4)
    rng = np.random.default_rng(0)
    a = Request(agent_name="a", msg_id="a", prompt_len=8,
                prompt_tokens=rng.integers(0, 500, 8).astype(np.int32))
    sched.submit(a)
    plan = sched.plan(0.0)
    batch = flatten_plan(plan, bm, {})
    kinds = [(s.kind, s.emits_token) for s in batch.segments]
    assert kinds == [("prefill", True)]
    # next iteration: the pending token decodes
    plan2 = sched.plan(1.0)
    batch2 = flatten_plan(plan2, bm, {a.req_id: 123})
    assert [(s.kind, s.emits_token) for s in batch2.segments] == [("decode", True)]
    assert batch2.tokens_d[0] == 123 and batch2.positions_d[0] == a.total_len
    # its sample row points at the decode part of the device layout,
    # and the absent prefill part compiles away (zero-sized)
    assert batch2.tokens_p.size == 0
    assert batch2.sample_rows[0] == batch2.tokens_p.size


def test_flatten_write_slots_and_padding():
    """Write slots address exact token positions through the block table;
    padding rows carry the out-of-range slot so scatters drop them."""
    from repro.serving import BatchScheduler, BlockManager
    reset_request_ids()
    bm = BlockManager(num_blocks=32, block_size=8)
    sched = BatchScheduler(bm, max_running=4, prefill_chunk_tokens=8)
    rng = np.random.default_rng(1)
    r = Request(agent_name="a", msg_id="m", prompt_len=20,
                prompt_tokens=rng.integers(0, 500, 20).astype(np.int32))
    sched.submit(r)
    plan = sched.plan(0.0)
    batch = flatten_plan(plan, bm, {})
    table = bm.block_table(r.req_id)
    n = batch.n_tokens
    assert n == 8 and plan.chunks[0].start == 0
    expect = [table[p // 8] * 8 + p % 8 for p in range(8)]
    assert batch.write_slots[:n].tolist() == expect
    assert (batch.write_slots[n:] == bm.num_blocks * 8).all()
    assert (np.asarray(batch.tokens_p[0, :n]) ==
            np.asarray(r.prompt_tokens[:8], np.int32)).all()
    # chunk tables are trimmed to the chunk's own extent (1 block here),
    # padded to the table bucket floor — decode tables never widen them
    assert batch.tables_p.shape[1] == 4 and batch.tables_p[0, 0] == table[0]


# =============================================================================
# ragged segment-mask attention helper
# =============================================================================


def _ragged_case(key, seg_specs, kv=2, g=4, hd=64, bs=8, nb=3, n_pool=32):
    """Build a (S, L) chunk tile: segments of the given (length, context)
    at staggered offsets, with KV already resident in the pool."""
    ks = jax.random.split(key, 4)
    k_pool = jax.random.normal(ks[0], (n_pool, kv, bs, hd), jnp.float32)
    v_pool = jax.random.normal(ks[1], (n_pool, kv, bs, hd), jnp.float32)
    perm = np.asarray(jax.random.permutation(ks[2], n_pool))
    s, lmax = len(seg_specs), max(n for n, _ in seg_specs)
    tables = np.stack([perm[i * nb:(i + 1) * nb] for i in range(s)])
    positions = np.zeros((s, lmax), np.int32)
    for i, (seg_len, ctx) in enumerate(seg_specs):
        positions[i, :seg_len] = np.arange(ctx, ctx + seg_len)
    q = jax.random.normal(ks[3], (s, lmax, kv, g, hd), jnp.float32)
    return (q, k_pool, v_pool, jnp.asarray(tables, jnp.int32),
            jnp.asarray(positions, jnp.int32))


@pytest.mark.parametrize("backend", ["interpret", "flat_interpret", "flat_ref"])
@pytest.mark.parametrize("seg_specs", [
    [(1, 9), (1, 4), (1, 17)],            # single-token segments
    [(6, 0), (5, 8), (1, 12), (1, 3)],    # ragged mix, padded tile rows
    [(8, 13)],                            # mid-block chunk start
])
def test_ragged_segment_attention_matches_oracle(seg_specs, backend):
    """Every lowering — the native segment-tiled Pallas kernel
    ("interpret") and the legacy flatten-and-repeat lowering onto the
    decode path ("flat_*") — agrees with the ref oracle on the
    segment-blocked causal mask.  The case's pages sit in layer 1 of a
    two-layer pool whose layer 0 is NaN."""
    q, k_pool, v_pool, bt, pos = _ragged_case(jax.random.PRNGKey(0), seg_specs)
    pool = jnp.stack([jnp.full((2,) + k_pool.shape, jnp.nan),
                      jnp.stack([k_pool, v_pool])])
    args = (q, pool, jnp.int32(1), bt, pos)
    ref = kops.ragged_segment_attention(*args, backend="ref")
    ker = kops.ragged_segment_attention(*args, backend=backend)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               rtol=2e-5, atol=2e-5)


def test_ragged_segment_attention_is_causal_within_segment():
    """Poisoning pool rows *after* a token's position never changes its
    output; poisoning a row at or before it does."""
    q, kp, vp, bt, pos = _ragged_case(jax.random.PRNGKey(1), [(4, 8)])
    out = ragged_segment_attention_ref(q, kp, vp, bt, pos)
    # token (0, 0) sits at position 8; rows 9.. of its table are future
    blk, off = int(bt[0, 9 // 8]), 9 % 8
    poisoned = ragged_segment_attention_ref(
        q, kp.at[blk, :, off].set(1e3), vp.at[blk, :, off].set(-1e3), bt, pos)
    np.testing.assert_allclose(np.asarray(out[0, 0]),
                               np.asarray(poisoned[0, 0]), rtol=1e-6)
    assert not np.allclose(np.asarray(out[0, 1]), np.asarray(poisoned[0, 1])), \
        "token at position 9 must see row 9"


def test_ragged_segment_attention_never_crosses_segments():
    """A segment's output is invariant to everything in other segments'
    pages (disjoint tables here)."""
    q, kp, vp, bt, pos = _ragged_case(jax.random.PRNGKey(2), [(4, 3), (4, 11)])
    out = ragged_segment_attention_ref(q, kp, vp, bt, pos)
    poisoned = ragged_segment_attention_ref(
        q, kp.at[np.asarray(bt[1])].set(1e3),
        vp.at[np.asarray(bt[1])].set(-1e3), bt, pos)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(poisoned[0]),
                               rtol=1e-6)
