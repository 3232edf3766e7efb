"""Pallas kernel validation: interpret-mode kernel vs pure-jnp oracle,
sweeping shapes and dtypes.

The kernels read one layer of the serving engine's whole pool
(L, 2, N, KV, bs, hd) at a traced ``layer``; the oracles take that
layer's K and V pages.  ``_pool`` stacks a case's pages as a one-layer
pool, and the layer sweep at the end puts them among poisoned layers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ragged_attention import ragged_segment_attention
from repro.kernels.ref import paged_attention_ref, ragged_segment_attention_ref


def _make_case(key, b, kv, g, hd, bs, nb_per_seq, n_pool, dtype):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (b, kv, g, hd), dtype)
    k_pool = jax.random.normal(ks[1], (n_pool, kv, bs, hd), dtype)
    v_pool = jax.random.normal(ks[2], (n_pool, kv, bs, hd), dtype)
    # unique block ids per sequence (like a real allocator would hand out)
    perm = jax.random.permutation(ks[3], n_pool)[: b * nb_per_seq]
    block_tables = perm.reshape(b, nb_per_seq).astype(jnp.int32)
    max_ctx = bs * nb_per_seq
    context_lens = jax.random.randint(ks[4], (b,), 1, max_ctx + 1).astype(jnp.int32)
    return q, k_pool, v_pool, block_tables, context_lens


def _pool(k_pool, v_pool):
    """One layer's K and V pages (N, KV, bs, hd) as a one-layer pool."""
    return jnp.stack([k_pool, v_pool])[None]


SHAPES = [
    # b, kv, g, hd, bs, nb_per_seq, n_pool
    (2, 2, 4, 64, 8, 3, 16),
    (1, 1, 8, 128, 16, 2, 8),
    (3, 4, 2, 64, 4, 5, 64),
    (2, 8, 1, 32, 8, 4, 64),   # MQA-ish: G=1
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_matches_oracle(shape, dtype):
    b, kv, g, hd, bs, nb, n_pool = shape
    args = _make_case(jax.random.PRNGKey(42), b, kv, g, hd, bs, nb, n_pool, dtype)
    q, k_pool, v_pool, bt, cl = args
    out_kernel = paged_attention(q, _pool(k_pool, v_pool), 0, bt, cl,
                                 interpret=True)
    out_ref = paged_attention_ref(*args)
    assert out_kernel.shape == out_ref.shape == (b, kv, g, hd)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out_kernel, np.float32),
                               np.asarray(out_ref, np.float32), rtol=tol, atol=tol)


def test_paged_attention_single_token_context():
    """context_len=1: attends to exactly the first token."""
    b, kv, g, hd, bs = 1, 1, 2, 64, 8
    q, k_pool, v_pool, bt, _ = _make_case(jax.random.PRNGKey(0), b, kv, g, hd, bs, 2, 8, jnp.float32)
    cl = jnp.array([1], jnp.int32)
    out = paged_attention(q, _pool(k_pool, v_pool), 0, bt, cl, interpret=True)
    expect = jnp.broadcast_to(v_pool[bt[0, 0], :, 0][None, :, None, :],
                              (b, kv, g, hd))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-5, atol=1e-5)


def test_paged_attention_ignores_garbage_beyond_context():
    """Pages past context_len must not affect the output."""
    b, kv, g, hd, bs, nb = 1, 2, 2, 64, 8, 4
    q, k_pool, v_pool, bt, _ = _make_case(jax.random.PRNGKey(7), b, kv, g, hd, bs, nb, 32, jnp.float32)
    cl = jnp.array([11], jnp.int32)  # 1.375 pages valid
    out1 = paged_attention(q, _pool(k_pool, v_pool), 0, bt, cl, interpret=True)
    # poison everything beyond page 2
    k2 = k_pool.at[bt[0, 2]].set(1e4)
    v2 = v_pool.at[bt[0, 3]].set(-1e4)
    out2 = paged_attention(q, _pool(k2, v2), 0, bt, cl, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


# =============================================================================
# native ragged segment-attention kernel
# =============================================================================


def _make_ragged_case(key, seg_specs, kv, g, hd, bs, nb, n_pool, dtype):
    """Segments of (length, n_cached): each segment's queries sit at
    absolute positions [n_cached, n_cached + length) of its own sequence
    — mid-block boundaries whenever n_cached % bs != 0 — tiled into a
    dense (S, L) block with padding rows where length < L."""
    ks = jax.random.split(key, 4)
    k_pool = jax.random.normal(ks[0], (n_pool, kv, bs, hd), dtype)
    v_pool = jax.random.normal(ks[1], (n_pool, kv, bs, hd), dtype)
    perm = np.asarray(jax.random.permutation(ks[2], n_pool))
    s, lmax = len(seg_specs), max(n for n, _ in seg_specs)
    tables = np.stack([perm[i * nb:(i + 1) * nb] for i in range(s)])
    positions = np.zeros((s, lmax), np.int32)
    for i, (seg_len, n_cached) in enumerate(seg_specs):
        positions[i, :seg_len] = np.arange(n_cached, n_cached + seg_len)
    q = jax.random.normal(ks[3], (s, lmax, kv, g, hd), dtype)
    return (q, k_pool, v_pool, jnp.asarray(tables, jnp.int32),
            jnp.asarray(positions, jnp.int32))


RAGGED_SWEEP = [
    # (seg_specs [(len, n_cached)...], kv, g, hd, bs, nb, n_pool)
    # uneven lengths + padding rows, chunks starting mid-block (13 % 8)
    ([(6, 0), (3, 13), (1, 20)], 2, 4, 64, 8, 4, 40),
    # n_cached > 0 everywhere: every chunk resumes a partially-written
    # last resident block
    ([(5, 3), (5, 11), (5, 19)], 1, 8, 128, 16, 2, 8),
    # chunk both starting AND ending mid-block, wide table
    ([(7, 9)], 4, 2, 64, 4, 6, 32),
    # MQA-ish single-group heads, single-token segments (decode-like)
    ([(1, 0), (1, 7), (1, 15), (1, 30)], 8, 1, 32, 8, 4, 64),
]


@pytest.mark.parametrize("case", RAGGED_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_kernel_matches_oracle_sweep(case, dtype):
    """Native kernel (interpret mode) vs the jnp oracle across shapes,
    head groupings, mid-block chunk boundaries, resumed contexts
    (n_cached > 0), uneven segment lengths, and padded tile rows —
    padding rows compare too (both paths compute position-0 attention
    for them, and they must stay NaN-free)."""
    seg_specs, kv, g, hd, bs, nb, n_pool = case
    args = _make_ragged_case(jax.random.PRNGKey(11), seg_specs,
                             kv, g, hd, bs, nb, n_pool, dtype)
    q, k_pool, v_pool, bt, pos = args
    out_k = ragged_segment_attention(q, _pool(k_pool, v_pool), 0, bt, pos,
                                     interpret=True)
    out_r = ragged_segment_attention_ref(*args)
    assert out_k.shape == out_r.shape == args[0].shape
    assert not np.isnan(np.asarray(out_k, np.float32)).any()
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol)


def test_ragged_kernel_page_bounds_ignore_out_of_reach_pages():
    """A segment never visits pages past max(positions)//bs: poisoning
    the table entries beyond a segment's bound — even with garbage
    *block ids* — cannot change its output (the index map clamps to the
    bound page)."""
    seg_specs = [(4, 6), (2, 0)]           # bounds: page 1, page 0
    q, kp, vp, bt, pos = _make_ragged_case(
        jax.random.PRNGKey(3), seg_specs, 2, 2, 64, 8, 4, 40, jnp.float32)
    out = ragged_segment_attention(q, _pool(kp, vp), 0, bt, pos, interpret=True)
    poisoned = np.array(bt)
    poisoned[0, 2:] = 39                   # unrelated garbage block
    poisoned[1, 1:] = 39
    kp2 = kp.at[39].set(1e4)
    vp2 = vp.at[39].set(-1e4)
    out2 = ragged_segment_attention(q, _pool(kp2, vp2), 0,
                                    jnp.asarray(poisoned), pos, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), rtol=1e-6)


# =============================================================================
# the layer operand: one layer of a multi-layer pool
# =============================================================================


@pytest.mark.parametrize("poison", [np.nan, 1e4])
@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("backend", ["interpret", "ref"])
def test_kernels_read_only_their_layer(backend, layer, poison):
    """Both kernels (interpret mode) and the ``ref`` backend read K from
    ``pool[layer, 0]`` and V from ``pool[layer, 1]`` of a three-layer
    pool whose other layers hold NaN or large values, and match the
    oracles on that layer's pages: the layer reaches the index maps, and
    nothing leaks across layers."""
    q, k_pool, v_pool, bt, cl = _make_case(
        jax.random.PRNGKey(5), 2, 2, 4, 64, 8, 3, 16, jnp.float32)
    pool = jnp.full((3, 2) + k_pool.shape, poison, jnp.float32)
    pool = pool.at[layer].set(jnp.stack([k_pool, v_pool]))
    lay = jnp.int32(layer)
    out = kops.paged_attention(q, pool, lay, bt, cl, backend=backend)
    want = paged_attention_ref(q, k_pool, v_pool, bt, cl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    qr, kr, vr, btr, pos = _make_ragged_case(
        jax.random.PRNGKey(6), [(6, 0), (3, 13), (1, 20)],
        2, 4, 64, 8, 4, 40, jnp.float32)
    pool = jnp.full((3, 2) + kr.shape, poison, jnp.float32)
    pool = pool.at[layer].set(jnp.stack([kr, vr]))
    out = kops.ragged_segment_attention(qr, pool, lay, btr, pos,
                                        backend=backend)
    want = ragged_segment_attention_ref(qr, kr, vr, btr, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
