"""Compile the serving hot path for a TPU v5e that is described, not attached.

Interpret mode cannot see what the chip's compiler refuses: block shapes
off the (8, 128) tiling, kernels over the scoped VMEM limit, programs over
HBM.  These tests hand shapes at qwen3-1.7b's published widths (8 KV heads
x 2 grouped query heads, head_dim 128) to the TPU compiler and check that a
Pallas kernel (``tpu_custom_call``) comes out.  Nothing runs.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and a fixture keeps every xdist
worker collecting the same tests.  The persistent compile cache is off
around the compiles (an entry compiled for a described chip cannot be read
back without one).
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ragged_attention import ragged_segment_attention

KV, G, HD = 8, 2, 128          # qwen3-1.7b: 16 heads over 8 KV heads
LAYERS = 28
N_BLOCKS = 3072                # 5.25 GiB of bf16 pool at 28 layers, bs 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                topo = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("bs", [8, 16])
def test_paged_attention_compiles_for_v5e(one_chip, bs):
    """Decode kernel, 8 rows over 128-page tables of one layer of the
    whole pool; bs 16 is the bf16 native tile the smoke serves, bs 8 the
    ServingConfig default."""
    pool = _shape(one_chip, (LAYERS, 2, N_BLOCKS, KV, bs, HD), jnp.bfloat16)
    compiled = jax.jit(paged_attention).lower(
        _shape(one_chip, (8, KV, G, HD), jnp.bfloat16), pool,
        _shape(one_chip, ()), _shape(one_chip, (8, 128)),
        _shape(one_chip, (8,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("s,lq,nb", [
    (2, 512, 96),     # 512-token chunks resuming after a cached prefix
    (8, 4, 4),        # short single-block chunks
])
def test_ragged_attention_compiles_for_v5e(one_chip, s, lq, nb):
    pool = _shape(one_chip, (LAYERS, 2, N_BLOCKS, KV, 16, HD), jnp.bfloat16)
    compiled = jax.jit(ragged_segment_attention).lower(
        _shape(one_chip, (s, lq, KV, G, HD), jnp.bfloat16), pool,
        _shape(one_chip, ()), _shape(one_chip, (s, nb)),
        _shape(one_chip, (s, lq))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_step_compiles_for_v5e(one_chip):
    """The engine's fused iteration (a 512-token chunk + 8 decode rows) at
    published widths and depth over the benchmark's 3,072-block pool, with
    the Pallas kernels inside.  The layer scan carries the pool and
    updates it in place: the step's temporaries are a small share of the
    pool (a scan over per-layer pool slices needed more than a whole pool
    of them), and no HLO copy has the shape of the pool or of a slice of
    it (a layout change of the carried pool would copy it whole)."""
    from repro.models import build_model
    from repro.serving import PagedModelRunner
    cfg = dataclasses.replace(get_config("qwen3-1.7b"), num_layers=LAYERS)
    model = build_model(cfg)
    params = jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype),
                          jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    runner = PagedModelRunner(model, params, num_blocks=4, block_size=16,
                              max_batch=8, backend="pallas")
    pool = _shape(one_chip, (cfg.num_layers, 2, N_BLOCKS, KV, 16, HD),
                  jnp.bfloat16)
    i32 = lambda *s: _shape(one_chip, s)
    compiled = runner._fused_fn.lower(
        params, pool, i32(1, 512), i32(1, 512), i32(1, 128), i32(8), i32(8),
        i32(8, 128), i32(520), i32(16), i32(4), i32(4)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2 ** 30
    pool_bytes = LAYERS * 2 * N_BLOCKS * KV * 16 * HD * 2
    assert mem.temp_size_in_bytes < pool_bytes / 16
    pool_copy = re.compile(
        r"= bf16\[(?:[0-9]+,)*%d,%d,16,%d\]\{[^}]*\} copy(-start)?\("
        % (N_BLOCKS, KV, HD))
    assert not [ln for ln in text.splitlines() if pool_copy.search(ln)]
