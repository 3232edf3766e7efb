"""A configuration brings its own architecture: a module beside its file
supplies the registry check, the weights, the readers' sizes and a
step's work, and the harness reaches no dense code for it."""
import json

import numpy as np
import pytest

from bench.driver import StepWork
from bench.model import (architecture, load_config, make_weights, registry_config,
                         serving_config)
from bench.roofline import kernel_cost
from bench.run import Ctx, read_metric

TOY = '''
"""A toy architecture that is not a dense stack: a router and experts in
every layer, and one windowed attention layer in three."""
import jax
import jax.numpy as jnp

CALLS = []


def check_registry(spec, cfg):
    CALLS.append(("check", cfg.num_layers))
    if spec["layers"] > cfg.num_layers:
        raise SystemExit("the registry entry is too shallow")


def dims(spec):
    return dict(d=spec["width"], e=spec["experts"], L=spec["layers"],
                attn=[dict(n=spec["attn_layers"], h=2, kv=1, hd=4,
                           window=spec["window"])])


def weights(spec, key):
    m = dims(spec)
    k1, k2 = jax.random.split(key)
    return {"router": jax.random.normal(k1, (m["L"], m["d"], m["e"])).astype(jnp.bfloat16),
            "experts": jax.random.normal(k2, (m["L"], m["e"], m["d"], m["d"])
                                         ).astype(jnp.bfloat16)}


def step_flops(work, m):
    n_tok = len(work.decode_ctx) + sum(e - s for s, e in work.chunks)
    return 2.0 * m["L"] * m["d"] * m["d"] * n_tok
'''

SPEC = {"name": "toy", "registry": "qwen3-1.7b", "reference": "toyarch", "width": 8,
        "experts": 4, "layers": 3, "attn_layers": 1, "window": 16, "vocab_size": 32,
        "serving": {"num_blocks": 16, "block_size": 4, "max_batch": 4,
                    "prefill_chunk_tokens": 32, "prefix_caching": False,
                    "policy": "kairos"}}


@pytest.fixture
def toy(tmp_path, monkeypatch):
    """The toy module and its file in a directory of their own; the dense
    module's model functions raise if anything reaches them."""
    dense = architecture(load_config("qwen3-1.7b"))
    for name in ("check_registry", "weights", "dims", "step_flops"):
        monkeypatch.setattr(dense, name, _unreachable)
    (tmp_path / "toyarch.py").write_text(TOY)
    (tmp_path / "toy.json").write_text(json.dumps(SPEC))
    spec = load_config("toy", root=tmp_path)
    return spec, architecture(spec)


def _unreachable(*a, **k):
    raise AssertionError("dense code reached for a toy configuration")


def test_the_harness_loads_checks_and_draws_a_toy_architecture(toy):
    spec, arch = toy
    assert arch.__file__.endswith("toyarch.py")
    assert architecture(spec) is arch                  # loaded once
    cfg = registry_config(spec)
    assert arch.CALLS == [("check", cfg.num_layers)]
    w = make_weights(spec, 2**33 + 1)
    assert set(w) == {"router", "experts"}
    assert w["experts"].shape == (3, 4, 8, 8) and str(w["router"].dtype) == "bfloat16"
    again, other = make_weights(spec, 2**33 + 1), make_weights(spec, 2**33 + 2)
    assert np.array_equal(np.asarray(w["router"], np.float32),
                          np.asarray(again["router"], np.float32))
    assert not np.array_equal(np.asarray(w["router"], np.float32),
                              np.asarray(other["router"], np.float32))
    sc = serving_config(spec)
    assert (sc.num_blocks, sc.block_size, sc.max_batch) == (16, 4, 4)


def test_a_toy_architecture_counts_its_own_work(toy):
    spec, arch = toy
    m = dict(arch.dims(spec), block_size=4)
    # one attention layer of 2 query heads, 1 KV head, hd 4, window 16
    flops, byts = kernel_cost("paged_attention")(StepWork([20], [], 1), m)
    assert flops == 4 * 2 * 4 * 16                     # the last 16 of 20 keys
    assert byts == 2 * 1 * 4 * 4 * 4 * 2 + 2 * 2 * 4 * 2
    flops, byts = kernel_cost("ragged_attention")(StepWork([], [(20, 30)], 1), m)
    keys = sum(min(p + 1, 16) for p in range(20, 30))  # 16 each
    assert flops == 4 * 2 * 4 * keys
    # keys at positions [5, 30): 25 tokens, at least 7 pages of 4
    assert byts == 2 * 1 * 7 * 4 * 4 * 2 + 2 * 10 * 2 * 4 * 2
    # the readers go through the module and m
    work = [StepWork([20], [(20, 30)], 2)]
    trace = {"window_s": 1.0, "steps": [{"work": work[0], "kernels": {
        "paged_attention": 1e-6, "ragged_attention": 1e-6}}]}
    ctx = Ctx([], {}, {}, trace, work, m, {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9},
              arch=arch)
    assert read_metric("model.mfu", ctx) == pytest.approx(
        100.0 * 2 * 3 * 8 * 8 * 11 / 1e12)
    assert read_metric("paged_attention_roofline", ctx) == pytest.approx(
        100.0 * (2 * 1 * 4 * 4 * 4 * 2 + 2 * 2 * 4 * 2) / 1e9 / 1e-6)


def test_a_serving_key_the_program_lacks_stops_the_run(toy):
    spec, _ = toy
    spec["serving"]["state_slots"] = 8
    with pytest.raises(SystemExit, match="state_slots"):
        serving_config(spec)
