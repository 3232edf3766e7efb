"""A configuration file, the model it names, and its weights.

``load_config`` reads ``bench/configs/<name>.json`` and checks that the
program's registry entry it names serves exactly these widths: a
mismatch stops the run before anything is built.  ``make_weights``
draws every parameter on the device in one jitted call from the seed, in
the types the program serves them in (matrices bf16, norm scales f32).
The reference in ``bench/configs`` draws the same weights through the
same function, never through the program.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parent

# config-file key -> (registry ModelConfig attribute, how to read it)
WIDTH_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "resolved_head_dim",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
    "qk_norm": "qk_norm",
}
EPS_KEYS = ("rms_norm_eps", "layer_norm_eps")


def load_config(name: str, root: Optional[Path] = None) -> dict:
    spec = json.loads(((root or HERE / "configs") / f"{name}.json").read_text())
    assert spec["name"] == name, f"{name}.json names {spec['name']!r}"
    return spec


def eps_of(spec: dict) -> float:
    return float(next(spec[k] for k in EPS_KEYS if k in spec))


def check_registry(spec: dict, cfg) -> None:
    """The program's ``ModelConfig`` must serve the file's widths; the
    program implements RMSNorm and full-width rotary only, so the file
    must say so (its ``departures`` list why)."""
    bad = []
    for key, attr in WIDTH_KEYS.items():
        want, got = spec[key], getattr(cfg, attr)
        if (float(want) != float(got)) if isinstance(want, (int, float)) \
                and not isinstance(want, bool) else (bool(want) != bool(got)):
            bad.append(f"{key}: file {want} vs registry {attr}={got}")
    if abs(eps_of(spec) - cfg.norm_eps) > 1e-12:
        bad.append(f"norm eps: file {eps_of(spec)} vs registry {cfg.norm_eps}")
    if spec["norm"] != "rms" or float(spec["partial_rotary_factor"]) != 1.0:
        bad.append("the program serves RMSNorm and full-width rotary only")
    if cfg.family != "dense" or cfg.moe is not None or cfg.sliding_window:
        bad.append(f"registry entry is {cfg.family}, not a dense attention stack")
    if spec["torch_dtype"] != cfg.dtype:
        bad.append(f"dtype: file {spec['torch_dtype']} vs registry {cfg.dtype}")
    if bad:
        raise SystemExit(f"config {spec['name']}: registry "
                         f"{spec['registry']!r} differs: " + "; ".join(bad))


def registry_config(spec: dict):
    from repro.configs import get_config
    cfg = get_config(spec["registry"])
    check_registry(spec, cfg)
    return cfg


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole seed (the driver's exceed 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    key = jax.random.PRNGKey(0)
    for w in words:
        key = jax.random.fold_in(key, int(w) & 0x7FFFFFFF)
    return key


def dims(spec: dict) -> dict:
    d, h, kv = spec["hidden_size"], spec["num_attention_heads"], spec["num_key_value_heads"]
    return dict(d=d, h=h, kv=kv, hd=spec["head_dim"], f=spec["intermediate_size"],
                v=spec["vocab_size"], L=spec["num_hidden_layers"],
                tied=bool(spec["tie_word_embeddings"]), qk_norm=bool(spec["qk_norm"]))


def _weights(spec: dict, key):
    """The parameter tree in the program's layout (stacked layers)."""
    m = dims(spec)
    d, h, kv, hd, f, v, L = m["d"], m["h"], m["kv"], m["hd"], m["f"], m["v"], m["L"]
    bf = jnp.bfloat16
    leaves = iter(range(64))

    def normal(shape, scale, dtype):
        k = jax.random.fold_in(key, next(leaves))
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    def dense(shape):                   # (..., d_in, d_out), 1/sqrt(d_in)
        return normal(shape, shape[-2] ** -0.5, bf)

    attn = {"wq": dense((L, d, h * hd)), "wk": dense((L, d, kv * hd)),
            "wv": dense((L, d, kv * hd)), "wo": dense((L, h * hd, d))}
    if m["qk_norm"]:
        attn["q_norm"] = normal((L, hd), 0.1, jnp.float32)
        attn["k_norm"] = normal((L, hd), 0.1, jnp.float32)
    params = {
        "embed": normal((v, d), 0.02, bf),
        "final_norm": normal((d,), 0.1, jnp.float32),
        "layers": {
            "ln1": normal((L, d), 0.1, jnp.float32),
            "ln2": normal((L, d), 0.1, jnp.float32),
            "attn": attn,
            "ffn": {"w_gate": dense((L, d, f)), "w_up": dense((L, d, f)),
                    "w_down": dense((L, f, d))},
        },
    }
    if not m["tied"]:
        params["lm_head"] = dense((d, v))
    return params


def make_weights(spec: dict, seed: int):
    """Every weight, drawn on the default device by one jitted call."""
    fn = jax.jit(lambda k: _weights(spec, k))
    return jax.block_until_ready(fn(seed_key(seed)))


def serving_config(spec: dict):
    from repro.serving import ServingConfig
    s = spec["serving"]
    return ServingConfig(num_blocks=s["num_blocks"], block_size=s["block_size"],
                         max_batch=s["max_batch"],
                         prefill_chunk_tokens=s["prefill_chunk_tokens"],
                         prefix_caching=s["prefix_caching"], policy=s["policy"])
