"""Plain reference for the dense attention configurations in this directory.

A decoder-only transformer as each file states it, written from the
published equations in straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``: embedding, then per layer
RMSNorm ``x * rsqrt(mean(x^2) + eps) * (1 + scale)``, q/k/v projections,
optional per-head RMSNorm of q and k (Qwen3), rotary embedding over the
head's two halves, causal softmax attention with grouped KV heads, the
output projection, RMSNorm, a SwiGLU feed-forward, and last the final
norm and the LM head (tied to the embedding or not).  No cache, no
paging, no batching, no kernels; it imports nothing of the program.

``hidden_states`` runs one sequence layer by layer (a scan over the
stacked weights, each layer's weights widened to float32 inside the
step) with the queries in blocks, so it fits beside nothing else on one
chip.  ``quant`` computes every matrix product with both inputs rounded
to an 8-bit float (e4m3, one scale per row of the activations and per
output column of the weights): the control that a lower precision must
fail.

The module is the architecture of every configuration that names it
under ``reference``, and the harness asks it for all that depends on
the model: ``check_registry`` (the program's entry serves the file's
widths), ``weights`` (the parameter tree in the program's layout),
``dims`` (the sizes the per-layer readers see, ``attn`` among them:
the attention layers in groups of like heads and window) and
``step_flops`` (the operations one step's served tokens need).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30
Q_BLOCK = 512

# config-file key -> registry ModelConfig attribute
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


def dims(spec: dict) -> dict:
    d, h, kv = spec["hidden_size"], spec["num_attention_heads"], spec["num_key_value_heads"]
    hd, L = spec["head_dim"], spec["num_hidden_layers"]
    return dict(d=d, h=h, kv=kv, hd=hd, f=spec["intermediate_size"],
                v=spec["vocab_size"], L=L,
                tied=bool(spec["tie_word_embeddings"]), qk_norm=bool(spec["qk_norm"]),
                attn=[dict(n=L, h=h, kv=kv, hd=hd, window=None)])


def weights(spec: dict, key):
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


def step_flops(work, m: dict) -> float:
    """Operations one step's served tokens need."""
    d, h, kv, hd, f, v, L = m["d"], m["h"], m["kv"], m["hd"], m["f"], m["v"], m["L"]
    per_token = 2.0 * L * (d * (h + 2 * kv) * hd + h * hd * d + 3 * d * f)
    attn = 4.0 * L * h * hd
    n_tok = len(work.decode_ctx) + sum(e - s for s, e in work.chunks)
    keys = sum(work.decode_ctx) + sum((e - s) * (s + e + 1) / 2.0
                                      for s, e in work.chunks)
    return per_token * n_tok + attn * keys + 2.0 * d * v * work.n_emit


def _fp8(x, axis):
    """Round ``x`` to e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant: bool):
    """x (..., k) @ w (k, n) in float32, or with fp8-rounded inputs."""
    if quant:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _rope(x, pos, theta):
    """x (S, H, hd); rotate (first half, second half) pairs."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal attention, q (S, H, hd), k/v (S, KV, hd), queries in blocks."""
    s, h, hd = q.shape
    kvh = k.shape[1]
    g = h // kvh
    pos = jnp.arange(s)
    qb = q.reshape(s // Q_BLOCK, Q_BLOCK, kvh, g, hd)
    pb = pos.reshape(s // Q_BLOCK, Q_BLOCK)

    def block(_, xs):
        qq, pp = xs
        sc = jnp.einsum("qkgd,tkd->kgqt", qq, k) / jnp.sqrt(jnp.float32(hd))
        sc = jnp.where(pp[:, None] >= pos[None, :], sc, NEG_INF)
        p = jax.nn.softmax(sc, axis=-1)
        return None, jnp.einsum("kgqt,tkd->qkgd", p, v).reshape(Q_BLOCK, h * hd)

    _, out = jax.lax.scan(block, None, (qb, pb))
    return out.reshape(s, h * hd)


@functools.partial(jax.jit, static_argnames=("spec_key", "quant"))
def _hidden(weights, tokens, spec_key, quant):
    spec = dict(spec_key)
    d, h, kv, hd = (spec["hidden_size"], spec["num_attention_heads"],
                    spec["num_key_value_heads"], spec["head_dim"])
    eps = spec["eps"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x = weights["embed"][tokens].astype(jnp.float32)

    def layer(x, lw):
        lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
        a = lw["attn"]
        hn = _rms(x, lw["ln1"], eps)
        q = _mm(hn, a["wq"], quant).reshape(s, h, hd)
        k = _mm(hn, a["wk"], quant).reshape(s, kv, hd)
        v = _mm(hn, a["wv"], quant).reshape(s, kv, hd)
        if "q_norm" in a:
            q = _rms(q, a["q_norm"], eps)
            k = _rms(k, a["k_norm"], eps)
        q = _rope(q, pos, spec["rope_theta"])
        k = _rope(k, pos, spec["rope_theta"])
        x = x + _mm(_attention(q, k, v), a["wo"], quant)
        hn = _rms(x, lw["ln2"], eps)
        ff = lw["ffn"]
        gate = jax.nn.silu(_mm(hn, ff["w_gate"], quant))
        x = x + _mm(gate * _mm(hn, ff["w_up"], quant), ff["w_down"], quant)
        return x, None

    x, _ = jax.lax.scan(layer, x, weights["layers"])
    return _rms(x, weights["final_norm"].astype(jnp.float32), eps)


def _spec_key(spec: dict):
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rope_theta")
    return tuple(sorted({**{k: spec[k] for k in keys}, "eps": eps_of(spec)}.items()))


def hidden_states(weights, tokens, spec: dict, quant: bool = False):
    """Final-norm hidden states (S, d) of one sequence, float32.  ``S``
    must be a multiple of the query block; pad at the end (causal
    attention leaves earlier rows unchanged)."""
    with jax.default_matmul_precision("highest"):
        return _hidden(weights, tokens, _spec_key(spec), quant)


@functools.partial(jax.jit, static_argnames=("tied", "quant"))
def _head(weights, x, tied, quant):
    w = weights["embed"].T if tied else weights["lm_head"]
    return _mm(x, w.astype(jnp.float32), quant)


def logits(weights, x, spec: dict, quant: bool = False):
    """LM-head logits of hidden rows ``x`` (n, d), float32."""
    with jax.default_matmul_precision("highest"):
        return _head(weights, x, bool(spec["tie_word_embeddings"]), quant)
