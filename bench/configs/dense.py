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
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30
Q_BLOCK = 512


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
    eps = next(spec[k] for k in ("rms_norm_eps", "layer_norm_eps") if k in spec)
    return tuple(sorted({**{k: spec[k] for k in keys}, "eps": float(eps)}.items()))


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
