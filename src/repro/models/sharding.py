"""GSPMD sharding rules for all architectures × input shapes (DESIGN.md §5).

Mesh axes: ("data", "model") single pod, ("pod", "data", "model") multi-pod.
Batch shards over pod×data; weights megatron-style over model; MoE experts
expert-parallel over model when divisible, else per-expert tensor parallel;
optional FSDP adds a data-axis shard on weight d_model dims (kimi-k2
training).  long_500k (batch=1) context-shards the KV sequence dim over
pod×data.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


# --------------------------------------------------------------------------- #
# parameter rules
# --------------------------------------------------------------------------- #


def param_pspec(path: Tuple[str, ...], leaf, cfg: ModelConfig, mesh: Mesh,
                fsdp: bool = False) -> P:
    """Map a parameter-tree path to a PartitionSpec.

    The returned spec addresses the TRAILING dims of the (possibly
    layer-stacked) leaf; leading stack dims are padded with None.
    """
    key = path[-1]
    msize = _axis_size(mesh, "model")
    d_axis = "data" if (fsdp and "data" in mesh.axis_names) else None

    def base() -> Optional[Tuple]:
        m = cfg.moe
        # ---- embeddings -----------------------------------------------------
        # vocab dim only: FSDP-sharding the d_model dim here would put the
        # contraction dim of the LM head on `data` and force a full-logits
        # fp32 all-reduce (measured 16.5 GiB/op on chameleon train, §Perf)
        if key == "embed":
            return ("model", None)
        if key == "lm_head":
            return (None, "model")
        # ---- MoE ------------------------------------------------------------
        if key == "router":
            return (None, None)
        if key in ("w_gate", "w_up", "w_down") and leaf.ndim >= 3 and m is not None:
            ep = _div(m.num_experts, msize)
            if key == "w_down":   # (E, f, d)
                return ("model", None, d_axis) if ep else (None, "model", d_axis)
            return ("model", d_axis, None) if ep else (None, d_axis, "model")
        # ---- dense ffn -------------------------------------------------------
        if key in ("w_gate", "w_up"):
            return (d_axis, "model")
        if key == "w_down":
            return ("model", d_axis)
        # ---- attention -------------------------------------------------------
        if key in ("wq",):
            return (d_axis, "model")
        if key in ("wk", "wv"):
            kv_flat = cfg.num_kv_heads * cfg.resolved_head_dim
            return (d_axis, "model") if _div(kv_flat, msize) else (None, None)
        if key == "wo":
            return ("model", d_axis)
        # ---- rwkv ------------------------------------------------------------
        if key in ("wr", "wg"):
            return (d_axis, "model")
        if key in ("w_lora_a", "w_lora_b", "w_bias", "u", "mu", "ln_x"):
            return None
        # ---- mamba -----------------------------------------------------------
        if key == "in_proj":
            return (d_axis, "model")
        if key == "out_proj":
            return ("model", d_axis)
        if key == "conv_w":
            return (None, "model")
        if key in ("conv_b", "d_skip", "dt_bias"):
            return ("model",)
        if key == "x_proj":
            return ("model", None)
        if key == "dt_proj":
            return (None, "model")
        if key == "a_log":
            return ("model", None)
        return None

    spec = base()
    if spec is None:
        return P()
    spec = tuple(spec)[-leaf.ndim:] if len(spec) > leaf.ndim else spec
    # verify divisibility; drop axes that don't divide (GSPMD would pad —
    # we prefer explicit replication for weights)
    dims = leaf.shape[leaf.ndim - len(spec):]
    fixed = []
    for ax, dim in zip(spec, dims):
        if ax is None:
            fixed.append(None)
            continue
        size = np.prod([_axis_size(mesh, a) for a in
                        (ax if isinstance(ax, tuple) else (ax,))])
        fixed.append(ax if _div(dim, int(size)) else None)
    pad = (None,) * (leaf.ndim - len(fixed))
    return P(*(pad + tuple(fixed)))


def param_shardings(params_shape, cfg: ModelConfig, mesh: Mesh, fsdp: bool = False):
    """Tree of NamedShardings matching an (abstract) params/opt-state tree."""
    def one(path, leaf):
        keys = tuple(getattr(k, "key", getattr(k, "idx", None)) for k in path)
        keys = tuple(str(k) for k in keys if k is not None)
        return NamedSharding(mesh, param_pspec(keys, leaf, cfg, mesh, fsdp))

    return jax.tree_util.tree_map_with_path(one, params_shape)


# --------------------------------------------------------------------------- #
# activation / batch / cache rules
# --------------------------------------------------------------------------- #


def batch_pspec(shape: ShapeConfig, mesh: Mesh) -> P:
    dp = dp_axes(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))
    if _div(shape.global_batch, n_dp):
        return P(dp, None)
    return P(None, None)              # long_500k: batch 1 replicated


def cache_pspecs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, cache_shape) -> dict:
    """PartitionSpecs for the decode cache tree.

    decode_32k: batch over pod×data, kv-heads over model (when divisible).
    long_500k (batch=1): KV **sequence** dim over pod×data (context
    parallelism) — GSPMD inserts the partial-softmax collectives."""
    dp = dp_axes(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))
    msize = _axis_size(mesh, "model")
    batch_ok = _div(shape.global_batch, n_dp)
    kv_ok = _div(cfg.num_kv_heads, msize)
    # GQA with n_kv < model size: split-KV (flash-decoding style) — shard
    # the cache SEQUENCE dim over `model`.  Scores stay S-sharded through
    # the softmax (GSPMD inserts only the tiny global-max/sum collectives)
    # and the PV contraction all-reduces just (B,H,1,hd).  The earlier
    # head_dim-sharding alternative forced a 268MB/layer score all-reduce
    # and an involuntary fp32 rematerialization of the cache (§Perf log).
    kv_axis = "model" if kv_ok else None
    seq_axis_model = None if kv_ok else "model"
    di = cfg.ssm_expand * cfg.d_model

    specs = {}
    for key, leaf in cache_shape.items():
        if key == "pos":
            specs[key] = P()
        elif key in ("kv", "memory_kv"):
            # (L, 2, B, S, KV, hd)
            seq = leaf.shape[3]
            if batch_ok:
                sa = seq_axis_model if _div(seq, msize) else None
                specs[key] = P(None, None, dp, sa, kv_axis, None)
            else:
                if kv_ok:
                    sa = dp if _div(seq, n_dp) else None
                else:
                    sa = (dp + ("model",)) if _div(seq, n_dp * msize) else (
                        dp if _div(seq, n_dp) else None)
                specs[key] = P(None, None, None, sa, kv_axis, None)
        elif key == "rwkv_state":      # (L, B, H, hd, hd)
            specs[key] = P(None, dp if batch_ok else None, None, None, None)
        elif key in ("rwkv_shift1", "rwkv_shift2"):   # (L, B, d)
            specs[key] = P(None, dp if batch_ok else None,
                           "model" if _div(cfg.d_model, msize) else None)
        elif key == "mamba_h":         # (L, B, di, N)
            specs[key] = P(None, dp if batch_ok else None,
                           "model" if _div(di, msize) else None, None)
        elif key == "mamba_conv":      # (L, B, k-1, di)
            specs[key] = P(None, dp if batch_ok else None, None,
                           "model" if _div(di, msize) else None)
        else:
            specs[key] = P()
    return specs


# --------------------------------------------------------------------------- #
# serving (paged-engine) rules
# --------------------------------------------------------------------------- #

# Paged KV pool (L, 2, num_blocks, n_kv, block_size, hd): shard the
# KV-head dim over "model" — the pool's logical shape is unchanged, the
# BlockManager stays head-agnostic (block ids address whole cross-shard
# pages), and each tensor-parallel shard holds exactly the head slice
# its megatron-sharded K/V projections produce.
POOL_PSPEC = P(None, None, None, "model", None, None)


def serving_param_specs(params, cfg: ModelConfig, mesh: Mesh):
    """PartitionSpec tree for the paged serving engine's params.

    The scanned layer stack gets the megatron rules from
    :func:`param_pspec` (QKV/O and FFN column/row-sharded over "model",
    so the only per-layer collectives are the two standard all-reduces).
    Embeddings / LM head / final norm are REPLICATED: the serving head
    is argmax-only, and a vocab-sharded head would trade the (tiny)
    replicated-weight memory for a per-iteration vocab collective on
    the hot path.
    """
    def one(path, leaf):
        keys = tuple(getattr(k, "key", getattr(k, "idx", None)) for k in path)
        keys = tuple(str(k) for k in keys if k is not None)
        if "layers" in keys:
            return param_pspec(keys, leaf, cfg, mesh)
        return P()

    return jax.tree_util.tree_map_with_path(one, params)


def validate_serving_tp(cfg: ModelConfig, tp: int) -> None:
    """Raise unless a ``tp``-way megatron shard of this config is exact.

    The shard_map'd engine step assumes every sharded dim divides: a
    silently-replicated weight (param_pspec's GSPMD fallback) would make
    the per-layer psum double-count that block's contribution.
    """
    if tp <= 1:
        return
    if cfg.num_kv_heads % tp or cfg.num_heads % tp:
        raise ValueError(
            f"model_parallel={tp} must divide num_heads={cfg.num_heads} "
            f"and num_kv_heads={cfg.num_kv_heads} ({cfg.name})")
    if cfg.d_ff % tp:
        raise ValueError(
            f"model_parallel={tp} must divide d_ff={cfg.d_ff} ({cfg.name})")
    if cfg.moe is not None:
        raise ValueError(
            "tensor-parallel paged serving of MoE archs is not supported "
            "(expert-parallel serving: see ROADMAP)")


def should_fsdp(cfg: ModelConfig, kind: str) -> bool:
    """Shard weights over the `data` axis as well (FSDP-style).

    Training: Adam keeps 12 bytes/param — 16-way model parallel alone OOMs
    a 16 GB v5e above ~10B params (jamba train measured 54.6 GiB/dev
    before this rule, 16x16 mesh; §Perf iteration 1).
    Serving: bf16 weights alone exceed HBM above ~64B params at 16-way
    (kimi-k2 decode measured 128 GiB/dev before; 8 GiB/dev after).
    """
    if kind == "train":
        return cfg.param_count() > 8e9
    return cfg.param_count() > 40e9
