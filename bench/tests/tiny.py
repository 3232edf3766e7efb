"""A configuration and a mix small enough for the CPU, for the tests."""
from __future__ import annotations

import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def tiny_spec(limit: float = 1.0) -> dict:
    """qwen3-1.7b's registry entry at its CPU test size (``reduced()``)."""
    spec = json.loads((BENCH / "configs" / "qwen3-1.7b.json").read_text())
    spec.update({"hidden_size": 256, "intermediate_size": 512,
                 "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
                 "head_dim": 64, "vocab_size": 512})
    spec["serving"] = dict(spec["serving"], num_blocks=256, max_batch=8,
                           prefill_chunk_tokens=64)
    spec["check"] = {"max_logit_gap": limit}
    return spec


def tiny_registry(spec: dict):
    """The registry entry cut to ``reduced()``, checked against the spec
    like a cell's; stands in for ``bench.model.registry_config``."""
    from bench.model import check_registry
    from repro.configs import get_config
    cfg = get_config(spec["registry"]).reduced()
    check_registry(spec, cfg)
    return cfg


def tiny_mix(rate: float = 4.0) -> dict:
    mix = json.loads((BENCH / "traffic" / "agents-shared.json").read_text())
    mix = copy.deepcopy(mix)
    mix["name"] = "tiny"
    mix["system_prompt_tokens"] = 32
    for app in mix["apps"]:
        for prof in app["agents"].values():
            prof["out_median"] = max(8, prof["out_median"] / 2)
            prof["prompt_median"] = 12
    mix["limits"] = dict(mix["limits"], max_unique_prompt=48, max_output=48)
    mix["arrival"] = dict(mix["arrival"], rate_wf_per_s=rate)
    mix["follow_cap_s"] = 20
    return mix
