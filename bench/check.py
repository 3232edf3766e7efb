"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample
of the measured calls that finished, drawn from the seed and always
holding the call that served the most tokens, is run through the plain
float32 reference (``bench/configs/<reference>.py``) over its prompt and
the tokens it was served.  Served tokens are greedy, so each should be
the reference's best token at its position, up to rounding: the number
compared is the widest gap by which a served token's logit lies below
the reference's best logit there.

The control puts the reference in the program's place at the next
precision down from the configuration's bf16 (every matrix product with
fp8 e4m3 inputs) and reads, at the same positions, the gap of the token
that it ranks first.  Every run reads it beside the program's, and
``verdict`` judges both alike: the control must come out not correct.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import List

import jax.numpy as jnp
import numpy as np

from bench.model import make_weights

HERE = Path(__file__).resolve().parent
SAMPLE_CALLS = 6
SEQ_BUCKET = 1024
ROW_BLOCK = 512


def reference(spec: dict, root: Path = HERE / "configs"):
    path = root / f"{spec['reference']}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_reference_{spec['reference']}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def sample(calls, seed: int, n: int = SAMPLE_CALLS) -> list:
    done = [c for c in calls if c.finished >= 0]
    if not done:
        return []
    longest = max(done, key=lambda c: (len(c.req.output_tokens), c.req.prompt_len))
    rest = [c for c in done if c is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 17]))
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def compare(spec: dict, seed: int, calls: List) -> dict:
    """Widest served-token logit gap over the sampled calls, the
    program's and the control's."""
    ref = reference(spec)
    weights = make_weights(spec, seed)
    picked = sample(calls, seed)
    gaps, ctrl_gaps, n_tok, short = [], [], 0, 0
    for c in picked:
        prompt = np.asarray(c.req.prompt_tokens, np.int32)
        served = np.asarray([int(t) for t in c.req.output_tokens], np.int32)
        if len(served) != c.req.max_new_tokens:
            short += 1
        if len(served) == 0:
            continue
        seq = np.concatenate([prompt, served[:-1]])
        s_pad = -(-len(seq) // SEQ_BUCKET) * SEQ_BUCKET
        padded = np.zeros(s_pad, np.int32)
        padded[:len(seq)] = seq
        x = ref.hidden_states(weights, jnp.asarray(padded), spec)
        xq = ref.hidden_states(weights, jnp.asarray(padded), spec, quant=True)
        first = len(prompt) - 1          # row whose logits give served[0]
        for b in range(0, len(served), ROW_BLOCK):
            rows = np.minimum(first + b + np.arange(ROW_BLOCK), s_pad - 1)
            valid = np.arange(ROW_BLOCK) < len(served) - b
            tgt = np.zeros(ROW_BLOCK, np.int32)
            tgt[valid] = served[b:b + ROW_BLOCK]
            lg = ref.logits(weights, x[jnp.asarray(rows)], spec)
            best = np.asarray(jnp.max(lg, -1))
            got = np.asarray(jnp.take_along_axis(lg, jnp.asarray(tgt)[:, None], -1))[:, 0]
            gaps.append((best - got)[valid])
            lq = ref.logits(weights, xq[jnp.asarray(rows)], spec, quant=True)
            pick = jnp.argmax(lq, -1)
            alt = np.asarray(jnp.take_along_axis(lg, pick[:, None], -1))[:, 0]
            ctrl_gaps.append((best - alt)[valid])
        n_tok += len(served)
    del weights

    def widest(g):
        return float(np.concatenate(g).max()) if g else float("inf")
    return {"calls": len(picked), "tokens": n_tok,
            "program": {"max_logit_gap": widest(gaps), "short_calls": short},
            "control": {"max_logit_gap": widest(ctrl_gaps), "short_calls": short}}


def verdict(numbers: dict, spec: dict) -> dict:
    """Each number compared beside its limit, and whether all hold."""
    rows = {"max_logit_gap": {"value": numbers["max_logit_gap"],
                              "limit": float(spec["check"]["max_logit_gap"])},
            "short_calls": {"value": numbers["short_calls"], "limit": 0}}
    return {"correct": all(r["value"] <= r["limit"] for r in rows.values()),
            "check": rows}
