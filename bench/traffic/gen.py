"""Open-loop multi-agent traffic from a data file of parameters.

One general generator reads every mix in ``bench/traffic/<name>.json``.
The applications are the paper's agent workflows (copied from the
program's ``sim/workload.py``, so the yardstick does not move when the
program does): each agent's unique prompt part and output length are
lognormal, each workflow is a chain of agent stages picked by the
routing table, and workflows arrive as a Gamma renewal process with a
coefficient of variation above 1 (the bursty Splitwise shape).

Steadiness across seeds: the schedule -- each workflow's stages and
sizes, their order and the inter-arrival gaps (scaled to sum to exactly
one period) -- is a fixed trace drawn once from the mix's own
``population_seed``.  A run's ``--seed`` draws the weights and every
token id, never the schedule, so every seed offers the same work at the
same times.  (Ordering the same workflows and gaps by ``--seed`` was
tried: with 16-22 workflows in a window, the order alone moved the
window's output tokens by 40% between two seeds.)

A run's schedule is a warm-up period, then the window, then
follow-through periods up to the cap in the mix file, each ``seconds``
long.  The warm-up replays the window's sizes and arrival times with
fresh unique tokens, so it meets the step shapes the window will meet;
the follow-through keeps the load on, in further fixed orders, while
the window's workflows finish.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent


def load_mix(name: str, root: Optional[Path] = None) -> dict:
    """The mix ``<name>.json``.  A file may name a ``base`` mix and give
    only what differs (a rate, say): its keys replace the base's, and a
    nested group such as ``arrival`` is merged key by key."""
    root = root or HERE
    mix = json.loads((root / f"{name}.json").read_text())
    base = mix.pop("base", None)
    if base is not None:
        merged = load_mix(base, root)
        for k, v in mix.items():
            if isinstance(v, dict) and isinstance(merged.get(k), dict):
                merged[k] = {**merged[k], **v}
            else:
                merged[k] = v
        mix = merged
    mix["name"] = name
    return mix


@dataclasses.dataclass(frozen=True)
class Stage:
    agent: str
    unique_len: int       # prompt tokens after the system prompt
    out_len: int          # tokens to generate (greedy, no EOS)


@dataclasses.dataclass(frozen=True)
class FlowShape:
    app: str
    stages: Tuple[Stage, ...]


@dataclasses.dataclass
class Flow:
    """One workflow of a run's schedule."""
    wid: int
    app: str
    stages: Tuple[Stage, ...]
    arrival: float        # scheduled arrival, seconds from schedule start
    period: str           # "warmup" | "window" | "follow"
    seed_offset: int      # distinguishes the token draws of replayed flows


def _lognormal_len(rng, median: float, sigma: float, lo: int, hi: int) -> int:
    return int(min(hi, max(lo, int(rng.lognormal(math.log(median), sigma)))))


def draw_population(mix: dict, n: int) -> List[FlowShape]:
    """``n`` workflows drawn from the mix's fixed population seed."""
    rng = np.random.default_rng(mix["population_seed"])
    apps = mix["apps"]
    weights = np.array([a["weight"] for a in apps], float)
    weights /= weights.sum()
    lim = mix["limits"]
    out = []
    for _ in range(n):
        app = apps[int(rng.choice(len(apps), p=weights))]
        agent, stages = app["entry"], []
        for _hop in range(lim["max_stages"]):
            prof = app["agents"][agent]
            stages.append(Stage(
                agent,
                _lognormal_len(rng, prof["prompt_median"], prof["prompt_sigma"],
                               lim["min_unique_prompt"], lim["max_unique_prompt"]),
                _lognormal_len(rng, prof["out_median"], prof["out_sigma"],
                               lim["min_output"], lim["max_output"])))
            nxt = app["routes"].get(agent)
            if not nxt:
                break
            names = [x[0] for x in nxt]
            p = np.array([x[1] for x in nxt], float)
            agent = names[int(rng.choice(len(names), p=p / p.sum()))]
        out.append(FlowShape(app["name"], tuple(stages)))
    return out


def draw_gaps(mix: dict, n: int, period: float) -> np.ndarray:
    """``n`` Gamma-renewal gaps scaled to sum to ``period`` exactly."""
    arr = mix["arrival"]
    cv = arr["cv"]
    shape = 1.0 / cv ** 2
    rng = np.random.default_rng(mix["population_seed"] + 1)
    gaps = rng.gamma(shape, 1.0 / (arr["rate_wf_per_s"] * shape), n)
    return gaps * (period / gaps.sum())


def n_per_period(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["arrival"]["rate_wf_per_s"] * seconds)))


def schedule(mix: dict, seconds: float) -> List[Flow]:
    """Warm-up, window and follow-through periods of ``seconds`` each
    (follow-through periods repeat to cover ``follow_cap_s``)."""
    n = n_per_period(mix, seconds)
    pop = draw_population(mix, n)
    gaps = draw_gaps(mix, n, seconds)
    rng = np.random.default_rng(mix["population_seed"] + 2)

    # a period's first arrival is at its start and its last one gap
    # before its end: periods tile time with no overlap
    def starts(g):
        return np.cumsum(g) - g

    offsets = starts(gaps)
    flows: List[Flow] = []
    n_follow = max(1, int(math.ceil(mix["follow_cap_s"] / seconds)))
    periods = ["warmup", "window"] + ["follow"] * n_follow
    for k, period in enumerate(periods):
        if period == "follow":
            # later periods keep the same sizes, in another fixed order
            order_k = rng.permutation(n)
            offs_k = starts(gaps[rng.permutation(n)])
        else:
            order_k, offs_k = np.arange(n), offsets   # warm-up replays the window
        for i in range(n):
            shape = pop[order_k[i]]
            flows.append(Flow(len(flows), shape.app, shape.stages,
                              float(k * seconds + offs_k[i]), period, k))
    return flows


class Prompts:
    """Token ids of every call, drawn from the run's seed.

    With ``shared_system_prompt`` each agent's calls start with the same
    ``system_prompt_tokens`` ids (drawn once per agent), which the prefix
    cache can serve; without it every token of every prompt is unique,
    at the same total length."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.seed = seed
        self.vocab = vocab
        self.sys_len = int(mix["system_prompt_tokens"])
        self.shared = bool(mix["shared_system_prompt"])
        self._sys: Dict[str, np.ndarray] = {}

    def _system(self, agent: str) -> np.ndarray:
        if agent not in self._sys:
            key = [self.seed, 11, sum(map(ord, agent)), len(agent)]
            rng = np.random.default_rng(np.random.SeedSequence(key))
            self._sys[agent] = rng.integers(0, self.vocab, self.sys_len,
                                            dtype=np.int64).astype(np.int32)
        return self._sys[agent]

    def prompt(self, flow: Flow, stage_idx: int) -> Tuple[np.ndarray, int]:
        """(token ids, shared prefix length) of one call."""
        st = flow.stages[stage_idx]
        rng = np.random.default_rng(np.random.SeedSequence(
            [self.seed, 13, flow.seed_offset, flow.wid, stage_idx]))
        if self.shared:
            uniq = rng.integers(0, self.vocab, st.unique_len,
                                dtype=np.int64).astype(np.int32)
            return np.concatenate([self._system(st.agent), uniq]), self.sys_len
        toks = rng.integers(0, self.vocab, self.sys_len + st.unique_len,
                            dtype=np.int64).astype(np.int32)
        return toks, 0
