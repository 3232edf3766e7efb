"""The traffic generator: exact repeats, and the same work for every seed."""
from collections import Counter

import numpy as np
import pytest

from bench.traffic.gen import Prompts, draw_gaps, load_mix, n_per_period, schedule

SECONDS = 30.0


@pytest.fixture(scope="module")
def mix():
    return load_mix("agents-shared")


def _sizes(flows, period):
    return Counter((f.app, f.stages) for f in flows if f.period == period)


def test_one_seed_repeats_exactly(mix):
    assert schedule(mix, SECONDS) == schedule(mix, SECONDS)
    seed = 2**33 + 5
    pa, pb = Prompts(mix, seed, 1000), Prompts(mix, seed, 1000)
    for f in schedule(mix, SECONDS)[:20]:
        for i in range(len(f.stages)):
            ta, sa = pa.prompt(f, i)
            tb, sb = pb.prompt(f, i)
            assert sa == sb and np.array_equal(ta, tb)


def test_seeds_change_the_tokens_and_not_the_schedule(mix):
    f = schedule(mix, SECONDS)[0]
    t1, _ = Prompts(mix, 1, 50_000).prompt(f, 0)
    t2, _ = Prompts(mix, 2, 50_000).prompt(f, 0)
    assert len(t1) == len(t2) and not np.array_equal(t1, t2)
    flows = schedule(mix, SECONDS)
    win = sorted(f.arrival for f in flows if f.period == "window")
    gaps = np.diff(win + [2 * SECONDS])
    assert np.allclose(gaps, draw_gaps(mix, len(win), SECONDS))
    for k in {f.seed_offset for f in flows if f.period != "window"}:
        assert Counter((f.app, f.stages) for f in flows if f.seed_offset == k) \
            == _sizes(flows, "window")


def test_warmup_replays_the_window_with_fresh_tokens(mix):
    flows = schedule(mix, SECONDS)
    warm = [f for f in flows if f.period == "warmup"]
    win = [f for f in flows if f.period == "window"]
    assert len(warm) == len(win) == n_per_period(mix, SECONDS)
    for w, x in zip(warm, win):
        assert w.stages == x.stages and x.arrival - w.arrival == pytest.approx(SECONDS)
    p = Prompts(mix, 3, 50_000)
    tw, sw = p.prompt(warm[0], 0)
    tx, sx = p.prompt(win[0], 0)
    assert sw == sx == mix["system_prompt_tokens"]
    assert np.array_equal(tw[:sw], tx[:sx]) and not np.array_equal(tw[sw:], tx[sx:])


def test_periods_tile_time(mix):
    flows = schedule(mix, SECONDS)
    for k, period in enumerate(["warmup", "window"]):
        arr = [f.arrival for f in flows if f.period == period]
        assert min(arr) == pytest.approx(k * SECONDS) and max(arr) < (k + 1) * SECONDS


def test_unshared_prompts_are_unique_at_the_same_length(mix):
    un = load_mix("agents-unshared")
    assert un["apps"] == mix["apps"] and not un["shared_system_prompt"]
    f = schedule(un, SECONDS)[0]
    t1, s1 = Prompts(un, 5, 50_000).prompt(f, 0)
    t2, s2 = Prompts(mix, 5, 50_000).prompt(f, 0)
    assert s1 == 0 and len(t1) == len(t2)
