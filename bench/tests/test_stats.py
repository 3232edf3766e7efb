"""End-to-end arithmetic and kernel work counts on hand-made inputs."""
import math
from types import SimpleNamespace

import pytest

from bench import stats
from bench.driver import Call, StepWork
from bench.roofline import kernel_cost, step_flops
from bench.traffic.gen import Flow, Stage


def _call(due, stamps, finished=-1.0):
    return Call(flow=None, stage=0, due=due, stamps=list(stamps), finished=finished)


def test_percentile_interpolates_linearly():
    xs = [1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0, 9.0, 10.0]
    assert stats.pct(xs, 50) == pytest.approx(5.5)
    assert stats.pct(xs, 90) == pytest.approx(9.1)
    assert math.isnan(stats.pct([], 90))


def test_ttft_counts_from_the_due_time_and_bounds_the_unserved():
    calls = [_call(10.0, [10.5, 10.6]), _call(11.0, []), _call(12.0, [14.0])]
    assert stats.ttft(calls, end=20.0) == pytest.approx([0.5, 9.0, 2.0])


def test_itl_takes_every_gap_of_every_call():
    a = _call(0.0, [1.0, 1.02, 1.06])
    b = _call(0.0, [1.0, 1.5])
    c = _call(0.0, [2.0])
    assert stats.itl_ms([a, b, c]) == pytest.approx([20.0, 40.0, 500.0])
    assert stats.pct(stats.itl_ms([a, b, c]), 99) == pytest.approx(40.0 + 0.98 * 460.0)


def test_workflow_e2e_and_rate():
    st = (Stage("A", 1, 1),)
    flows = [Flow(0, "app", st, 1.0, "window", 1), Flow(1, "app", st, 2.0, "window", 1)]
    assert stats.workflow_e2e(flows, {0: 104.0}, t0=100.0, end=110.0) == pytest.approx([3.0, 8.0])
    assert stats.rate(300, 30.0) == pytest.approx(10.0)


M = dict(d=8, h=4, kv=2, hd=16, f=32, v=100, L=3, block_size=16)


def test_paged_attention_work():
    flops, byts = kernel_cost("paged_attention")(StepWork([17, 16], [], 2), M)
    # 17 tokens -> 2 pages, 16 tokens -> 1 page; K and V, 2 KV heads, bf16
    assert flops == 3 * 4 * 4 * 16 * (17 + 16)
    assert byts == 3 * (2 * 2 * (2 + 1) * 16 * 16 * 2 + 2 * (2 * 4 * 16 * 2))


def test_ragged_attention_work():
    flops, byts = kernel_cost("ragged_attention")(StepWork([], [(32, 40)], 1), M)
    keys = sum(p + 1 for p in range(32, 40))
    assert flops == 3 * 4 * 4 * 16 * keys
    assert byts == 3 * (2 * 2 * 3 * 16 * 16 * 2 + 2 * 8 * 4 * 16 * 2)


def test_step_flops():
    w = StepWork([5], [(0, 4)], 2)
    per_tok = 2 * 3 * (8 * (4 + 4) * 16 + 4 * 16 * 8 + 3 * 8 * 32)
    attn = 4 * 3 * 4 * 16 * (5 + (1 + 2 + 3 + 4))
    assert step_flops(w, M) == per_tok * 5 + attn + 2 * 8 * 100 * 2


def test_delivered_tokens_inside_the_window():
    from bench.driver import Driver
    d = SimpleNamespace(t0=100.0, calls=[_call(0, [99.9, 100.0, 101.0, 130.0])])
    assert Driver.delivered_between(d, 0.0, 30.0) == 2
