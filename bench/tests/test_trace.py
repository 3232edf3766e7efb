"""The reduction from trace events to the per-layer numbers."""
from pathlib import Path

import pytest

from bench import trace as tr
from bench.driver import StepWork

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6  # ns
PAGED = ("%paged_attention.11 = bf16[32,8,2,128]{3,2,1,0:T(2,128)(2,1)} custom-call("
         "s32[32,128]{1,0} %copy-done.25), custom_call_target=\"tpu_custom_call\"")


def _synthetic():
    """Two bench.step spans, each dispatching one fused step whose ops
    include one paged-attention kernel; a bench.stamp span between."""
    return [
        ("span", "bench.step", 0 * MS, 10 * MS),
        ("module", "jit_step(7)", 2 * MS, 8 * MS),
        ("op", "fusion.1 | fusion.1", 2 * MS, 4 * MS),
        ("op", PAGED, 4 * MS, 5 * MS),
        ("op", "%copy.85 = bf16[32,8,2,128]{3,2,1,0} copy(bf16[32,8,2,128] %paged_attention.11)",
         5 * MS, 6 * MS),                             # names the kernel as an operand only
        ("op", "fusion.2 | fusion.2", 6 * MS, 8 * MS),
        ("span", "bench.stamp", 10 * MS, 12 * MS),
        ("span", "bench.step", 12 * MS, 20 * MS),
        ("module", "jit_step(7)", 13 * MS, 18 * MS),
        ("op", PAGED, 13 * MS, 15 * MS),
        ("op", "fusion.1 | fusion.1", 15 * MS, 18 * MS),
        ("span", "bench.step", 20 * MS, 22 * MS),    # its step runs past the trace
        ("module", "jit_step(7)", 21 * MS, 22 * MS),
    ]


def test_reduce_on_a_synthetic_trace():
    work = [StepWork([10], [], 1), StepWork([11], [], 1)]
    red = tr.reduce(_synthetic(), work)
    assert red["window_s"] == pytest.approx(0.022)
    assert red["busy_s"] == pytest.approx(0.011)          # 2..8 and 13..18 ms
    assert red["idle"]["bench.step"] == pytest.approx(0.002 + 0.002 + 0.001 + 0.002 + 0.002)
    assert red["idle"]["bench.stamp"] == pytest.approx(0.002)
    assert [s["span"] for s in red["steps"]] == [0, 1]
    assert [s["module_s"] for s in red["steps"]] == pytest.approx([0.006, 0.005])
    assert [s["kernels"]["paged_attention"] for s in red["steps"]] == pytest.approx([0.001, 0.002])
    assert [s["kernels"]["ragged_attention"] for s in red["steps"]] == [0.0, 0.0]
    assert red["steps"][1]["work"] is work[1]
    assert red["device_ops"][0] == ("fusion.1", pytest.approx(0.005))
    assert ("%paged_attention.11 = bf16[32,8,2,128]", pytest.approx(0.003)) in red["device_ops"]


def test_reduce_on_a_trace_recorded_on_the_chip():
    """Three bench.step spans of qwen3-1.7b.agents-shared on one TPU v5e,
    taken by ``trace.events`` from a traced run's ``.xplane.pb`` and kept
    with ``trace.save_events``: op names shortened, nothing else changed."""
    evs = tr.load_events(DATA / "trace_v5e_qwen3.json.gz")
    red = tr.reduce(evs)
    spans = [e for e in evs if e[0] == "span"]
    lo, hi = min(e[2] for e in spans), max(e[3] for e in spans)
    # busy, by an independent merge of the op intervals
    busy, end = 0.0, lo
    for _, _, a, b in sorted((e for e in evs if e[0] == "op"), key=lambda e: e[2]):
        a, b = max(a, lo, end), min(b, hi)
        if b > a:
            busy += b - a
            end = b
    assert red["busy_s"] == pytest.approx(busy * 1e-9)
    assert sum(red["idle"].values()) == pytest.approx(red["window_s"] - red["busy_s"])
    # the first two spans dispatched one execution each; the third's is cut
    assert red["n_step_spans"] == 3 and [s["span"] for s in red["steps"]] == [0, 1]
    mods = [e for e in evs if e[0] == "module"]
    for s in red["steps"]:
        m = next(m for m in mods if m[3] - m[2] == pytest.approx(s["module_s"] * 1e9))
        inside = [e for e in evs if e[0] == "op" and m[2] <= e[2] < m[3]]
        paged = sum(e[3] - e[2] for e in inside
                    if e[1].startswith("%paged_attention.") and "custom-call" in e[1])
        ragged = sum(e[3] - e[2] for e in inside
                     if e[1].startswith("%ragged_segment_attention.") and "custom-call" in e[1])
        assert s["kernels"]["paged_attention"] == pytest.approx(paged * 1e-9)
        assert s["kernels"]["ragged_attention"] == pytest.approx(ragged * 1e-9)
        assert 0 < paged + ragged < m[3] - m[2]
    # read by hand off the recorded trace: 390.7 ms and 475.1 ms steps,
    # of which the paged decode kernel took 206.6 ms and 214.2 ms
    assert [s["module_s"] for s in red["steps"]] == pytest.approx([0.3907, 0.4751], abs=1e-4)
    assert [s["kernels"]["paged_attention"] for s in red["steps"]] == pytest.approx(
        [0.2066, 0.2142], abs=1e-4)
