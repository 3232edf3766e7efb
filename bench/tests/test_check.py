"""``correct`` at the CPU test size: the program passes, the fp8 control
fails by the same verdict, and a broken timed path fails.

Each test drives the rest of a run (``run_cell``: build, warm-up,
window, follow-through, reference and control) with the chip look
skipped, at the tiny size of ``tiny.py``.  The limit here is the test
size's own, set from its readings like the cells' limits: over seeds 1-6
on the CPU the program's widest gap read at most 0.0132, the control's
at least 0.1047.
"""
import json

import pytest

from bench.run import BENCH, load_benchmark, run_cell
from bench.tests.tiny import tiny_mix, tiny_registry, tiny_spec

LIMIT = 0.04
SECONDS = 3.0
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
E2E = [{"name": "output_tok_per_s", "unit": "tokens/s"}]


@pytest.fixture(autouse=True)
def _tiny_registry(monkeypatch):
    monkeypatch.setattr("bench.model.registry_config", tiny_registry)


def _run(seed, trace=False, metrics=E2E):
    return run_cell(tiny_spec(LIMIT), tiny_mix(), seed, SECONDS, trace, metrics, PEAKS)


def test_program_passes_and_control_fails():
    res = _run(2**32 + 3)
    assert res["correct"], res["check"]
    assert res["check"]["max_logit_gap"]["value"] <= LIMIT
    assert not res["control"]["correct"], res["control"]
    assert res["control"]["check"]["max_logit_gap"]["value"] > LIMIT
    assert list(res)[-1] == "check"


def test_a_traced_run_reads_the_program_counters():
    """The counters' readers find their input on the CPU too; the device
    trace's readers need the chip's trace."""
    per_layer = load_benchmark()["per_layer"]
    res = _run(5, trace=True, metrics=per_layer)
    got = res["metrics"]
    assert set(got) >= {m["name"] for m in per_layer
                        if m["source"] == "program_counter"}, got
    assert 0 < got["sched.pool_live_share"]["value"] < 100
    assert res["correct"] and list(res)[-1] == "check"
    json.dumps(res)
    assert all((BENCH / "metrics" / f"{m['name']}.py").exists() for m in per_layer)


def _broken(kind):
    from repro.serving.engine import PagedModelRunner
    build = PagedModelRunner._build_fused

    def broken_build(self):
        step = build(self)

        def step2(params, pool, *args):
            nxt, new_pool = step(params, pool, *args)
            if kind == "token":        # a token altered where it is produced
                return (nxt + 1) % self.cfg.vocab_size, new_pool
            return nxt, pool           # a step that returns its state unchanged
        return step2
    return broken_build


@pytest.mark.parametrize("kind", ["token", "state"])
def test_broken_timed_path_is_not_correct(monkeypatch, kind):
    from repro.serving.engine import PagedModelRunner
    monkeypatch.setattr(PagedModelRunner, "_build_fused", _broken(kind))
    res = _run(11)
    assert not res["correct"], res["check"]
    assert res["check"]["max_logit_gap"]["value"] > LIMIT
