"""The reduction of the program's own spans (``bench/program.py``)."""
import pytest

from bench import program
from bench import trace as tr
from bench.tests.test_trace import DATA, MS, _synthetic


def _with_program_spans():
    """The synthetic trace with the program's spans: one ``kairos.step``
    inside the first ``bench.step`` on the main thread, and a worker
    thread's spans during the second."""
    main, worker = "/host:CPU#0", "/host:CPU#1"
    return _synthetic() + [
        ("program", "kairos.step", 0.5 * MS, 9.5 * MS, main),
        ("program", "kairos.ctrl.tick", 0.5 * MS, 1 * MS, main),
        ("program", "kairos.sched.plan", 1 * MS, 1.5 * MS, main),
        ("program", "kairos.sched.flatten", 1.5 * MS, 3 * MS, main),
        ("program", "kairos.engine.sync", 1.6 * MS, 2.8 * MS, main),
        ("program", "kairos.engine.dispatch", 3 * MS, 3.5 * MS, main),
        ("program", "kairos.engine.collect", 8.5 * MS, 9 * MS, main),
        ("program", "kairos.sched.plan", 12 * MS, 13 * MS, worker),
        ("program", "kairos.engine.sync", 13 * MS, 14 * MS, worker),
    ]


def test_reduce_charges_idle_to_the_innermost_program_span():
    evs = _with_program_spans()
    red, base = program.reduce(evs), tr.reduce(evs)
    prog = red["program"]
    assert red["n_program_steps"] == 1
    assert prog["kairos.step"]["total_s"] == pytest.approx(0.009)
    assert prog["kairos.step"]["self_s"] == pytest.approx(0.009 - 0.0035)
    assert prog["kairos.sched.flatten"]["self_s"] == pytest.approx(0.0015 - 0.0012)
    assert prog["kairos.sched.plan"]["n"] == 2          # both threads count
    assert prog["kairos.sched.plan"]["self_s"] == pytest.approx(0.0015)
    assert prog["kairos.engine.sync"]["self_s"] == pytest.approx(0.0022)
    # self times add up to the parent's duration on the main thread
    main = [n for n in prog if n != "kairos.sched.plan" and n != "kairos.engine.sync"]
    assert sum(prog[n]["self_s"] for n in main) + 0.0005 + 0.0012 == pytest.approx(
        prog["kairos.step"]["total_s"])
    # idle gaps 0-2, 8-13, 18-22 ms; only the main thread's spans take idle
    idle = red["program_idle"]
    assert idle == pytest.approx({
        "outside program": 0.0005 + 0.0035 + 0.004, "kairos.ctrl.tick": 0.0005,
        "kairos.sched.plan": 0.0005, "kairos.sched.flatten": 0.0001,
        "kairos.engine.sync": 0.0004, "kairos.step": 0.001,
        "kairos.engine.collect": 0.0005})
    assert sum(idle.values()) == pytest.approx(base["idle"]["bench.step"] + base["idle"]["bench.stamp"])
    assert sum(idle.values()) == pytest.approx(base["window_s"] - base["busy_s"])
    under_step = sum(v for k, v in idle.items() if k.startswith("kairos."))
    assert under_step <= base["idle"]["bench.step"]


def test_reduce_without_program_spans_charges_all_idle_outside():
    evs = tr.load_events(DATA / "trace_v5e_qwen3.json.gz")
    red, base = program.reduce(evs), tr.reduce(evs)
    assert red["program"] == {} and red["n_program_steps"] == 0
    assert red["program_idle"] == {"outside program": pytest.approx(
        base["window_s"] - base["busy_s"])}


def test_reduce_on_a_trace_with_program_spans_recorded_on_the_chip():
    """Three bench.step spans of qwen3-1.7b.agents-shared on one TPU v5e,
    from a first run that compiled cold: the middle step met a step
    shape the warm-up had not, and the device sat idle while it
    compiled.  ``trace.events`` and ``program.events`` of a traced run's
    ``.xplane.pb``, kept with ``trace.save_events`` (op names cut to
    160 characters)."""
    evs = tr.load_events(DATA / "trace_v5e_qwen3_program.json.gz")
    red, base = program.reduce(evs), tr.reduce(evs)
    prog, idle = red["program"], red["program_idle"]
    assert set(prog) == {"kairos.step", "kairos.ctrl.tick", "kairos.sched.plan",
                         "kairos.sched.flatten", "kairos.engine.dispatch",
                         "kairos.engine.compile", "kairos.engine.collect",
                         "kairos.engine.sync"}
    assert red["n_program_steps"] == base["n_step_spans"] == 3
    # every idle gap is charged once, and the program's spans hold what
    # the harness's bench.step held: the spans share the device's clock
    assert sum(idle.values()) == pytest.approx(base["window_s"] - base["busy_s"])
    under = sum(v for k, v in idle.items() if k.startswith("kairos."))
    assert under == pytest.approx(base["idle"]["bench.step"], rel=0.01)
    # one thread: self times add up to the steps' durations
    assert sum(p["self_s"] for p in prog.values()) == pytest.approx(
        prog["kairos.step"]["total_s"])
    # the token syncs nest in flatten, which keeps little of its own
    assert prog["kairos.sched.flatten"]["total_s"] > prog["kairos.engine.sync"]["total_s"]
    assert prog["kairos.sched.flatten"]["self_s"] < 0.01
    # read by hand off the recorded trace: a 2.6625 s compile, 2.6619 s
    # of it with the device idle, of 2.6720 s idle in a 3.6258 s window
    assert prog["kairos.engine.compile"]["n"] == 1
    assert prog["kairos.engine.compile"]["total_s"] == pytest.approx(2.6625, abs=1e-4)
    assert idle["kairos.engine.compile"] == pytest.approx(2.6619, abs=1e-4)
    assert base["window_s"] - base["busy_s"] == pytest.approx(2.6720, abs=1e-4)
