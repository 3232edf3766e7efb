"""Observability stack (src/repro/obs): tracing, metrics, SLO, export.

Covered here:

* ring-buffer ``Tracer`` semantics: per-instance streams, merged
  time-ordering, overwrite-oldest + ``dropped()``, ``NullTracer`` no-op;
* a traced real-engine cluster drain emits a well-ordered lifecycle per
  request (submit <= dispatch <= admit <= first-token <= finish) and is
  **token-identical** to the untraced drain;
* critical-path extraction on a hand-built workflow DAG (chain with a
  fan-out branch) — picks the gating chain, decomposes queue / prefill /
  decode / orch exactly;
* SLO math: per-request clauses, NaN fails closed, workflow goodput and
  good-token fraction on hand-built samples;
* Chrome/Perfetto export validates and the plain-dict round-trip is
  loss-free;
* metrics registry snapshots + engine counter consolidation
  (``runner.n_dispatches`` is registry-backed);
* orchestrator EMA: ``expected_exec_time`` feeds from measured spans
  when traced, static profiler fallback otherwise;
* the serving step's profiler spans (``obs.span``): every ``kairos.*``
  name reaches the ``.xplane.pb`` of a drain, spans nest on their
  thread, the runner's compile counter, and a first token stamped when
  it reaches the host.
"""
import json
import tempfile
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import Orchestrator
from repro.core.orchestrator import HardwareProfile
from repro.obs import (
    NULL_TRACER,
    SLO,
    CriticalPath,
    Event,
    MetricsRegistry,
    RequestSample,
    StageSpan,
    Tracer,
    critical_path,
    events_from_dicts,
    events_to_dicts,
    merge_snapshots,
    slo_report,
    spans_from_events,
    stage_breakdown,
    to_chrome_trace,
    validate_chrome_trace,
)
from repro.serving import (
    LLMEngine,
    PagedModelRunner,
    Request,
    ServingCluster,
    reset_request_ids,
)
from repro.serving.request import CompletionRecord


# =============================================================================
# tracer
# =============================================================================


def test_tracer_orders_and_merges_per_instance_streams():
    tr = Tracer()
    tr.emit("submit", req_id=1, instance_id=-1, ts=1.0)
    tr.emit("admit", req_id=1, instance_id=0, ts=2.0)
    tr.emit("decode", req_id=1, instance_id=0, ts=4.0)
    tr.emit("finish", req_id=1, instance_id=0, ts=5.0)
    tr.emit("dispatch", req_id=1, instance_id=-1, ts=1.5)
    evs = tr.events()
    assert [e.kind for e in evs] == \
        ["submit", "dispatch", "admit", "decode", "finish"]
    assert [e.ts for e in evs] == sorted(e.ts for e in evs)
    assert sorted(tr.instance_ids()) == [-1, 0]
    assert len(tr.events(instance_id=0)) == 3
    assert len(tr) == 5


def test_tracer_ring_overwrites_oldest_and_counts_drops():
    tr = Tracer(capacity_per_instance=4)
    for i in range(10):
        tr.emit("decode", req_id=i, instance_id=0, ts=float(i))
    evs = tr.events()
    assert len(evs) == 4 and tr.dropped() == 6
    assert [e.req_id for e in evs] == [6, 7, 8, 9]   # oldest overwritten
    tr.clear()
    assert len(tr) == 0 and tr.dropped() == 0


def test_null_tracer_is_inert():
    NULL_TRACER.emit("submit", req_id=1, ts=0.0)
    assert not NULL_TRACER.enabled
    assert NULL_TRACER.events() == [] and len(NULL_TRACER) == 0


def test_unknown_event_kind_rejected():
    with pytest.raises(AssertionError):
        Tracer().emit("no-such-kind", ts=0.0)


# =============================================================================
# traced real drain: ordering + token identity
# =============================================================================


@pytest.fixture(scope="module")
def model_and_params():
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("qwen3-1.7b").reduced()
    model = build_model(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def _reqs(n=5, max_new=4, seed=3):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(10, 30))
        reqs.append(Request(
            agent_name="a", msg_id=f"m{i}", prompt_len=plen,
            prompt_tokens=rng.integers(0, 500, plen).astype(np.int32),
            max_new_tokens=max_new, arrival_time=float(i) * 1e-3))
    return reqs


def _drain(model_and_params, tracer, n_instances=2):
    model, params = model_and_params
    reset_request_ids()
    runner0 = PagedModelRunner(model, params, num_blocks=64, block_size=8,
                               max_batch=4)
    engines = [
        LLMEngine(runner0 if i == 0 else runner0.clone(), instance_id=i,
                  max_batch=4, prefill_chunk_tokens=16, tracer=tracer)
        for i in range(n_instances)]
    orch = Orchestrator(hardware=HardwareProfile(
        decode_tok_per_s=20.0, kv_capacity_tokens=64 * 8))
    cluster = ServingCluster(engines, orch, tracer=tracer)
    pending = _reqs()
    done = []
    for _ in range(4000):
        while pending:
            cluster.submit(pending.pop(0))
        done.extend(cluster.step())
        if not cluster.has_work:
            break
    cluster.close()
    assert len(done) == 5
    return sorted((r.msg_id, tuple(r.output_tokens)) for r in done), cluster


def test_traced_drain_lifecycle_order_and_token_identity(model_and_params):
    tr = Tracer()
    out_traced, _ = _drain(model_and_params, tr)
    out_plain, _ = _drain(model_and_params, NULL_TRACER)
    assert out_traced == out_plain, \
        "enabling tracing must not change a single generated token"

    evs = tr.events()
    by_req = {}
    for e in evs:
        if e.req_id >= 0:
            by_req.setdefault(e.req_id, []).append(e)
    assert len(by_req) == 5
    order = {"submit": 0, "dispatch": 1, "admit": 2, "first-token": 3,
             "finish": 5}
    for req_id, res in by_req.items():
        kinds = [e.kind for e in res]
        for needed in ("submit", "dispatch", "admit", "first-token", "finish"):
            assert kinds.count(needed) == 1, (req_id, kinds)
        anchors = [e for e in res if e.kind in order]
        anchors.sort(key=lambda e: order[e.kind])
        ts = [e.ts for e in anchors]
        assert ts == sorted(ts), f"req {req_id} lifecycle out of order: {ts}"
        # control plane writes ring -1; engine events carry their instance
        sub = next(e for e in res if e.kind == "submit")
        adm = next(e for e in res if e.kind == "admit")
        assert sub.instance_id == -1 and adm.instance_id >= 0

    # spans rebuild losslessly from the stream and fully timed
    spans = spans_from_events(evs)
    assert len(spans) == 5
    assert all(s.exec_start >= 0 and s.first_token >= 0 and s.finish >= 0
               for s in spans)
    bd = stage_breakdown(spans)
    assert bd["total"]["mean"] > 0

    # export path: valid Chrome trace with both engine tracks
    trace = to_chrome_trace(evs, dropped=tr.dropped())
    assert validate_chrome_trace(trace) == []
    pnames = {e["args"]["name"] for e in trace["traceEvents"]
              if e.get("ph") == "M" and e["name"] == "process_name"}
    assert {"control-plane", "engine0", "engine1"} <= pnames
    assert any(e["ph"] == "X" and e["name"] == "decode"
               for e in trace["traceEvents"])
    json.dumps(trace)   # must serialize


def test_engine_metrics_snapshot_consolidates_counters(model_and_params):
    tr = Tracer()
    _, cluster = _drain(model_and_params, tr)
    snap = cluster.metrics_snapshot()
    for i in (0, 1):
        assert snap[f"engine{i}.n_dispatches"] >= 1
        assert snap[f"engine{i}.n_finished"] >= 1
        assert snap[f"engine{i}.iteration_tokens.count"] >= 1
    # the legacy attribute and the registry are the same counter
    e0 = cluster.engines[0]
    assert e0.runner.n_dispatches == snap["engine0.n_dispatches"]
    e0.runner.n_dispatches += 1
    assert e0.runner.metrics.counter("n_dispatches").value \
        == snap["engine0.n_dispatches"] + 1


# =============================================================================
# critical path on a hand-built DAG
# =============================================================================


def _span(name, upstream, arrival, exec_start, first_token, finish,
          msg_id="wf", req_id=0):
    return StageSpan(name=name, msg_id=msg_id, upstream=upstream,
                     arrival=arrival, exec_start=exec_start,
                     first_token=first_token, finish=finish, req_id=req_id)


def test_critical_path_hand_built_dag():
    # entry A fans out to B (slow) and C (fast); D starts after B gated it.
    spans = [
        _span("A", None, 0.0, 1.0, 2.0, 4.0, req_id=1),
        _span("B", "A", 4.5, 5.0, 6.0, 10.0, req_id=2),     # gating branch
        _span("C", "A", 4.2, 4.3, 4.5, 5.0, req_id=3),      # fast branch
        _span("D", "B", 10.5, 11.0, 12.0, 15.0, req_id=4),  # last finisher
    ]
    cp = critical_path(spans)
    assert isinstance(cp, CriticalPath)
    assert [s.name for s in cp.stages] == ["A", "B", "D"]   # C not on path
    bd = cp.breakdown()
    # queue: (1-0) + (5-4.5) + (11-10.5); prefill: 1+1+1; decode: 2+4+3
    assert bd["queue"] == pytest.approx(2.0)
    assert bd["prefill"] == pytest.approx(3.0)
    assert bd["decode"] == pytest.approx(9.0)
    assert bd["orch"] == pytest.approx(0.5 + 0.5)           # A->B, B->D gaps
    assert cp.total == pytest.approx(15.0)
    assert bd["queue"] + bd["prefill"] + bd["decode"] + bd["orch"] \
        == pytest.approx(cp.total)
    rows = cp.stage_rows()
    assert [r["agent"] for r in rows] == ["A", "B", "D"]


def test_critical_path_fan_in_picks_latest_gating_upstream():
    # two A-stage calls feed B; the later finisher is the gate
    spans = [
        _span("A", None, 0.0, 0.0, 0.5, 1.0, req_id=1),
        _span("A", None, 0.0, 0.0, 0.5, 3.0, req_id=2),
        _span("B", "A", 3.5, 3.5, 4.0, 5.0, req_id=3),
    ]
    cp = critical_path(spans)
    assert [s.req_id for s in cp.stages] == [2, 3]
    assert cp.gaps == pytest.approx([0.0, 0.5])


def test_critical_path_dangling_upstream_truncates():
    spans = [_span("B", "ghost", 1.0, 1.0, 1.5, 2.0, req_id=1)]
    cp = critical_path(spans)
    assert [s.name for s in cp.stages] == ["B"]
    assert cp.total == pytest.approx(1.0)


# =============================================================================
# SLO / goodput math
# =============================================================================


def test_slo_per_request_clauses_and_nan_fail_closed():
    slo = SLO(ttft_s=1.0, tpot_s=0.5, e2e_s=10.0)
    ok = RequestSample(msg_id="w", arrival=0.0, finish=2.0, output_len=3,
                       exec_start=0.1, first_token=0.8)   # tpot 0.6 fails
    assert not ok.meets(slo)
    ok2 = RequestSample(msg_id="w", arrival=0.0, finish=1.6, output_len=3,
                        exec_start=0.1, first_token=0.8)  # tpot 0.4
    assert ok2.meets(slo)
    # no first-token timing recorded: TTFT/TPOT are NaN -> fail closed
    missing = RequestSample(msg_id="w", arrival=0.0, finish=1.0, output_len=2)
    assert not missing.meets(slo)
    assert missing.meets(SLO(e2e_s=10.0))   # disabled clauses don't fail


def test_slo_report_workflow_goodput():
    slo = SLO(e2e_s=5.0, workflow_deadline_s=8.0)
    mk = lambda wf, a, f, n: RequestSample(
        msg_id=wf, arrival=a, finish=f, output_len=n,
        exec_start=a, first_token=a)
    samples = [
        mk("w1", 0.0, 3.0, 10), mk("w1", 3.0, 7.0, 10),   # attained, span 7
        mk("w2", 0.0, 3.0, 10), mk("w2", 4.0, 13.0, 10),  # e2e 9 > 5: miss
        mk("w3", 0.0, 2.0, 10), mk("w3", 5.0, 9.5, 10),   # span 9.5 > 8: miss
    ]
    rep = slo_report(samples, slo, duration_s=10.0)
    assert rep["n_workflows"] == 3
    assert rep["request_attainment"] == pytest.approx(5 / 6)
    assert rep["goodput_slo"] == pytest.approx(1 / 3)
    assert rep["workflow_attainment"] == rep["goodput_slo"]
    assert rep["good_token_frac"] == pytest.approx(20 / 60)
    assert rep["goodput_wf_per_s"] == pytest.approx(0.1)
    empty = slo_report([], slo)
    assert empty["goodput_slo"] == 0.0 and empty["n_requests"] == 0.0


# =============================================================================
# export round-trip
# =============================================================================


def test_event_dict_round_trip_is_loss_free():
    tr = Tracer()
    tr.emit("submit", req_id=7, instance_id=-1, agent="qa", msg_id="w1",
            ts=1.0, upstream=None)
    tr.emit("prefill-chunk", req_id=7, instance_id=0, ts=2.0,
            start=0, end=16, last=True)
    evs = tr.events()
    back = events_from_dicts(json.loads(json.dumps(events_to_dicts(evs))))
    assert [tuple(e) for e in back] == [tuple(e) for e in evs]
    assert all(isinstance(e, Event) for e in back)
    with pytest.raises(AssertionError):
        events_from_dicts([{**evs[0]._asdict(), "kind": "bogus"}])


def test_chrome_trace_collapses_missing_first_token_to_exec_span():
    tr = Tracer()
    tr.emit("submit", req_id=1, instance_id=-1, msg_id="w", ts=0.0)
    tr.emit("admit", req_id=1, instance_id=0, ts=1.0)
    tr.emit("finish", req_id=1, instance_id=0, ts=3.0)
    trace = to_chrome_trace(tr.events())
    assert validate_chrome_trace(trace) == []
    names = [e["name"] for e in trace["traceEvents"] if e["ph"] == "X"]
    assert "exec" in names and "prefill" not in names
    # in-flight request (no finish) fabricates no span
    tr2 = Tracer()
    tr2.emit("submit", req_id=2, instance_id=-1, msg_id="w", ts=0.0)
    tr2.emit("admit", req_id=2, instance_id=0, ts=1.0)
    assert [e for e in to_chrome_trace(tr2.events())["traceEvents"]
            if e["ph"] == "X"] == []


# =============================================================================
# metrics registry
# =============================================================================


def test_metrics_registry_snapshot_and_merge():
    m = MetricsRegistry()
    m.inc("reqs")
    m.inc("reqs", 2)
    m.set("depth", 7)
    for v in (1.0, 2.0, 3.0, 4.0):
        m.observe("lat", v)
    snap = m.snapshot()
    assert snap["reqs"] == 3.0 and snap["depth"] == 7.0
    assert snap["lat.count"] == 4.0
    assert snap["lat.mean"] == pytest.approx(2.5)
    assert snap["lat.max"] == 4.0
    merged = merge_snapshots({"e0": snap, "e1": {"reqs": 1.0}})
    assert merged["e0.reqs"] == 3.0 and merged["e1.reqs"] == 1.0
    with pytest.raises(AssertionError):
        m.counter("depth")   # name already registered as a gauge


# =============================================================================
# orchestrator EMA feed
# =============================================================================


def _rec(agent, exec_start, first_token, end, out_len):
    return CompletionRecord(
        agent_name=agent, msg_id="w", upstream_name=None, app_name="app",
        start_time=0.0, end_time=end, prompt_len=8, output_len=out_len,
        exec_start_time=exec_start, first_token_time=first_token)


def test_orchestrator_expected_exec_time_feeds_from_measured_spans():
    tr = Tracer()
    orch = Orchestrator(tracer=tr)
    static = Orchestrator()   # NULL_TRACER: static profiler path
    for o in (orch, static):
        o.on_completion(_rec("qa", 0.0, 2.0, 6.0, 5))
    # traced: TTFT 2.0 + TPOT 1.0 * (E[out]-1) — differs from the static
    # mode-of-distribution estimate fed the same single completion
    t_traced = orch.expected_exec_time("qa")
    exp_out = orch.profiler.expected_output_len("qa")
    assert t_traced == pytest.approx(2.0 + 1.0 * max(exp_out - 1, 1))
    # unseen agent falls back to the static path even when traced
    assert orch.expected_exec_time("ghost") \
        == static.expected_exec_time("ghost")
    # EMA moves toward a faster second sample
    orch.on_completion(_rec("qa", 0.0, 1.0, 3.0, 5))
    assert orch.expected_exec_time("qa") < t_traced


# =============================================================================
# the serving step's profiler spans
# =============================================================================

SPAN_NAMES = ("kairos.step", "kairos.ctrl.tick", "kairos.sched.plan",
              "kairos.sched.flatten", "kairos.engine.dispatch",
              "kairos.engine.compile", "kairos.engine.collect",
              "kairos.engine.sync")


def _program_spans(trace_dir):
    """(name, start_ns, end_ns, line) of every ``kairos.*`` host span in
    the profile written under ``trace_dir``."""
    from jax.profiler import ProfileData
    xplane = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("kairos."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                f"{plane.name}#{i}"))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _one_engine_cluster(model_and_params, clock=None):
    model, params = model_and_params
    reset_request_ids()
    runner = PagedModelRunner(model, params, num_blocks=64, block_size=8,
                              max_batch=4)
    kw = {} if clock is None else {"clock": clock}
    eng = LLMEngine(runner, max_batch=4, prefill_chunk_tokens=16, **kw)
    orch = Orchestrator(hardware=HardwareProfile(
        decode_tok_per_s=20.0, kv_capacity_tokens=64 * 8))
    return ServingCluster([eng], orch, **kw), eng


def test_drain_under_the_profiler_writes_every_span(model_and_params):
    cluster, _ = _one_engine_cluster(model_and_params)
    for r in _reqs(n=3, max_new=4):
        cluster.submit(r)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            done = cluster.drain()
        finally:
            jax.profiler.stop_trace()
        spans = _program_spans(d)
    cluster.close()
    assert len(done) == 3
    assert set(SPAN_NAMES) <= {s[0] for s in spans}
    steps = [s for s in spans if s[0] == "kairos.step"]
    assert len({s[3] for s in steps}) == 1       # one engine: one thread
    # every span nests on its thread: inside its parent, and the self
    # times (duration less the direct children) add up to each step
    self_ns = {}
    stack = []
    for name, a, b, line in spans:
        while stack and stack[-1][2] <= a:
            stack.pop()
        if stack:
            assert b <= stack[-1][2] and line == stack[-1][3], (name, stack[-1])
            self_ns[stack[-1]] -= b - a
        parent = stack[-1][0] if stack else None
        if name == "kairos.engine.sync" and parent != "kairos.engine.collect":
            assert parent == "kairos.sched.flatten", parent
        if name != "kairos.step":
            assert parent is not None, name
        s = (name, a, b, line)
        self_ns[s] = b - a
        stack.append(s)
    syncs = [s for s in spans if s[0] == "kairos.engine.sync"]
    flats = [s for s in spans if s[0] == "kairos.sched.flatten"]
    assert any(f[1] <= s[1] and s[2] <= f[2] for s in syncs for f in flats)
    for st in steps:
        inside = [k for k in self_ns if st[1] <= k[1] and k[2] <= st[2]]
        assert sum(self_ns[k] for k in inside) == st[2] - st[1]


def test_runner_counts_a_compile_once_per_new_shape(model_and_params):
    """``compiles`` rises by one exactly when the fused step's compiled
    specializations grow (a padded shape new to the runner), and by
    zero on a shape it has issued before."""
    cluster, eng = _one_engine_cluster(model_and_params)
    runner = eng.runner
    for r in _reqs(n=5, max_new=6):
        cluster.submit(r)
    rises = []
    while cluster.has_work:
        c0 = runner.metrics_snapshot()["compiles"]
        k0 = runner._fused_fn._cache_size()
        cluster.step()
        snap = runner.metrics_snapshot()
        rises.append((snap["compiles"] - c0,
                      runner._fused_fn._cache_size() - k0))
    cluster.close()
    assert all(a == b for a, b in rises), rises
    assert {a for a, _ in rises} == {0, 1}       # new shapes and repeats
    snap = runner.metrics_snapshot()
    assert snap["compiles"] == runner._fused_fn._cache_size()
    assert snap["compile_s"] > 0
    # a clone shares the compiled step, so it counts no compile for it
    assert runner.clone().metrics_snapshot()["compiles"] == 0


def test_first_token_is_stamped_when_it_reaches_the_host(model_and_params,
                                                          monkeypatch):
    """With a clock that steps at every reading, each call's
    ``first_token_time`` is no earlier than the reading taken when the
    buffer holding its first token synced, and later than the collect
    of the step that computed it."""
    from repro.serving import engine as engine_mod
    ticks = iter(range(1, 1_000_000))
    now = {"t": 0.0}

    def clock():
        now["t"] = float(next(ticks))
        return now["t"]

    synced_at = {}
    host = engine_mod.TokenBuffer.host

    def recording_host(buf):
        if id(buf) not in synced_at:
            synced_at[id(buf)] = clock()
        return host(buf)

    monkeypatch.setattr(engine_mod.TokenBuffer, "host", recording_host)
    cluster, eng = _one_engine_cluster(model_and_params, clock=clock)
    first_buf, collected_at = {}, {}
    for r in _reqs(n=3, max_new=4):
        cluster.submit(r)
    done = []
    while cluster.has_work:
        done.extend(cluster.step())
        for rid, tok in eng._next_tok.items():
            if isinstance(tok, engine_mod.TokenRef) and rid not in first_buf:
                first_buf[rid] = tok.buf
                collected_at[rid] = now["t"]
    cluster.close()
    assert len(done) == 3
    for r in done:
        assert r.first_token_time >= synced_at[id(first_buf[r.req_id])]
        assert r.first_token_time > collected_at[r.req_id]
        assert r.first_token_time <= r.finish_time
