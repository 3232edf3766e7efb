"""Pipelined multi-instance cluster runtime (serving/cluster.py).

Covered here:

* token identity of the pipelined ``ServingCluster`` vs the legacy
  serial loop — multi-instance, prefix caching + chunked prefill on,
  preemption pressure;
* a dispatch-overlap guard: the pipelined loop issues all engine
  dispatches before the first collect (verified with a barrier the
  serial loop could never pass);
* OOM feedback: a real preemption fences the instance via
  ``dispatcher.on_oom`` (and the legacy ``oom_feedback=False`` baseline
  leaves fencing dead);
* the dispatcher admit probe is ``BatchScheduler.can_admit`` (memory
  watermark), not the legacy queue-length check;
* ``Workflow._llm_call`` raises ``TimeoutError`` instead of returning
  ``[]``, and a failed agent stage surfaces in ``run()`` results;
* deferred-sync ``TokenRef`` semantics.
"""
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import Orchestrator
from repro.core.orchestrator import HardwareProfile
from repro.serving import (
    LLMEngine,
    PagedModelRunner,
    Request,
    ServingCluster,
    TokenBuffer,
    TokenRef,
    reset_request_ids,
)


@pytest.fixture(scope="module")
def model_and_params():
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("qwen3-1.7b").reduced()
    model = build_model(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def _reqs(seed=11, sys_len=16, n=6, uniq=7, max_new=4):
    """Shared-prefix requests (full-block cached prefix when caching on)."""
    rng = np.random.default_rng(seed)
    sys_toks = rng.integers(0, 500, sys_len).astype(np.int32)
    reqs = []
    for i in range(n):
        toks = np.concatenate(
            [sys_toks, rng.integers(0, 500, uniq + i).astype(np.int32)])
        reqs.append(Request(agent_name="a", msg_id=f"m{i}", prompt_len=len(toks),
                            prompt_tokens=toks, max_new_tokens=max_new))
    return reqs


def _cluster(model_and_params, *, n_instances=2, num_blocks=64, cache=False,
             chunk=None, pipelined=True, **kw):
    model, params = model_and_params
    runner0 = PagedModelRunner(model, params, num_blocks=num_blocks,
                               block_size=8, max_batch=4)
    engines = [
        LLMEngine(runner0 if i == 0 else runner0.clone(), instance_id=i,
                  max_batch=4, enable_prefix_cache=cache,
                  prefill_chunk_tokens=chunk)
        for i in range(n_instances)]
    orch = Orchestrator(hardware=HardwareProfile(
        decode_tok_per_s=20.0, kv_capacity_tokens=num_blocks * 8))
    return ServingCluster(engines, orch, pipelined=pipelined, **kw)


def _drain(cluster, reqs, max_steps=4000):
    pending = list(reqs)
    done = []
    for _ in range(max_steps):
        if pending:
            r = pending.pop(0)
            r.arrival_time = time.monotonic()
            cluster.submit(r)
        done.extend(cluster.step())
        if not pending and not cluster.has_work:
            break
    cluster.close()
    assert not cluster.has_work, "cluster failed to drain"
    return sorted((r.msg_id, tuple(r.output_tokens)) for r in done)


# =============================================================================
# token identity: pipelined vs legacy serial loop
# =============================================================================


def test_pipelined_token_identical_multi_instance(model_and_params):
    """2 instances, prefix caching + chunked prefill on: the pipelined
    breadth-first loop generates exactly the serial loop's tokens."""
    kw = dict(n_instances=2, cache=True, chunk=16)
    reset_request_ids()
    serial = _drain(_cluster(model_and_params, pipelined=False, **kw), _reqs())
    reset_request_ids()
    pipelined = _drain(_cluster(model_and_params, pipelined=True, **kw), _reqs())
    assert len(serial) == 6
    assert pipelined == serial


def test_pipelined_token_identical_under_preemption(model_and_params):
    """Tight pools force preemption-by-recompute; the pipelined cluster
    still drains with tokens identical to the serial loop."""
    kw = dict(n_instances=2, num_blocks=12, cache=False, chunk=8)
    mk = lambda: _reqs(seed=3, sys_len=8, n=6, uniq=2, max_new=24)
    reset_request_ids()
    cl_s = _cluster(model_and_params, pipelined=False, **kw)
    serial = _drain(cl_s, mk())
    reset_request_ids()
    cl_p = _cluster(model_and_params, pipelined=True, **kw)
    pipelined = _drain(cl_p, mk())
    assert sum(e.stats.n_preempted for e in cl_s.engines) > 0, \
        "workload must actually exercise preemption"
    assert pipelined == serial


# =============================================================================
# dispatch overlap guard
# =============================================================================


def test_pipelined_issues_all_dispatches_before_first_collect(model_and_params):
    """Both engines' dispatches must be in flight concurrently before any
    collect runs: each dispatch waits on a 2-party barrier, which only
    passes if the loop issues every dispatch before collecting (a serial
    dispatch->collect->dispatch loop would deadlock here)."""
    cluster = _cluster(model_and_params, n_instances=2)
    barrier = threading.Barrier(2)
    events = []
    lock = threading.Lock()
    for e in cluster.engines:
        orig_d, orig_c = e.dispatch_iteration, e.collect

        def dispatch(e=e, f=orig_d):
            barrier.wait(timeout=30)       # both dispatches concurrent
            with lock:
                events.append(("dispatch", e.instance_id))
            return f()

        def collect(force_sync=False, e=e, f=orig_c):
            with lock:
                events.append(("collect", e.instance_id))
            return f(force_sync=force_sync)

        e.dispatch_iteration = dispatch
        e.collect = collect
    # seed both engines directly so the step has work everywhere
    for i, e in enumerate(cluster.engines):
        rng = np.random.default_rng(i)
        e.submit(Request(agent_name="a", msg_id=f"g{i}", prompt_len=12,
                         prompt_tokens=rng.integers(0, 500, 12).astype(np.int32),
                         max_new_tokens=2))
    cluster.step()
    cluster.close()
    kinds = [k for k, _ in events]
    assert kinds.index("collect") == 2, \
        f"all dispatches must precede the first collect: {events}"
    assert kinds.count("dispatch") == 2 and kinds.count("collect") == 2


def test_serial_mode_interleaves_dispatch_and_collect(model_and_params):
    """The legacy loop steps one engine at a time: dispatch/collect
    strictly interleaved, in instance order."""
    cluster = _cluster(model_and_params, n_instances=2, pipelined=False)
    events = []
    for e in cluster.engines:
        orig_d, orig_c = e.dispatch_iteration, e.collect
        e.dispatch_iteration = (lambda e=e, f=orig_d:
                                (events.append(("dispatch", e.instance_id)),
                                 f())[1])
        e.collect = (lambda force_sync=False, e=e, f=orig_c:
                     (events.append(("collect", e.instance_id)),
                      f(force_sync=force_sync))[1])
    for i, e in enumerate(cluster.engines):
        rng = np.random.default_rng(i)
        e.submit(Request(agent_name="a", msg_id=f"g{i}", prompt_len=12,
                         prompt_tokens=rng.integers(0, 500, 12).astype(np.int32),
                         max_new_tokens=2))
    cluster.step()
    assert events == [("dispatch", 0), ("collect", 0),
                      ("dispatch", 1), ("collect", 1)]


# =============================================================================
# control-plane feedback
# =============================================================================


def _pressure_reqs(n=5, max_new=12):
    rng = np.random.default_rng(7)
    return [Request(agent_name="a", msg_id=f"p{i}", prompt_len=14,
                    prompt_tokens=rng.integers(0, 500, 14).astype(np.int32),
                    max_new_tokens=max_new) for i in range(n)]


def test_preemption_fences_instance_via_on_oom(model_and_params):
    """A real preemption must reach ``dispatcher.on_oom``: the instance
    is fenced for the OOM cooldown (§6 adaptive), exactly like the
    simulator's control plane."""
    reset_request_ids()
    cluster = _cluster(model_and_params, n_instances=1, num_blocks=12)
    for r in _pressure_reqs():
        r.arrival_time = time.monotonic()
        cluster.submit(r)
    fenced_seen = False
    for _ in range(2000):
        cluster.step()
        e = cluster.engines[0]
        if e.stats.n_preempted > 0 and not fenced_seen:
            # fencing happens at the collect that observed the OOM
            fenced_seen = cluster.dispatcher.is_fenced(0, cluster.clock())
        if not cluster.has_work:
            break
    assert cluster.engines[0].stats.n_preempted > 0, \
        "workload must actually exercise preemption"
    assert fenced_seen, "preemption never fenced the instance"


def test_legacy_loop_leaves_fencing_dead(model_and_params):
    """``oom_feedback=False`` reproduces the old driver: preemptions
    happen but the dispatcher never fences (the §6 hook stays dead)."""
    reset_request_ids()
    cluster = _cluster(model_and_params, n_instances=1, num_blocks=12,
                       pipelined=False, oom_feedback=False)
    for r in _pressure_reqs():
        r.arrival_time = time.monotonic()
        cluster.submit(r)
    ever_fenced = False
    for _ in range(2000):
        cluster.step()
        ever_fenced = ever_fenced or cluster.dispatcher.is_fenced(
            0, cluster.clock())
        if not cluster.has_work:
            break
    assert cluster.engines[0].stats.n_preempted > 0
    assert not ever_fenced


def test_admit_probe_is_can_admit_watermark(model_and_params):
    """The dispatcher's admit probe must track the scheduler's memory
    watermark: an instance whose pool is nearly committed rejects a new
    prompt even though the legacy queue-length probe (running + waiting
    < max_batch) would admit it."""
    reset_request_ids()
    cluster = _cluster(model_and_params, n_instances=1, num_blocks=16)
    e = cluster.engines[0]
    rng = np.random.default_rng(1)
    # occupy most of the 16-block pool: 2 running requests x ~6 blocks
    for i in range(2):
        r = Request(agent_name="a", msg_id=f"big{i}", prompt_len=44,
                    prompt_tokens=rng.integers(0, 500, 44).astype(np.int32),
                    max_new_tokens=16)
        e.submit(r)
    cluster.step()
    assert len(e.running) == 2
    probe_req = Request(agent_name="a", msg_id="probe", prompt_len=20,
                        prompt_tokens=rng.integers(0, 500, 20).astype(np.int32),
                        max_new_tokens=4)
    # legacy probe would say yes (2 running + 0 waiting < max_batch=4)...
    assert len(e.running) + len(e.waiting) < e.max_batch
    # ...but the watermark probe refuses: no admission capacity
    assert cluster.can_admit(0, probe_req) is False
    assert cluster.dispatcher.admit_probe == cluster.can_admit
    probe_req.arrival_time = time.monotonic()
    cluster.submit(probe_req)
    cluster.step()
    assert probe_req in cluster.balancer.queue, \
        "the dispatcher must keep the request queued, not place it"


def test_workflow_wires_cluster_probe_and_feedback():
    """Workflow.add_engine builds a ServingCluster whose dispatcher
    probes ``can_admit`` (not the old ad-hoc queue-length lambda)."""
    from repro.agents import Workflow
    from repro.configs import get_config
    from repro.serving import ServingConfig
    wf = Workflow(app_name="t", config=ServingConfig(
        n_instances=2, num_blocks=32, block_size=8, max_batch=4))
    wf.add_engine("e0", model=get_config("qwen3-1.7b").reduced())
    assert wf.cluster is not None
    assert wf.cluster.dispatcher.admit_probe == wf.cluster.can_admit
    assert wf.cluster.oom_feedback
    assert wf.balancer is wf.cluster.balancer          # back-compat alias
    assert len(wf.cluster.engines) == 2
    # cloned runners share the compiled step functions
    r0, r1 = (e.runner for e in wf.cluster.engines)
    assert r0._fused_fn is r1._fused_fn and r0.pool is not r1.pool


def test_cluster_rejects_engines_sharing_a_runner(model_and_params):
    """Donated in-place pools make a shared PagedModelRunner structurally
    unsafe (instance A's dispatch overwrites — in place — the buffer
    instance B is about to read): the cluster refuses to build one."""
    model, params = model_and_params
    runner = PagedModelRunner(model, params, num_blocks=16, block_size=8,
                              max_batch=2)
    engines = [LLMEngine(runner, instance_id=i, max_batch=2)
               for i in range(2)]
    orch = Orchestrator(hardware=HardwareProfile(
        decode_tok_per_s=20.0, kv_capacity_tokens=128))
    with pytest.raises(AssertionError, match="share a PagedModelRunner"):
        ServingCluster(engines, orch)


# =============================================================================
# Workflow failure surfacing
# =============================================================================


def test_llm_call_timeout_raises():
    """An unserved LLM call must raise TimeoutError, not return []."""
    from repro.agents import Workflow
    from repro.agents.messaging import Headers
    wf = Workflow(app_name="t", llm_timeout_s=0.05)
    h = Headers(msg_id="m1", app_name="t", upstream_name=None,
                app_start_time=0.0)
    with pytest.raises(TimeoutError, match="timed out"):
        wf._llm_call("agent", np.zeros(4, np.int32), h, max_new_tokens=2)


def test_failed_agent_stage_surfaces_in_results():
    """An agent stage that raises ends its workflow with a failed result
    (and decrements the outstanding count) instead of hanging run()."""
    from repro.agents import BaseAgent, Workflow

    class Exploding(BaseAgent):
        def _run_impl(self, input_data, metadata):
            raise RuntimeError("boom")

    wf = Workflow(app_name="t")
    wf.add_agent("Boom", Exploding)
    msg_id = wf.submit_task("Boom", {})
    wf.bus.drain()
    for t in wf._threads:
        t.join(timeout=10)
    assert wf._outstanding == 0
    res = wf._results[msg_id]
    assert res["failed"] and "RuntimeError: boom" in res["error"]


# =============================================================================
# deferred-sync token references
# =============================================================================


def test_token_ref_defers_and_materializes():
    import jax.numpy as jnp
    buf = TokenBuffer(jnp.asarray([7, 11, 13], jnp.int32))
    ref = TokenRef(buf, 1)
    assert buf._host is None, "construction must not sync"
    assert int(ref) == 11 and ref == 11 and ref == TokenRef(buf, 1)
    assert buf._host is not None and buf._dev is None
    # numpy consumes refs through __index__ (flatten_plan's tokens_d)
    arr = np.zeros(2, np.int32)
    arr[0] = int(ref)
    assert arr[0] == 11
    assert hash(ref) == hash(11)
