"""The open-loop client: drives a schedule of workflows through the cluster.

Workflows arrive on the schedule whether or not earlier ones finished;
inside a workflow the next agent's call is submitted when its upstream
call finishes, as an agent waits on its LLM call.  Every call carries
the fields ``Workflow._llm_call`` sets.

Token stamps are client-side and use public fields only.  ``collect``
grows ``Request.output_len`` by the *previous* step's token, which the
step's ``flatten`` has already synced to the host, so a token is
host-resident at the ``cluster.step()`` return at which ``output_len``
first counts it: that return is its stamp.  A preempted call restarts
from nothing; its tokens are counted once, at their first delivery.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench.traffic.gen import Flow, Prompts


@dataclasses.dataclass
class Call:
    flow: Flow
    stage: int
    due: float                       # absolute monotonic seconds
    req: object = None
    stamps: List[float] = dataclasses.field(default_factory=list)
    delivered: int = 0
    finished: float = -1.0
    measured: bool = False


@dataclasses.dataclass
class StepWork:
    """What one dispatched iteration computed, read off the requests:
    decode rows by context length, prefill chunks by [start, end)."""
    decode_ctx: List[int]
    chunks: List[tuple]
    n_emit: int          # rows whose logits give a token (LM head rows)


class Driver:
    def __init__(self, cluster, orch, flows: List[Flow], prompts: Prompts, *,
                 window: tuple, cap: float, measured_period: str = "window",
                 span: Optional[Callable[[str], object]] = None):
        self.cluster, self.orch = cluster, orch
        self.flows = sorted(flows, key=lambda f: f.arrival)
        self.prompts = prompts
        self.window = window          # (w0, w1) offsets from the schedule start
        self.cap = cap                # offset at which the run stops waiting
        self.measured_period = measured_period
        self.span = span or (lambda name: contextlib.nullcontext())
        self.calls: List[Call] = []
        self.live: Dict[int, Call] = {}
        self.flow_done: Dict[int, float] = {}
        self.lateness: List[float] = []
        self.t0 = 0.0
        self.record_work = False
        self.work: List[StepWork] = []
        self.n_steps = 0

    # ------------------------------------------------------------ submission
    def _submit(self, flow: Flow, stage: int, due: float):
        from repro.serving import Request
        toks, shared = self.prompts.prompt(flow, stage)
        st = flow.stages[stage]
        up = flow.stages[stage - 1].agent if stage else None
        req = Request(agent_name=st.agent, msg_id=f"wf{flow.wid}",
                      upstream_name=up, app_name=flow.app,
                      prompt_len=len(toks), prompt_tokens=toks,
                      max_new_tokens=st.out_len, shared_prefix_len=shared,
                      cache_key=st.agent, arrival_time=due,
                      app_start_time=self.t0 + flow.arrival)
        w0, w1 = self.window
        call = Call(flow, stage, due, req,
                    measured=self.t0 + w0 <= due < self.t0 + w1)
        self.calls.append(call)
        self.live[req.req_id] = call
        self.cluster.submit(req)

    def measured_flows(self) -> List[Flow]:
        return [f for f in self.flows if f.period == self.measured_period]

    def _all_measured_done(self) -> bool:
        return (all(f.wid in self.flow_done for f in self.measured_flows())
                and all(c.finished >= 0 for c in self.calls if c.measured))

    # ------------------------------------------------------------------ loop
    def run(self, on_tick: Optional[Callable[[float], None]] = None):
        """Run the schedule until every measured workflow and call has
        finished after the window, or the cap is reached."""
        self.t0 = time.monotonic()
        nxt = 0
        w1 = self.t0 + self.window[1]
        stop = self.t0 + self.cap
        while True:
            now = time.monotonic()
            if on_tick is not None:
                on_tick(now - self.t0)
            while nxt < len(self.flows) and self.t0 + self.flows[nxt].arrival <= now:
                f = self.flows[nxt]
                due = self.t0 + f.arrival
                with self.span("bench.submit"):
                    self._submit(f, 0, due)
                self.lateness.append(now - due)
                nxt += 1
            if now >= stop or (now >= w1 and nxt == len(self.flows)
                               and not self.cluster.has_work) \
                    or (now >= w1 and self._all_measured_done()):
                break
            if not self.cluster.has_work:
                wait = (self.t0 + self.flows[nxt].arrival - now
                        if nxt < len(self.flows) else 0.001)
                with self.span("bench.idle"):
                    time.sleep(min(max(wait, 0.0), 0.002))
                continue
            before = self._snapshot() if self.record_work else None
            with self.span("bench.step"):
                done = self.cluster.step()
            t = time.monotonic()
            self.n_steps += 1
            with self.span("bench.stamp"):
                if before is not None:
                    self.work.append(self._work(before))
                self._stamp(t, done)

    def _stamp(self, t: float, done):
        for call in self.live.values():
            n = call.req.output_len
            if n > call.delivered:
                call.stamps.extend([t] * (n - call.delivered))
                call.delivered = n
        for r in done:
            call = self.live.pop(r.req_id)
            call.finished = t
            flow = call.flow
            if call.stage + 1 < len(flow.stages):
                self._submit(flow, call.stage + 1, t)
            else:
                self.flow_done[flow.wid] = t
                self.orch.on_workflow_complete(r.msg_id)

    # ------------------------------------------------------- per-step work
    def _snapshot(self):
        return {rid: (c.req.prefilled_len, c.req.output_len)
                for rid, c in self.live.items()}

    def _work(self, before) -> StepWork:
        ctx, chunks, last = [], [], 0
        for rid, (pre0, out0) in before.items():
            r = self.live[rid].req              # finishers leave in _stamp
            if r.output_len == out0 + 1:        # one decode row
                ctx.append(r.prompt_len + out0 + 1)
            start = pre0 if pre0 > 0 else r.cached_prefix_len
            if r.prefilled_len > start and r.output_len >= out0:
                chunks.append((start, r.prefilled_len))
                last += r.prefilled_len == r.prompt_len
        return StepWork(ctx, chunks, len(ctx) + last)

    # -------------------------------------------------------------- results
    def delivered_between(self, a: float, b: float) -> int:
        lo, hi = self.t0 + a, self.t0 + b
        n = 0
        for c in self.calls:
            s = np.asarray(c.stamps)
            n += int(np.count_nonzero((s >= lo) & (s < hi)))
        return n
