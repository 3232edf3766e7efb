"""Control plane (core/: Orchestrator, LoadBalancer with KairosScheduler,
TimeSlotDispatcher): p75 over measured calls of the wait from the call's
arrival at the balancer to its admission on the instance
(``exec_start_time - arrival_time``, host stamps of host events)."""
from bench.stats import pct


def read(ctx):
    waits = [c.req.exec_start_time - c.req.arrival_time
             for c in ctx.calls if c.req.exec_start_time >= 0]
    return pct(waits, 75) if waits else None
