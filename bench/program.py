"""The program's own host spans in a device trace, beside ``trace.reduce``.

``repro.obs.span`` writes a ``kairos.*`` span at each layer of the
serving step into the same ``.xplane.pb`` as the device's ops, on the
same clock.  ``events`` reads them off the file with the host line each
ran on; ``reduce`` takes them together with ``trace.events``' ops and
harness spans and gives:

* ``program``: per span name, its count, total and self seconds (self:
  less its direct children on the same thread);
* ``program_idle``: the device's idle time inside the harness's window,
  charged to the innermost program span open at each moment of it on
  the thread that runs ``kairos.step``, the rest to ``"outside program"``;
* ``n_program_steps``: the ``kairos.step`` spans in the window.

``run.py`` does not read this yet; PERF.md lists the edit that wires it
in and the per-layer metrics that would read it.
"""
from __future__ import annotations

import re
from bisect import bisect_right
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

from bench import trace as tr

SPANS = re.compile(r"^kairos\.")

# (kind, name, start_ns, end_ns, thread) with kind "program": the thread
# is the host line the span ran on
Span = Tuple[str, str, float, float, str]


def events(xplane: Path) -> List[Span]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(xplane))
    dev = re.compile(tr.NAMES["device_plane"])
    out: List[Span] = []
    for plane in pd.planes:
        if dev.search(plane.name):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if SPANS.search(ev.name):
                    out.append(("program", ev.name, float(ev.start_ns),
                                float(ev.start_ns + ev.duration_ns), f"{plane.name}#{i}"))
    out.sort(key=lambda e: e[2])
    return out


def _nest(sps: List[Span], stats: Dict[str, Dict[str, float]]) -> List[tuple]:
    """Walk one thread's spans, which nest, in start order: add each to
    ``stats`` and return the innermost span at each instant they cover,
    as ``(start_ns, end_ns, name)`` pieces."""
    pieces: List[tuple] = []
    stack: List[tuple] = []          # (name, end_ns) of the open spans
    t = 0.0

    def close():
        nonlocal t
        name, end = stack.pop()
        if end > t:
            pieces.append((t, end, name))
            t = end

    for _, name, a, b, _ in sorted(sps, key=lambda e: (e[2], -e[3])):
        while stack and stack[-1][1] <= a:
            close()
        if stack:
            stats[stack[-1][0]]["self_s"] -= (b - a) * 1e-9
            if a > t:
                pieces.append((t, a, stack[-1][0]))
        t = a
        st = stats.setdefault(name, {"n": 0, "total_s": 0.0, "self_s": 0.0})
        st["n"] += 1
        st["total_s"] += (b - a) * 1e-9
        st["self_s"] += (b - a) * 1e-9
        stack.append((name, b))
    while stack:
        close()
    return pieces


def reduce(evs: Sequence[tuple]) -> dict:
    """``evs``: ``trace.events``' list with ``events``' spans added."""
    spans = [e for e in evs if e[0] == "span"]
    if not spans:
        raise ValueError("the trace holds none of the harness's host spans")
    lo, hi = min(e[2] for e in spans), max(e[3] for e in spans)
    busy = tr._union([(max(e[2], lo), min(e[3], hi)) for e in evs
                      if e[0] == "op" and e[3] > lo and e[2] < hi])
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]

    threads: Dict[str, List[Span]] = {}
    for e in evs:
        if e[0] == "program" and e[2] >= lo and e[3] <= hi:
            threads.setdefault(e[4], []).append(e)
    stats: Dict[str, Dict[str, float]] = {}
    pieces: List[tuple] = []
    for sps in threads.values():
        p = _nest(sps, stats)
        if any(e[1] == "kairos.step" for e in sps):
            pieces += p
    pieces.sort()
    starts = [p[0] for p in pieces]
    idle: Dict[str, float] = {}
    for a, b in gaps:
        covered = 0.0
        k = max(bisect_right(starts, a) - 1, 0)
        while k < len(pieces) and pieces[k][0] < b:
            s, t, name = pieces[k]
            ov = min(b, t) - max(a, s)
            if ov > 0:
                idle[name] = idle.get(name, 0.0) + ov * 1e-9
                covered += ov
            k += 1
        if b - a - covered > 0:
            idle["outside program"] = idle.get("outside program", 0.0) + (b - a - covered) * 1e-9
    return {"program": stats, "program_idle": idle,
            "n_program_steps": stats.get("kairos.step", {}).get("n", 0)}
