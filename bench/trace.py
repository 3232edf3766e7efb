"""The reduction from a device trace to numbers.

``run.py`` profiles the window's last seconds with ``jax.profiler``;
``events`` flattens the ``.xplane.pb`` into plain tuples; ``reduce``
turns them into what the per-layer readers use:

* ``window_s`` / ``busy_s``: the traced span of the harness's own host
  spans (``bench.*``), and the union of device op intervals inside it;
* ``idle``: the device's idle time, charged to the host span running at
  each moment of it (``bench.step``, ``bench.stamp``, ...; the harness's
  spans do not nest);
* ``steps``: each execution of the fused step program paired with the
  ``bench.step`` span that dispatched it (the latest execution starting
  inside that span: a step waits for the previous iteration's tokens,
  then dispatches its own just before it returns), its device time, the
  device time of each kernel inside it, and the work the harness
  recorded for that step (``driver.StepWork``).

Names come from ``trace_names.json`` alone.
"""
from __future__ import annotations

import gzip
import json
import re
from bisect import bisect_right
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

HERE = Path(__file__).resolve().parent
NAMES = json.loads((HERE / "trace_names.json").read_text())

# (kind, name, start_ns, end_ns) with kind "op" | "module" | "span"
Event = Tuple[str, str, float, float]
CONTAINER = re.compile(r"^%?(while|conditional|call)(\.[0-9]+)? = ")


def xplane_file(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _label(ev) -> str:
    """The event's name, with its HLO op / long name when the profiler
    gives them, so a kernel matches whichever carries its name."""
    parts = [ev.name]
    try:
        for k, v in ev.stats:
            if k in ("hlo_op", "long_name", "tf_op", "hlo_category") and isinstance(v, str):
                parts.append(v)
    except Exception:
        pass
    return " | ".join(parts)


def events(xplane: Path) -> List[Event]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(xplane))
    dev = re.compile(NAMES["device_plane"])
    ops, mods = re.compile(NAMES["ops_line"]), re.compile(NAMES["modules_line"])
    span = re.compile(NAMES["host_spans"])
    out: List[Event] = []
    for plane in pd.planes:
        is_dev = bool(dev.search(plane.name))
        for line in plane.lines:
            kind = ("op" if is_dev and ops.search(line.name) else
                    "module" if is_dev and mods.search(line.name) else None)
            for ev in line.events:
                if kind is not None:
                    out.append((kind, _label(ev) if kind == "op" else ev.name,
                                float(ev.start_ns), float(ev.start_ns + ev.duration_ns)))
                elif not is_dev and span.search(ev.name):
                    out.append(("span", ev.name, float(ev.start_ns),
                                float(ev.start_ns + ev.duration_ns)))
    out.sort(key=lambda e: e[2])
    return out


def short_name(label: str) -> str:
    """An op's HLO instruction name and result shape, without layouts or
    operands: ``%paged_attention.11 = bf16[32,8,2,128]``."""
    text = label.split(" | ")[0]
    head, eq, rest = text.partition(" = ")
    if not eq:
        return text[:120]
    shape = rest.split("{")[0].split(" ")[0]
    return f"{head} = {shape}"[:120]


def save_events(evs: Sequence[Event], path: Path):
    with gzip.open(path, "wt") as f:
        json.dump([list(e) for e in evs], f)


def load_events(path: Path) -> List[Event]:
    with gzip.open(path, "rt") as f:
        return [tuple(e) for e in json.load(f)]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def reduce(evs: Sequence[Event], work: Optional[list] = None) -> dict:
    spans = [e for e in evs if e[0] == "span"]
    if not spans:
        raise ValueError("the trace holds none of the harness's host spans")
    lo = min(e[2] for e in spans)
    hi = max(e[3] for e in spans)
    ops = [e for e in evs if e[0] == "op" and e[3] > lo and e[2] < hi]
    busy = _union([(max(a, lo), min(b, hi)) for _, _, a, b in ops])
    busy_ns = sum(b - a for a, b in busy)

    # idle time, charged to the host span running at each moment of it
    idle: Dict[str, float] = {}
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    ends = sorted(spans, key=lambda e: e[3])
    end_at = [e[3] for e in ends]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        covered = 0.0
        k = bisect_right(end_at, a)          # first span ending after a
        while k < len(ends) and ends[k][2] < b:
            _, name, s, t = ends[k]
            ov = min(b, t) - max(a, s)
            if ov > 0:
                idle[name] = idle.get(name, 0.0) + ov * 1e-9
                covered += ov
            k += 1
        if b - a - covered > 0:
            idle["no bench span"] = idle.get("no bench span", 0.0) + (b - a - covered) * 1e-9

    per_op: Dict[str, float] = {}
    for _, name, a, b in ops:
        if CONTAINER.search(name):      # a loop's event spans its body's ops
            continue
        key = short_name(name)
        per_op[key] = per_op.get(key, 0.0) + (b - a) * 1e-9
    device_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]

    # fused-step executions, paired with the bench.step that dispatched them
    fused = re.compile(NAMES["fused_step_module"])
    mods = [e for e in evs if e[0] == "module" and fused.search(e[1])
            and e[2] >= lo and e[2] < hi]
    steps_sp = [e for e in spans if e[1] == "bench.step"]
    starts = [e[2] for e in steps_sp]
    kern = {k: re.compile(v) for k, v in NAMES["kernels"].items()}
    op_starts = [e[2] for e in ops]
    # a step dispatches its iteration after waiting for the previous
    # one, near the end of its span; an execution queued before the
    # trace began can start inside the first span too, so each span
    # keeps the latest execution that starts inside it; the last
    # span's execution runs past the end of the trace and is cut
    latest: Dict[int, Event] = {}
    for m in mods:
        i = bisect_right(starts, m[2]) - 1
        if 0 <= i < len(steps_sp) - 1 and m[2] <= steps_sp[i][3]:
            latest[i] = m
    steps = []
    for i, m in sorted(latest.items()):
        ks = {k: 0.0 for k in kern}
        j = bisect_right(op_starts, m[2]) - 1
        j = max(j, 0)
        while j < len(ops) and ops[j][2] < m[3]:
            if ops[j][2] >= m[2]:
                for k, rx in kern.items():
                    if rx.search(ops[j][1]):
                        ks[k] += (ops[j][3] - ops[j][2]) * 1e-9
            j += 1
        steps.append({"span": i, "module_s": (m[3] - m[2]) * 1e-9,
                      "kernels": ks,
                      "work": work[i] if work is not None and i < len(work) else None})
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy_ns * 1e-9,
            "idle": idle, "device_ops": device_ops, "steps": steps,
            "n_step_spans": len(steps_sp)}
