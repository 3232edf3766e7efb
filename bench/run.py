#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root
of the checkout; it names a configuration (``bench/configs/<name>.json``,
whose architecture module beside it supplies the reference, the weights,
the registry check, the readers' sizes and a step's work) and a traffic
mix (``bench/traffic/<name>.json``).  Per-layer metrics
are read by ``bench/metrics/<metric>.py``, kernel work is counted by
``bench/kernels/<kernel>.py``, peaks come from ``bench/peaks.json``.

A run: build the cluster (``ServingCluster.from_config``, policy kairos)
over weights drawn on the device from the seed; warm up by replaying
the window's own schedule with fresh tokens until it has finished, twice
(this compiles the step shapes the window uses, fills the prefix cache,
and gives the orchestrator its latency samples); issue one padding-only
step of every shape the window can reach that the replay did not
(``bench/shapes.py``), so that nothing compiles in the window; then offer the schedule
open-loop for ``--seconds`` and keep it running until the window's
workflows have finished or the mix's cap is reached.  ``setup_s`` runs
from process start to the window's start.  With ``--trace 1`` the last
seconds of the window are profiled and the per-layer metrics are
printed instead of the end-to-end ones.  Then the program's state is
freed and ``bench/check.py`` compares a sample of the served tokens with
the plain reference, and reads its lower-precision control beside it.

The last line of standard output is one JSON object; the numbers
compared for ``correct`` are the last lines of standard error.  With no
accelerator, or fewer chips than the cell asks for, the run exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TRACE_SECONDS = 10.0
WARMUP_CAP_S = 900.0     # the warm-up runs to its end: the window starts empty
WARMUP_PASSES = 2         # a third still met new step shapes, for 60 s more set-up


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_of(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(bench: dict, cell: str, trace: bool) -> list:
    """The cell's metrics: end-to-end ones untraced, per-layer ones traced."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def device_check(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < chips:
        log(f"run.py: needs {chips} accelerator chip(s); JAX sees "
            f"{len(devs)} {devs[0].platform} device(s)")
        sys.exit(2)
    return devs


def enable_cache(root: Path = ROOT) -> str:
    """JAX's persistent compile cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts lowerings, backend compiles and cache hits, by phase."""
    EVENTS = {"/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
              "/jax/core/compile/backend_compile_duration": "compiled",
              "/jax/compilation_cache/cache_hits": "cache_hits"}

    def __init__(self):
        import jax
        self.counts = {v: 0 for v in self.EVENTS.values()}
        self.counts["seconds"] = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event: str, **kw):
        k = self.EVENTS.get(event)
        if k is not None:
            self.counts[k] += 1

    def _on_duration(self, event: str, secs: float, **kw):
        self._on(event)
        if event in self.EVENTS:
            self.counts["seconds"] += secs

    def snap(self) -> dict:
        return dict(self.counts)

    _one = None

    @classmethod
    def get(cls) -> "CompileCounter":
        """One per process: JAX's listeners cannot be removed."""
        if cls._one is None:
            cls._one = cls()
        return cls._one


class Ctx:
    """What a per-layer reader sees."""

    def __init__(self, calls, snap0, snap1, trace, work, m, peaks, pool_live=(),
                 arch=None):
        self.calls, self.trace, self.work, self.m, self.peaks = calls, trace, work, m, peaks
        self.arch = arch                     # the configuration's architecture module
        self._s0, self._s1 = snap0, snap1
        self.pool_live = list(pool_live)     # share of the pool held by running calls

    def _sum(self, snap, key):
        return sum(v for k, v in snap.items()
                   if k.startswith("engine") and k.split(".", 1)[1] == key)

    def delta(self, key):
        return self._sum(self._s1, key) - self._sum(self._s0, key)

    def delta_product(self, a, b):
        def prod(snap):
            return sum(snap.get(k, 0.0) * snap.get(k[:-len(a)] + b, 0.0)
                       for k in snap if k.endswith("." + a) and k.startswith("engine"))
        return prod(self._s1) - prod(self._s0)


def read_metric(name: str, ctx):
    from bench.model import load_module
    return load_module(BENCH / "metrics" / f"{name}.py").read(ctx)


def build(spec: dict, seed: int):
    """Model, weights on the device, orchestrator and cluster."""
    from repro.core import Orchestrator
    from repro.core.orchestrator import HardwareProfile
    from repro.models import build_model
    from repro.serving import ServingCluster
    from bench.model import make_weights, registry_config, serving_config
    cfg = registry_config(spec)
    model = build_model(cfg)
    weights = make_weights(spec, seed)
    sc = serving_config(spec)
    orch = Orchestrator(hardware=HardwareProfile(
        decode_tok_per_s=20.0, kv_capacity_tokens=sc.kv_capacity_tokens),
        prefix_caching=sc.prefix_caching)
    cluster = ServingCluster.from_config(model, weights, orch, sc)
    return cluster, orch


def live_share(engines) -> float:
    """Share of the engines' KV pools neither free nor parked in the
    prefix cache."""
    return sum(1.0 - e.memory_free_fraction() - e.kv_cached_tokens / e.kv_capacity_tokens
               for e in engines) / len(engines)


def run_cell(spec: dict, mix: dict, seed: int, seconds: float, trace: bool,
             metric_names: list, peaks: dict) -> dict:
    import jax
    from repro.serving.request import reset_request_ids
    from bench import stats
    from bench.check import compare, verdict
    from bench.driver import Driver
    from bench.model import architecture
    from bench.shapes import StepShapes, reachable, warm as warm_shapes
    from bench.traffic.gen import Prompts, schedule

    counter = CompileCounter.get()
    reset_request_ids()
    cluster, orch = build(spec, seed)
    shapes = StepShapes(cluster.engines, log)
    flows = schedule(mix, seconds)
    prompts = Prompts(mix, seed, spec["vocab_size"])
    cap = seconds + float(mix["follow_cap_s"])
    warm = [dataclasses.replace(f) for f in flows if f.period == "warmup"]
    main = [dataclasses.replace(f, arrival=f.arrival - seconds)
            for f in flows if f.period != "warmup"]

    # ---- warm-up: the window's schedule with fresh tokens, to its end,
    # twice: the second pass runs without the first's compile stalls, so
    # it meets the step shapes of the window's own timing
    t_warm = time.monotonic()
    for k in range(WARMUP_PASSES):
        t_pass, shapes0 = time.monotonic(), len(shapes.all_issued())
        wd = Driver(cluster, orch,
                    [dataclasses.replace(f, wid=f.wid + k * len(flows),
                                         seed_offset=1000 + k) for f in warm],
                    prompts, window=(0.0, seconds), cap=WARMUP_CAP_S,
                    measured_period="warmup")
        wd.run()
        if cluster.has_work:
            raise SystemExit(f"warm-up did not finish within {WARMUP_CAP_S:g}s")
        new = len(shapes.all_issued()) - shapes0
        log(f"[setup] warm-up pass {k + 1}: {time.monotonic() - t_pass:.3f}s, "
            f"{wd.n_steps} steps, {new} new step shapes; compiles so far {counter.snap()}")
    shapes.replay = shapes.all_issued()
    log(f"[setup] replay step shapes: {sorted(list(k) for k in shapes.replay)}")
    t_shapes = time.monotonic()
    n_warm = warm_shapes(cluster.engines, shapes,
                         reachable(shapes.replay))
    log(f"[setup] shape warm: {n_warm} shapes, {time.monotonic() - t_shapes:.3f} s")
    log(f"[setup] build {t_warm - T_START:.3f}s, warm-up {time.monotonic() - t_warm:.3f}s")

    # ---- the window and its follow-through
    tdir = Path(tempfile.mkdtemp(prefix="trace")) if trace else None
    tracing = {"on": False}
    t_lo = max(0.0, seconds - TRACE_SECONDS)

    def span(name):
        return (jax.profiler.TraceAnnotation(name) if tracing["on"]
                else contextlib.nullcontext())

    drv = Driver(cluster, orch, main, prompts, window=(0.0, seconds), cap=cap,
                 span=span)

    marks, pool_live = {}, []
    engines = cluster.engines

    def on_tick(t):
        if t < seconds:
            pool_live.append(live_share(engines))
        if "w0" not in marks:
            marks["w0"] = (cluster.metrics_snapshot(), counter.snap(),
                           time.monotonic())
            shapes.window_t0 = drv.t0
        if "w1" not in marks and t >= seconds:
            marks["w1"] = (cluster.metrics_snapshot(), counter.snap())
        if trace and not tracing["on"] and "traced" not in marks and t >= t_lo:
            jax.profiler.start_trace(str(tdir))
            tracing["on"] = True
            drv.record_work = True
        if tracing["on"] and t >= seconds:
            drv.record_work = False
            tracing["on"] = False
            marks["traced"] = True
            jax.profiler.stop_trace()

    drv.run(on_tick=on_tick)
    t_end = time.monotonic()
    if tracing["on"]:
        tracing["on"] = False
        jax.profiler.stop_trace()
    if "w1" not in marks:
        marks["w1"] = (cluster.metrics_snapshot(), counter.snap())
    snap0, comp0, t_w0 = marks["w0"]
    snap1, comp1 = marks["w1"]
    setup_s = t_w0 - T_START

    measured = [c for c in drv.calls if c.measured]
    mflows = drv.measured_flows()
    failed = sum(c.finished < 0 for c in measured)
    in_window = {k: comp1[k] - comp0[k] for k in comp0}
    log(f"[window] {seconds:g}s: {len(mflows)} workflows, {len(measured)} calls "
        f"measured; {failed} calls unfinished at the cap; {drv.n_steps} steps; "
        f"follow-through ended {t_end - drv.t0 - seconds:.3f}s after the window")
    log(f"[window] step shapes unmet by the replay: "
        f"{sorted(list(k) for k in shapes.window_keys - shapes.replay)}; "
        f"{len(shapes.window_new)} of them new to the runners")
    log(f"[window] compilations inside the window: {in_window} "
        f"(engine jit shapes {snap0.get('engine0.jit_cache_size', 0):.0f} -> "
        f"{snap1.get('engine0.jit_cache_size', 0):.0f})")
    if in_window["lowered"] or in_window["compiled"]:
        log(f"[window] WARNING: {in_window['lowered']} programs were traced and "
            f"{in_window['compiled']} compiled or loaded inside the window, "
            f"{in_window['seconds']:.3f}s: the warm-up missed a step shape")
    late = sorted(drv.lateness)
    if late:
        log(f"[window] generator lateness: median {stats.pct(late, 50) * 1e3:.3f} ms, "
            f"p99 {stats.pct(late, 99) * 1e3:.3f} ms, max {late[-1] * 1e3:.3f} ms")

    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}

    values = {}
    ttft = stats.ttft(measured, t_end)
    itl = stats.itl_ms(measured)
    e2e = stats.workflow_e2e(mflows, drv.flow_done, drv.t0, t_end)
    n_out = drv.delivered_between(0.0, seconds)
    log("[e2e] " + stats.summary("ttft_s", ttft))
    log("[e2e] " + stats.summary("itl_ms", itl, (50, 90, 99)))
    log("[e2e] " + stats.summary("workflow_e2e_s", e2e))
    log(f"[e2e] output tokens in the window: {n_out} over {seconds:g}s")
    e2e_values = {"ttft_p50_s": stats.pct(ttft, 50),
                  "workflow_e2e_p50_s": stats.pct(e2e, 50),
                  "output_tok_per_s": stats.rate(n_out, seconds),
                  "setup_s": setup_s}

    red = None
    if trace:
        from bench import trace as tr
        evs = tr.events(tr.xplane_file(tdir))
        red = tr.reduce(evs, drv.work)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"[trace] window {red['window_s']:.6f}s busy {red['busy_s']:.6f}s; "
            f"{len(red['steps'])} fused steps paired with {red['n_step_spans']} "
            f"bench.step spans and {len(drv.work)} work records; idle by host span "
            f"{red['idle']}")
        arch = architecture(spec)
        m = dict(arch.dims(spec), block_size=spec["serving"]["block_size"])
        ctx = Ctx(measured, snap0, snap1, red, drv.work, m, peaks, pool_live, arch)
        for name in metric_names:
            v = read_metric(name["name"], ctx)
            if v is not None:
                values[name["name"]] = v
    else:
        for name in metric_names:
            if name["name"] in e2e_values:
                values[name["name"]] = e2e_values[name["name"]]

    # ---- free the program's state, then the reference
    cluster.close()
    drv.cluster = drv.orch = None
    del cluster, orch, wd, on_tick, engines
    gc.collect()
    t_ref = time.monotonic()
    cmp = compare(spec, seed, measured)
    prog, ctrl = verdict(cmp["program"], spec), verdict(cmp["control"], spec)
    correct = prog["correct"] and cmp["tokens"] > 0
    log(f"[check] reference over {cmp['calls']} calls, {cmp['tokens']} served "
        f"tokens, in {time.monotonic() - t_ref:.3f}s")
    for k, v in ctrl["check"].items():
        log(f"control: {k} {v['value']} limit {v['limit']}")
    log(f"control: correct {ctrl['correct']} (the fp8 control must not be)")

    result = {"correct": correct, "attempted": len(measured), "failed": failed,
              "metrics": {k: {"value": v, "unit": next(
                  x["unit"] for x in metric_names if x["name"] == k)}
                  for k, v in values.items()},
              "device": device, "control": ctrl}
    if red is not None:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in red["device_ops"]],
            "idle_gaps": sorted(([n, s] for n, s in red["idle"].items()),
                                key=lambda x: -x[1])[:10]}
    result["check"] = prog["check"]
    for k, v in prog["check"].items():
        log(f"check: {k} {v['value']} limit {v['limit']}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_benchmark()
    cell = cell_of(bench, args.workload)
    devs = device_check(int(cell["chips"]))
    enable_cache()
    from bench.model import load_config
    from bench.traffic.gen import load_mix
    spec = load_config(cell["config"])
    mix = load_mix(cell["traffic"])
    peaks_all = json.loads((BENCH / "peaks.json").read_text())
    kind = devs[0].device_kind
    if kind not in peaks_all:
        raise SystemExit(f"device kind {kind!r} is not in bench/peaks.json")
    result = run_cell(spec, mix, args.seed, args.seconds, bool(args.trace),
                      metrics_for(bench, cell["name"], bool(args.trace)),
                      peaks_all[kind])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
