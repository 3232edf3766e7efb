"""Shared helpers for the per-figure benchmarks, including the
machine-readable BENCH JSON schema the CI perf pipeline consumes:

    {"bench": <suite name>,
     "config": {<knobs the run used>},
     "metrics": {<flat name -> number | {...}>},
     "commit": <git HEAD or "unknown">}

``BENCH_baseline.json`` (committed) is the reference trajectory;
``BENCH_ci.json`` (uploaded as a CI artifact on every PR) is checked
against it by ``benchmarks/check_regression.py``.
"""
from __future__ import annotations

import json
import os
import platform
import subprocess
import time
from typing import Dict, Tuple

from repro.launch.cache import enable_compile_cache
from repro.sim import run_policy

# every benchmark entry point imports this module: compiles land in the
# persistent cache ($JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache)
enable_compile_cache()

Row = Tuple[str, float, str]   # (name, us_per_call, derived)

# calibrated operating points (see EXPERIMENTS.md §Setup)
RATE_SINGLE = {"QA": 7.0, "RG": 3.2, "CG": 1.9}
RATE_COLOC = 2.8
DUR = 150.0
SEED = 1


def sim(apps, policy: str, rate: float, duration: float = DUR, seed: int = SEED,
        **kw):
    t0 = time.time()
    res = run_policy(apps, policy, rate=rate, duration=duration, seed=seed, **kw)
    res.wall_s = time.time() - t0
    return res


def pct_gain(base: float, ours: float) -> float:
    return 100.0 * (base - ours) / base


def row(name: str, seconds_per_call: float, derived: str) -> Row:
    return (name, seconds_per_call * 1e6, derived)


# =============================================================================
# BENCH JSON (perf-tracking CI)
# =============================================================================


def git_commit() -> str:
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "HEAD"], text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stderr=subprocess.DEVNULL).strip()
    except (OSError, subprocess.CalledProcessError):
        return "unknown"


def bench_host() -> str:
    """Coarse hardware-class tag: wall-clock numbers are only comparable
    between runs that share it (the regression gate downgrades wall
    comparisons across different hosts to advisory)."""
    return f"{platform.system()}-{platform.machine()}-{os.cpu_count()}cpu"


def bench_json(bench: str, config: Dict, metrics: Dict) -> Dict:
    return {"bench": bench, "config": config, "metrics": metrics,
            "commit": git_commit(), "host": bench_host()}


def write_bench_json(path: str, bench: str, config: Dict, metrics: Dict) -> Dict:
    doc = bench_json(bench, config, metrics)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return doc
