"""Multi-pod dry-run regression: a representative subset of (arch × shape
× mesh) combinations must lower + compile with 512 placeholder devices.

Runs in a subprocess because the dry-run forces
XLA_FLAGS=--xla_force_host_platform_device_count=512 before jax init,
while the rest of the suite must see 1 device.
"""
import json
import subprocess
import sys
import tempfile
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

CASES = [
    ("qwen3-1.7b", "decode_32k"),       # GQA split-KV decode
    ("rwkv6-3b", "long_500k"),          # attention-free 524k context
    ("jamba-v0.1-52b", "decode_32k"),   # hybrid + MoE + FSDP serving
]


@pytest.mark.slow
def test_dryrun_subset_compiles_both_meshes():
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        out = f.name
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--both-meshes", "--out", out]
    for arch, shape in CASES:
        cmd += ["--arch", arch, "--shape", shape]
    env = {"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin:/usr/local/bin",
           "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT,
                          env=env, timeout=1200)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    records = json.loads(Path(out).read_text())
    ok = [r for r in records if r["status"] == "ok"]
    skipped = [r for r in records if r["status"].startswith("skipped")]
    # CLI runs the cartesian product: 3 archs x 3 shapes x 2 meshes = 18,
    # minus the sanctioned qwen3 x long_500k skips (full attention)
    assert len(records) == 18
    assert len(skipped) == 2
    assert len(ok) == 16
    for r in ok:
        assert r["memory"].get("peak_bytes"), r
        assert sum(r["collectives"].values()) > 0
        # fits a 16 GB v5e
        assert r["memory"]["peak_bytes"] < 16 * 2 ** 30, (
            r["arch"], r["shape"], r["mesh"], r["memory"]["peak_bytes"] / 2 ** 30)
