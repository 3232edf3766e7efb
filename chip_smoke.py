#!/usr/bin/env python3
"""Smoke test of the serving path on a TPU: qwen3-1.7b at published widths.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # tensor-parallel phase only, 4 chips

One process drives the chip.  Weights are random, drawn from ``--seed``
by ``init_params``; prompts are random tokens from the same seed.  The
phases, each a hard check (any failure raises, exits non-zero and prints
no result line):

1. device   -- JAX must report a TPU; anything else exits non-zero.
2. kernels  -- both Pallas kernels at the served widths (8 KV heads x 2
               grouped query heads, head_dim 128, 16-token pages) against
               the jnp oracles of ``kernels/ref.py`` on the same inputs,
               in bf16 and in f32.
3. fp32     -- a float32 drain under ``default_matmul_precision("highest")``
               with the native kernels and with ``backend="ref"``: the token
               streams must be identical.  Depth is cut (printed).
4. serve    -- the full 28-layer bf16 model through
               ``ServingCluster.from_config``: submit / drain /
               metrics_snapshot with prefix caching and 512-token prefill
               chunks.  The engine must run the Pallas kernels, the
               compiled fused step must contain ``tpu_custom_call``, and
               every request must finish with its full token count.

``--four-chips`` runs only: tp=4 on one instance against tp=1 (float32,
highest precision, token-identical), then a 2-instance x tp=2 drain on
disjoint mesh slices that must use both instances.

Times printed here are a smoke, not a benchmark.  The last line of
standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

SRC = Path(__file__).resolve().parent / "src"

MODEL = "qwen3-1.7b"
BLOCK_SIZE = 16          # bf16's native (16, 128) tile: one page per tile
# KV pool of the full-width phase: 3072 blocks x 16 tokens = 49,152 tokens,
# 5.25 GiB in bf16.  Compiled for v5e, the largest of the serve phase's
# fused-step shapes reports 8.46 GiB of arguments (params + pool, the pool
# aliased to the output) and 6.40 GiB of temporaries (the layer scan
# re-emits a whole pool), 14.86 of the chip's 15.75 GiB.
POOL_BLOCKS = 3072
FP32_LAYERS = 2          # depth of the float32 identity phases
# kernel-vs-oracle tolerances (max |kernel - ref| <= atol + rtol * |ref|)
TOL = {"bfloat16": (4e-2, 4e-2), "float32": (1e-4, 1e-4)}


class CompileClock:
    """Seconds this process has spent getting executables from XLA
    (compiling, or reading the persistent cache), summed over threads
    from JAX's monitoring events.  Tracing and lowering are not counted:
    their events nest inside each other."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_):
        if event == self.EVENT:
            self.secs += secs


def log(phase: str, msg: str):
    print(f"[{phase}] {msg}", flush=True)


def check(ok: bool, what: str):
    if not ok:
        raise AssertionError(what)


# --------------------------------------------------------------------------- #
# traffic
# --------------------------------------------------------------------------- #

def make_waves(rng, vocab: int, *, sys_len: int, suffixes, unshared,
               new_tokens):
    """Two waves of requests.  Wave 1 computes a shared system prompt once;
    wave 2's shared requests then find it in the prefix cache, so their
    prefill chunks start mid-sequence on cached pages."""
    from repro.serving import Request
    system = rng.integers(0, vocab, sys_len).astype(np.int32)
    shared = [np.concatenate([system, rng.integers(0, vocab, n).astype(np.int32)])
              for n in suffixes]
    alone = [rng.integers(0, vocab, n).astype(np.int32) for n in unshared]

    def req(i, toks, n_shared):
        return Request(agent_name=f"agent{i % 3}", msg_id=f"m{i}",
                       app_name="smoke", prompt_len=len(toks),
                       prompt_tokens=toks, shared_prefix_len=n_shared,
                       max_new_tokens=int(new_tokens[i % len(new_tokens)]))

    prompts = [(t, sys_len) for t in shared] + [(t, 0) for t in alone]
    reqs = [req(i, t, n) for i, (t, n) in enumerate(prompts)]
    n_sh, n_al = len(shared), len(alone)
    wave1 = reqs[:1] + reqs[n_sh:n_sh + n_al // 2]
    wave2 = reqs[1:n_sh] + reqs[n_sh + n_al // 2:]
    return wave1, wave2


def build_cluster(model, params, config, *, backend=None):
    """The cluster a user builds: ``ServingCluster.from_config``."""
    from repro.core import Orchestrator
    from repro.core.orchestrator import HardwareProfile
    from repro.serving import ServingCluster
    orch = Orchestrator(hardware=HardwareProfile(
        decode_tok_per_s=20.0, kv_capacity_tokens=config.kv_capacity_tokens),
        prefix_caching=config.prefix_caching)
    return ServingCluster.from_config(model, params, orch, config,
                                      backend=backend)


def drain_waves(cluster, waves):
    """Submit and drain each wave in turn; finished requests by msg_id."""
    done = {}
    for wave in waves:
        for r in wave:
            cluster.submit(r)
        for r in cluster.drain():
            done[r.msg_id] = r
    return done


def check_finished(phase: str, waves, done):
    want = {r.msg_id: r.max_new_tokens for w in waves for r in w}
    check(set(done) == set(want), f"{phase}: finished {sorted(done)} of {sorted(want)}")
    for mid, r in done.items():
        check(r.output_len == want[mid] == len(r.output_tokens),
              f"{phase}: {mid} produced {len(r.output_tokens)} of {want[mid]} tokens")


def tokens(done):
    return {mid: [int(t) for t in r.output_tokens] for mid, r in done.items()}


# --------------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------------- #

def phase_kernels(cfg, seed: int, clock: CompileClock):
    from repro.kernels import ref
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.ragged_attention import ragged_segment_attention
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    g = cfg.num_heads // kv
    bs, n_pages, nb = BLOCK_SIZE, 1024, 128
    rng = np.random.default_rng(seed)
    tables = rng.permutation(n_pages)[:8 * nb].reshape(8, nb).astype(np.int32)
    ctx = rng.integers(1, nb * bs + 1, 8).astype(np.int32)
    # ragged: a 512-token chunk resuming after a 1,024-token cached prefix,
    # and a 300-token first chunk padded to the tile
    positions = np.zeros((2, 512), np.int32)
    positions[0] = np.arange(1024, 1536)
    positions[1, :300] = np.arange(300)
    for dtype in (jnp.bfloat16, jnp.float32):
        name = jnp.dtype(dtype).name
        atol, rtol = TOL[name]
        key = jax.random.PRNGKey(seed)
        kq, kk, kr = jax.random.split(key, 3)
        # the kernels read layer 1 of a two-layer pool; the oracles take
        # that layer's pages
        pool = jax.random.normal(kk, (2, 2, n_pages, kv, bs, hd), dtype)
        k_pool, v_pool = pool[1, 0], pool[1, 1]
        q = jax.random.normal(kq, (8, kv, g, hd), dtype)
        qr = jax.random.normal(kr, (2, 512, kv, g, hd), dtype)
        cases = [
            ("paged_attention", paged_attention, ref.paged_attention_ref,
             q, (jnp.asarray(tables), jnp.asarray(ctx))),
            ("ragged_segment_attention", ragged_segment_attention,
             ref.ragged_segment_attention_ref,
             qr, (jnp.asarray(tables[:2, :96]), jnp.asarray(positions))),
        ]
        with jax.default_matmul_precision("highest" if name == "float32"
                                          else "default"):
            for kname, kernel, oracle, qq, rest in cases:
                out = np.asarray(kernel(qq, pool, jnp.int32(1), *rest),
                                 np.float32)
                want = np.asarray(oracle(qq, k_pool, v_pool, *rest),
                                  np.float32)
                err = np.abs(out - want)
                bound = atol + rtol * np.abs(want)
                ok = bool(np.isfinite(out).all() and (err <= bound).all())
                log("kernels", f"{kname} {name} shape={out.shape} "
                    f"max_abs_err={err.max():.3e} tol=atol {atol:g} + rtol {rtol:g} "
                    f"{'ok' if ok else 'FAIL'}")
                check(ok, f"{kname} {name} differs from kernels/ref.py")


def phase_fp32(cfg, seed: int, clock: CompileClock):
    from repro.models import build_model
    from repro.serving import ServingConfig
    cut = dataclasses.replace(cfg, num_layers=FP32_LAYERS, dtype="float32")
    model = build_model(cut)
    params = model.init_params(jax.random.PRNGKey(seed))
    def waves():   # the same requests, unserved, for each cluster
        return make_waves(np.random.default_rng(seed), cfg.vocab_size,
                          sys_len=256, suffixes=(48, 80, 112),
                          unshared=(128, 200), new_tokens=(16, 24))
    config = ServingConfig(num_blocks=512, block_size=BLOCK_SIZE, max_batch=8,
                           prefix_caching=True, prefill_chunk_tokens=128)
    streams = {}
    with jax.default_matmul_precision("highest"):
        for backend in ("pallas", "ref"):
            cluster = build_cluster(model, params, config, backend=backend)
            sent = waves()
            done = drain_waves(cluster, sent)
            r = cluster.engines[0].runner
            check(r.backend == r.ragged_backend == backend,
                  f"fp32 cluster runs {r.backend}/{r.ragged_backend}")
            check_finished(f"fp32/{backend}", sent, done)
            streams[backend] = tokens(done)
            saved = cluster.metrics_snapshot()["engine0.prefill_tokens_saved"]
            check(saved > 0, "fp32 drain never hit the prefix cache")
            cluster.close()
    n_tok = sum(len(t) for t in streams["pallas"].values())
    same = streams["pallas"] == streams["ref"]
    log("fp32", f"depth cut {cfg.num_layers}->{FP32_LAYERS} layers, widths "
        f"published; {len(streams['pallas'])} requests, {n_tok} tokens; "
        f"pallas vs ref token-identical: {same}")
    check(same, "float32 native kernels and the ref oracle disagree on tokens")


def phase_serve(cfg, seed: int, clock: CompileClock):
    from repro.models import build_model
    from repro.serving import ServingConfig
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(model.init_params(jax.random.PRNGKey(seed)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log("serve", f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.num_heads} heads / {cfg.num_kv_heads} KV, head_dim "
        f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"{model.dtype.name}; {n_params / 1e9:.3f}B params "
        f"(init {time.perf_counter() - t0:.1f}s)")
    config = ServingConfig(num_blocks=POOL_BLOCKS, block_size=BLOCK_SIZE,
                           max_batch=8, prefix_caching=True,
                           prefill_chunk_tokens=512)
    waves = make_waves(np.random.default_rng(seed + 1), cfg.vocab_size,
                       sys_len=1024, suffixes=(64, 128, 192, 256),
                       unshared=(512, 512, 512, 512), new_tokens=(32, 48, 64))
    cluster = build_cluster(model, params, config)
    runner = cluster.engines[0].runner
    log("serve", f"backend={runner.backend} ragged_backend={runner.ragged_backend} "
        f"pool {POOL_BLOCKS} blocks x {BLOCK_SIZE} tokens "
        f"({runner.pool.nbytes / 2**30:.2f} GiB)")
    check(runner.backend == runner.ragged_backend == "pallas",
          "the engine must serve with the Pallas kernels on a TPU")

    # the fused step at one served shape: a 512-token chunk + 8 decode rows
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    c0 = clock.secs
    compiled = runner._fused_fn.lower(
        runner.params, runner.pool, i32(1, 512), i32(1, 512), i32(1, 128),
        i32(8), i32(8), i32(8, 128), i32(520), i32(16), i32(4), i32(4)).compile()
    ma = compiled.memory_analysis()
    custom = "tpu_custom_call" in compiled.as_text()
    log("serve", f"fused step compiled in {clock.secs - c0:.1f}s: "
        f"tpu_custom_call={custom} argument_GiB="
        f"{ma.argument_size_in_bytes / 2**30:.3f} temp_GiB="
        f"{ma.temp_size_in_bytes / 2**30:.3f} alias_GiB="
        f"{ma.alias_size_in_bytes / 2**30:.3f}")
    check(custom, "the compiled fused step holds no Pallas kernel")

    c0, t0 = clock.secs, time.perf_counter()
    done = drain_waves(cluster, waves)
    wall = time.perf_counter() - t0
    compile_s = clock.secs - c0
    check_finished("serve", waves, done)
    snap = cluster.metrics_snapshot()
    cluster.close()
    n_tok = sum(len(r.output_tokens) for r in done.values())
    saved = snap["engine0.prefill_tokens_saved"]
    log("serve", f"{len(done)} requests finished, {n_tok} tokens generated, "
        f"prefill {snap['engine0.prefill_tokens']:.0f} tokens computed + "
        f"{saved:.0f} from the prefix cache, "
        f"{snap['engine0.n_dispatches']:.0f} dispatches, "
        f"{snap['engine0.jit_cache_size']:.0f} compiled step shapes")
    check(saved >= 3 * 1024, "wave 2 must reuse the cached system prompt")
    stats = jax.devices()[0].memory_stats() or {}
    log("serve", f"smoke timing (not a benchmark): drain {wall:.2f}s of which "
        f"compile {compile_s:.2f}s; peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 0) / 2**30:.3f} GiB of "
        f"{stats.get('bytes_limit', 0) / 2**30:.3f} GiB")


def phase_four_chips(cfg, seed: int, clock: CompileClock):
    from repro.models import build_model
    from repro.serving import ServingConfig
    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    cut = dataclasses.replace(cfg, num_layers=FP32_LAYERS, dtype="float32")
    model = build_model(cut)
    params = model.init_params(jax.random.PRNGKey(seed))
    def waves():   # the same requests, unserved, for each cluster
        return make_waves(np.random.default_rng(seed), cfg.vocab_size,
                          sys_len=256, suffixes=(48, 80, 112),
                          unshared=(128, 200, 160), new_tokens=(16, 24))
    base = ServingConfig(num_blocks=512, block_size=BLOCK_SIZE, max_batch=8,
                         prefix_caching=True, prefill_chunk_tokens=128)

    def in_use():
        return " ".join(f"{d.id}:{(d.memory_stats() or {}).get('bytes_in_use', 0) / 2**20:.0f}MiB"
                        for d in devs)

    streams = {}
    with jax.default_matmul_precision("highest"):
        for tp in (1, 4):
            config = dataclasses.replace(base, model_parallel=tp)
            cluster = build_cluster(model, params, config)
            sent = waves()
            done = drain_waves(cluster, sent)
            check_finished(f"tp={tp}", sent, done)
            streams[tp] = tokens(done)
            log("four-chips", f"tp={tp}: {len(done)} requests, "
                f"{sum(len(t) for t in streams[tp].values())} tokens; "
                f"bytes_in_use per device {in_use()}")
            cluster.close()
            del cluster
            gc.collect()
    same = streams[1] == streams[4]
    log("four-chips", f"depth cut {cfg.num_layers}->{FP32_LAYERS} layers, float32 "
        f"highest precision: tp=4 vs tp=1 token-identical: {same}")
    check(same, "tp=4 and tp=1 disagree on tokens")

    config = dataclasses.replace(base, n_instances=2, model_parallel=2)
    cluster = build_cluster(model, params, config)
    sent = waves()
    done = drain_waves(cluster, sent)
    check_finished("2x tp=2", sent, done)
    snap = cluster.metrics_snapshot()
    per = [snap[f"engine{i}.n_finished"] for i in range(2)]
    log("four-chips", f"2 instances x tp=2 on disjoint slices: {len(done)} "
        f"requests finished, per instance {per[0]:.0f} / {per[1]:.0f}; "
        f"bytes_in_use per device {in_use()}")
    check(min(per) > 0, "the 2x2 drain must use both instances")
    cluster.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tensor-parallel phase on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    n_dev = len(jax.devices())
    log("device", f"platform={dev.platform} device_kind={dev.device_kind} "
        f"count={n_dev}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})", file=sys.stderr)
        return 2

    sys.path.insert(0, str(SRC))
    from repro.configs import get_config
    from repro.launch.cache import enable_compile_cache
    log("device", f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    cfg = get_config(MODEL)
    phases = ([phase_four_chips] if args.four_chips
              else [phase_kernels, phase_fp32, phase_serve])
    for phase in phases:
        t0, c0 = time.perf_counter(), clock.secs
        phase(cfg, args.seed, clock)
        gc.collect()
        log(phase.__name__[6:].replace("_", "-"), f"phase done in {time.perf_counter() - t0:.1f}s "
            f"(compile {clock.secs - c0:.1f}s)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
