"""Step shapes: which the runners issued, which the window can reach, and
a warm of the rest before the window opens.

A fused step's program is specialized on its batch's shape key,
``IterationBatch.shape_key``: (prefill chunks Sp, chunk tile L, chunk
table width, decode rows Td, decode table width, sample rows S,
copy-on-write pairs C), each padded by the program's ``pad_bucket`` to
its floor times a power of two.  A key met for the first time traces,
lowers and compiles or loads a program, which stalls every call in
flight.  The replay passes of set-up meet most of the window's keys, but
which calls share a step depends on timing, so the window can pair a
prefill part with a decode part that the replay never put in one step.
``reachable`` widens the replay's keys to every such pairing, and
``warm`` issues one padding-only batch of each key a
runner has not issued: every write slot past the pool, every
copy-on-write destination past the pool, every sample row 0, so the
pool is left as it was and only the program is loaded.
"""
from __future__ import annotations

import time
from typing import Dict, Iterable, Optional, Set, Tuple

import jax
import numpy as np

from repro.serving.batch_scheduler import (CHUNK_SEG_FLOOR, SEGMENT_BUCKET_FLOOR,
                                           IterationBatch, pad_bucket)

Key = Tuple[int, int, int, int, int, int, int]


class StepShapes:
    """Records the shape key of every batch each engine's runner runs,
    through its public ``run_iteration``.  Once ``window_t0`` is set it
    keeps the keys the window runs, and logs each key new to its runner
    (one that compiles or loads a program) with its time."""

    def __init__(self, engines, log):
        self.log = log
        self.issued: Dict[int, Set[Key]] = {}
        self.replay: Set[Key] = set()
        self.window_t0: Optional[float] = None
        self.window_keys: Set[Key] = set()
        self.window_new: list = []
        for e in engines:
            self._wrap(e.runner)

    def _wrap(self, runner):
        seen = self.issued.setdefault(id(runner), set())
        run = runner.run_iteration

        def run_iteration(batch):
            key = batch.shape_key
            if self.window_t0 is not None:
                self.window_keys.add(key)
                if key not in seen:
                    self.window_new.append(key)
                    self.log(f"[window] new step shape {list(key)} at "
                             f"{time.monotonic() - self.window_t0:.3f}s")
            seen.add(key)
            return run(batch)
        runner.run_iteration = run_iteration

    def all_issued(self) -> Set[Key]:
        return set().union(*self.issued.values())


def _counts(bucket: int, floor: int) -> range:
    """The real sizes ``pad_bucket`` puts in ``bucket``."""
    if bucket == 0:
        return range(0, 1)
    return range(1 if bucket == floor else bucket // 2 + 1, bucket + 1)


def sample_rows(sp: int, td: int) -> Set[int]:
    """Every sample-row bucket a step with ``sp`` chunk rows and ``td``
    decode rows can have: one row per chunk and per decode."""
    return {pad_bucket(c + d, SEGMENT_BUCKET_FLOOR)
            for c in _counts(sp, CHUNK_SEG_FLOOR) for d in _counts(td, SEGMENT_BUCKET_FLOOR)}


def reachable(replay: Iterable[Key]) -> Set[Key]:
    """The replay's keys and every recombination of their parts: each
    prefill part (Sp, L, chunk table) the replay met, beside each decode
    part (Td, decode table) it met, with each copy-on-write size it met
    and every sample-row bucket the pair allows.  Which chunks and which
    decode rows share a step depends on timing; the parts themselves
    depend on the schedule's lengths, which every run shares."""
    replay = set(replay)
    prefill = {k[0:3] for k in replay}
    decode = {k[3:5] for k in replay}
    cow = {k[6] for k in replay}
    out: Set[Key] = set(replay)
    for p in prefill:
        for d in decode:
            if p[0] == 0 and d[0] == 0:
                continue
            for s in sample_rows(p[0], d[0]):
                out.update((*p, *d, s, c) for c in cow)
    return out


def padding_batch(key: Key, num_blocks: int, block_size: int) -> IterationBatch:
    """A batch of ``key``'s shape made of padding alone: it runs the
    step's program and changes nothing in the pool."""
    sp, lp, nbp, td, nbd, s, c = key

    def z(*shape):
        return np.zeros(shape, np.int32)
    return IterationBatch(z(sp, lp), z(sp, lp), z(sp, nbp), z(td), z(td), z(td, nbd),
                          np.full(sp * lp + td, num_blocks * block_size, np.int32),
                          z(s), z(c), np.full(c, num_blocks, np.int32), [], 0)


def warm(engines, shapes: StepShapes, keys: Iterable[Key]) -> int:
    """Issue a padding batch of every key in ``keys`` that an engine's
    runner has not issued, then wait for the device; the number issued."""
    n = 0
    for e in engines:
        r = e.runner
        for key in sorted(set(keys) - shapes.issued[id(r)]):
            r.run_iteration(padding_batch(key, r.num_blocks, r.block_size))
            n += 1
        jax.block_until_ready(r.pool)
    return n
