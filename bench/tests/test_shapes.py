"""The shape warm: a padding-only batch loads its shape's program and
leaves the pool as it was, and a drive whose shapes were warmed
compiles nothing and serves the same tokens."""
import jax
import numpy as np
import pytest

from bench.run import build
from bench.shapes import StepShapes, padding_batch, reachable, sample_rows, warm
from bench.tests.tiny import tiny_registry, tiny_spec


@pytest.fixture(autouse=True)
def _tiny_registry(monkeypatch):
    monkeypatch.setattr("bench.model.registry_config", tiny_registry)


def _compiles(runner) -> float:
    return runner.metrics.counter("compiles").value


def _drive(cluster):
    """Six calls of uneven lengths submitted at once, served to the end;
    the tokens each was served."""
    from repro.serving import Request
    rng = np.random.default_rng(5)
    reqs = []
    for i, (n, out) in enumerate([(40, 6), (13, 9), (70, 4), (5, 12), (33, 7), (90, 3)]):
        toks = rng.integers(0, 512, n).astype(np.int32)
        reqs.append(Request(agent_name=f"a{i}", msg_id=f"m{i}", prompt_len=n,
                            prompt_tokens=toks, max_new_tokens=out))
        cluster.submit(reqs[-1])
    for _ in range(500):
        if not cluster.has_work:
            break
        cluster.step()
    assert not cluster.has_work
    return [[int(t) for t in r.output_tokens] for r in reqs]


def test_a_padding_batch_leaves_every_pool_byte_and_loads_its_shape():
    cluster, _ = build(tiny_spec(), 7)
    runner = cluster.engines[0].runner
    shapes = StepShapes(cluster.engines, lambda msg: None)
    runner.pool = jax.random.normal(jax.random.PRNGKey(1), runner.pool.shape,
                                    np.float32).astype(runner.pool.dtype)
    before = np.asarray(runner.pool).view(np.uint16).copy()
    key = (2, 8, 4, 4, 8, 8, 4)          # chunks, decode rows and copy-on-write pairs
    assert padding_batch(key, runner.num_blocks, runner.block_size).shape_key == key
    c0 = _compiles(runner)
    assert warm(cluster.engines, shapes, [key]) == 1
    assert np.array_equal(np.asarray(runner.pool).view(np.uint16), before)
    assert key in shapes.issued[id(runner)] and _compiles(runner) == c0 + 1
    assert warm(cluster.engines, shapes, [key]) == 0
    cluster.close()


def test_a_drive_on_warmed_shapes_compiles_nothing_and_serves_the_same_tokens():
    cold, _ = build(tiny_spec(), 3)
    seen = StepShapes(cold.engines, lambda msg: None)
    served = _drive(cold)
    keys = seen.all_issued()
    cold.close()
    assert len(keys) > 2

    cluster, _ = build(tiny_spec(), 3)
    runner = cluster.engines[0].runner
    shapes = StepShapes(cluster.engines, lambda msg: None)
    assert warm(cluster.engines, shapes, keys) == len(keys)
    c0, jit0 = _compiles(runner), runner.jit_cache_size()
    assert _drive(cluster) == served
    assert _compiles(runner) == c0 and runner.jit_cache_size() == jit0
    cluster.close()


def test_reachable_pairs_every_prefill_part_with_every_decode_part():
    assert sample_rows(1, 4) == {4, 8} and sample_rows(1, 8) == {8, 16}
    assert sample_rows(0, 8) == {8} and sample_rows(2, 0) == {4}
    replay = [(1, 64, 8, 4, 8, 8, 0), (0, 0, 0, 8, 16, 8, 0), (2, 32, 4, 0, 0, 4, 4)]
    got = reachable(replay)
    assert set(replay) <= got
    # the prefill parts met, each beside each decode part met, every S, every C
    assert {(1, 64, 8, 8, 16, s, c) for s in (8, 16) for c in (0, 4)} <= got
    assert {(1, 64, 8, 0, 0, 4, c) for c in (0, 4)} <= got
    assert {(2, 32, 4, 4, 8, s, c) for s in (4, 8) for c in (0, 4)} <= got
    assert {(2, 32, 4, 8, 16, s, c) for s in (8, 16) for c in (0, 4)} <= got
    assert {(0, 0, 0, 4, 8, 4, c) for c in (0, 4)} <= got
    assert not any(k[0] == 0 and k[3] == 0 for k in got)             # no empty step
    assert len(got) == 2 * (2 + 1 + 2 + 1 + 2 + 2 + 1 + 1)
    # no size the replay did not meet
    assert {k[1] for k in got} == {0, 32, 64} and {k[4] for k in got} == {0, 8, 16}
