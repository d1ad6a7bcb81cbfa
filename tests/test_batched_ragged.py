"""Ragged-edge golden tests for the batched propagation entry points.

``propagate_batched`` and the batched dense closed form are exercised at the
bucket boundaries the executor produces — burst sizes 1, tile, tile+1, mixed
buckets, and the empty batch — against the row-by-row oracle in kernels/ref.
"""

import numpy as np
import pytest

from repro.core.batch_exec import PaneBatchExecutor
from repro.kernels import ops, ref


def _golden(base, mask):
    return np.stack([ref.numpy_prefix_propagate(base[i], mask[i])
                     for i in range(base.shape[0])]) if base.shape[0] else base


def _rand(nb, b, d, seed, density=0.5):
    rng = np.random.default_rng(seed)
    base = rng.random((nb, b, d)) * 0.01
    mask = np.tril(rng.random((nb, b, b)) < density, k=-1).astype(np.float64)
    return base, mask


@pytest.mark.parametrize("b", [1, 128, 129])
@pytest.mark.parametrize("backend", ["np", "jax"])
def test_propagate_batched_edge_sizes(b, backend):
    base, mask = _rand(3, b, 4, seed=b)
    got = np.asarray(ops.propagate_batched(base, mask, backend=backend))
    want = _golden(base, mask)
    assert np.max(np.abs(got - want) / (1 + np.abs(want))) < 1e-9


def test_propagate_batched_empty():
    base = np.zeros((0, 16, 4))
    mask = np.zeros((0, 16, 16))
    for backend in ("np", "jax"):
        got = np.asarray(ops.propagate_batched(base, mask, backend=backend))
        assert got.shape == (0, 16, 4)


def test_propagate_batched_zero_padded_rows_inert():
    """Trailing zero-padded rows (zero mask rows/cols) yield zeros and leave
    real rows untouched — the property ragged buckets rely on."""
    base, mask = _rand(2, 40, 3, seed=7)
    bp = 64
    pbase = np.zeros((2, bp, 3))
    pbase[:, :40] = base
    pmask = np.zeros((2, bp, bp))
    pmask[:, :40, :40] = mask
    got = np.asarray(ops.propagate_batched(pbase, pmask, backend="np"))
    want = _golden(base, mask)
    # real rows agree to fp tolerance (padding changes the GEMM shape, so
    # bitwise-sensitive callers bucket masked jobs by exact shape instead)
    assert np.max(np.abs(got[:, :40] - want) / (1 + np.abs(want))) < 1e-9
    assert np.all(got[:, 40:] == 0.0)


@pytest.mark.parametrize("b", [1, 64, 65, 512])
def test_dense_batched_edge_sizes(b):
    rng = np.random.default_rng(b)
    base = rng.random((3, b, 5)) * 1e-3
    got = np.asarray(ops.propagate_dense_batched(base, backend="np"))
    mask = np.tril(np.ones((b, b)), k=-1)
    want = _golden(base, np.broadcast_to(mask, (3, b, b)))
    assert np.max(np.abs(got - want) / (1 + np.abs(want))) < 1e-9
    # per-slice bitwise vs the unbatched closed form
    for i in range(3):
        assert np.array_equal(got[i], ref.prefix_propagate_dense_np(base[i]))


def test_dense_batched_empty_and_oversize():
    assert ops.propagate_dense_batched(np.zeros((0, 8, 2))).shape == (0, 8, 2)
    with pytest.raises(ValueError):
        ops.propagate_dense_batched(np.zeros((1, 513, 2)))


def test_dense_batched_pallas_interpret():
    rng = np.random.default_rng(3)
    base = (rng.random((2, 65, 2)) * 1e-3).astype(np.float32)  # tile+1 pad
    # the platform (CPU here) selects the interpreter
    got = np.asarray(ops.propagate_dense_batched(base, backend="pallas",
                                                 tile=64))
    want = np.stack([ref.prefix_propagate_dense_np(base[i].astype(np.float64))
                     for i in range(2)])
    assert np.max(np.abs(got - want) / (1 + np.abs(want))) < 1e-5


def test_executor_mixed_buckets_golden():
    """Mixed dense+masked jobs of ragged sizes through the executor: every
    result matches the oracle, and bucketing collapses the launch count."""
    rng = np.random.default_rng(0)
    ex = PaneBatchExecutor(backend="np", batched=True)
    jobs = []
    # dense jobs: sizes straddling pow2 bucket edges, constant basis width
    for b in [1, 7, 8, 9, 64, 65, 128, 64, 9, 7]:
        base = rng.random((b, 3)) * 1e-3
        jobs.append((ex.submit(base, None), base, None))
    # masked jobs: below and above the fast threshold, repeated shapes
    for b in [3, 24, 25, 40, 40, 40, 129]:
        base = rng.random((b, 5)) * 1e-2
        mask = np.tril(rng.random((b, b)) < 0.5, k=-1).astype(np.float64)
        jobs.append((ex.submit(base, mask), base, mask))
    ex.flush()
    for job, base, mask in jobs:
        if mask is None:
            want = ref.prefix_propagate_dense_np(base)
        else:
            want = ref.numpy_prefix_propagate(base, mask)
        assert np.max(np.abs(job.result - want) / (1 + np.abs(want))) < 1e-9
    # 10 dense jobs collapse into pow2 buckets; 3 equal-shape masked jobs
    # into one launch; tiny masked jobs stay per-item
    assert ex.launches < ex.jobs


def test_executor_empty_flush_noop():
    ex = PaneBatchExecutor(backend="np", batched=True)
    ex.flush()
    assert ex.jobs == 0 and ex.launches == 0


def test_pane_bucket_shards():
    from repro.distributed.sharding import pane_bucket_shards

    assert pane_bucket_shards(0, 4) == []
    assert pane_bucket_shards(3, 8) == [slice(0, 1), slice(1, 2), slice(2, 3)]
    sl = pane_bucket_shards(10, 3)
    assert [s.stop - s.start for s in sl] == [3, 4, 3]
    covered = np.concatenate([np.arange(s.start, s.stop) for s in sl])
    assert np.array_equal(covered, np.arange(10))


def test_pane_batch_pspecs_and_device_put():
    """The device-placement hooks produce valid specs/shardings on a live
    mesh: batch axis over the data axes, burst rows/basis columns local."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import (pane_batch_pspecs,
                                            shard_pane_bucket)

    mesh = jax.make_mesh((1,), ("data",))
    assert pane_batch_pspecs(mesh, 3) == P(("data",), None, None)
    assert pane_batch_pspecs(mesh, 2) == P(("data",), None)

    class NoDp:
        axis_names = ("model",)

    assert pane_batch_pspecs(NoDp(), 3) == P(None, None, None)

    arr = np.arange(24.0).reshape(2, 4, 3)
    placed = shard_pane_bucket(arr, mesh)
    assert np.array_equal(np.asarray(placed), arr)
    assert placed.sharding.spec == pane_batch_pspecs(mesh, 3)
