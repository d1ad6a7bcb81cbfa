"""Pane-batch executor: ragged propagation jobs -> few bucketed launches.

The engine's plan phase walks every burst in a pane and *submits* its
propagation problems here instead of solving them inline; ``flush`` then
executes the backlog with one launch per size bucket:

* **dense jobs** (``mask is None``: strictly-lower all-ones adjacency) share
  a constant basis width per component, so they bucket by
  ``(next_pow2(b), d)`` with zero-row padding — padding is exact for the
  dense closed form — and run as one ``propagate_dense_batched`` call;
* **masked jobs** bucket by exact ``(b, d)`` (stacking needs equal shapes,
  and exact shapes keep each slice bitwise identical to the per-burst call)
  and run as one ``propagate_batched`` call per bucket;
* tiny masked jobs (``b <= 24`` on the numpy backend) keep the exact
  row-by-row oracle per item, matching the per-burst path bit for bit.

``batched=False`` degrades to the legacy one-launch-per-burst execution —
the differential tests assert the two modes agree bitwise.

``shard_slices`` is the pane-batch sharding hook: a callable mapping a
bucket's batch size to a list of slices (e.g.
``distributed.sharding.pane_bucket_shards``); each sub-batch is launched
separately so buckets can be split across devices/hosts.

Residency rules (cross-pane micro-batching support):

* **numpy backend** — the stacked *input* staging arrays are reused across
  flushes (one buffer per bucket shape, grown to the high-water batch size),
  so a steady-state stream stops allocating per pane.  Outputs are always
  freshly allocated: job results are views into them and must survive later
  flushes.
* **jax/pallas backends** — every bucket of a flush is launched before any
  result is pulled back; the whole flush then syncs with **one**
  ``ops.device_get_all`` call, keeping bucket outputs device-resident for
  the duration of the flush instead of round-tripping through
  ``np.asarray`` per bucket.  Host staging is *not* reused here: device
  transfers may be asynchronous, so inputs get fresh buffers.

Device boundary (jax/pallas backends): each launch is staged on the host
at a padded shape — the batch to the next power of two, masked bursts to
the kernel's 128-row tile — so a stream compiles a bounded set of
programs, and a warm pass over shapes already seen compiles nothing.
Padding is exact (zero rows and zero mask columns propagate to zeros).
Operands cross in ``platform.device_dtype`` (f32 on a TPU) by one explicit
cast here, and results come back into the jobs' host dtype.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..kernels import ops
from ..kernels.platform import device_dtype
from ..obs.metrics import OCCUPANCY_BUCKETS

__all__ = ["PropagateJob", "PaneBatchExecutor"]

# numpy-backend threshold below which the exact row-loop oracle beats the
# doubling GEMMs for a single burst (mirrors ops.propagate_batched)
_FAST_MIN_B = 25
_DENSE_B_MAX = ops.DENSE_B_MAX


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


@dataclass
class PropagateJob:
    """One propagation problem: ``mask is None`` marks a dense burst."""

    base: np.ndarray              # [b, d]
    mask: np.ndarray | None       # [b, b] strictly-lower adjacency
    result: np.ndarray | None = None


class PaneBatchExecutor:
    def __init__(self, backend: str = "np", batched: bool = True,
                 shard_slices=None, obs=None):
        self.backend = backend
        self.batched = batched
        self.shard_slices = shard_slices
        self.obs = obs
        self._pending: list[PropagateJob] = []
        # reusable host staging for stacked inputs, keyed by (kind, b, d,
        # dtype) and grown to the high-water bucket size (numpy backend only;
        # see the module docstring's residency rules)
        self._staging: dict[tuple, np.ndarray] = {}
        self.jobs = 0
        self.launches = 0
        self.flushes = 0

    def submit(self, base: np.ndarray,
               mask: np.ndarray | None = None) -> PropagateJob:
        job = PropagateJob(np.asarray(base), mask)
        self._pending.append(job)
        self.jobs += 1
        return job

    # -- execution --

    def flush(self) -> None:
        jobs, self._pending = self._pending, []
        if not jobs:
            return
        self.flushes += 1
        l0 = self.launches
        if not self.batched:
            for j in jobs:
                self.launches += 1
                if j.mask is None:
                    j.result = np.asarray(
                        ops.propagate_dense(j.base, backend=self.backend))
                else:
                    j.result = np.asarray(
                        ops.propagate(j.base, j.mask, backend=self.backend))
            return
        dense = [j for j in jobs if j.mask is None
                 and j.base.shape[0] <= _DENSE_B_MAX]
        masked = [j for j in jobs if j.mask is not None]
        # oversize "dense" jobs fall back to an explicit all-ones mask
        for j in jobs:
            if j.mask is None and j.base.shape[0] > _DENSE_B_MAX:
                b = j.base.shape[0]
                j.mask = np.tril(np.ones((b, b)), k=-1)
                masked.append(j)
        # launch every bucket, then resolve the whole flush with one host
        # sync (device backends stay device-resident until here)
        launched = self._launch_dense(dense) + self._launch_masked(masked)
        outs = ops.device_get_all([o for _, _, _, o in launched])
        full: dict[int, np.ndarray] = {}
        for (bucket, shape, sl, _), host in zip(launched, outs):
            arr = full.get(id(bucket))
            if arr is None:
                arr = full[id(bucket)] = np.empty(
                    shape, dtype=np.promote_types(host.dtype,
                                                  bucket[0].base.dtype))
            # drop the device path's padding (a no-op view on numpy)
            arr[sl] = host[:len(bucket[sl]), :shape[1], :shape[2]]
        done: set[int] = set()
        for bucket, _, _, _ in launched:
            if id(bucket) in done:
                continue
            done.add(id(bucket))
            arr = full[id(bucket)]
            for i, j in enumerate(bucket):
                j.result = arr[i, : j.base.shape[0]]
        if self.obs is not None:
            self.obs.observe("batch_exec.launches_per_flush",
                             self.launches - l0, OCCUPANCY_BUCKETS)

    def _slices(self, nb: int) -> list[slice]:
        if self.shard_slices is None:
            return [slice(0, nb)]
        return list(self.shard_slices(nb))

    def _stage(self, kind: str, nb: int, item_shape: tuple,
               dtype) -> np.ndarray:
        """A reusable stacked staging buffer (numpy backend only)."""
        key = (kind,) + item_shape + (np.dtype(dtype),)
        buf = self._staging.get(key)
        if buf is None or buf.shape[0] < nb:
            buf = np.empty((nb,) + item_shape, dtype=dtype)
            self._staging[key] = buf
        return buf[:nb]

    def _device_stack(self, items: list[PropagateJob], b_pad: int,
                      masked: bool) -> tuple:
        """Stage one device launch: zero-padded to a power-of-two batch and
        ``b_pad`` rows, cast to the device dtype (see module doc)."""
        nb = _next_pow2(len(items))
        d = items[0].base.shape[1]
        base = np.zeros((nb, b_pad, d), device_dtype(items[0].base.dtype))
        for i, j in enumerate(items):
            base[i, :j.base.shape[0]] = j.base
        if not masked:
            return (base,)
        mask = np.zeros((nb, b_pad, b_pad), base.dtype)
        for i, j in enumerate(items):
            b = j.mask.shape[0]
            mask[i, :b, :b] = j.mask
        return base, mask

    def _count(self, kind: str) -> None:
        self.launches += 1
        if self.obs is not None:
            self.obs.count(f"batch_exec.launches.{kind}")

    def _launch_dense(self, jobs: list[PropagateJob]) -> list:
        buckets: dict[tuple, list[PropagateJob]] = {}
        for j in jobs:
            b, d = j.base.shape
            buckets.setdefault((_next_pow2(b), d, j.base.dtype), []).append(j)
        launched = []
        for (bp, d, dtype), bucket in buckets.items():
            nb = len(bucket)
            if self.obs is not None:
                self.obs.observe("batch_exec.bucket_occupancy", nb,
                                 OCCUPANCY_BUCKETS)
            if self.backend != "np":
                for sl in self._slices(nb):
                    self._count("dense")
                    launched.append((bucket, (nb, bp, d), sl,
                                     ops.propagate_dense_batched(
                                         *self._device_stack(bucket[sl], bp,
                                                             False),
                                         backend=self.backend)))
                continue
            stacked = self._stage("dense", nb, (bp, d), dtype)
            for i, j in enumerate(bucket):
                bj = j.base.shape[0]
                stacked[i, :bj] = j.base
                stacked[i, bj:] = 0.0
            for sl in self._slices(nb):
                self._count("dense")
                launched.append((bucket, (nb, bp, d), sl,
                                 ops.propagate_dense_batched(
                                     stacked[sl], backend=self.backend)))
        return launched

    def _launch_masked(self, jobs: list[PropagateJob]) -> list:
        from ..kernels import ref

        buckets: dict[tuple, list[PropagateJob]] = {}
        for j in jobs:
            buckets.setdefault(j.base.shape + (j.base.dtype,), []).append(j)
        launched = []
        for (b, d, dtype), bucket in buckets.items():
            nb = len(bucket)
            if self.obs is not None:
                self.obs.observe("batch_exec.bucket_occupancy", nb,
                                 OCCUPANCY_BUCKETS)
            if self.backend != "np":
                b_pad = -(-b // ops.MASKED_TILE) * ops.MASKED_TILE
                for sl in self._slices(nb):
                    self._count("masked")
                    launched.append((bucket, (nb, b, d), sl,
                                     ops.propagate_batched(
                                         *self._device_stack(bucket[sl],
                                                             b_pad, True),
                                         backend=self.backend)))
                continue
            base = self._stage("mbase", nb, (b, d), dtype)
            mask = self._stage("mmask", nb, (b, b), bucket[0].mask.dtype)
            for i, j in enumerate(bucket):
                base[i] = j.base
                mask[i] = j.mask
            small = b < _FAST_MIN_B
            for sl in self._slices(nb):
                self._count("masked")
                if small:
                    # stacked row-loop oracle: b row steps for the whole
                    # bucket, each slice bitwise equal to the per-burst call
                    out = ref.numpy_prefix_propagate_batched(base[sl],
                                                             mask[sl])
                else:
                    out = ops.propagate_batched(base[sl], mask[sl],
                                                backend=self.backend)
                launched.append((bucket, (nb, b, d), sl, out))
        return launched
