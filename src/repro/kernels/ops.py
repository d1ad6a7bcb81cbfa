"""jit'd public wrappers around the propagation primitive.

``propagate(base, mask, backend=...)`` pads shapes to kernel tiles, dispatches
to the numpy oracle / jnp reference / Pallas kernel, and unpads.  The engine
uses ``backend="np"`` for small host-side bursts and the accelerator backends
for large panes; the dry-run lowers the jnp/pallas paths on the production
mesh.

Every device entry is one jitted program per input shape.  The executors
keep that set bounded by padding on the host before the call
(``core/batch_exec.py``: ``nb`` to a power of two, masked ``b`` to the
128-row tile); padding is exact because zero rows propagate to zeros.
Whether a Pallas kernel compiles or interprets is the platform's call
(``platform.interpret_kernels``), not the caller's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .hamlet_dense import dense_propagate_pallas
from .hamlet_propagate import masked_prefix_propagate_pallas
from .platform import interpret_kernels

__all__ = ["propagate", "propagate_batched", "propagate_dense",
           "propagate_dense_batched", "fold_stacked", "fold_rounds_scan",
           "device_get_all", "PROPAGATE_BACKENDS", "DENSE_B_MAX"]

# largest burst the dense closed form handles exactly (2^b weight range);
# the engine's dense-eligibility test and the executor's fallback share it
DENSE_B_MAX = 512

PROPAGATE_BACKENDS = ("np", "jax", "jax_blocked", "jax_solve", "pallas")

_LANE = 128
# f32 matmuls at full precision on a TPU (its default pass is bf16); a
# no-op for the CPU backend
_HIGHEST = jax.lax.Precision.HIGHEST
# row tile of the masked kernel; the executor pads masked buckets to it
MASKED_TILE = 128


def _pad_to(x: np.ndarray | jax.Array, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _pallas_padded(base, mask, tile, interpret):
    base, b = _pad_to(base, 1, tile)
    base, d = _pad_to(base, 2, _LANE)
    mask, _ = _pad_to(mask, 1, tile)
    mask, _ = _pad_to(mask, 2, tile)
    out = masked_prefix_propagate_pallas(base, mask, tile=tile,
                                         interpret=interpret)
    return out[:, :b, :d]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _dense_pallas_padded(base, tile, interpret):
    x, b = _pad_to(base, 1, tile)
    x, d = _pad_to(x, 2, _LANE)
    out = dense_propagate_pallas(x, tile=tile, interpret=interpret)
    return out[:, :b, :d]


_jax_batched = jax.jit(jax.vmap(ref.masked_prefix_propagate_ref))
_jax_dense_batched = jax.jit(jax.vmap(ref.prefix_propagate_dense))


def propagate_batched(base, mask, *, backend: str = "np",
                      tile: int = MASKED_TILE):
    """Batched propagation: base [nb, b, d], mask [nb, b, b] -> [nb, b, d].

    The batch is ragged-friendly at the edges: ``nb == 0`` returns an empty
    result, and zero-padded trailing rows (zero mask rows/columns) propagate
    to zeros without touching real rows, so callers may pad within a bucket.
    """
    if np.shape(base)[0] == 0:
        return (np.zeros(np.shape(base), dtype=np.asarray(base).dtype)
                if backend == "np"
                else jnp.zeros(np.shape(base), dtype=jnp.asarray(base).dtype))
    if backend == "np":
        base = np.asarray(base)
        mask = np.asarray(mask)
        fast = (base.shape[1] > 24 and
                not np.issubdtype(base.dtype, np.integer))
        if fast:
            # one stacked doubling sweep — slices are bitwise equal to the
            # per-item call (see ref.numpy_prefix_propagate_fast_batched)
            return ref.numpy_prefix_propagate_fast_batched(base, mask)
        return np.stack([ref.numpy_prefix_propagate(base[i], mask[i])
                         for i in range(base.shape[0])])
    if backend == "jax":
        return _jax_batched(jnp.asarray(base), jnp.asarray(mask))
    if backend == "jax_blocked":
        base = jnp.asarray(base)
        mask = jnp.asarray(mask)
        b = base.shape[1]
        tile = 128 if b % 128 == 0 else b
        return jax.vmap(lambda bb, mm: ref.masked_prefix_propagate_blocked(
            bb, mm, tile=tile))(base, mask)
    if backend == "jax_solve":
        return jax.vmap(ref.masked_prefix_propagate_solve)(jnp.asarray(base),
                                                           jnp.asarray(mask))
    if backend == "pallas":
        return _pallas_padded(jnp.asarray(base), jnp.asarray(mask), tile,
                              interpret_kernels())
    raise ValueError(f"unknown backend {backend!r}; use one of {PROPAGATE_BACKENDS}")


def device_get_all(arrays: list) -> list[np.ndarray]:
    """Fetch many (possibly device-resident) arrays with **one** host sync.

    The pane-batch executor launches every bucket of a flush before pulling
    any result back, then converts the whole backlog here: on the jax/pallas
    backends this is a single ``jax.device_get`` over the list (results stay
    device-resident until this point), instead of one blocking
    ``np.asarray`` round trip per bucket per pane.  Pure-numpy inputs pass
    through untouched.
    """
    if not arrays:
        return []
    if all(isinstance(a, np.ndarray) for a in arrays):
        return list(arrays)
    return [np.asarray(a) for a in jax.device_get(list(arrays))]


def fold_stacked(u0, Ms, *, backend: str = "np"):
    """Stacked window-chain fold: ``u0 [N, C]``, ``Ms [N, n, C, C]`` ->
    ``[N, C]``.

    Slice ``i`` applies the chain ``u = u @ M.T`` over ``Ms[i, 0..n)`` in
    order — the :func:`repro.core.engine.fold_panes` recurrence — so each
    slice is bitwise equal to the per-window fold (the same stacked-matmul
    twin convention as ``propagate_batched``).  One call folds a whole
    bucket of same-length windows: a revision storm re-folds every dirty
    window with ``n`` launches instead of ``n`` per window.

    On the jax backends the result stays device-resident; callers batch
    several buckets and resolve them with **one** :func:`device_get_all`
    sync (see ``core/fold_exec.py``).
    """
    n = np.shape(Ms)[1] if np.ndim(Ms) >= 2 else 0
    if backend == "np":
        U = np.asarray(u0)
        Ms = np.asarray(Ms)
        with np.errstate(over="ignore", invalid="ignore"):
            for j in range(n):
                U = np.matmul(U[:, None, :],
                              np.swapaxes(Ms[:, j], 1, 2))[:, 0]
        return U
    U = jnp.asarray(u0)
    if n == 0:
        return U
    # one compiled lax.scan over the window axis instead of n Python-level
    # matmul dispatches — the whole chain is a single device program whose
    # per-round body is the identical jnp matmul (bitwise equal to the
    # eager per-round loop; see tests/test_fold_scan.py)
    return _fold_stacked_scan(U, jnp.swapaxes(jnp.asarray(Ms), 0, 1))


@jax.jit
def _fold_stacked_scan(U, Ms_t):
    """``u = u @ M.T`` chain as one scanned program; ``Ms_t [n, N, C, C]``."""

    def step(u, M):
        return jnp.matmul(u[:, None, :], jnp.swapaxes(M, 1, 2),
                          precision=_HIGHEST)[:, 0], None

    u, _ = jax.lax.scan(step, U, Ms_t)
    return u


@functools.partial(jax.jit, static_argnames=("nu", "t", "n_used"))
def fold_rounds_scan(Z0, S, PTM, GQ, SIDX, SC, ER, *, nu, t, n_used):
    """Whole warm fold-flush as **one** device program (see fold_exec.py).

    Executes every d == 0 fold round of a flush with a single
    ``jax.lax.scan`` whose carry is the fused flat state ``Zf
    [J*k*R + 1, C]`` (row ``J*k*R`` is a scratch row absorbing padded
    lanes).  Per round the body runs the exact stacked-twin ops of
    ``FoldExecutor._fold_bucket_fast``: one state gather, the ``W`` build
    matmul, one ``S`` gather, the update matmul, and two scatter-adds
    (arow targets + rrow/end targets).  All index operands are
    precomputed per flush plan and device-resident:

    * ``S    [G*n_used + 1, B_local]`` — per-group column-sum rows, last
      row zeros (padded lanes);
    * ``PTM  [rounds, NMAX, t]``       — pt_mask rows, padded zero;
    * ``GQ   [rounds, NMAX, R]``       — flat state gather rows (padded →
      scratch);
    * ``SIDX [rounds, NMAX, n_used]``  — rows into ``S`` (padded → zeros
      row);
    * ``SC / ER [rounds, NMAX * n_used]`` — scatter rows (padded /
      non-end → scratch).

    Padded lanes read the scratch row and write back only to the scratch
    row / zero ``S`` row, so real state rows never see padding artifacts
    even in the inf/NaN overflow regime.  Within a round the real scatter
    targets are query-disjoint by level construction, so the accumulation
    is order-free.
    """
    C = Z0.shape[1]

    def step(Zf, xs):
        gq, sidx, sc, er, ptm = xs
        zm = Zf[gq]                                       # [NMAX, R, C]
        Wu = jnp.matmul(ptm[:, None, None, :],
                        zm[:, 1:1 + nu * t].reshape(-1, nu, t, C),
                        precision=_HIGHEST)[:, :, 0, :]
        W = jnp.concatenate([zm[:, 0:1], Wu], axis=1)     # [NMAX, 1+nu, C]
        S_m = S[sidx]                                     # [NMAX, n_used, 1+nu]
        upd = jnp.matmul(S_m, W, precision=_HIGHEST).reshape(-1, C)
        Zf = Zf.at[sc].add(upd)
        return Zf.at[er].add(upd), None

    Zf, _ = jax.lax.scan(step, Z0, (GQ, SIDX, SC, ER, PTM))
    return Zf


def propagate(base, mask, *, backend: str = "np", tile: int = MASKED_TILE):
    """Unbatched propagation: base [b, d], mask [b, b] -> [b, d]."""
    out = propagate_batched(base[None], mask[None], backend=backend, tile=tile)
    return out[0]


def propagate_dense(base, *, backend: str = "np"):
    """Propagation for a *dense* burst (strictly-lower all-ones adjacency —
    no edge predicates, no divergent/dead rows): closed form in O(b*d)
    via exponentially weighted cumsum (paper Table 3's doubling).  Falls
    back to the masked path for b > 512 (weight range)."""
    b = base.shape[0]
    if b > DENSE_B_MAX:
        mask = np.tril(np.ones((b, b)), k=-1)
        return propagate(base, mask, backend=backend)
    if backend == "np":
        return ref.prefix_propagate_dense_np(np.asarray(base))
    return ref.prefix_propagate_dense(jnp.asarray(base))


def propagate_dense_batched(base, *, backend: str = "np", tile: int = 64):
    """Batched dense-burst propagation: base [nb, b, d] -> [nb, b, d].

    One launch for a whole size bucket of dense bursts.  ``nb == 0`` returns
    an empty result; trailing zero-padded rows/columns are safe (each real
    row's prefix is unchanged), so ragged buckets pad to a common shape.
    Requires b <= DENSE_B_MAX per burst (the dense weight range) — the
    engine's planner routes larger bursts to the masked path.
    """
    nb, b, d = np.shape(base)
    if b > DENSE_B_MAX:
        raise ValueError(
            f"dense closed form needs b <= {DENSE_B_MAX}, got {b}")
    if nb == 0:
        return (np.zeros((0, b, d), dtype=np.asarray(base).dtype)
                if backend == "np"
                else jnp.zeros((0, b, d), dtype=jnp.asarray(base).dtype))
    if backend == "np":
        return ref.prefix_propagate_dense_np_batched(np.asarray(base))
    if backend in ("jax", "jax_blocked", "jax_solve"):
        return _jax_dense_batched(jnp.asarray(base))
    if backend == "pallas":
        return _dense_pallas_padded(jnp.asarray(base), tile,
                                    interpret_kernels())
    raise ValueError(f"unknown backend {backend!r}; use one of {PROPAGATE_BACKENDS}")
