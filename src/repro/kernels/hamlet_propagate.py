"""Pallas TPU kernel for HAMLET's masked prefix propagation.

Solves (I - L) C = B per batch element, where L is strictly lower triangular
(the within-pane predecessor adjacency) and the columns of C are snapshot
coefficients (shared execution) or per-query channels (non-shared execution).

TPU-native formulation: rows are processed in tiles of
``tile`` (default 128, MXU-aligned).  For row tile ``r``:

    y_r = B_r + L[r, :] @ C_acc          (cross-tile contribution; one matmul
                                          against the VMEM-resident running C)
    C_r = (I - L_rr)^(-1) y_r            (in-tile solve)

The in-tile solve uses the nilpotency of the strictly-lower-triangular block:
(I - L)^(-1) = prod_i (I + L^(2^i)), realised as log2(tile) rounds of
``c += P @ c; P = P @ P`` — dense MXU matmuls instead of a length-``tile``
sequential dependence chain.  The running solution C_acc lives in a VMEM
scratch buffer that persists across the sequential grid.

Grid: (batch, row_tiles); scratch is re-zeroed at row tile 0 of every batch
element.  On a TPU it compiles with Mosaic; elsewhere it runs in the Pallas
interpreter (``platform.interpret_kernels``), validated against ``ref.py``.
Compiled kernels take 32-bit operands only: the executor casts at its
boundary (``platform.device_dtype``), and a 64-bit input is refused.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import interpret_kernels

__all__ = ["masked_prefix_propagate_pallas"]

# block-index literal: a Python 0 becomes int64 under jax x64, which Mosaic
# cannot return from an index map
_I0 = np.int32(0)


def _require_32bit(*arrays) -> None:
    for a in arrays:
        if jnp.dtype(a.dtype).itemsize > 4:
            raise TypeError(
                f"compiled Pallas kernels take 32-bit operands, got "
                f"{a.dtype}: cast at the executor boundary "
                f"(repro.kernels.platform.device_dtype)")


def _dot(a, b, acc_dtype):
    # f32 at full precision: the MXU's default pass rounds f32 operands to
    # bf16, which trend counts cannot afford
    return jnp.dot(a, b, preferred_element_type=acc_dtype,
                   precision=jax.lax.Precision.HIGHEST)


def _propagate_kernel(base_ref, mask_ref, out_ref, acc_ref, *, tile: int,
                      n_iters: int, acc_dtype):
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _init():  # fresh batch element: clear the running solution
        acc_ref[...] = jnp.zeros_like(acc_ref)

    base = base_ref[0].astype(acc_dtype)          # [T, d]
    stripe = mask_ref[0].astype(acc_dtype)        # [T, b]

    # Cross-tile contribution.  Rows >= r*tile of acc are still zero, so the
    # full-width matmul only picks up previously solved tiles.
    y = base + _dot(stripe, acc_ref[...], acc_dtype)

    # In-tile Neumann-doubling solve with the diagonal block, read from the
    # ref (Mosaic cannot slice a loaded value at a dynamic offset).
    col = pl.multiple_of(r * tile, tile)
    L = mask_ref[0, :, pl.ds(col, tile)].astype(acc_dtype)   # [T, T]
    c = y
    P = L
    for it in range(n_iters):
        c = c + _dot(P, c, acc_dtype)
        if it + 1 < n_iters:
            P = _dot(P, P, acc_dtype)

    acc_ref[pl.ds(col, tile), :] = c
    out_ref[0] = c.astype(out_ref.dtype)


def masked_prefix_propagate_pallas(base: jax.Array, mask: jax.Array, *,
                                   tile: int = 128,
                                   interpret: bool | None = None
                                   ) -> jax.Array:
    """Batched masked prefix propagation.

    base : [nb, b, d]  injection rows (b and d already padded: b % tile == 0)
    mask : [nb, b, b]  strictly lower triangular adjacency
    returns [nb, b, d] with c[i] = base[i] + sum_{j<i} mask[i,j] c[j].

    ``interpret=None`` follows the platform; pass ``False`` only to compile
    for a described (not attached) TPU.
    """
    if interpret is None:
        interpret = interpret_kernels()
    return _propagate(base, mask, tile=tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _propagate(base, mask, *, tile: int, interpret: bool):
    nb, b, d = base.shape
    if b % tile:
        raise ValueError(f"b={b} must be a multiple of tile={tile}")
    if mask.shape != (nb, b, b):
        raise ValueError(f"mask shape {mask.shape} != {(nb, b, b)}")
    n_tiles = b // tile
    n_iters = max(1, math.ceil(math.log2(tile)))
    if not interpret:
        _require_32bit(base, mask)
    if jnp.issubdtype(base.dtype, jnp.integer):
        acc_dtype = jnp.int32
    elif base.dtype == jnp.float64:
        acc_dtype = jnp.float64   # the interpreter's f64 oracle runs
    else:
        acc_dtype = jnp.float32

    kernel = functools.partial(_propagate_kernel, tile=tile, n_iters=n_iters,
                               acc_dtype=acc_dtype)
    return pl.pallas_call(
        kernel,
        grid=(nb, n_tiles),
        in_specs=[
            pl.BlockSpec((1, tile, d), lambda bi, r: (bi, r, _I0)),
            pl.BlockSpec((1, tile, b), lambda bi, r: (bi, r, _I0)),
        ],
        out_specs=pl.BlockSpec((1, tile, d), lambda bi, r: (bi, r, _I0)),
        out_shape=jax.ShapeDtypeStruct((nb, b, d), base.dtype),
        scratch_shapes=[pltpu.VMEM((b, d), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(base, mask)
