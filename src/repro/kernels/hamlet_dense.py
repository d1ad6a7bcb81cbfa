"""Pallas TPU kernel for *dense* burst propagation (no edge predicates).

For a dense burst the adjacency is strictly-lower all-ones and
(I-L)^{-1}[i,j] = 2^{i-j-1}, so

    c_i = b_i + s_{i-1},   s_i = 2 s_{i-1} + b_i

(the paper's Table-3 doubling in closed form — §Perf it.5).  The kernel
processes row tiles with a precomputed [T, T] weight matrix
K[i,j] = 2^{i-j-1} (j < i) — one MXU matmul per tile — and carries the
running weighted sum ``s`` across tiles in VMEM:

    c_tile = b_tile + K @ b_tile + s_in * pow2[i]
    s_out  = 2^T * s_in + rowpow @ b_tile,   rowpow[j] = 2^{T-1-j}

Tile must satisfy 2^T finite in f32 (T <= 64 keeps the carry exact until
counts themselves saturate — the engine's documented overflow semantics).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .hamlet_propagate import _I0, _dot, _require_32bit
from .platform import interpret_kernels

__all__ = ["dense_propagate_pallas"]


def _dense_kernel(k_ref, pow2_ref, rowpow_ref, base_ref, out_ref, s_ref,
                  *, tile):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    b = base_ref[0].astype(jnp.float32)                 # [T, d]
    K = k_ref[...]                                      # [T, T]
    c = b + _dot(K, b, jnp.float32)
    c = c + pow2_ref[...].T * s_ref[...]                # s_in * 2^i
    out_ref[0] = c.astype(out_ref.dtype)
    s_new = ((2.0 ** tile) * s_ref[...] +
             _dot(rowpow_ref[...], b, jnp.float32))
    s_ref[...] = s_new


def dense_propagate_pallas(base: jax.Array, *, tile: int = 64,
                           interpret: bool | None = None) -> jax.Array:
    """base [nb, b, d] with b % tile == 0; returns the dense-burst counts.

    ``interpret=None`` follows the platform (see
    :func:`~repro.kernels.hamlet_propagate.masked_prefix_propagate_pallas`).
    """
    if interpret is None:
        interpret = interpret_kernels()
    return _dense(base, tile=tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _dense(base, *, tile: int, interpret: bool):
    nb, b, d = base.shape
    if not interpret:
        _require_32bit(base)
    if b % tile:
        raise ValueError(f"b={b} must be a multiple of tile={tile}")
    if tile > 64:
        raise ValueError("tile > 64 overflows the f32 carry scale 2^T")
    n_tiles = b // tile

    i = np.arange(tile)
    K = np.where(i[:, None] > i[None, :],
                 2.0 ** (i[:, None] - i[None, :] - 1.0), 0.0)
    pow2 = (2.0 ** i)[None, :].astype(np.float32)        # [1, T]
    rowpow = (2.0 ** (tile - 1.0 - i))[None, :].astype(np.float32)

    kernel = functools.partial(_dense_kernel, tile=tile)
    return pl.pallas_call(
        kernel,
        grid=(nb, n_tiles),
        in_specs=[
            pl.BlockSpec((tile, tile), lambda bi, t: (_I0, _I0)),
            pl.BlockSpec((1, tile), lambda bi, t: (_I0, _I0)),
            pl.BlockSpec((1, tile), lambda bi, t: (_I0, _I0)),
            pl.BlockSpec((1, tile, d), lambda bi, t: (bi, t, _I0)),
        ],
        out_specs=pl.BlockSpec((1, tile, d), lambda bi, t: (bi, t, _I0)),
        out_shape=jax.ShapeDtypeStruct((nb, b, d), base.dtype),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(K, jnp.float32), jnp.asarray(pow2), jnp.asarray(rowpow),
      base)
