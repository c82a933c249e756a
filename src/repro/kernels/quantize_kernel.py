"""Pallas TPU kernel: elementwise round-to-format (RNE) on f32 tensors.

Used by the numerics policies to quantize activations/gradients to a generated
FPU format.  Trivial compute, but bandwidth-critical at scale: the BlockSpec
keeps (rows x 128-lane) tiles streaming HBM->VMEM->HBM with no transposes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.formats import FloatFormat, quantize

BLOCK_COLS = 512


def _quantize_kernel(x_ref, o_ref, *, fmt: FloatFormat):
    o_ref[...] = quantize(x_ref[...], fmt)


@functools.partial(
    jax.jit, static_argnames=("fmt", "block_rows", "interpret")
)
def quantize_2d(
    x: jax.Array,
    *,
    fmt: FloatFormat,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Round a 2D f32 array onto fmt's grid.

    Tiles are (block_rows, up to BLOCK_COLS lanes): the lane dimension is
    tiled too, so a tile's VMEM footprint does not grow with the row length
    (a whole 2048-wide row per tile overflows v5e's 16 MiB scoped VMEM).
    """
    if x.ndim != 2:
        raise ValueError(f"quantize_2d wants 2D, got {x.shape}")
    m, n = x.shape
    bm = min(block_rows, max(8, m))
    bn = min(BLOCK_COLS, n + (-n) % 128)
    pm, pn = (-m) % bm, (-n) % bn
    x_p = jnp.pad(x.astype(jnp.float32), ((0, pm), (0, pn)))
    gm, gn = (m + pm) // bm, (n + pn) // bn
    out = pl.pallas_call(
        functools.partial(_quantize_kernel, fmt=fmt),
        grid=(gm, gn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + pm, n + pn), jnp.float32),
        interpret=interpret,
    )(x_p)
    return out[:m, :n]


def quantize_nd(x: jax.Array, *, fmt: FloatFormat, interpret: bool = False):
    """Quantize an arbitrary-rank tensor by folding leading dims."""
    shape = x.shape
    if x.ndim == 0:
        return quantize(x, fmt)
    lead = 1
    for d in shape[:-1]:
        lead *= d
    y = quantize_2d(x.reshape(lead, shape[-1]), fmt=fmt, interpret=interpret)
    return y.reshape(shape)
