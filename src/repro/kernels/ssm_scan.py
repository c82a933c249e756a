"""Pallas TPU kernel: fused selective-scan (the Mamba recurrence).

h_t = a_t * h_{t-1} + b_t ;  y_t = <h_t, C_t>

The kernel takes the (B, S, d_inner, d_state) decay and injection
expansions ``a`` and ``b`` as inputs from HBM, with the (B, S, d_state)
readout ``C``.  Each grid step loads a (chunk x d_block) tile of them, runs
the recurrence sequentially in registers/VMEM, and writes only y
(chunk x d_block) and the carried state (d_block x N) back, so the state
sequence ``h`` never reaches HBM.  Building the expansions from dt, x, B, C
and A inside the kernel, which would also keep ``a`` and ``b`` off HBM, is
not done here.

Grid: (B, d_inner/bd, S/chunk); the chunk axis is ``arbitrary`` (sequential —
it carries the state in a VMEM scratch accumulator).  d-tiles are parallel.
Validated in interpret mode against the pure-jnp chunked oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_scan_kernel(a_ref, b_ref, c_ref, y_ref, h_ref, hstate,
                     *, nchunks: int, chunk: int):
    """a,b: (chunk, bd, N); c: (chunk, N); y: (chunk, bd); h: (bd, N)."""
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        hstate[...] = jnp.zeros_like(hstate)

    def step(i, h):
        a_i = a_ref[0, i]  # (bd, N)
        b_i = b_ref[0, i]
        h = a_i * h + b_i
        y_ref[0, i, :] = jnp.sum(h * c_ref[0, i][None, :], axis=-1)
        return h

    h = jax.lax.fori_loop(0, chunk, step, hstate[...])
    hstate[...] = h

    @pl.when(t == nchunks - 1)
    def _flush():
        h_ref[0] = hstate[...]


@functools.partial(jax.jit, static_argnames=("chunk", "bd", "interpret"))
def ssm_scan(a, b, c, *, chunk: int = 16, bd: int = 256,
             interpret: bool = False):
    """a, b: (B, S, D, N) decay/injection; c: (B, S, N) readout.

    Returns (y (B,S,D) f32, h_last (B,D,N) f32).  S % chunk == 0 and
    D % bd == 0 are required (pad at the caller; the model layers use
    power-of-two D and S).  The default (chunk, bd) fits v5e's 16 MiB of
    scoped VMEM for d_state up to 128: the a and b tiles are lane-padded to
    (16, 256, 128) f32, 2 MiB each, 8 MiB double-buffered.
    """
    B, S, D, N = a.shape
    if S % chunk or D % min(bd, D):
        raise ValueError(f"S={S} % chunk={chunk} or D={D} % bd={bd} != 0")
    bd = min(bd, D)
    nchunks = S // chunk
    grid = (B, D // bd, nchunks)
    kernel = functools.partial(_ssm_scan_kernel, nchunks=nchunks, chunk=chunk)
    y, h = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, bd, N), lambda i, j, t: (i, t, j, 0)),
            pl.BlockSpec((1, chunk, bd, N), lambda i, j, t: (i, t, j, 0)),
            pl.BlockSpec((1, chunk, N), lambda i, j, t: (i, t, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, bd), lambda i, j, t: (i, t, j)),
            pl.BlockSpec((1, bd, N), lambda i, j, t: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, D), jnp.float32),
            jax.ShapeDtypeStruct((B, D, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a.astype(jnp.float32), b.astype(jnp.float32), c.astype(jnp.float32))
    return y, h


def ssm_scan_ref(a, b, c):
    """Pure-jnp oracle: sequential recurrence + readout."""
    B, S, D, N = a.shape

    def step(h, inp):
        a_t, b_t, c_t = inp
        h = a_t * h + b_t
        y = jnp.einsum("bdn,bn->bd", h, c_t)
        return h, y

    h0 = jnp.zeros((B, D, N), jnp.float32)
    h_last, ys = jax.lax.scan(
        step, h0,
        (a.astype(jnp.float32).swapaxes(0, 1),
         b.astype(jnp.float32).swapaxes(0, 1),
         c.astype(jnp.float32).swapaxes(0, 1)))
    return ys.swapaxes(0, 1), h_last
