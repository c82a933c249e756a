"""Pallas TPU kernel: emulated-precision matmul with FPMax accumulation styles.

This is the perf-critical hot spot of the paper's technique on TPU: a matmul
whose numerics follow one of the FPMax FMAC units.  The hardware units round
per scalar FMA; a systolic MXU contracts a whole k-block per pass, so the
TPU-native mapping (DESIGN.md §2) is:

  * ``fused``        : f32 accumulator across k-blocks, single final round
                       (FMA unit with extended accumulator).
  * ``cascade``      : accumulator rounded to the target format after every
                       k-block — round-after-add, the CMA without forwarding.
  * ``cascade_fwd``  : multiplier output (the k-block partial product sums)
                       rounded to the format, accumulator kept un-rounded —
                       the CMA with internal forwarding before rounding.

Inputs are quantized to the target format on the fly inside VMEM (models the
operand registers of the unit).  ``ref.py`` implements the identical k-block
semantics in pure jnp; tests assert bitwise equality in interpret mode.

Tiling: (bm x bk) @ (bk x bn) per grid step, MXU-aligned (multiples of 128 on
the minor dims, f32 min tile (8,128)).  VMEM footprint per step:
3 * 128*128*4B + acc scratch = ~256 KiB << 16 MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import FloatFormat, quantize

STYLES = ("fused", "cascade", "cascade_fwd")


def _fma_emu_kernel(a_ref, b_ref, o_ref, acc_ref, *, fmt: FloatFormat,
                    style: str, nk: int, out_fmt: FloatFormat | None):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qa = quantize(a_ref[...], fmt)
    qb = quantize(b_ref[...], fmt)
    part = jnp.dot(qa, qb, preferred_element_type=jnp.float32)

    if style == "fused":
        acc_ref[...] = acc_ref[...] + part
    elif style == "cascade_fwd":
        acc_ref[...] = acc_ref[...] + quantize(part, fmt)
    elif style == "cascade":
        acc_ref[...] = quantize(acc_ref[...] + quantize(part, fmt), fmt)
    else:
        raise ValueError(f"style must be one of {STYLES}, got {style!r}")

    @pl.when(k == nk - 1)
    def _flush():
        acc = acc_ref[...]
        if out_fmt is not None:
            acc = quantize(acc, out_fmt)
        o_ref[...] = acc


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "style", "out_fmt", "bm", "bn", "bk", "interpret"),
)
def fma_emu_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    fmt: FloatFormat,
    style: str = "fused",
    out_fmt: FloatFormat | None = None,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """(M,K) @ (K,N) in emulated precision ``fmt`` with FPMax-style accumulation."""
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise ValueError(f"bad matmul shapes {a.shape} @ {b.shape}")
    m, kdim = a.shape
    _, n = b.shape

    # An output smaller than a tile is one full-extent block: no padding, so
    # each k-step is the same (m, bk) @ (bk, n) dot as kernels/ref.py's and
    # the two agree bitwise.  Otherwise pad to tile multiples; zero rows/cols
    # quantize to zero and are exact no-ops under every accumulation style.
    bm, bn = min(bm, m), min(bn, n)
    pm, pn, pk = (-m) % bm, (-n) % bn, (-kdim) % bk
    a_p = jnp.pad(a.astype(jnp.float32), ((0, pm), (0, pk)))
    b_p = jnp.pad(b.astype(jnp.float32), ((0, pk), (0, pn)))
    gm, gn, gk = (m + pm) // bm, (n + pn) // bn, (kdim + pk) // bk

    kernel = functools.partial(
        _fma_emu_kernel, fmt=fmt, style=style, nk=gk, out_fmt=out_fmt
    )
    out = pl.pallas_call(
        kernel,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + pm, n + pn), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(a_p, b_p)
    return out[:m, :n]
