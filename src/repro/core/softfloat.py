"""Bit-exact software FPU semantics: fused (FMA) vs cascade (CMA) multiply-add.

FPMax fabricates four FMAC units; their *numeric* difference is where rounding
happens:

  * FMA  (fused):    r = RNE_F( a*b + c )               -- one rounding
  * CMA  (cascade):  r = RNE_F( RNE_F(a*b) + c )        -- two roundings
  * CMA + internal forwarding [Trong'07]: the un-rounded result of a dependent
    op is forwarded into the next op, i.e. the accumulator is effectively held
    in extended precision and rounded once at the end of the dependence chain.

This module implements those semantics bit-exactly for arbitrary formats with
man_bits <= 23 (incl. IEEE SP, the paper's SP units) via f64 arithmetic plus
round-to-odd double-rounding protection, and for IEEE DP (the paper's DP
units) via error-free transformations (Dekker TwoProduct + Knuth TwoSum +
Boldo-Melquiond round-to-odd FMA emulation).

Exactness arguments (documented per DESIGN.md §2):
  * mul: a,b in F (man<=23) => product has <=48 significand bits, exact in
    f64; quantize64 rounds it exactly once.  Bit-exact.
  * add: double rounding through f64 (53 bits) then to F (<=24 bits) is
    innocuous because 53 >= 2*24 + 2 (Figueroa).  Bit-exact.
  * fma: the 48-bit product plus a 24-bit addend is NOT double-rounding safe
    through 53 bits, so we use TwoSum + round-to-odd before the final RNE
    (round-to-odd at 53 bits then RNE to <=24 bits is exact since 53 >= 26).
  * DP fused fma: Boldo-Melquiond emulation, exact barring extreme
    over/underflow; property-tested against an exact-rational FMA
    (``repro.numerics.accuracy.rne_fraction``).

All public functions run under a local x64 context so the framework itself
never flips global jax config.

Subnormal semantics: XLA:CPU — like the TPU target — runs DAZ/FTZ, so
f32-subnormal inputs/outputs act as zero.  Exactness claims therefore hold
for normal-range f32 values (property-tested in tests/test_softfloat.py).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.formats import FP32, FloatFormat


def _with_x64(fn: Callable) -> Callable:
    """Run ``fn`` (and its tracing) under ``jax.enable_x64(True)``."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with jax.enable_x64(True):
            return fn(*args, **kwargs)

    return wrapper


# ---------------------------------------------------------------------------
# f64 quantizer (host-side oracle; exact RNE for man_bits <= 51)
# ---------------------------------------------------------------------------
def _pow2_f64(e: jnp.ndarray) -> jnp.ndarray:
    """Exact 2**e for integer e in (-1022, 1024), via exponent bits.

    (jnp.exp2 lowers through exp/log on CPU and can be 1 ulp off — enough
    to break round-to-nearest ties.)"""
    bits = ((e.astype(jnp.int64) + 1023) << 52).astype(jnp.uint64)
    return lax.bitcast_convert_type(bits, jnp.float64)


def quantize64(x: jnp.ndarray, fmt: FloatFormat) -> jnp.ndarray:
    """RNE-round f64 values onto fmt's grid (result f64). Must run under x64."""
    x = x.astype(jnp.float64)
    bits = lax.bitcast_convert_type(x, jnp.uint64)
    e = (jnp.right_shift(bits, jnp.uint64(52)) & jnp.uint64(0x7FF)).astype(
        jnp.int32
    ) - 1023
    q_exp = jnp.clip(e, fmt.emin, fmt.emax)
    scale = _pow2_f64(q_exp - fmt.man_bits)
    q = jnp.round(x / scale)  # RNE; division by pow2 exact in f64 here
    y = q * scale
    max_f = jnp.float64(fmt.max_finite)
    y = jnp.where(jnp.abs(y) > max_f, jnp.sign(y) * jnp.float64(jnp.inf), y)
    y = jnp.where(jnp.isfinite(x), y, x)
    y = jnp.where(x == 0, x, y)
    return y


# ---------------------------------------------------------------------------
# Error-free transformations (f64)
# ---------------------------------------------------------------------------
def _two_sum(a, b):
    """Knuth TwoSum: s + e == a + b exactly (no branches)."""
    s = a + b
    bp = s - a
    ap = s - bp
    e = (a - ap) + (b - bp)
    return s, e


# 2**27 + 1, Dekker split constant for f64.  A *Python* float, not a jnp
# array: this line runs at import time, outside any enable_x64 scope, where
# jnp.float64(...) silently truncates to f32 — and 2**27 + 1 needs 28
# significand bits, so the truncated constant would be 2**27 and every
# Dekker split (hence dp_fma's error term) would be wrong.  A weakly-typed
# Python scalar promotes to the f64 of its operand inside the x64-scoped
# kernels with the value preserved exactly.
_SPLIT = 134217729.0


def _split(a):
    c = _SPLIT * a
    hi = c - (c - a)
    lo = a - hi
    return hi, lo


def _two_product(a, b):
    """Dekker TwoProduct: p + e == a * b exactly (assuming no overflow)."""
    p = a * b
    ah, al = _split(a)
    bh, bl = _split(b)
    e = ((ah * bh - p) + ah * bl + al * bh) + al * bl
    return p, e


def _round_to_odd(s, e):
    """Given s = RNE(x), e = x - s exact: return RTO(x) (round-to-odd)."""
    bits = lax.bitcast_convert_type(s, jnp.uint64)
    lsb_even = (bits & jnp.uint64(1)) == 0
    inexact = e != 0
    toward = jnp.where(e > 0, jnp.float64(jnp.inf), jnp.float64(-jnp.inf))
    nudged = jnp.nextafter(s, toward)
    return jnp.where(inexact & lsb_even, nudged, s)


# ---------------------------------------------------------------------------
# Sub-f32 formats (man_bits <= 23): exact scalar/elementwise ops
# ---------------------------------------------------------------------------
@_with_x64
def sf_mul(a, b, fmt: FloatFormat):
    """Exact RNE multiply in fmt (inputs assumed on fmt's grid)."""
    p = a.astype(jnp.float64) * b.astype(jnp.float64)  # exact (<=48 bits)
    return quantize64(p, fmt).astype(jnp.float32)


@_with_x64
def sf_add(a, b, fmt: FloatFormat):
    """Exact RNE add in fmt (double rounding through f64 is innocuous)."""
    s = a.astype(jnp.float64) + b.astype(jnp.float64)
    return quantize64(s, fmt).astype(jnp.float32)


@_with_x64
def sf_fma(a, b, c, fmt: FloatFormat):
    """Exact fused multiply-add in fmt: RNE_F(a*b + c), single rounding."""
    a64 = a.astype(jnp.float64)
    b64 = b.astype(jnp.float64)
    c64 = c.astype(jnp.float64)
    p = a64 * b64  # exact: <= 48 significand bits
    s, e = _two_sum(p, c64)
    s_odd = _round_to_odd(s, e)  # 53-bit round-to-odd of the exact sum
    return quantize64(s_odd, fmt).astype(jnp.float32)


@_with_x64
def sf_cma(a, b, c, fmt: FloatFormat):
    """Cascade multiply-add: round the product, then round the sum."""
    p = quantize64(a.astype(jnp.float64) * b.astype(jnp.float64), fmt)
    s = p + c.astype(jnp.float64)
    return quantize64(s, fmt).astype(jnp.float32)


# ---------------------------------------------------------------------------
# IEEE DP (binary64) ops — the paper's DP CMA / DP FMA units
# ---------------------------------------------------------------------------
@_with_x64
def dp_mul(a, b):
    return (a.astype(jnp.float64) * b.astype(jnp.float64))


@_with_x64
def dp_add(a, b):
    return (a.astype(jnp.float64) + b.astype(jnp.float64))


@_with_x64
def dp_cma(a, b, c):
    """DP cascade: hardware f64 mul and add ARE the two RNE roundings."""
    return a.astype(jnp.float64) * b.astype(jnp.float64) + c.astype(jnp.float64)


@_with_x64
def dp_fma(a, b, c):
    """Correctly-rounded DP fused multiply-add (Boldo-Melquiond emulation)."""
    a = a.astype(jnp.float64)
    b = b.astype(jnp.float64)
    c = c.astype(jnp.float64)
    ph, pl = _two_product(a, b)  # ph + pl == a*b exactly
    sh, se = _two_sum(ph, c)  # sh + se == ph + c exactly
    # exact low-order sum, rounded to odd to protect the final RNE
    t, te = _two_sum(pl, se)
    t_odd = _round_to_odd(t, te)
    return sh + t_odd


# ---------------------------------------------------------------------------
# Dot-product / accumulation semantics (the framework-facing policies)
# ---------------------------------------------------------------------------
@_with_x64
def dot_fused(a_vec, b_vec, fmt: FloatFormat):
    """Sequential fused accumulation: acc = RNE_F(acc + a_k*b_k) per step.

    This is what a single FMA unit computes for a dot product.
    Shapes: a_vec, b_vec: (..., K) -> (...,).
    """
    a64 = a_vec.astype(jnp.float64)
    b64 = b_vec.astype(jnp.float64)

    def step(acc, ab):
        a_k, b_k = ab
        p = a_k * b_k
        s, e = _two_sum(p, acc)
        acc = quantize64(_round_to_odd(s, e), fmt)
        return acc, None

    k = a_vec.shape[-1]
    init = jnp.zeros(a_vec.shape[:-1], jnp.float64)
    a_t = jnp.moveaxis(a64, -1, 0)
    b_t = jnp.moveaxis(b64, -1, 0)
    acc, _ = lax.scan(step, init, (a_t, b_t), length=k)
    return acc.astype(jnp.float32)


@_with_x64
def dot_cascade(a_vec, b_vec, fmt: FloatFormat, forwarding: bool = False):
    """Sequential cascade accumulation (CMA unit).

    forwarding=False: p = RNE_F(a*b); acc = RNE_F(acc + p)   (2 roundings/step)
    forwarding=True : the un-rounded result is forwarded — the accumulator is
      held in extended precision (f64 here, as the hardware holds the pre-round
      intermediate) and rounded to F once at the end of the chain.
    """
    a64 = a_vec.astype(jnp.float64)
    b64 = b_vec.astype(jnp.float64)

    if forwarding:

        def step(acc, ab):
            a_k, b_k = ab
            p = quantize64(a_k * b_k, fmt)  # multiplier array still rounds
            return acc + p, None

    else:

        def step(acc, ab):
            a_k, b_k = ab
            p = quantize64(a_k * b_k, fmt)
            return quantize64(acc + p, fmt), None

    k = a_vec.shape[-1]
    init = jnp.zeros(a_vec.shape[:-1], jnp.float64)
    a_t = jnp.moveaxis(a64, -1, 0)
    b_t = jnp.moveaxis(b64, -1, 0)
    acc, _ = lax.scan(step, init, (a_t, b_t), length=k)
    out = quantize64(acc, fmt) if forwarding else acc
    return out.astype(jnp.float32)


def dot(a_vec, b_vec, fmt: FloatFormat = FP32, style: str = "fma",
        forwarding: bool = False):
    """Dispatch on FMAC style — the four FPMax units as dot-product semantics."""
    if style == "fma":
        return dot_fused(a_vec, b_vec, fmt)
    if style == "cma":
        return dot_cascade(a_vec, b_vec, fmt, forwarding=forwarding)
    raise ValueError(f"unknown FMAC style {style!r}")
