"""Bit-exact FMA/CMA semantics vs the exact-rational oracle."""
import math
from fractions import Fraction

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import assume, given, settings, strategies as st

from repro.core import softfloat as sf
from repro.core.formats import BF16, FP16, FP32, FP64, TF32
from repro.numerics.accuracy import rne_fraction

f64s = st.floats(allow_nan=False, allow_infinity=False,
                 min_value=-1e15, max_value=1e15)


def f32(x):
    return float(np.float32(x))


def exact_fma(a, b, c, fmt):
    """a*b + c computed exactly in rationals, rounded once onto ``fmt``."""
    return float(rne_fraction(Fraction(a) * Fraction(b) + Fraction(c), fmt))


@settings(max_examples=300, deadline=None)
@given(f64s, f64s, f64s)
def test_sp_fma_matches_math_fma(a, b, c):
    a, b, c = f32(a), f32(b), f32(c)
    ref = exact_fma(a, b, c, FP32)
    # XLA:CPU (and TPU) are DAZ/FTZ: subnormal f32 in/outputs act as zero
    assume(all(_normal_f32(v) for v in (a, b, c, ref)))
    ours = float(sf.sf_fma(jnp.float32(a), jnp.float32(b), jnp.float32(c),
                           FP32))
    assert ours == ref or (math.isnan(ours) and math.isnan(ref))


@settings(max_examples=300, deadline=None)
@given(f64s, f64s)
def test_sp_mul_add_exact(a, b):
    a, b = f32(a), f32(b)
    prod, ssum = f32(np.float32(a) * np.float32(b)), f32(np.float32(a) + np.float32(b))
    # XLA:CPU (and TPU) are DAZ/FTZ: subnormal f32 in/outputs act as zero
    assume(all(_normal_f32(v) for v in (a, b, prod, ssum)))
    assert float(sf.sf_mul(jnp.float32(a), jnp.float32(b), FP32)) == prod
    assert float(sf.sf_add(jnp.float32(a), jnp.float32(b), FP32)) == ssum


def _normal_f32(v):
    return v == 0 or abs(v) >= 2 ** -126


def _normal_range(*vals):
    # documented softfloat limitation: EFT emulation is exact except at
    # extreme over/underflow (subnormal intermediates)
    return all(v == 0 or 1e-290 < abs(v) < 1e290 for v in vals)


@settings(max_examples=200, deadline=None)
@given(f64s, f64s, f64s)
def test_dp_fma_matches_math_fma(a, b, c):
    assume(_normal_range(a * b, a * b + c))
    ours = float(sf.dp_fma(np.float64(a), np.float64(b), np.float64(c)))
    ref = exact_fma(a, b, c, FP64)
    assert ours == ref or (math.isnan(ours) and math.isnan(ref))


@settings(max_examples=100, deadline=None)
@given(f64s, f64s)
def test_dp_fma_cancellation(a, b):
    # c ~ -a*b: the catastrophic-cancellation case that breaks naive
    # double-rounding emulations
    c = -(a * b) * (1 + 2 ** -50)
    assume(_normal_range(a * b, a * b + c))
    ours = float(sf.dp_fma(np.float64(a), np.float64(b), np.float64(c)))
    ref = exact_fma(a, b, c, FP64)
    assert ours == ref or (math.isnan(ours) and math.isnan(ref))


def test_cma_vs_fma_rounding_counts():
    """CMA (two roundings) differs from FMA (one) exactly where the rounded
    product loses bits that matter to the sum."""
    a = jnp.float32(1.0 + 2.0 ** -7)  # product needs > 7 bits
    b = jnp.float32(1.0 + 2.0 ** -7)
    c = jnp.float32(-1.0)
    fused = float(sf.sf_fma(a, b, c, BF16))
    cascade = float(sf.sf_cma(a, b, c, BF16))
    exact = float(a) * float(b) + float(c)
    assert abs(fused - exact) <= abs(cascade - exact)


@pytest.mark.parametrize("fmt", [BF16, FP16, TF32])
def test_dot_error_ordering(fmt):
    """Forwarding (unrounded accumulator) <= fused <= cascade error, on
    average — the paper's motivation for internal forwarding [8]."""
    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 128)).astype(np.float32)
    b = rng.standard_normal((64, 128)).astype(np.float32)
    exact = np.sum(a.astype(np.float64) * b.astype(np.float64), -1)
    e_fwd = np.abs(np.asarray(sf.dot_cascade(a, b, fmt, forwarding=True),
                              np.float64) - exact).mean()
    e_fused = np.abs(np.asarray(sf.dot_fused(a, b, fmt), np.float64)
                     - exact).mean()
    e_casc = np.abs(np.asarray(sf.dot_cascade(a, b, fmt, forwarding=False),
                               np.float64) - exact).mean()
    # the paper's claim: internal forwarding (unrounded accumulator) is the
    # clear win; fused vs cascade are the same ballpark (both round the
    # accumulator every step)
    assert e_fwd < 0.5 * min(e_fused, e_casc)
    assert 0.5 < e_fused / e_casc < 2.0


def test_dot_dispatch():
    rng = np.random.default_rng(1)
    a = rng.standard_normal((8, 16)).astype(np.float32)
    b = rng.standard_normal((8, 16)).astype(np.float32)
    assert np.allclose(np.asarray(sf.dot(a, b, BF16, "fma")),
                       np.asarray(sf.dot_fused(a, b, BF16)))
    with pytest.raises(ValueError):
        sf.dot(a, b, BF16, "nope")


def test_two_sum_exact():
    rng = np.random.default_rng(2)
    with jax.enable_x64(True):
        a = jnp.asarray(rng.standard_normal(1000) * 1e10)
        b = jnp.asarray(rng.standard_normal(1000) * 1e-10)
        s, e = sf._two_sum(a, b)
        # s + e == a + b exactly: check via arbitrary-precision floats
        for i in range(0, 1000, 97):
            lhs = Fraction(float(s[i])) + Fraction(float(e[i]))
            rhs = Fraction(float(a[i])) + Fraction(float(b[i]))
            assert lhs == rhs
