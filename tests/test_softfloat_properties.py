"""Property-based tests (hypothesis) for the softfloat round-to-odd helpers.

An exact rational oracle (``fractions.Fraction``) independently re-derives
RNE-to-format rounding, so ``sf_fma`` (round-to-odd double-rounding
protection) and ``sf_cma`` (two explicit roundings) are checked bit-exactly
against first principles rather than against another float path.  Also:
commutativity of ``sf_add`` and idempotence of ``quantize64``.

This module is collect-ignored when hypothesis is not installed (see
tests/conftest.py); CI installs hypothesis and runs it.
"""
import fractions
import math

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax

from repro.core import softfloat as sf
from repro.core.formats import BF16, FloatFormat
from repro.numerics import REGISTRY

# Exhaustive property sweep over the whole format ladder: minutes of wall
# clock, so it rides in the slow lane (CI fast lane runs -m "not slow").
pytestmark = pytest.mark.slow

# The whole sub-f32 transprecision ladder of the registry (satellite: the
# fp8 tiers join the suite) — every format the tuner can downshift to is
# property-tested against the exact rational oracle.
FMTS = [REGISTRY.format(n) for n in ("bf16", "fp16", "tf32",
                                     "fp8_e4m3", "fp8_e5m2")]


# ---------------------------------------------------------------------------
# Exact rational RNE oracle (mirrors quantize64's semantics: exponent
# clamped to [emin, emax] — the clamp makes the grid flush to the subnormal
# quantum — IEEE overflow to inf past max_finite).
# ---------------------------------------------------------------------------
def _rne_int(q: fractions.Fraction) -> int:
    """Round a rational to the nearest integer, ties to even."""
    fl = q.numerator // q.denominator  # floor division, exact
    rem = q - fl
    if rem > fractions.Fraction(1, 2):
        return fl + 1
    if rem < fractions.Fraction(1, 2):
        return fl
    return fl if fl % 2 == 0 else fl + 1


def rne_reference(v: fractions.Fraction, fmt: FloatFormat) -> float:
    """Exact RNE of a rational onto fmt's grid, from first principles."""
    if v == 0:
        return 0.0
    av = abs(v)
    e = math.frexp(float(av))[1] - 1  # binade estimate, then make it exact
    while fractions.Fraction(2) ** e > av:
        e -= 1
    while fractions.Fraction(2) ** (e + 1) <= av:
        e += 1
    q_exp = min(max(e, fmt.emin), fmt.emax)
    scale = fractions.Fraction(2) ** (q_exp - fmt.man_bits)
    y = _rne_int(v / scale) * scale
    if abs(y) > fractions.Fraction(fmt.max_finite):
        return math.copysign(math.inf, float(v))
    return float(y)  # exact: small-integer multiple of a power of two


def on_grid(fmt: FloatFormat):
    """Strategy for exact normal-range fmt-grid values (sign x mantissa x
    exponent).  Exponents stay inside [emin, emax] so inputs honor the
    "inputs assumed on fmt's grid" contract; *results* of mul/fma still
    exercise the overflow and subnormal-clamp branches (e.g. two FP16
    values at e=15 multiply to e~30 -> inf)."""
    return st.one_of(
        st.just(0.0),
        st.builds(
            lambda s, m, e: s * (2 ** fmt.man_bits + m) * 2.0 ** (
                e - fmt.man_bits),
            st.sampled_from([-1.0, 1.0]),
            st.integers(0, 2 ** fmt.man_bits - 1),
            st.integers(max(fmt.emin, -18), min(fmt.emax, 18))))


def _f(x):
    return float(np.float32(x))


# ---------------------------------------------------------------------------
# sf_fma / sf_cma vs the rational reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", FMTS, ids=lambda f: f.name)
@settings(max_examples=250, deadline=None)
@given(data=st.data())
def test_fma_matches_exact_rational_reference(fmt, data):
    a = data.draw(on_grid(fmt))
    b = data.draw(on_grid(fmt))
    c = data.draw(on_grid(fmt))
    ref = rne_reference(
        fractions.Fraction(a) * fractions.Fraction(b) + fractions.Fraction(c),
        fmt)
    ours = float(sf.sf_fma(jnp.float32(a), jnp.float32(b), jnp.float32(c),
                           fmt))
    assert ours == _f(ref), (a, b, c, ours, ref)


@pytest.mark.parametrize("fmt", FMTS, ids=lambda f: f.name)
@settings(max_examples=250, deadline=None)
@given(data=st.data())
def test_cma_matches_two_rounding_reference(fmt, data):
    a = data.draw(on_grid(fmt))
    b = data.draw(on_grid(fmt))
    c = data.draw(on_grid(fmt))
    p = rne_reference(fractions.Fraction(a) * fractions.Fraction(b), fmt)
    if math.isinf(p):
        ref = p  # inf + finite addend stays inf
    else:
        ref = rne_reference(fractions.Fraction(p) + fractions.Fraction(c),
                            fmt)
    ours = float(sf.sf_cma(jnp.float32(a), jnp.float32(b), jnp.float32(c),
                           fmt))
    assert ours == _f(ref) or (math.isnan(ours) and math.isnan(ref)), \
        (a, b, c, ours, ref)


def test_fma_vs_cma_divergence_case():
    """Deterministic witness that the oracle distinguishes one rounding from
    two: the rounded product loses exactly the bits the sum needs.  With
    a = 1 + 2**-5, a*a - 1 = 2**-4 + 2**-10 is on the bf16 grid, but the
    bf16-rounded product 1 + 2**-4 has already dropped the 2**-10 term."""
    a = 1.0 + 2.0 ** -5
    fused = float(sf.sf_fma(jnp.float32(a), jnp.float32(a),
                            jnp.float32(-1.0), BF16))
    casc = float(sf.sf_cma(jnp.float32(a), jnp.float32(a),
                           jnp.float32(-1.0), BF16))
    exact = fractions.Fraction(a) ** 2 - 1
    assert fused == rne_reference(exact, BF16)
    assert fused != casc


# ---------------------------------------------------------------------------
# Algebraic properties
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", FMTS, ids=lambda f: f.name)
@settings(max_examples=250, deadline=None)
@given(data=st.data())
def test_add_commutative(fmt, data):
    a = data.draw(on_grid(fmt))
    b = data.draw(on_grid(fmt))
    ab = float(sf.sf_add(jnp.float32(a), jnp.float32(b), fmt))
    ba = float(sf.sf_add(jnp.float32(b), jnp.float32(a), fmt))
    assert ab == ba or (math.isnan(ab) and math.isnan(ba))


finite_f64 = st.floats(allow_nan=False, allow_infinity=False,
                       min_value=-1e30, max_value=1e30)


@pytest.mark.parametrize("fmt", FMTS, ids=lambda f: f.name)
@settings(max_examples=250, deadline=None)
@given(x=finite_f64)
def test_quantize64_idempotent(fmt, x):
    with jax.enable_x64(True):
        q1 = float(sf.quantize64(jnp.float64(x), fmt))
        q2 = float(sf.quantize64(jnp.float64(q1), fmt))
        assert q1 == q2  # finite input never rounds to NaN; inf == inf


@pytest.mark.parametrize("fmt", FMTS, ids=lambda f: f.name)
@settings(max_examples=250, deadline=None)
@given(data=st.data())
def test_quantize64_fixes_grid_points(fmt, data):
    """Every on-grid value is its own rounding (grid points are fixed
    points), tying the input strategy to quantize64's grid definition."""
    x = data.draw(on_grid(fmt))
    with jax.enable_x64(True):
        assert float(sf.quantize64(jnp.float64(x), fmt)) == x
