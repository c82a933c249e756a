"""Workload-aware autotuner: known mixes must reproduce the paper's Table I
design split, the compile cache must make warm same-shape tuning dispatch
without recompiling, and the shared objective/constraint API must stay
consistent with the legacy ad-hoc selectors."""
import numpy as np
import pytest

from repro.core import autotune as at
from repro.core import objective as obj
from repro.core.body_bias import energy_per_flop, energy_per_op, leak_bb_scale
from repro.core.dse import (enumerate_structures, enumerate_structures_full,
                            sweep_arrays)
from repro.core.energy_model import SweepExecutableCache, calibrate, predict
from repro.core.fpu_arch import FABRICATED
from repro.core.trace import OpProfile

# Small electrical grids keep unit-test sweeps fast; the library defaults are
# the full TUNE_* grids.
VDD = np.round(np.arange(0.55, 1.101, 0.05), 3)
VBB = np.round(np.arange(0.0, 1.21, 0.3), 2)


@pytest.fixture(scope="module")
def params():
    return calibrate()


@pytest.fixture(scope="module")
def cache():
    return SweepExecutableCache()


# --------------------------------------------------------- design selection
@pytest.mark.parametrize("precision", ["sp", "dp"])
def test_known_mixes_select_paper_table1_designs(params, cache, precision):
    """Tuning over the four fabricated units (silicon-anchored): a GEMM-like
    100%-activity mix must pick the FMA throughput unit, a dependent-chain
    mix the CMA latency unit — the paper's Table I split."""
    units = [d for d in FABRICATED.values() if d.precision == precision]
    gemm = at.autotune(at.GEMM_STREAM, precision, designs=units,
                       params=params, vdd_grid=VDD, vbb_grid=VBB,
                       anchored=True, cache=cache)
    chain = at.autotune(at.DEPENDENT_CHAIN, precision, designs=units,
                        params=params, vdd_grid=VDD, vbb_grid=VBB,
                        anchored=True, cache=cache)
    assert gemm.design.name == f"{precision}_fma"
    assert chain.design.name == f"{precision}_cma"


def test_full_grid_split_selects_distinct_designs(params, cache):
    """Acceptance criterion: on the expanded enumeration the throughput-heavy
    and latency-critical mixes land on different optimal designs, with the
    latency optimum having the shorter accumulation wait."""
    tp, lat = at.tune_split("sp", params=params, vdd_grid=VDD, vbb_grid=VBB,
                            cache=cache)
    assert tp.design.name != lat.design.name
    assert lat.design.accum_latency_cycles <= tp.design.accum_latency_cycles
    assert lat.metrics["avg_latency_penalty"] <= \
        tp.metrics["avg_latency_penalty"] + 1e-12


def test_constraint_filters_operating_points(params, cache):
    cons = (obj.Constraint("freq_ghz", lo=1.0),)
    r = at.autotune(at.GEMM_STREAM, "sp", params=params, vdd_grid=VDD,
                    vbb_grid=VBB, cache=cache, constraints=cons)
    assert r.metrics["freq_ghz"] >= 1.0
    free = at.autotune(at.GEMM_STREAM, "sp", params=params, vdd_grid=VDD,
                       vbb_grid=VBB, cache=cache)
    # optimality guarantee: the unconstrained optimum scores no worse on the
    # profile's own objective (e_eff * area), not on any single factor
    def score(t):
        return t.metrics["e_eff_pj"] * t.metrics["area_mm2"]
    assert score(free) <= score(r) * (1 + 1e-12)
    with pytest.raises(ValueError):
        at.autotune(at.GEMM_STREAM, "sp", params=params, vdd_grid=VDD,
                    vbb_grid=VBB, cache=cache,
                    constraints=(obj.Constraint("freq_ghz", lo=1e9),))


def test_adaptive_bb_low_activity_savings(params, cache):
    """Paper Fig. 4: at 10% activity and an iso-performance-constrained
    operating point, adaptive body bias recovers ~2x energy/op vs holding
    the active bias (the 3x -> 1.5x claim)."""
    cons = (obj.Constraint("freq_ghz", lo=1.0),)
    r = at.autotune(at.GEMM_LOW_ACTIVITY, "sp", params=params,
                    vdd_grid=VDD, vbb_grid=VBB, cache=cache,
                    constraints=cons)
    saving = at.static_bb_energy(r) / r.metrics["e_eff_pj"]
    assert 1.5 <= saving <= 4.0, saving


# ------------------------------------------------------------ compile cache
def test_compile_cache_hit_on_same_shape_retune(params):
    fresh = SweepExecutableCache()
    r1 = at.autotune(at.GEMM_STREAM, "sp", params=params, vdd_grid=VDD,
                     vbb_grid=VBB, cache=fresh)
    assert fresh.stats == dict(hits=0, misses=1, executables=1)
    r2 = at.autotune(at.GEMM_STREAM, "sp", params=params, vdd_grid=VDD,
                     vbb_grid=VBB, cache=fresh)
    # second same-shape tune dispatches the cached executable — no recompile
    assert fresh.stats == dict(hits=1, misses=1, executables=1)
    assert r2.key == r1.key
    assert r2.metrics == r1.metrics


def test_compile_cache_shared_across_design_spaces(params):
    """The SP and DP enumerations have identical grid shapes (288
    structures each), so the second precision reuses the first one's
    executable."""
    fresh = SweepExecutableCache()
    at.autotune(at.GEMM_STREAM, "sp", params=params, vdd_grid=VDD,
                vbb_grid=VBB, cache=fresh)
    at.autotune(at.GEMM_STREAM, "dp", params=params, vdd_grid=VDD,
                vbb_grid=VBB, cache=fresh)
    assert fresh.stats == dict(hits=1, misses=1, executables=1)


def test_cached_sweep_matches_uncached(params):
    fresh = SweepExecutableCache()
    designs = enumerate_structures("sp")[:7]
    a = sweep_arrays(designs, params, VDD, VBB, cache=fresh)
    b = sweep_arrays(designs, params, VDD, VBB)
    assert len(a) == len(b)
    for k in b.metrics:
        np.testing.assert_allclose(a.metrics[k], b.metrics[k], rtol=1e-12,
                                   atol=0)


# ------------------------------------------------- enumeration and profiles
def test_enumerate_structures_full_is_superset():
    for precision in ("sp", "dp"):
        full = enumerate_structures_full(precision)
        names = [d.name for d in full]
        assert len(names) == len(set(names)) == 288
        assert {d.name for d in enumerate_structures(precision)} <= set(names)
        assert any(not d.forwarding for d in full)


def test_profile_from_trace_interleave_shifts_objective():
    profs = [OpProfile("chain", 512, 1e9), OpProfile("independent", 1, 1e8)]
    seq = at.profile_from_trace("seq", profs, interleave=1)
    par = at.profile_from_trace("par", profs, interleave=16)
    assert seq.w_delay > par.w_delay
    assert seq.q_acc == 0.0 and par.q_acc == 1.0 - 1.0 / 16
    assert abs((seq.w_area + seq.w_delay) - 1.0) < 1e-12


def test_profile_from_config_shapes_split():
    train = at.profile_from_config("tinyllama-1.1b", "train_4k")
    decode = at.profile_from_config("tinyllama-1.1b", "decode_32k")
    assert train.w_area > train.w_delay  # GEMM-dominated, throughput-shaped
    assert decode.w_delay > decode.w_area  # dependent, latency-leaning
    assert decode.activity < train.activity
    with pytest.raises(KeyError):
        at.profile_from_config("no-such-arch")


# -------------------------------------------------- shared objective pieces
def test_leak_bb_scale_matches_model_ratio(params):
    d = FABRICATED["sp_cma"]
    act = predict(d, params, vdd=0.8, vbb=1.2)["p_leak_mw"]
    idle = predict(d, params, vdd=0.8, vbb=0.0)["p_leak_mw"]
    np.testing.assert_allclose(idle / act, leak_bb_scale(params, 1.2, 0.0),
                               rtol=1e-9)


def test_energy_per_flop_consistent_with_energy_per_op(params):
    d = FABRICATED["dp_cma"]
    for util, vbb_idle in ((1.0, None), (0.1, None), (0.1, 0.0)):
        ref = energy_per_op(d, params, vdd=0.7, vbb_active=1.2,
                            vbb_idle=vbb_idle, util=util)
        p = predict(d, params, vdd=0.7, vbb=1.2)
        idle = None if vbb_idle is None else \
            predict(d, params, vdd=0.7, vbb=vbb_idle)["p_leak_mw"]
        got = energy_per_flop(p["e_op_pj"], p["p_leak_mw"], p["freq_ghz"],
                              util, p_leak_idle_mw=idle)
        np.testing.assert_allclose(float(got), ref["e_total_pj"], rtol=1e-12)


def test_objective_argbest_matches_legacy_expressions(params):
    res = sweep_arrays(enumerate_structures("sp")[:12], params, VDD, VBB,
                       mix=at.GEMM_STREAM.mix(), with_latency=True)
    gw = res.metrics["gflops_per_w"]
    gm = res.metrics["gflops_per_mm2"]
    assert res.argbest_throughput() == int(np.argmax(gw * gm ** 1.0))
    assert res.argbest_throughput(0.5) == int(np.argmax(gw * gm ** 0.5))
    edp = res.metrics["e_per_flop_pj"] * res.metrics["avg_delay_ns"]
    assert res.argbest_latency() == int(np.argmin(edp))
    assert res.argbest(obj.THROUGHPUT) == res.argbest_throughput()


def test_workload_objective_terms():
    o = obj.workload_objective("w", 0.5, 0.0)
    assert ("area_mm2", 0.5) in o.terms
    assert all(k != "avg_delay_ns" for k, _ in o.terms)
    with pytest.raises(ValueError):
        obj.Objective("bad", (("x", 1.0),), sense="sideways")
