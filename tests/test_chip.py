"""Chip-level API: tune_chip's degenerate 2-unit case must reproduce the
Table I throughput/latency split the autotuner picks, the precision_policy
shim must return designs identical to the pre-refactor selectors (golden),
recalibration must be respected (the old select_fpu lru_cache footgun), and
routing/budgets/telemetry must behave."""
import json

import numpy as np
import pytest

from repro.core import autotune as at
from repro.core import chip
from repro.core import dse
from repro.core import objective as obj
from repro.core.energy_model import SweepExecutableCache, TechParams, calibrate
from repro.core.formats import BF16
from repro.core.fpu_arch import FABRICATED

# Small electrical grids keep unit-test sweeps fast (same grids as
# tests/test_autotune.py); the library defaults are the full TUNE_* grids.
VDD = np.round(np.arange(0.55, 1.101, 0.05), 3)
VBB = np.round(np.arange(0.0, 1.21, 0.3), 2)


@pytest.fixture(scope="module")
def params():
    return calibrate()


@pytest.fixture(scope="module")
def cache():
    return SweepExecutableCache()


@pytest.fixture(scope="module")
def two_phase():
    return [chip.PhaseSpec("train", at.GEMM_STREAM, flops_fraction=0.7),
            chip.PhaseSpec("decode", at.DEPENDENT_CHAIN, flops_fraction=0.3)]


# -------------------------------------------------------------- golden split
def test_tune_chip_two_unit_degenerate_equals_autotune_split(
        params, cache, two_phase):
    """Acceptance criterion: a 2-unit SP chip under an open budget picks
    exactly the units ``autotune`` picks per workload — tune_chip is the
    chip-level generalization, not a different optimizer."""
    r = chip.tune_chip(two_phase, params=params, vdd_grid=VDD, vbb_grid=VBB,
                       cache=cache)
    tp, lat = at.tune_split("sp", params=params, vdd_grid=VDD, vbb_grid=VBB,
                            cache=cache)
    u_tp, u_lat = r.spec.units
    assert (u_tp.design.name, u_tp.vdd, u_tp.vbb) == \
        (tp.design.name, tp.vdd, tp.vbb)
    assert (u_lat.design.name, u_lat.vdd, u_lat.vbb) == \
        (lat.design.name, lat.vdd, lat.vbb)
    assert r.report["distinct_designs"] == 2
    # the report row of each unit carries the autotuner's metric row
    assert r.report["units"][0]["e_eff_pj"] == \
        pytest.approx(tp.metrics["e_eff_pj"])


def test_select_fpu_shim_matches_pre_refactor_designs(params):
    """Golden: the deprecated shim resolves through the default chip to the
    *identical* designs the pre-refactor ``select_fpu`` computed directly
    from ``dse.best_throughput_design`` / ``dse.best_latency_design``."""
    from repro.core import precision_policy as pp
    for precision in ("sp", "dp"):
        with pytest.warns(DeprecationWarning):
            got_tp = pp.select_fpu("throughput", precision, params)
        with pytest.warns(DeprecationWarning):
            got_lat = pp.select_fpu("latency", precision, params)
        assert got_tp == dse.best_throughput_design(precision, params).design
        assert got_lat == dse.best_latency_design(precision, params).design
    with pytest.warns(DeprecationWarning), pytest.raises(ValueError):
        pp.select_fpu("sideways", "sp", params)


def test_policy_for_shape_shim_matches_pre_refactor(params):
    from repro.core import precision_policy as pp
    with pytest.warns(DeprecationWarning):
        train = pp.policy_for_shape("train_4k")
    with pytest.warns(DeprecationWarning):
        decode = pp.policy_for_shape("decode_32k")
    assert train.fpu_design == dse.best_throughput_design("sp",
                                                          params).design
    assert decode.fpu_design == dse.best_latency_design("sp", params).design
    assert train.fmt is BF16
    # accumulation style mapping is unchanged
    assert train.accum_style == chip.kernel_style_for(train.fpu_design)
    assert decode.accum_style == chip.kernel_style_for(decode.fpu_design)


def test_step_energy_telemetry_shim_bit_identical(params):
    """The shim keeps the pre-refactor telemetry arithmetic: nominal V_DD,
    full forward bias active, 0.45V idle bias under adaptive BB."""
    from repro.core.body_bias import energy_per_op
    from repro.core import precision_policy as pp
    d = FABRICATED["sp_fma"]
    kw = dict(achieved_flops=1e12, step_time_s=0.5, peak_flops=4e12)
    with pytest.warns(DeprecationWarning):
        tele = pp.step_energy_telemetry(d, params=params, **kw)
    util = 1e12 / 0.5 / 4e12
    e = energy_per_op(d, params, vdd=d.vdd, vbb_active=1.2, vbb_idle=0.45,
                      util=util)
    assert tele["utilization"] == pytest.approx(util)
    assert tele["pj_per_flop"] == pytest.approx(e["e_total_pj"])
    assert tele["policy"] == "adaptive_bb"


# ------------------------------------------------- recalibration (the footgun)
def test_recalibration_respected_by_shim(params, monkeypatch):
    """Regression for the old ``select_fpu`` lru_cache on an
    Optional[TechParams] default: with ``params=None`` the *current*
    calibration must win — a changed calibrate() result may not be shadowed
    by whatever calibration ran first."""
    from repro.core import precision_policy as pp
    chip.clear_policy_cache()
    with pytest.warns(DeprecationWarning):
        first = pp.select_fpu("throughput", "sp")
    assert first == dse.best_throughput_design("sp", params).design

    # recalibrate: a slower, leakier process corner — the optimum moves
    vals = dict(zip(
        ("tau_fo4_ns", "alpha", "vt0", "k_bb", "s_leak_dec", "s_cap",
         "s_leak", "s_area", "c_mul", "c_dp_fma", "c_dp_cma", "c_regs",
         "c_speed_cma", "c_speed_fma"), params.values))
    vals["s_leak"] *= 40.0
    vals["c_speed_cma"] *= 2.5
    recal = TechParams(tuple(vals.values()))
    monkeypatch.setattr(chip, "calibrate", lambda *a, **k: recal)
    with pytest.warns(DeprecationWarning):
        second = pp.select_fpu("throughput", "sp")
    # the shim must track the NEW calibration, not the pinned first one
    assert second == dse.best_throughput_design("sp", recal).design
    # and explicit params still resolve exactly
    with pytest.warns(DeprecationWarning):
        explicit = pp.select_fpu("throughput", "sp", params)
    assert explicit == first


def test_default_policy_cache_reuses_resolved_params(params):
    chip.clear_policy_cache()
    a = chip.default_policy("sp", params)
    b = chip.default_policy("sp", params)
    assert a is b
    c = chip.default_policy("dp", params)
    assert c is not a


# ----------------------------------------------------------------- routing
def test_routing_phases_and_classes(params):
    pol = chip.ChipPolicy(chip.fabricated_chip(params=params), params)
    # exact phase tags
    assert pol.unit_for_phase("train", precision="sp").name == "sp_fma"
    assert pol.unit_for_phase("decode", precision="dp").name == "dp_cma"
    # shape names / kinds route through the workload class
    assert pol.unit_for_phase("decode_32k", precision="sp").name == "sp_cma"
    assert pol.unit_for_phase("long_500k", precision="sp").name == "sp_cma"
    assert pol.unit_for_phase("prefill_32k", precision="sp").name == "sp_fma"
    # workload-class aliases (the legacy select_fpu vocabulary)
    assert pol.select_fpu("throughput", "sp").name == "sp_fma"
    assert pol.select_fpu("latency", "dp").name == "dp_cma"
    with pytest.raises(ValueError):
        pol.select_fpu("sideways")
    with pytest.raises(KeyError):
        pol.spec.unit("no_such_unit")


def test_objective_tie_break_routing(params):
    """Two units of the same class: the class objective (PR 2 API) picks."""
    fab = chip.fabricated_chip(params=params)
    sp_fma, dp_fma = fab.unit("sp_fma"), fab.unit("dp_fma")
    spec = chip.ChipSpec("both_fma", (sp_fma, dp_fma))
    pol = chip.ChipPolicy(spec, params)
    unit = pol.unit_for_phase("train")
    rows = {k: np.asarray([u.metric(k) for u in (sp_fma, dp_fma)])
            for k in ("gflops_per_w", "gflops_per_mm2")}
    want = (sp_fma, dp_fma)[obj.argbest(rows, obj.THROUGHPUT)]
    assert unit.name == want.name


def test_numerics_policy_emulate_routes_model_matmul(params):
    import jax.numpy as jnp
    from repro.models.numerics import chip_matmul, matmul
    pol = chip.ChipPolicy(chip.fabricated_chip("sp", params), params)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    out = chip_matmul(x, w, pol, "decode")
    # bf16-emulated under the decode unit's cascade semantics: close to but
    # not bitwise the native result
    native = matmul(x, w)
    assert np.allclose(np.asarray(out), np.asarray(native), atol=0.35)
    assert not np.array_equal(np.asarray(out), np.asarray(native))
    # inert policies pass through
    inert = pol.numerics_for_phase("decode")
    assert not inert.emulate
    np.testing.assert_array_equal(np.asarray(matmul(x, w, inert)),
                                  np.asarray(native))


def test_kernel_matmul_for_policy_matches_style(params):
    import jax.numpy as jnp
    from repro.kernels.ops import emulated_matmul, matmul_for_policy
    pol = chip.ChipPolicy(chip.fabricated_chip("sp", params), params)
    np_pol = pol.numerics_for_phase("decode", fmt=BF16)
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    got = matmul_for_policy(a, b, np_pol)
    want = emulated_matmul(a, b, fmt=BF16, style=np_pol.accum_style)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ----------------------------------------------------------- budgets / fleet
def test_budgets_size_the_fleet_and_validate(params, cache, two_phase):
    r = chip.tune_chip(two_phase, params=params, vdd_grid=VDD, vbb_grid=VBB,
                       cache=cache, area_budget_mm2=0.5,
                       tdp_budget_mw=2000.0, name="budgeted")
    assert r.spec.area_mm2 <= 0.5 + 1e-12
    assert r.spec.peak_power_mw <= 2000.0 + 1e-12
    counts = [u.count for u in r.spec.units]
    assert all(c >= 1 for c in counts) and sum(counts) > 2
    # the throughput phase carries 70% of the FLOPs -> more instances
    assert r.spec.units[0].count > r.spec.units[1].count
    assert r.spec.gflops_per_w > 0
    # report is json-serializable
    json.dumps(r.report)


def test_per_unit_budget_constraint_filters_designs(params, cache):
    """A per-unit budget cap (folded in as an objective.Constraint) must
    exclude operating points a single instance can't afford.  Power spans
    orders of magnitude over the V_DD grid, so a sub-winner TDP stays
    feasible while excluding the unconstrained optimum."""
    free = chip.tune_chip([chip.PhaseSpec("decode", at.DEPENDENT_CHAIN)],
                          params=params, vdd_grid=VDD, vbb_grid=VBB,
                          cache=cache)
    u_free = free.spec.units[0]
    cap = u_free.metric("p_total_mw") * 0.8
    r = chip.tune_chip([chip.PhaseSpec("decode", at.DEPENDENT_CHAIN)],
                       params=params, vdd_grid=VDD, vbb_grid=VBB,
                       cache=cache, tdp_budget_mw=cap)
    u = r.spec.units[0]
    assert u.metric("p_total_mw") <= cap
    assert (u.design.name, u.vdd, u.vbb) != \
        (u_free.design.name, u_free.vdd, u_free.vbb)


def test_infeasible_chip_raises(params):
    fab = chip.fabricated_chip("sp", params)
    with pytest.raises(ValueError, match="infeasible"):
        chip.ChipSpec("tiny", fab.units, area_budget_mm2=1e-6)
    with pytest.raises(ValueError, match="infeasible"):
        chip.ChipSpec("cold", fab.units, tdp_budget_mw=1e-3)
    with pytest.raises(ValueError):
        chip.ChipSpec("empty", ())


def test_adaptive_bb_saving_on_idle_heavy_unit(params, cache):
    """Fig. 4 behavior per unit: the 10%-activity unit of a mixed chip
    recovers ~2x energy/op from adaptive body bias at an iso-frequency
    point (the 3x -> 1.5x claim); the 100%-activity unit has nothing to
    recover."""
    cons = (obj.Constraint("freq_ghz", lo=1.0),)
    phases = [
        chip.PhaseSpec("train", at.GEMM_STREAM, flops_fraction=0.9,
                       constraints=cons),
        chip.PhaseSpec("decode", at.GEMM_LOW_ACTIVITY, flops_fraction=0.1,
                       constraints=cons),
    ]
    r = chip.tune_chip(phases, params=params, vdd_grid=VDD, vbb_grid=VBB,
                       cache=cache, name="fig4")
    busy, idle = r.report["units"]
    assert 1.5 <= idle["adaptive_bb_saving"] <= 4.0, idle
    assert busy["adaptive_bb_saving"] == pytest.approx(1.0)


# ------------------------------------------------------ config-derived chips
def test_phases_from_config_weights_and_precision(params):
    phases = chip.phases_from_config("tinyllama-1.1b",
                                     shapes=("train_4k", "decode_32k"),
                                     results_dir=None)
    assert [p.name for p in phases] == ["train_4k", "decode_32k"]
    assert sum(p.flops_fraction for p in phases) == pytest.approx(1.0)
    # training FLOPs dominate the config-derived workload
    assert phases[0].flops_fraction > phases[1].flops_fraction
    assert all(p.precision == "sp" for p in phases)


def test_profile_from_config_uses_measured_utilization(tmp_path):
    """Satellite: measured roofline utilizations replace the hand-set
    activity constants when dry-run artifacts exist."""
    rows = {
        "tinyllama-1.1b|train_4k": {"status": "ok",
                                    "roofline_fraction": 0.42},
        "tinyllama-1.1b|decode_32k": {"status": "ok",
                                      "roofline_fraction": 0.06},
        "tinyllama-1.1b|prefill_32k": {"status": "FAIL: boom"},
    }
    (tmp_path / "dryrun_pod16x16.json").write_text(json.dumps(rows))
    # a second mesh with a better train number: the max wins
    rows2 = {"tinyllama-1.1b|train_4k": {"status": "ok",
                                         "roofline_fraction": 0.55}}
    (tmp_path / "dryrun_pod2x16x16.json").write_text(json.dumps(rows2))
    d = str(tmp_path)
    assert at.profile_from_config("tinyllama-1.1b", "train_4k",
                                  results_dir=d).activity == 0.55
    assert at.profile_from_config("tinyllama-1.1b", "decode_32k",
                                  results_dir=d).activity == 0.06
    # failed cell -> heuristic constant
    assert at.profile_from_config("tinyllama-1.1b", "prefill_32k",
                                  results_dir=d).activity == 0.8
    # explicit activity always wins
    assert at.profile_from_config("tinyllama-1.1b", "train_4k",
                                  activity=0.3,
                                  results_dir=d).activity == 0.3


def test_cell_spec_tags_routed_unit(params):
    """launch.specs routes every dry-run cell to its chip unit."""
    from repro.configs.base import SHAPES, get_config
    from repro.launch.specs import _routed_unit
    pol = chip.ChipPolicy(chip.fabricated_chip(params=params), params)
    cfg = get_config("tinyllama-1.1b")
    assert _routed_unit(pol, cfg, SHAPES["train_4k"]) == "sp_fma"
    assert _routed_unit(pol, cfg, SHAPES["decode_32k"]) == "sp_cma"
    assert _routed_unit(None, cfg, SHAPES["train_4k"]) == ""


# ------------------------------------------------------------ health model
def test_health_change_invalidates_route_cache(params):
    """A stale route-cache entry would keep sending traffic to a dead
    unit: any health transition must flush the bounded cache and bump
    health_version, and routing must then avoid the unit."""
    pol = chip.ChipPolicy(chip.fabricated_chip(params=params), params)
    assert pol.unit_for_phase("decode", precision="sp").name == "sp_cma"
    assert pol._route  # cached
    v0 = pol.health_version
    pol.set_health("sp_cma", chip.UnitHealth.DEAD, reason="test")
    assert pol.health_version > v0
    assert not pol._route  # flushed with the transition
    assert pol.unit_for_phase("decode", precision="sp").name == "sp_fma"
    assert not pol.in_service("sp_cma")
    pol.clear_health("sp_cma")
    assert pol.unit_for_phase("decode", precision="sp").name == "sp_cma"


def test_throttled_unit_deprioritized_but_still_in_service(params):
    pol = chip.ChipPolicy(chip.fabricated_chip(params=params), params)
    pol.set_health("sp_cma", chip.UnitHealth.THROTTLED, freq_scale=0.5,
                   reason="thermal")
    assert pol.in_service("sp_cma")  # degraded, still serving
    # healthy units outrank throttled ones for new routing decisions
    assert pol.unit_for_phase("decode", precision="sp").name == "sp_fma"
    assert pol.unit_time_scale("sp_cma") == 2.0
    scale = pol.unit_energy_scale("sp_cma")
    assert 1.0 < scale <= 2.0  # leakage share repriced at half frequency
    u = pol.spec.unit("sp_cma")
    assert pol.unit_energy_j(u, 1e9) == pytest.approx(
        u.energy_j(1e9) * scale)


def test_all_units_dead_raises_unit_fault(params):
    from repro.faults import UnitFault
    pol = chip.ChipPolicy(chip.fabricated_chip("sp", params), params)
    for u in pol.spec.units:
        pol.set_health(u.name, chip.UnitHealth.DEAD)
    with pytest.raises(UnitFault):
        pol.unit_for_phase("decode", precision="sp")


def test_spec_replacement_prunes_health_and_flushes_routes(params):
    """Fleet-membership change: the route cache and the health of removed
    units must go with it (satellite: stale entries would route to units
    no longer on the die)."""
    fab = chip.fabricated_chip(params=params)
    pol = chip.ChipPolicy(fab, params)
    pol.unit_for_phase("decode", precision="sp")
    pol.set_health("sp_cma", chip.UnitHealth.THROTTLED, freq_scale=0.5)
    v0 = pol.health_version
    dp_only = chip.ChipSpec(
        "dp-only", tuple(u for u in fab.units
                         if u.design.precision == "dp"))
    pol.replace_spec(dp_only)
    assert pol.health_version > v0
    assert not pol._route
    with pytest.raises(KeyError):
        pol.unit_health("sp_cma")  # pruned with the membership change
    assert pol.unit_for_phase("decode").name == "dp_cma"


def test_health_report_round_trips(params):
    pol = chip.ChipPolicy(chip.fabricated_chip(params=params), params)
    pol.set_health("sp_cma", chip.UnitHealth.QUARANTINED,
                   reason="nan burst", now=4.2)
    rep = pol.health_report()
    assert rep["sp_cma"]["status"] == "quarantined"
    assert rep["sp_cma"]["in_service"] is False
    assert rep["sp_fma"]["status"] == "healthy"
    assert pol.unit_health("sp_cma").since_s == 4.2
    with pytest.raises(ValueError):
        chip.UnitHealth(status="zombie")
    with pytest.raises(ValueError):
        chip.UnitHealth(freq_scale=0.0)
