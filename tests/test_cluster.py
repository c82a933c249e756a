"""Cluster layer: spec/budget validation, slot partitioning edge cases,
the reusable local-search engine, the seeded open-loop load generator, and
the ``ClusterRouter`` acceptance scenarios — cross-chip precision/accuracy/
deadline routing, die failure with zero-loss bitwise migration, parking
when no feasible die survives, the 1-die degenerate equivalence with a
bare ``BatchedServer``, and ``tune_cluster``'s degenerate golden against
``tune_chip``."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.cluster import (ChipClass, ClusterRouter, ClusterSpec,
                           RequestClass, SimClock, TraceConfig, generate,
                           homogeneous, latency_stats, replay, tune_cluster)
from repro.configs.base import get_config
from repro.core import autotune as at
from repro.core import chip
from repro.core.energy_model import SweepExecutableCache, calibrate
from repro.core.formats import FP32, FP8_E4M3
from repro.core.localsearch import hillclimb
from repro.models import LM
from repro.serve.engine import (BatchedServer, Request, RequestRejected,
                                greedy_decode)

from helpers import make_chip_unit as unit

# Small electrical grids keep the tune_cluster sweeps fast (same grids as
# tests/test_chip.py); the library defaults are the full TUNE_* grids.
VDD = np.round(np.arange(0.55, 1.101, 0.05), 3)
VBB = np.round(np.arange(0.0, 1.21, 0.3), 2)
TICK = 0.05
MAX_LEN = 64


@pytest.fixture(scope="module")
def dense():
    cfg = get_config("tinyllama-1.1b").reduced()
    model = LM(cfg)
    return cfg, model, model.init(jax.random.key(3))


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """Drop this module's jitted executables on teardown: the suite's
    cumulative XLA compile footprint is what segfaults later modules'
    compiles on small hosts, and every module builds its own LM anyway."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def params():
    return calibrate()


@pytest.fixture(scope="module")
def cache():
    return SweepExecutableCache()


def _eco_gold_cluster():
    """The bench's heterogeneous pair: a cheap fp8 die and an FP32 die."""
    return ClusterSpec("eco+gold", (
        chip.ChipSpec("eco", (unit("decode_eco", FP8_E4M3, 1e-2, 0.5),)),
        chip.ChipSpec("gold", (unit("decode_gold", FP32, 1e-8, 4.0),))))


def _router(dense, cluster, *, slots=4, **kw):
    cfg, model, model_params = dense
    clock = SimClock()
    kw.setdefault("accuracy_fleets", (5e-2, 1e-7))
    kw.setdefault("dispatch_tokens", 3)
    return ClusterRouter(model, model_params, cluster, slots=slots,
                         max_len=MAX_LEN, clock=clock, **kw), clock


def _requests(cfg, n=6, new_tokens=8, seed=5, **kw):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        4 + i % 4).astype(np.int32),
                    max_new_tokens=new_tokens, **kw)
            for i in range(n)]


def _refs(dense, reqs):
    cfg, model, model_params = dense
    return {r.uid: greedy_decode(model, model_params, r.prompt,
                                 r.max_new_tokens, max_len=MAX_LEN)
            for r in reqs}


def _drive(target, clock, max_steps=400):
    for _ in range(max_steps):
        clock.t += TICK
        target.step()
        if target.idle():
            break


# ------------------------------------------------------- partition_slots
def test_partition_slots_one_slot_per_fleet_floor():
    """Exactly as many slots as fleets: everyone gets one, contiguously —
    even when proportionality would starve the small fleet."""
    units = [dataclasses.replace(unit("a", FP32, 1e-8, 1.0), count=5),
             unit("b", FP32, 1e-8, 1.0)]
    assert chip.partition_slots(2, units) == {"a": (0,), "b": (1,)}


def test_partition_slots_proportional_largest_remainder():
    units = [dataclasses.replace(unit("a", FP32, 1e-8, 1.0), count=3),
             unit("b", FP32, 1e-8, 1.0)]
    assert chip.partition_slots(8, units) == {
        "a": (0, 1, 2, 3, 4, 5), "b": (6, 7)}


def test_partition_slots_remainder_tie_is_deterministic():
    """Equal fractional remainders break by unit order (stable argsort)."""
    units = [unit(n, FP32, 1e-8, 1.0) for n in ("a", "b", "c")]
    assert chip.partition_slots(5, units) == {
        "a": (0, 1), "b": (2, 3), "c": (4,)}


def test_partition_slots_floor_overshoot_is_clawed_back():
    """Tiny n_slots with a dominant fleet: the per-fleet 1-slot floors can
    overshoot the target and must be clawed back from the biggest fleet."""
    units = [dataclasses.replace(unit("big", FP32, 1e-8, 1.0), count=100),
             unit("s1", FP32, 1e-8, 1.0), unit("s2", FP32, 1e-8, 1.0)]
    fleets = chip.partition_slots(3, units)
    assert all(len(s) == 1 for s in fleets.values())


def test_partition_slots_covers_exactly_and_contiguously():
    units = [dataclasses.replace(unit(n, FP32, 1e-8, 1.0), count=c)
             for n, c in (("a", 2), ("b", 7), ("c", 1), ("d", 3))]
    for n_slots in (4, 5, 9, 16, 33):
        fleets = chip.partition_slots(n_slots, units)
        flat = [s for ids in fleets.values() for s in ids]
        assert sorted(flat) == list(range(n_slots))   # exact cover
        for ids in fleets.values():                   # nonempty + contiguous
            assert ids == tuple(range(ids[0], ids[-1] + 1))


def test_partition_slots_too_few_slots_raises():
    units = [unit("a", FP32, 1e-8, 1.0), unit("b", FP32, 1e-8, 1.0)]
    with pytest.raises(ValueError, match="cannot cover"):
        chip.partition_slots(1, units)
    with pytest.raises(ValueError, match="at least one unit"):
        chip.partition_slots(4, [])


# ----------------------------------------------------------- local search
def test_hillclimb_converges_and_memoizes():
    calls = []

    def score(x):
        calls.append(x)
        return -(x - 3) ** 2

    r = hillclimb(0, lambda x: (x - 1, x + 1), score, key=lambda x: x)
    assert r.best == 3 and r.best_score == 0 and r.converged
    assert len(calls) == len(set(calls))       # each state scored once
    assert r.evaluations == len(calls)


def test_hillclimb_infeasible_states_are_walls():
    # feasible region [0, 4]: the climb must stop at the boundary optimum
    def score(x):
        return x if 0 <= x <= 4 else None

    r = hillclimb(1, lambda x: (x - 1, x + 1), score, key=lambda x: x)
    assert r.best == 4 and r.converged


def test_hillclimb_infeasible_init_raises():
    with pytest.raises(ValueError, match="infeasible"):
        hillclimb(9, lambda x: (x - 1, x + 1),
                  lambda x: x if x < 5 else None, key=lambda x: x)


# ------------------------------------------------------------ ClusterSpec
def test_cluster_spec_validation():
    die = chip.ChipSpec("d0", (unit("u", FP32, 1e-8, 1.0),))
    with pytest.raises(ValueError, match="at least one"):
        ClusterSpec("empty", ())
    with pytest.raises(ValueError, match="duplicate"):
        ClusterSpec("dup", (die, die))
    with pytest.raises(ValueError, match="area"):
        ClusterSpec("tight", (die,), area_budget_mm2=die.area_mm2 / 2)
    with pytest.raises(ValueError, match="TDP"):
        ClusterSpec("hot", (die,), tdp_budget_mw=die.peak_power_mw / 2)


def test_homogeneous_replicates_and_aggregates():
    die = chip.ChipSpec("base", (unit("u", FP32, 1e-8, 1.0),))
    c = homogeneous(die, 3)
    assert [d.name for d in c.chips] == [f"base/die{i}" for i in range(3)]
    assert c.area_mm2 == pytest.approx(3 * die.area_mm2)
    assert c.peak_power_mw == pytest.approx(3 * die.peak_power_mw)
    assert c.chip("base/die2").units == die.units


# --------------------------------------------------------------- load gen
def test_trace_generation_is_deterministic_and_ordered():
    cfg = TraceConfig(horizon_s=10.0, base_rate_rps=2.0, seed=11,
                      classes=(RequestClass("a", weight=2),
                               RequestClass("b", deadline_slack_s=1.5)))
    t1, t2 = generate(cfg, 100), generate(cfg, 100)
    assert len(t1) > 0
    assert [a.at_s for a in t1] == [a.at_s for a in t2]   # seeded: identical
    assert [a.cls for a in t1] == [a.cls for a in t2]
    for a1, a2 in zip(t1, t2):
        assert np.array_equal(a1.request.prompt, a2.request.prompt)
    assert [a.at_s for a in t1] == sorted(a.at_s for a in t1)
    assert all(0.0 <= a.at_s < cfg.horizon_s for a in t1)
    for a in t1:                                          # deadline = t+slack
        if a.cls == "b":
            assert a.request.deadline_s == pytest.approx(a.at_s + 1.5)
        else:
            assert a.request.deadline_s is None


def test_trace_config_validation():
    with pytest.raises(ValueError, match="diurnal_amplitude"):
        TraceConfig(diurnal_amplitude=1.5)
    with pytest.raises(ValueError, match="burst_multiplier"):
        TraceConfig(burst_multiplier=0.5)
    with pytest.raises(ValueError, match="request class"):
        TraceConfig(classes=())


# ------------------------------------------------------ router: routing
def test_cross_chip_accuracy_routing_is_bitwise(dense):
    """Tight-SLO traffic can only land on the FP32 die; loose-SLO traffic
    spreads least-loaded over both dies (gold meets 5e-2 natively too) and
    the cheap fp8 die does real work — every output matches the reference
    decoder regardless of placement."""
    cfg = dense[0]
    router, clock = _router(dense, _eco_gold_cluster())
    loose = _requests(cfg, n=3, accuracy_slo=5e-2)
    tight = _requests(cfg, n=3, seed=6, accuracy_slo=1e-7)
    for r in tight:
        r.uid += 100
    refs = _refs(dense, loose + tight)
    targets = [router.submit(r) for r in loose]
    assert targets[0] == "eco"          # empty cluster: name-tiebreak
    assert "eco" in targets             # the cheap die takes loose traffic
    assert all(router.submit(r) == "gold" for r in tight)  # only gold meets
    _drive(router, clock)
    done = {r.uid: r for r in router.drain_finished()}
    assert set(done) == {r.uid for r in loose + tight}
    assert any(done[r.uid].routed_unit == "decode_eco" for r in loose)
    for r in tight:
        assert done[r.uid].routed_unit == "decode_gold"
    for uid, ref in refs.items():
        assert done[uid].output == ref


def test_deadline_class_routing_through_the_cluster(dense):
    """With deadline routing on, deadline-bound traffic takes the
    latency-class fleet and bulk traffic the throughput-class fleet."""
    cfg = dense[0]
    spec = chip.ChipSpec("tiered", (
        unit("decode_lat", FP32, 1e-8, 4.0, phases=("decode",)),
        unit("decode_bulk", FP32, 1e-8, 1.0, phases=("bulk",))))
    router, clock = _router(dense, ClusterSpec("solo", (spec,)),
                            deadline_routing=True, accuracy_fleets=())
    interactive = _requests(cfg, n=2, deadline_s=1e9)
    bulk = _requests(cfg, n=2, seed=6)
    for r in bulk:
        r.uid += 100
    for r in interactive + bulk:
        router.submit(r)
    _drive(router, clock)
    done = {r.uid: r for r in router.drain_finished()}
    assert all(done[r.uid].routed_unit == "decode_lat" for r in interactive)
    assert all(done[r.uid].routed_unit == "decode_bulk" for r in bulk)


def test_least_loaded_placement_alternates_identical_dies(dense):
    cfg = dense[0]
    twins = ClusterSpec("twins", (
        chip.ChipSpec("a", (unit("decode_eco", FP8_E4M3, 1e-2, 0.5),)),
        chip.ChipSpec("b", (unit("decode_eco", FP8_E4M3, 1e-2, 0.5),))))
    router, _ = _router(dense, twins, slots=2)
    targets = [router.submit(r)
               for r in _requests(cfg, n=4, accuracy_slo=5e-2)]
    assert targets == ["a", "b", "a", "b"]


def test_cluster_wide_structured_rejects(dense):
    cfg = dense[0]
    router, _ = _router(dense, _eco_gold_cluster())
    with pytest.raises(RequestRejected) as exc:
        router.submit(_requests(cfg, n=1, precision="dp")[0])
    assert exc.value.code == "unknown_precision"
    assert "eco+gold" in exc.value.reason
    with pytest.raises(RequestRejected) as exc:
        router.submit(_requests(cfg, n=1, accuracy_slo=1e-12)[0])
    assert exc.value.code == "accuracy_slo_unmeetable"
    assert "1e-08" in exc.value.reason          # best achievable is named
    assert len(router.rejected) == 2


# --------------------------------------------- router: failure / parking
def test_die_failure_migrates_bitwise_with_zero_loss(dense):
    """THE cluster acceptance scenario: the eco die is killed with traffic
    seated on its slots and queued behind them; everything completes on
    the gold die, bitwise-identical to the reference."""
    cfg = dense[0]
    router, clock = _router(dense, _eco_gold_cluster())
    reqs = _requests(cfg, n=6, accuracy_slo=5e-2)
    refs = _refs(dense, reqs)
    targets = {r.uid: router.submit(r) for r in reqs}
    on_eco = {u for u, t in targets.items() if t == "eco"}
    assert on_eco                           # the kill lands on live traffic
    for _ in range(2):                      # commit a few eco tokens first
        clock.t += TICK
        router.step()
    moved = router.fail_chip("eco")
    assert {r.uid for r in moved} == on_eco
    assert router.migrations == len(moved)
    _drive(router, clock)
    done = {r.uid: r for r in router.drain_finished() if r.done}
    assert set(done) == {r.uid for r in reqs}     # zero loss
    for r in reqs:
        assert done[r.uid].output == refs[r.uid]  # bitwise continuation
    for uid in on_eco:                            # resumed on the survivor
        assert done[uid].routed_unit == "decode_gold"
        assert done[uid].requeues >= 1


def test_all_dies_failed_parks_then_restore_drains(dense):
    cfg = dense[0]
    router, clock = _router(dense, _eco_gold_cluster())
    router.fail_chip("eco")
    router.fail_chip("gold")
    reqs = _requests(cfg, n=3, accuracy_slo=5e-2)
    assert all(router.submit(r) == "" for r in reqs)   # parked, not dropped
    assert len(router._parked) == 3 and router.idle() is False
    clock.t += TICK
    assert router.step() == 0                          # nothing to serve
    router.restore_chip("gold")
    _drive(router, clock)
    done = {r.uid for r in router.drain_finished() if r.done}
    assert done == {r.uid for r in reqs}
    assert not router._parked


def test_one_die_cluster_matches_batched_server_bitwise(dense):
    """Degenerate acceptance criterion: a 1-chip cluster routes every
    request to its only server and the outputs (and routed units) are
    identical to driving a BatchedServer directly."""
    cfg, model, model_params = dense
    spec = chip.ChipSpec("solo", (unit("decode_eco", FP8_E4M3, 1e-2, 0.5),
                                  unit("decode_gold", FP32, 1e-8, 4.0)))
    router, rclock = _router(dense, ClusterSpec("one", (spec,)))
    sclock = SimClock()
    solo = BatchedServer(model, model_params, slots=4, max_len=MAX_LEN,
                         chip_policy=chip.ChipPolicy(spec),
                         accuracy_fleets=(5e-2, 1e-7), dispatch_tokens=3,
                         clock=sclock)
    kw = dict(n=4, accuracy_slo=5e-2)
    via_router, via_solo = _requests(cfg, **kw), _requests(cfg, **kw)
    for rr, rs in zip(via_router, via_solo):
        assert router.submit(rr) == "solo"
        solo.submit(rs)
    _drive(router, rclock)
    _drive(solo, sclock)
    done_r = {r.uid: r for r in router.drain_finished()}
    done_s = {r.uid: r for r in solo.finished}
    assert set(done_r) == set(done_s)
    for uid, rs in done_s.items():
        assert done_r[uid].output == rs.output
        assert done_r[uid].routed_unit == rs.routed_unit


# ----------------------------------------------------- trace -> cluster
@pytest.mark.parametrize("prefill_chunk", [None, 4],
                         ids=["monolithic", "chunked"])
def test_trace_replay_over_heterogeneous_dies(dense, prefill_chunk):
    """A small seeded bursty trace end-to-end through the router, with
    monolithic or cluster-wide chunked admission: every arrival finishes
    with the ``greedy_decode`` output, latencies are positive, stats are
    consistent."""
    cfg = dense[0]
    router, clock = _router(dense, _eco_gold_cluster(),
                            prefill_chunk=prefill_chunk)
    trace = generate(
        TraceConfig(horizon_s=4.0, base_rate_rps=1.5, seed=9,
                    classes=(RequestClass("loose", weight=3,
                                          max_new_tokens=6,
                                          accuracy_slo=5e-2),
                             RequestClass("tight", max_new_tokens=6,
                                          accuracy_slo=1e-7))),
        cfg.vocab_size)
    assert trace, "seeded trace unexpectedly empty"
    rep = replay(router, trace, clock, tick_s=TICK, dispatch_tokens=3)
    assert len(rep["finished"]) == len(trace)
    assert not rep["rejected"] and not rep["expired"]
    refs = _refs(dense, [a.request for a in trace])
    for r in rep["finished"]:
        assert r.output == refs[r.uid], r.uid
    st = latency_stats(rep["latency_s"])
    assert st["n"] == len(trace)
    assert 0.0 < st["p50_s"] <= st["p99_s"] <= st["max_s"]
    energy = router.energy_report()
    assert energy["tokens_decoded"] > 0 and energy["total_j"] > 0


# ----------------------------------------------------------- tune_cluster
def test_tune_cluster_degenerate_matches_tune_chip(params, cache):
    """One class, one die allowed: tune_cluster must reproduce the
    tune_chip result unit-for-unit — it is the same optimizer one level
    up, not a different one."""
    phases = (chip.PhaseSpec("train", at.GEMM_STREAM, flops_fraction=0.7),
              chip.PhaseSpec("decode", at.DEPENDENT_CHAIN,
                             flops_fraction=0.3))
    golden = chip.tune_chip(phases, params=params, vdd_grid=VDD,
                            vbb_grid=VBB, cache=cache)
    rc = tune_cluster([ChipClass("solo", phases)], max_chips=1,
                      params=params, vdd_grid=VDD, vbb_grid=VBB,
                      cache=cache)
    assert rc.counts == {"solo": 1}
    die, = rc.spec.chips
    assert die.name == "solo/die0"
    assert [(u.design.name, u.vdd, u.vbb, u.count, u.fmt)
            for u in die.units] == \
        [(u.design.name, u.vdd, u.vbb, u.count, u.fmt)
         for u in golden.spec.units]
    assert rc.search.converged


def test_tune_cluster_covers_classes_under_budget(params, cache):
    classes = [
        ChipClass("bulk", (chip.PhaseSpec("train", at.GEMM_STREAM),),
                  workload_share=3.0),
        ChipClass("interactive",
                  (chip.PhaseSpec("decode", at.DEPENDENT_CHAIN),),
                  workload_share=1.0),
    ]
    rc = tune_cluster(classes, max_chips=4, params=params,
                      vdd_grid=VDD, vbb_grid=VBB, cache=cache)
    assert rc.report["classes_covered"] == 2       # every class gets a die
    assert all(k >= 1 for k in rc.counts.values())
    assert sum(rc.counts.values()) <= 4
    assert rc.report["balanced_throughput_gflops"] > 0
    assert rc.search.converged
    # the heavier class gets at least as many replicas
    assert rc.counts["bulk"] >= rc.counts["interactive"]
    # ClusterSpec re-validates the aggregate budgets on construction
    assert rc.spec.area_mm2 <= rc.spec.area_budget_mm2
    # per-class sweeps went through the shared executable cache
    assert rc.report["cache_stats"].get("hits", 0) > 0
