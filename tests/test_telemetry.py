"""Unified telemetry: span tracing, energy reconciliation, metric
timelines, exporters, and trace-derived workload profiles.

The three contracts under test:

  * **Causal completeness** — one root span per request uid, every attempt
    parented into the same uid's tree, no orphans — including across a
    die kill mid-prefill with chunked admission (the continuity-under-
    faults scenario).
  * **Energy reconciliation** — span energy is charged from the engine's
    single choke point (``_charge_unit``), so the sum over spans equals
    the chip-level ledger to 1e-9, per unit and per request, across mixed
    prefill/decode/fault traffic (wasted corrupt-retry work included).
  * **Measured profiles** — ``profile_from_trace`` yields activities read
    off the recorded occupancy timeline, not hand-set defaults, and
    ``latency_stats``/``run_report`` stay NaN-free and per-run-scoped at
    the edges.
"""
import json
import math

import jax
import numpy as np
import pytest

from repro.cluster import (ClusterRouter, ClusterSpec, SimClock,
                           latency_stats, trace_cluster)
from repro.configs.base import get_config
from repro.core import chip
from repro.core.energy_model import calibrate
from repro.core.formats import FP32, FP8_E4M3
from repro.faults import FaultEvent, FaultInjector, FaultKind
from repro.models import LM
from repro.serve.engine import BatchedServer, Request, greedy_decode
from repro.serve.resilience import ResilienceConfig, ResilientServer
from repro.telemetry import (Event, NULL_SPAN, NULL_TRACER, Tracer,
                             load_jsonl, MIN_ACTIVITY, phases_from_trace,
                             profile_from_trace, summarize_trace,
                             to_chrome_trace, write_chrome_trace,
                             write_jsonl)

from repro.telemetry.export import ENGINE_TID

from helpers import FakeClock, make_chip_unit as unit

TICK = 0.05
MAX_LEN = 64
# small electrical grids keep the trace-driven tune_chip fast
TUNE_VDD = np.round(np.arange(0.55, 1.101, 0.05), 3)
TUNE_VBB = np.round(np.arange(0.0, 1.21, 0.3), 2)


@pytest.fixture(scope="module")
def dense():
    cfg = get_config("tinyllama-1.1b").reduced()
    model = LM(cfg)
    return cfg, model, model.init(jax.random.key(3))


def _requests(cfg, n=6, new_tokens=8, seed=5, **kw):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        4 + i % 4).astype(np.int32),
                    max_new_tokens=new_tokens, **kw)
            for i in range(n)]


def _drive(target, clock, max_steps=400):
    for _ in range(max_steps):
        clock.t += TICK
        target.step()
        if target.idle():
            break


# ------------------------------------------------------------ tracer core
def test_root_span_is_idempotent_and_attr_merging():
    tr = Tracer()
    a = tr.request_begin(7, 1.0, prompt_tokens=4)
    b = tr.request_begin(7, 2.0, precision="sp")
    assert a is b and a.start_s == 1.0
    assert a.attrs == dict(prompt_tokens=4, precision="sp")
    assert len(tr.spans) == 1 and a.is_root


def test_attempt_chain_parents_previous_attempt():
    tr = Tracer()
    tr.request_begin(1, 0.0)
    a1 = tr.begin_attempt(1, 0.1, site="eco", fleet="decode_eco")
    tr.end_attempt(1, 0.5, status="drained")
    a2 = tr.begin_attempt(1, 0.6, site="gold", fleet="decode_gold")
    assert a1.parent_id == tr.roots()[1].span_id
    assert a2.parent_id == a1.span_id          # the causal migration chain
    assert a1.status == "drained" and a2.status == "open"
    assert tr.check_integrity() == []


def test_begin_attempt_closes_stale_open_attempt():
    tr = Tracer()
    a1 = tr.begin_attempt(1, 0.0, site="a")
    a2 = tr.begin_attempt(1, 1.0, site="b")   # no explicit end_attempt
    assert a1.end_s == 1.0 and a1.status == "drained"
    assert a2.parent_id == a1.span_id
    assert tr.check_integrity() == []


def test_events_land_on_current_attempt_and_bump_token_counters():
    tr = Tracer()
    tr.request_begin(3, 0.0)
    tr.event(3, Event.ADMIT, 0.0)              # before any attempt: on root
    at = tr.begin_attempt(3, 0.1, site="die")
    tr.event(3, Event.PREFILL_CHUNK, 0.2, tokens=16)
    tr.event(3, Event.PREFILL_CHUNK, 0.3, tokens=4)
    tr.event(3, Event.DECODE_DISPATCH, 0.4, tokens=3)
    tr.event(3, Event.FINISH, 0.5, tokens_out=3)   # tokens_out: no bump
    root = tr.roots()[3]
    assert [e[0] for e in root.events] == [Event.ADMIT]
    assert at.prefill_tokens == 20 and at.decode_tokens == 3
    assert [e[0] for e in tr.events_for(3)] == [
        Event.ADMIT, Event.PREFILL_CHUNK, Event.PREFILL_CHUNK,
        Event.DECODE_DISPATCH, Event.FINISH]


def test_integrity_flags_orphans_double_roots_and_open_attempts():
    tr = Tracer()
    tr.request_begin(1, 0.0)
    tr.begin_attempt(1, 0.1)
    tr.end_request(1, 0.2, "ok")               # attempt still open
    problems = tr.check_integrity()
    assert any("still open" in p for p in problems)
    tr2 = Tracer()
    s = tr2.begin_attempt(5, 0.0)
    s.parent_id = 999                          # corrupt: orphan
    assert any("orphaned" in p for p in tr2.check_integrity())


def test_null_tracer_is_inert():
    assert not NULL_TRACER.enabled
    assert NULL_TRACER.request_begin(1, 0.0) is None
    assert NULL_TRACER.event(1, Event.ADMIT, 0.0) is None
    assert NULL_TRACER.charge(1, "u", 1.0, 1.0, 0.0) is None


def test_null_tracer_span_records_nothing():
    calls = []

    def clock():
        calls.append(1)
        return 0.0

    ctx = NULL_TRACER.span("engine.step", clock, site="eco", seated=1)
    assert ctx is NULL_SPAN                  # shared, nothing allocated
    with ctx as span:
        assert span is None
    assert calls == []                       # the clock is never read
    assert not hasattr(NULL_TRACER, "step_spans")


def test_step_spans_nest_and_stamp_on_the_given_clock():
    clock = FakeClock(1.0)
    tr = Tracer()
    with tr.span("engine.step", clock, site="eco") as step:
        clock.t = 2.0
        with tr.span("engine.dispatch", clock, site="eco", n=4) as disp:
            with tr.span("engine.sync", clock, site="eco") as sync:
                clock.t = 3.0
        disp.attrs["lanes"] = [[5, 4]]
        clock.t = 4.0
    with tr.span("engine.step", clock, site="gold") as nxt:
        pass
    assert [s.name for s in tr.step_spans] == [
        "engine.step", "engine.dispatch", "engine.sync", "engine.step"]
    assert (step.parent_id, disp.parent_id, sync.parent_id,
            nxt.parent_id) == (None, step.span_id, disp.span_id, None)
    assert (step.start_s, step.end_s) == (1.0, 4.0)
    assert (sync.start_s, sync.end_s) == (2.0, 3.0)
    assert disp.attrs == {"n": 4, "lanes": [[5, 4]]}
    assert nxt.site == "gold" and nxt.duration_s == 0.0
    assert tr.spans == [] and tr.check_integrity() == []


# ------------------------------------------------------------- exporters
def _hand_trace():
    tr = Tracer()
    tr.request_begin(1, 0.0, prompt_tokens=4, precision="sp")
    tr.event(1, Event.ADMIT, 0.0)
    tr.begin_attempt(1, 0.1, site="eco", fleet="decode_eco", slot=2)
    tr.event(1, Event.PREFILL, 0.1, tokens=4, bucket=4)
    tr.charge(1, "decode_eco", 1.5e-6, 2e6, 0.1, phase="prefill")
    tr.event(1, Event.DECODE_DISPATCH, 0.2, tokens=3, slot=2)
    tr.charge(1, "decode_eco", 2.5e-6, 3e6, 0.2)
    tr.end_attempt(1, 0.3, status="ok")
    tr.end_request(1, 0.3, "ok")
    tr.count("occupancy", 0.1, 0.5, site="eco")
    tr.count("occupancy", 0.2, 0.75, site="eco")
    tr.system_event(Event.FAULT, 0.25, site="eco", unit="decode_eco",
                    kind="kill")
    return tr


def _hand_steps(tr):
    clock = FakeClock(0.1)
    for site in ("eco", "gold"):
        with tr.span("engine.step", clock, site=site, seated=1):
            with tr.span("engine.chunk", clock, site=site, shape=[1, 4],
                         lanes=[[0, 4]], finals=1):
                clock.t += 0.05
            clock.t += 0.05
    return tr


def test_jsonl_round_trip_preserves_everything(tmp_path):
    tr = _hand_trace()
    path = tmp_path / "t.jsonl"
    write_jsonl(tr, str(path))
    back = load_jsonl(str(path))
    assert len(back.spans) == len(tr.spans)
    for a, b in zip(tr.spans, back.spans):
        assert (a.span_id, a.uid, a.parent_id, a.name, a.site, a.fleet,
                a.status) == (b.span_id, b.uid, b.parent_id, b.name,
                              b.site, b.fleet, b.status)
        assert a.energy_j == pytest.approx(b.energy_j, abs=0.0)
        assert a.unit_energy_j == b.unit_energy_j
        assert a.prefill_tokens == b.prefill_tokens
        assert a.decode_tokens == b.decode_tokens
        assert [tuple(e) for e in a.events] == [tuple(e) for e in b.events]
    assert back.metrics == tr.metrics
    assert back.system_events == tr.system_events
    assert back.step_spans == []
    assert back.check_integrity() == []
    # a re-loaded tracer is live: new spans keep ids unique
    s = back.begin_attempt(1, 0.4, site="gold")
    assert s.span_id not in {x.span_id for x in tr.spans}


def test_jsonl_round_trips_step_spans(tmp_path):
    tr = _hand_steps(_hand_trace())
    path = tmp_path / "t.jsonl"
    write_jsonl(tr, str(path))
    back = load_jsonl(str(path))
    assert back.step_spans == tr.step_spans
    assert len(back.spans) == len(tr.spans)


def test_chrome_trace_puts_step_spans_on_one_engine_track_per_site():
    tr = _hand_steps(_hand_trace())
    evs = to_chrome_trace(tr)["traceEvents"]
    pid = {e["args"]["name"]: e["pid"] for e in evs
           if e["ph"] == "M" and e["name"] == "process_name"}
    names = [e for e in evs if e["ph"] == "M" and e["name"] == "thread_name"]
    assert {(e["pid"], e["tid"], e["args"]["name"]) for e in names} == {
        (pid["eco"], ENGINE_TID, "engine"), (pid["gold"], ENGINE_TID,
                                             "engine")}
    eng = [e for e in evs if e.get("cat") == "engine"]
    assert [(e["ph"], e["name"], e["tid"]) for e in eng] == [
        ("X", "engine.step", ENGINE_TID), ("X", "engine.chunk", ENGINE_TID)
    ] * 2
    assert [e["pid"] for e in eng] == [pid["eco"]] * 2 + [pid["gold"]] * 2
    step = eng[0]
    assert step["ts"] == pytest.approx(0.1e6)
    assert step["dur"] == pytest.approx(0.1e6)
    assert eng[1]["args"]["lanes"] == [[0, 4]]
    # the request spans keep their own tracks
    assert len([e for e in evs if e["ph"] == "X"]) == 2 + 4


def test_chrome_trace_structure(tmp_path):
    tr = _hand_trace()
    doc = to_chrome_trace(tr)
    evs = doc["traceEvents"]
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)
    slices = [e for e in evs if e["ph"] == "X"]
    assert len(slices) == 2                    # root + one attempt
    att = next(e for e in slices if e["name"].startswith("attempt"))
    assert att["ts"] == pytest.approx(0.1e6) and \
        att["dur"] == pytest.approx(0.2e6)     # microseconds
    assert att["args"]["energy_j"] == pytest.approx(4e-6)
    assert any(e["ph"] == "i" for e in evs)    # instants
    assert any(e["ph"] == "C" and e["name"] == "occupancy" for e in evs)
    path = tmp_path / "t.json"
    write_chrome_trace(tr, str(path))
    assert json.loads(path.read_text())["traceEvents"]


# ------------------------------------- energy reconciliation (satellite c)
def test_span_energy_reconciles_with_engine_ledger_under_faults(dense):
    """Mixed prefill/decode/fault traffic: transient corruption forces a
    retry (wasted work is still charged), then the whole eco fleet's
    traffic migrates.  Span energy == chip ledger to 1e-9, per unit and
    in total; finished requests' root trees match req.energy_j."""
    cfg, model, params = dense
    clock = FakeClock()
    tracer = Tracer()
    spec = chip.ChipSpec("tiered", (unit("decode_eco", FP8_E4M3, 1e-2, 0.5),
                                    unit("decode_gold", FP32, 1e-8, 4.0)))
    events = (FaultEvent(at_s=0.3, unit="decode_eco",
                         kind=FaultKind.CORRUPT, magnitude=1.0,
                         duration_s=2 * TICK),
              FaultEvent(at_s=0.8, unit="decode_eco", kind=FaultKind.KILL,
                         magnitude=1.0))
    srv = ResilientServer(
        model, params, slots=4, max_len=MAX_LEN,
        chip_policy=chip.ChipPolicy(spec, calibrate()),
        accuracy_fleets=(5e-2, 1e-7), dispatch_tokens=3, clock=clock,
        injector=FaultInjector(events, seed=3),
        resilience=ResilienceConfig(synthetic_dispatch_s=TICK),
        tracer=tracer)
    reqs = _requests(cfg, n=6, accuracy_slo=5e-2)
    for r in reqs:
        srv.submit(r)
    _drive(srv, clock)
    assert all(r.done and not r.expired for r in reqs)
    assert tracer.check_integrity() == []

    ledger = srv._unit_energy_j
    assert tracer.total_energy_j() == pytest.approx(
        sum(ledger.values()), abs=1e-9)
    for name, e in tracer.unit_energy_j().items():
        assert e == pytest.approx(ledger.get(name, 0.0), abs=1e-9)
    for r in reqs:                      # per-request attribution
        assert tracer.request_energy_j(r.uid) == pytest.approx(
            r.energy_j, abs=1e-9)
    # the kill actually moved traffic, and every move is in the trace (a
    # request drained from the *queue* keeps one attempt; one drained off
    # a slot gets a chained second attempt — causality either way)
    migrated = [r for r in reqs if r.requeues]
    assert migrated
    for r in migrated:
        assert any(e[0] in (Event.REQUEUE, Event.PARK)
                   for e in tracer.events_for(r.uid))
        attempts = tracer.attempts_for(r.uid)
        for prev, nxt in zip(attempts, attempts[1:]):
            assert nxt.parent_id == prev.span_id


# --------------------------------- per-run counter hygiene (satellite a)
def test_run_counters_reset_between_back_to_back_runs(dense):
    """A stall-heavy first run must not bleed into the second: run_report
    is per-run, energy_report stays cumulative."""
    cfg, model, params = dense
    srv = BatchedServer(model, params, slots=4, max_len=MAX_LEN,
                        dispatch_tokens=3, prefill_chunk=8)
    long = Request(uid=100, max_new_tokens=4,
                   prompt=np.arange(40, dtype=np.int32) % cfg.vocab_size)
    shorts = _requests(cfg, n=3, new_tokens=4)
    for r in [long] + shorts:
        srv.submit(r)
    srv.run()
    rep1 = srv.run_report()
    assert rep1["prefill_tokens"] > 0 and rep1["tokens_decoded"] > 0

    clean = _requests(cfg, n=2, new_tokens=4, seed=9)
    for r in clean:
        r.uid += 200
        srv.submit(r)
    srv.run()
    rep2 = srv.run_report()
    assert rep2["tokens_decoded"] == sum(len(r.output) for r in clean)
    assert rep2["prefill_tokens"] == sum(len(r.prompt) for r in clean)
    assert rep2["decode_stall_frac"] == 0.0   # no long prompt this run
    assert srv._stall_prefill_tokens == 0 or rep1["decode_stall_frac"] == 0.0
    # cumulative counters keep the whole history
    assert srv.tokens_decoded == rep1["tokens_decoded"] \
        + rep2["tokens_decoded"]


def test_identical_runs_produce_identical_run_reports(dense):
    cfg, model, params = dense
    srv = BatchedServer(model, params, slots=4, max_len=MAX_LEN,
                        dispatch_tokens=3)
    reports = []
    for base in (0, 50):
        reqs = _requests(cfg, n=4, new_tokens=4)
        for r in reqs:
            r.uid += base
            srv.submit(r)
        srv.run()
        reports.append(srv.run_report())
    assert reports[0] == reports[1]


# ----------------------------------- latency_stats edges (satellite b)
def test_latency_stats_empty_records_are_nan_free():
    st = latency_stats({})
    assert st == dict(n=0, p50_s=0.0, p99_s=0.0, mean_s=0.0, max_s=0.0)
    st = latency_stats({}, {})
    assert st["n_ttft"] == 0 and st["p99_ttft_s"] == 0.0
    assert not any(isinstance(v, float) and math.isnan(v)
                   for v in st.values())


def test_latency_stats_drops_non_finite_records():
    st = latency_stats({1: 1.0, 2: float("nan"), 3: float("inf"), 4: 3.0},
                       {1: 0.5, 2: float("nan")})
    assert st["n"] == 2 and st["max_s"] == 3.0
    assert st["mean_s"] == pytest.approx(2.0)
    assert st["n_ttft"] == 1 and st["max_ttft_s"] == 0.5
    assert not any(isinstance(v, float) and math.isnan(v)
                   for v in st.values())


def test_latency_stats_all_parked_trace_shape():
    # every request parked/expired before first commit -> empty records
    st = latency_stats({}, {})
    for k in ("p50_s", "p99_s", "mean_s", "max_s",
              "p50_ttft_s", "p99_ttft_s"):
        assert st[k] == 0.0


# --------------------------- trace continuity under faults (satellite f)
def _eco_gold_cluster():
    return ClusterSpec("eco+gold", (
        chip.ChipSpec("eco", (unit("decode_eco", FP8_E4M3, 1e-2, 0.5),)),
        chip.ChipSpec("gold", (unit("decode_gold", FP32, 1e-8, 4.0),))))


def test_die_kill_mid_prefill_keeps_one_causal_tree_per_request(dense):
    """Chunked prefill, die killed while prompts are mid-chunk: every
    request keeps exactly one root span, attempts chain across dies, no
    orphaned spans — and the streams still complete bitwise."""
    cfg, model, params = dense
    clock = SimClock()
    router = ClusterRouter(model, params, _eco_gold_cluster(), slots=4,
                           max_len=MAX_LEN, clock=clock,
                           accuracy_fleets=(5e-2, 1e-7), dispatch_tokens=3,
                           prefill_chunk=8)
    tracer = trace_cluster(router)
    rng = np.random.default_rng(5)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        20 + 4 * i).astype(np.int32),
                    max_new_tokens=6, accuracy_slo=5e-2)
            for i in range(5)]
    refs = {r.uid: greedy_decode(model, params, r.prompt, r.max_new_tokens,
                                 max_len=MAX_LEN) for r in reqs}
    targets = {r.uid: router.submit(r) for r in reqs}
    on_eco = {u for u, t in targets.items() if t == "eco"}
    assert on_eco
    clock.t += TICK
    router.step()                       # prompts are now mid-chunk
    assert any(not r.done and not r.output for r in reqs)
    moved = router.fail_chip("eco")     # the kill lands mid-prefill
    assert {r.uid for r in moved} == on_eco
    _drive(router, clock)
    done = {r.uid: r for r in router.drain_finished() if r.done}
    assert set(done) == {r.uid for r in reqs}
    for r in reqs:
        assert done[r.uid].output == refs[r.uid]

    assert tracer.check_integrity() == []
    roots = tracer.roots()
    assert set(roots) == {r.uid for r in reqs}          # one tree each
    for uid in on_eco:
        attempts = tracer.attempts_for(uid)
        assert len(attempts) >= 2                       # re-seated
        sites = [a.site for a in attempts]
        assert "eco" in sites and "gold" in sites       # crossed dies
        # the chain is causal: each attempt parents the previous one
        assert attempts[0].parent_id == roots[uid].span_id
        for prev, nxt in zip(attempts, attempts[1:]):
            assert nxt.parent_id == prev.span_id
    # the kill itself is in the system log
    assert any(t == Event.FAULT and a.get("kind") == "die_kill"
               for t, _, _, a in tracer.system_events)
    # and the cluster-side migrations were recorded
    migrate_uids = {uid for uid in on_eco
                    if any(e[0] == Event.MIGRATE
                           for e in tracer.events_for(uid))}
    assert migrate_uids == on_eco


# ------------------------------------------- trace-derived profiles
@pytest.mark.parametrize("tune", [False, True], ids=["profile", "tune_chip"])
def test_profile_from_trace_uses_measured_activity(dense, tune):
    """The trace yields measured activities; with ``tune``, ``tune_chip``
    over the trace's phases builds one unit per phase at that phase's
    measured activity (the trace -> profile -> chip loop closed)."""
    cfg, model, params = dense
    clock = FakeClock()
    tracer = Tracer()
    srv = BatchedServer(model, params, slots=4, max_len=MAX_LEN,
                        dispatch_tokens=3, clock=clock, tracer=tracer)
    reqs = _requests(cfg, n=4, new_tokens=6)
    for r in reqs:
        srv.submit(r)
    _drive(srv, clock)
    summ = summarize_trace(tracer)
    assert summ.n_requests == 4 and summ.n_completed == 4
    assert summ.prefill_tokens == sum(len(r.prompt) for r in reqs)
    assert summ.decode_tokens == sum(len(r.output) for r in reqs)
    assert summ.energy_j == pytest.approx(
        sum(srv._unit_energy_j.values()), abs=1e-9)
    assert 0.0 < summ.activity <= 1.0
    assert abs(summ.phase_weights["prefill"]
               + summ.phase_weights["decode"] - 1.0) < 1e-9

    prof = profile_from_trace(tracer, name="measured")
    assert prof.name == "measured"
    assert prof.activity == pytest.approx(
        max(summ.activity, MIN_ACTIVITY))
    # the blend interpolates the hand mixes by measured phase weight
    w = summ.phase_weights["decode"]
    assert prof.p_acc == pytest.approx(0.05 * (1 - w) + 0.45 * w)
    assert prof.w_delay == pytest.approx(0.7 * w)

    phases = phases_from_trace(tracer, name="measured")
    assert [p.name for p in phases] == ["measured:prefill",
                                        "measured:decode"]
    assert sum(p.flops_fraction for p in phases) == pytest.approx(1.0)
    for p in phases:
        assert p.profile.activity >= MIN_ACTIVITY
    if not tune:
        return
    tuned = chip.tune_chip(phases, params=calibrate(), vdd_grid=TUNE_VDD,
                           vbb_grid=TUNE_VBB, name="trace_die")
    rows = tuned.report["units"]
    assert [r["unit"] for r in rows] == [p.name for p in phases]
    for row, p in zip(rows, phases):
        assert row["activity"] == pytest.approx(p.profile.activity)


def test_profile_from_trace_round_trips_through_jsonl(dense, tmp_path):
    cfg, model, params = dense
    clock = FakeClock()
    tracer = Tracer()
    srv = BatchedServer(model, params, slots=2, max_len=MAX_LEN,
                        dispatch_tokens=3, clock=clock, tracer=tracer)
    for r in _requests(cfg, n=2, new_tokens=4):
        srv.submit(r)
    _drive(srv, clock)
    path = tmp_path / "trace.jsonl"
    write_jsonl(tracer, str(path))
    live = profile_from_trace(tracer)
    from_file = profile_from_trace(str(path))   # coerce_tracer path
    assert from_file == live


def test_summarize_trace_empty_tracer_is_nan_free():
    summ = summarize_trace(Tracer())
    assert summ.n_requests == 0 and summ.total_tokens == 0
    assert summ.activity == 0.0 and summ.stall_frac == 0.0
    prof = profile_from_trace(Tracer())
    assert prof.activity == MIN_ACTIVITY


# -------------------------------------------- engine instrumentation
def test_disabled_tracing_leaves_no_spans_and_identical_outputs(dense):
    cfg, model, params = dense
    out = {}
    for tr in (None, Tracer()):
        srv = BatchedServer(model, params, slots=4, max_len=MAX_LEN,
                            dispatch_tokens=3, tracer=tr)
        reqs = _requests(cfg, n=4, new_tokens=6)
        for r in reqs:
            srv.submit(r)
        srv.run()
        out["on" if tr else "off"] = {r.uid: tuple(r.output) for r in reqs}
        if tr is None:
            assert srv.tracer is NULL_TRACER
        else:
            assert tr.check_integrity() == []
            assert set(tr.roots()) == {r.uid for r in reqs}
            for r in reqs:
                root = tr.roots()[r.uid]
                assert root.status == "ok" and root.end_s is not None
                att, = tr.attempts_for(r.uid)
                assert att.prefill_tokens == len(r.prompt)
                assert att.decode_tokens == len(r.output)
            assert "occupancy" in tr.metrics
            assert "bucket_hit" in tr.metrics
    assert out["on"] == out["off"]      # tracing never perturbs outputs


ENGINE_SPANS = {"engine.step", "engine.seat", "engine.chunk",
                "engine.dispatch", "engine.sync", "engine.commit"}


def _inside(child, parent):
    return parent.start_s <= child.start_s <= child.end_s <= parent.end_s


@pytest.mark.parametrize("chunk", [None, 4])
def test_engine_step_spans_cover_each_phase(dense, chunk):
    """Every step() is one closed ``engine.step`` holding its phases; each
    host sync is an ``engine.sync`` inside the phase that waited; the
    spans' attrs count what the step did."""
    cfg, model, params = dense
    clock = FakeClock()
    tracer = Tracer()
    srv = BatchedServer(model, params, slots=2, max_len=MAX_LEN,
                        dispatch_tokens=3, clock=clock, tracer=tracer,
                        prefill_chunk=chunk)
    reqs = _requests(cfg, n=3, new_tokens=5)
    for r in reqs:
        srv.submit(r)
    steps = 0
    while not srv.idle():
        clock.t += TICK
        srv.step(3)
        steps += 1
    spans = tracer.step_spans
    by_id = {s.span_id: s for s in spans}
    assert {s.name for s in spans} <= ENGINE_SPANS
    top = [s for s in spans if s.parent_id is None]
    assert [s.name for s in top] == ["engine.step"] * steps
    for s in spans:
        assert s.end_s is not None and s.site == ""
        if s.parent_id is not None:
            parent = by_id[s.parent_id]
            assert _inside(s, parent)
            want = ({"engine.chunk", "engine.dispatch", "engine.seat"}
                    if s.name == "engine.sync" else {"engine.step"})
            assert parent.name in want, (s.name, parent.name)
    seats = [s for s in spans if s.name == "engine.seat"]
    assert len(seats) == steps
    assert sum(s.attrs["seated"] for s in seats) == len(reqs)
    syncs = [s for s in spans if s.name == "engine.sync"]
    assert len(syncs) == srv.host_syncs
    program = {None: "_admit_jit", 4: "_chunk_jit"}[chunk]
    assert {s.attrs["program"] for s in syncs} == {program, "_dispatch_jit"}
    commits = [s for s in spans if s.name == "engine.commit"]
    disp = [s for s in spans if s.name == "engine.dispatch"]
    assert len(commits) == len(disp) == srv.dispatches
    # every output token but each request's first came out of a dispatch
    assert sum(c.attrs["tokens"] for c in commits) == \
        sum(len(r.output) - 1 for r in reqs)
    for d in disp:
        assert d.attrs["n"] == 3 and 1 <= len(d.attrs["lanes"]) <= 2
    for s in top:
        assert s.attrs["seated"] >= s.attrs["decode_lanes"]
    if chunk:
        chunks = [s for s in spans if s.name == "engine.chunk"]
        assert sum(c for s in chunks for _, c in s.attrs["lanes"]) == \
            sum(len(r.prompt) for r in reqs)
        assert sum(s.attrs["finals"] for s in chunks) == len(reqs)
        assert sum(s.attrs["prefill_lanes"] for s in top) == \
            sum(len(s.attrs["lanes"]) for s in chunks)


def test_cluster_step_spans_keep_dies_apart(dense):
    cfg, model, params = dense
    clock = SimClock()
    router = ClusterRouter(model, params, _eco_gold_cluster(), slots=2,
                           max_len=MAX_LEN, clock=clock, dispatch_tokens=3,
                           prefill_chunk=8)
    tracer = trace_cluster(router)
    for r in _requests(cfg, n=4, new_tokens=4):
        router.submit(r)
    _drive(router, clock)
    steps = [s for s in tracer.step_spans if s.name == "engine.step"]
    assert {s.site for s in steps} == {"eco", "gold"}
    by_id = {s.span_id: s for s in tracer.step_spans}
    for s in tracer.step_spans:
        root = s
        while root.parent_id is not None:
            root = by_id[root.parent_id]
        assert root.name == "engine.step" and root.site == s.site


def test_reject_and_expire_paths_close_the_root(dense):
    cfg, model, params = dense
    clock = FakeClock()
    tracer = Tracer()
    srv = BatchedServer(model, params, slots=2, max_len=MAX_LEN,
                        dispatch_tokens=3, clock=clock, tracer=tracer)
    bad = Request(uid=1, prompt=np.arange(MAX_LEN + 8, dtype=np.int32),
                  max_new_tokens=4)
    with pytest.raises(Exception):
        srv.submit(bad)
    assert tracer.roots()[1].status == "rejected"
    late = Request(uid=2, prompt=np.arange(4, dtype=np.int32),
                   max_new_tokens=8, deadline_s=0.01)
    srv.submit(late)
    clock.t = 5.0                        # blow the deadline
    _drive(srv, clock)
    root = tracer.roots()[2]
    assert root.status == "expired"
    assert any(e[0] == Event.EXPIRE for e in tracer.events_for(2))
    assert tracer.check_integrity() == []
