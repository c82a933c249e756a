"""Telemetry benchmark: tracing overhead on the warm serving hot path,
and the trace -> workload-profile -> chip-tune loop.

Three claims, each asserted before the record is appended:

  * **Overhead** — a recording ``Tracer`` on the fused decode path (span
    events, per-dispatch energy attribution, per-step metric gauges) costs
    < 5% warm decode throughput vs the ``NULL_TRACER`` default.  Measured
    in-process as an enabled/disabled ratio of best-of-wave tokens/sec, so
    runner speed cancels; ``overhead_frac`` is guarded against an absolute
    0.05 ceiling in ``scripts/check_bench_regression.py``.
  * **Fidelity** — the recorded trace is causally complete
    (``check_integrity() == []``), its span energy reconciles exactly with
    the engine's per-unit ledger, and it survives a JSONL round trip.
  * **Measured-traffic tuning** — ``profile_from_trace`` on a recorded
    seeded bursty trace yields phase activities that are *measured*, not
    the hand-set defaults (0.8 prefill / 0.15 decode of
    ``profile_from_config``), and ``tune_chip`` over
    ``phases_from_trace(...)`` completes on them (the Fig. 4
    adaptive-body-bias machinery now sees real lane occupancy).

Appends one record to ``results/telemetry_bench.json`` per run.

Run: PYTHONPATH=src python benchmarks/telemetry_bench.py
"""
import os
import tempfile
import time

import jax
import numpy as np

from repro.cluster import (RequestClass, SimClock, TraceConfig, generate,
                           replay)
from repro.configs.base import get_config
from repro.core import chip
from repro.core.energy_model import SweepExecutableCache, calibrate
from repro.models import LM
from repro.serve.engine import BatchedServer, Request
from repro.telemetry import (Tracer, load_jsonl, phases_from_trace,
                             profile_from_trace, summarize_trace,
                             write_chrome_trace, write_jsonl)

from bench_lib import append_trajectory, emit

ARCH = "tinyllama-1.1b"
SLOTS = 8
MAX_LEN = 64
N_REQUESTS = 16
NEW_TOKENS = 24
DISPATCH_TOKENS = 12
PROMPT_LENS = (5, 9, 6, 12, 7, 11, 8, 10)
WARM_WAVES = 6
OVERHEAD_CEILING = 0.05  # mirrored by the abs_ceiling regression guard

#: hand-set activities a measured profile must not silently collapse to
HAND_SET_ACTIVITIES = (0.8, 0.15)

TRACE_HORIZON_S = 12.0
TRACE_RATE_RPS = 1.2
TRACE_TICK_S = 0.05
AREA_BUDGET_MM2 = 2.0
TDP_BUDGET_MW = 10_000.0


def make_requests(cfg, uid0=0):
    rng = np.random.default_rng(uid0 + 1)
    return [Request(uid=uid0 + i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        PROMPT_LENS[i % len(PROMPT_LENS)]
                                        ).astype(np.int32),
                    max_new_tokens=NEW_TOKENS)
            for i in range(N_REQUESTS)]


def drive(server, reqs):
    for r in reqs:
        server.submit(r)
    t0 = time.perf_counter()
    server.run(dispatch_tokens=DISPATCH_TOKENS)
    dt = time.perf_counter() - t0
    assert all(r.done for r in reqs)
    return sum(len(r.output) for r in reqs), dt


def measure_overhead(model, params, cfg):
    """Warm decode tokens/sec with tracing off vs on.  Both engines are
    built and warmed first, then identical request waves alternate
    off/on so machine drift (CI neighbors, thermal) cancels out of the
    ratio; best-of-wave throughput on each side."""
    off = BatchedServer(model, params, slots=SLOTS, max_len=MAX_LEN,
                        dispatch_tokens=DISPATCH_TOKENS)
    on = BatchedServer(model, params, slots=SLOTS, max_len=MAX_LEN,
                       dispatch_tokens=DISPATCH_TOKENS, tracer=Tracer())
    drive(off, make_requests(cfg))     # cold: compile
    drive(on, make_requests(cfg, 50))
    best = {"off": 0.0, "on": 0.0}
    for wave in range(1, WARM_WAVES + 1):
        for label, srv in (("off", off), ("on", on)):
            toks, dt = drive(srv, make_requests(cfg, wave * 100
                                                + (0 if label == "off"
                                                   else 50)))
            best[label] = max(best[label], toks / dt)
    return best["off"], best["on"], on


def record_bursty_trace(model, params, cfg):
    """Serve the seeded bursty open-loop trace with tracing on; returns
    the tracer and the replay report."""
    clock = SimClock()
    tracer = Tracer()
    server = BatchedServer(model, params, slots=SLOTS, max_len=MAX_LEN,
                           dispatch_tokens=DISPATCH_TOKENS, clock=clock,
                           tracer=tracer)
    trace = generate(
        TraceConfig(horizon_s=TRACE_HORIZON_S, base_rate_rps=TRACE_RATE_RPS,
                    seed=11,
                    classes=(RequestClass("bulk", weight=3),
                             RequestClass("tight", weight=1,
                                          max_new_tokens=8,
                                          deadline_slack_s=60.0))),
        cfg.vocab_size)
    rep = replay(server, trace, clock, tick_s=TRACE_TICK_S,
                 dispatch_tokens=DISPATCH_TOKENS, tracer=tracer)
    assert len(rep["finished"]) == len(trace), "bursty trace did not drain"
    problems = tracer.check_integrity()
    assert not problems, f"trace integrity: {problems}"
    # span energy must reconcile exactly with the engine ledger
    ledger = sum(server._unit_energy_j.values())
    diff = abs(tracer.total_energy_j() - ledger)
    assert diff <= 1e-9 * max(ledger, 1.0), \
        f"span energy diverged from engine ledger by {diff:.3e} J"
    return tracer, rep


def run():
    cfg = get_config(ARCH).reduced()
    model = LM(cfg)
    params = model.init(jax.random.key(0))

    # --- tracing overhead on the warm fused decode path
    tps_off, tps_on, traced_srv = measure_overhead(model, params, cfg)
    overhead = max(0.0, tps_off / tps_on - 1.0)
    emit("telemetry_bench.overhead", 1e6 / tps_on,
         f"tok_per_s_off={tps_off:.1f};tok_per_s_on={tps_on:.1f};"
         f"overhead_frac={overhead:.4f};ceiling={OVERHEAD_CEILING}")
    assert overhead <= OVERHEAD_CEILING, (
        f"tracing overhead {overhead:.1%} exceeds the "
        f"{OVERHEAD_CEILING:.0%} budget")
    tr = traced_srv.tracer
    assert not tr.check_integrity()

    # --- exporter round trip on the wave trace
    with tempfile.TemporaryDirectory() as td:
        jl = os.path.join(td, "trace.jsonl")
        t0 = time.perf_counter()
        write_jsonl(tr, jl)
        loaded = load_jsonl(jl)
        rt_us = (time.perf_counter() - t0) * 1e6
        assert len(loaded.spans) == len(tr.spans)
        jl_bytes = os.path.getsize(jl)
        chrome = os.path.join(td, "trace.json")
        write_chrome_trace(tr, chrome)
        assert os.path.getsize(chrome) > 0
    emit("telemetry_bench.jsonl_roundtrip", rt_us,
         f"spans={len(tr.spans)};"
         f"bytes_per_span={jl_bytes / max(len(tr.spans), 1):.0f}")

    # --- record a bursty trace and tune the chip on *measured* traffic
    trace_tr, rep = record_bursty_trace(model, params, cfg)
    summ = summarize_trace(trace_tr)
    prof = profile_from_trace(trace_tr, name="bursty")
    degenerate = any(abs(prof.activity - h) < 1e-3
                     for h in HAND_SET_ACTIVITIES)
    assert 0.0 < prof.activity <= 1.0 and not degenerate, (
        f"measured activity {prof.activity:.4f} is degenerate "
        f"(hand-set defaults {HAND_SET_ACTIVITIES})")
    emit("telemetry_bench.profile", 0.0,
         f"activity={prof.activity:.4f};"
         f"prefill_act={summ.prefill_activity:.4f};"
         f"decode_act={summ.decode_activity:.4f};"
         f"phase_weights={summ.phase_weights};"
         f"bucket_hit_rate={summ.bucket_hit_rate:.3f};"
         f"stall_frac={summ.stall_frac:.3f}")

    phases = phases_from_trace(trace_tr, name="bursty")
    tune_params = calibrate()
    cache = SweepExecutableCache()
    t0 = time.perf_counter()
    tuned = chip.tune_chip(phases, params=tune_params, cache=cache,
                           area_budget_mm2=AREA_BUDGET_MM2,
                           tdp_budget_mw=TDP_BUDGET_MW, name="trace_die")
    tune_us = (time.perf_counter() - t0) * 1e6
    for row in tuned.report["units"]:
        assert not any(abs(row["activity"] - h) < 1e-3
                       for h in HAND_SET_ACTIVITIES), (
            f"tuned unit {row['unit']} ran at a hand-set activity "
            f"{row['activity']} — trace-derived profile was dropped")
        emit("telemetry_bench.tuned_unit", 0.0,
             f"{row['unit']}={row['design']}@{row['vdd']:.3f}V;"
             f"activity={row['activity']:.4f};"
             f"bb_saving={row['adaptive_bb_saving']:.2f}x")
    emit("telemetry_bench.tune_from_trace", tune_us,
         f"n_units={len(tuned.spec.units)};"
         f"chip_gflops_per_w={tuned.spec.gflops_per_w:.0f}")

    path = append_trajectory("telemetry_bench.json", dict(
        ts=time.strftime("%Y-%m-%dT%H:%M:%S"),
        arch=ARCH, slots=SLOTS, dispatch_tokens=DISPATCH_TOKENS,
        tok_per_s_disabled=tps_off,
        tok_per_s_enabled=tps_on,
        overhead_frac=overhead,
        trace_spans=len(trace_tr.spans),
        trace_requests=summ.n_requests,
        trace_completed=summ.n_completed,
        trace_energy_j=summ.energy_j,
        measured_activity=float(prof.activity),
        prefill_activity=float(summ.prefill_activity),
        decode_activity=float(summ.decode_activity),
        phase_weights={k: float(v) for k, v in summ.phase_weights.items()},
        bucket_hit_rate=float(summ.bucket_hit_rate),
        tune_from_trace_s=tune_us / 1e6,
        tuned_units=[dict(unit=r["unit"], design=r["design"],
                          activity=float(r["activity"]))
                     for r in tuned.report["units"]],
    ))
    emit("telemetry_bench.trajectory", 0.0, f"appended={path}")
    return overhead


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
