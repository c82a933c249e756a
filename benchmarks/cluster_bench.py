"""Cluster serving bench: a heterogeneous two-die cluster under a seeded
bursty/diurnal open-loop trace, with the degrade-don't-drop invariants
asserted hard.

Two scenarios over the same trace (simulated time throughout — every
number is machine-independent and deterministic for the seed):

  * ``steady``  — both dies up: reports p50/p99 request latency,
    energy-per-request, and per-die utilization; every request must
    complete with output bitwise-identical to ``greedy_decode``;
  * ``die-kill`` — the cheap die is killed mid-trace with traffic in
    flight: the router must evacuate and re-admit its requests on the
    surviving die (continuation replay — still bitwise-identical), zero
    requests lost.

Appends one record to ``results/cluster_bench.json``; the CI guard
watches ``p99_latency_s`` and ``energy_per_request_j`` (lower is better)
and ``completed_frac`` (must stay 1.0).

Run: PYTHONPATH=src python benchmarks/cluster_bench.py
"""
import time

import jax

from repro.cluster import (ClusterRouter, ClusterSpec, RequestClass,
                           SimClock, TraceConfig, generate, latency_stats,
                           replay)
from repro.configs.base import get_config
from repro.core import chip
from repro.core.formats import FP32, FP8_E4M3
from repro.core.fpu_arch import FABRICATED
from repro.models import LM
from repro.serve.engine import greedy_decode

from bench_lib import append_trajectory, emit

ARCH = "tinyllama-1.1b"
SLOTS = 4           # per die
MAX_LEN = 64
DISPATCH_TOKENS = 4
PREFILL_CHUNK = 16  # continuous batching on every die replica
TICK_S = 0.05       # simulated seconds per engine step
HORIZON_S = 20.0
BASE_RATE_RPS = 0.9
SEED = 7
FAIL_AT_S = 4.0     # die-kill scenario: kill the eco die here

TRACE = TraceConfig(
    horizon_s=HORIZON_S, base_rate_rps=BASE_RATE_RPS,
    diurnal_amplitude=0.6, diurnal_period_s=12.0,
    burst_multiplier=3.0, burst_on_s=1.5, burst_off_s=5.0,
    seed=SEED,
    classes=(
        # loose accuracy, bulk: the eco die's traffic
        RequestClass("loose_bulk", weight=3, prompt_lens=(4, 6, 8, 10),
                     max_new_tokens=10, accuracy_slo=5e-2),
        # tight accuracy, deadline-bound: the gold die's traffic
        # (slack is generous — the invariant here is zero loss, not SLO
        # attainment; deadline attainment under overload is serve_bench's
        # shed_unmeetable territory)
        RequestClass("tight_interactive", weight=1, prompt_lens=(5, 7, 9),
                     max_new_tokens=8, accuracy_slo=1e-7,
                     deadline_slack_s=120.0),
    ))


def _unit(name, fmt, rel_err, e_pj):
    metrics = dict(freq_ghz=1.0, cycle_ns=1.0, p_total_mw=2e3 * e_pj,
                   area_mm2=0.01, gflops_per_w=1.0 / (e_pj * 1e-3),
                   gflops_per_mm2=200.0, e_eff_pj=e_pj, rel_err=rel_err,
                   avg_latency_penalty=0.0)
    return chip.ChipUnit(name, FABRICATED["sp_cma"], 0.8, 1.2,
                         metrics=metrics, fmt=fmt)


def make_cluster() -> ClusterSpec:
    """Two dies with different unit/format mixes: a cheap fp8 eco die and
    an accurate FP32 gold die."""
    return ClusterSpec("eco+gold", (
        chip.ChipSpec("eco", (_unit("decode_eco", FP8_E4M3, 1e-2, 0.5),)),
        chip.ChipSpec("gold", (_unit("decode_gold", FP32, 1e-8, 4.0),))))


def make_router(model, params, clock):
    # prefill_chunk rides through **server_kw to every die replica; at this
    # trace's prompt lengths (4-10 tokens) every prompt is a single chunk,
    # so the latency/energy trajectory is identical to monolithic admission
    # while exercising the continuous-batching scheduler cluster-wide
    return ClusterRouter(model, params, make_cluster(), slots=SLOTS,
                         max_len=MAX_LEN, clock=clock,
                         accuracy_fleets=(5e-2, 1e-7),
                         dispatch_tokens=DISPATCH_TOKENS,
                         prefill_chunk=PREFILL_CHUNK)


def check_bitwise(tag, trace, finished, refs):
    done = {r.uid: r for r in finished if r.done and not r.expired}
    lost = [a.request.uid for a in trace if a.request.uid not in done]
    assert not lost, f"{tag}: requests lost: {lost}"
    for a in trace:
        got = done[a.request.uid].output
        assert got == refs[a.request.uid], \
            f"{tag}: uid {a.request.uid} diverged from greedy_decode"
    return len(done) / len(trace)


def run():
    cfg = get_config(ARCH).reduced()
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    trace = generate(TRACE, cfg.vocab_size)
    n_bursty = sum(1 for a in trace if a.cls == "loose_bulk")
    emit("cluster_bench.trace", 0.0,
         f"arrivals={len(trace)};loose={n_bursty};"
         f"tight={len(trace) - n_bursty}")
    refs = {a.request.uid: greedy_decode(model, params, a.request.prompt,
                                         a.request.max_new_tokens,
                                         max_len=MAX_LEN)
            for a in trace}

    # --- steady: both dies up for the whole trace
    clock = SimClock()
    router = make_router(model, params, clock)
    rep = replay(router, trace, clock, tick_s=TICK_S,
                 dispatch_tokens=DISPATCH_TOKENS)
    completed_frac = check_bitwise("steady", trace, rep["finished"], refs)
    st = latency_stats(rep["latency_s"], rep["ttft_s"])
    energy = router.energy_report()
    util = router.utilization_report()
    e_per_req = energy["total_j"] / len(trace)
    # cluster-wide decode-stall fraction: pool the per-die counters
    sp = sum(s._stall_prefill_tokens for s in router.servers.values())
    cd = sum(s._contended_decode_tokens for s in router.servers.values())
    stall = sp / max(sp + cd, 1)
    assert completed_frac == 1.0
    assert not router.rejected and not router._parked
    emit("cluster_bench.steady", st["p99_s"] * 1e6,
         f"p50={st['p50_s']:.3f}s;p99={st['p99_s']:.3f}s;"
         f"p99_ttft={st['p99_ttft_s']:.3f}s;stall={stall:.3f};"
         f"e_per_req={e_per_req:.3e}J;"
         f"util_eco={util['eco']:.3f};util_gold={util['gold']:.3f}")

    # --- die-kill: the eco die dies mid-trace, traffic in flight
    # (a fresh deterministic trace: the steady run mutated its Request
    # objects — same seed, same arrivals, same prompts)
    trace_k = generate(TRACE, cfg.vocab_size)
    clock_k = SimClock()
    router_k = make_router(model, params, clock_k)
    pre = [a for a in trace_k if a.at_s < FAIL_AT_S]
    post = [a for a in trace_k if a.at_s >= FAIL_AT_S]
    rep_pre = replay(router_k, pre, clock_k, tick_s=TICK_S,
                     dispatch_tokens=DISPATCH_TOKENS,
                     max_steps=int(FAIL_AT_S / TICK_S))
    evacuated = router_k.fail_chip("eco")
    rep_k = replay(router_k, post, clock_k, tick_s=TICK_S,
                   dispatch_tokens=DISPATCH_TOKENS,
                   carryover={a.request.uid: a.at_s for a in pre})
    finished_k = rep_pre["finished"] + rep_k["finished"]
    kill_frac = check_bitwise("die-kill", trace_k, finished_k, refs)
    assert kill_frac == 1.0
    assert evacuated, "kill landed on an idle die: no in-flight traffic"
    migrated = sum(1 for a in trace_k if a.request.requeues)
    assert migrated >= len(evacuated)
    # with the eco die gone, everything after the kill serves on gold
    for a in post:
        assert a.request.routed_unit == "decode_gold", a.request.uid
    st_k = latency_stats({**rep_pre["latency_s"], **rep_k["latency_s"]})
    energy_k = router_k.energy_report()
    overhead = energy_k["total_j"] / energy["total_j"] - 1.0
    util_k = router_k.utilization_report()
    assert util_k["gold"] > util["gold"], \
        "killed-die traffic never reached the survivor"
    emit("cluster_bench.die_kill", st_k["p99_s"] * 1e6,
         f"evacuated={len(evacuated)};migrated={migrated};"
         f"energy_overhead={overhead:.2f};p99={st_k['p99_s']:.3f}s")

    path = append_trajectory("cluster_bench.json", dict(
        ts=time.strftime("%Y-%m-%dT%H:%M:%S"),
        arch=ARCH, dies=2, slots_per_die=SLOTS,
        arrivals=len(trace), horizon_s=HORIZON_S,
        base_rate_rps=BASE_RATE_RPS, seed=SEED,
        requests_lost=0,
        completed_frac=completed_frac,
        outputs_identical=True,
        p50_latency_s=st["p50_s"],
        p99_latency_s=st["p99_s"],
        p50_ttft_s=st["p50_ttft_s"],
        p99_ttft_s=st["p99_ttft_s"],
        decode_stall_frac=stall,
        prefill_chunk=PREFILL_CHUNK,
        energy_per_request_j=e_per_req,
        utilization={k: round(v, 4) for k, v in util.items()},
        kill_requests_migrated=migrated,
        kill_energy_overhead_frac=overhead,
        kill_p99_latency_s=st_k["p99_s"],
    ))
    emit("cluster_bench.trajectory", 0.0, f"appended={path}")
    return completed_frac


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
