"""Chaos harness: seeded fault scenarios through the resilient serving
engine, with the degrade-don't-drop invariants asserted hard.

Drives the same request wave through a tiered two-fleet die (a cheap fp8
unit + an accurate FP32 unit) under four seeded scenarios:

  * ``baseline``  — fault-free run (the energy reference);
  * ``kill``      — the cheap unit dies mid-run with in-flight traffic:
    every affected request must complete on the surviving fleet with output
    bitwise-identical to ``greedy_decode``, zero requests lost; records
    the recovery latency (fault detection -> every drained request
    re-seated) and the energy overhead of degraded routing (continuations
    re-prefill + replay committed tokens on the expensive unit);
  * ``throttle``  — a thermal derate on the cheap unit: the trailing-median
    watchdog must detect it from dispatch timings alone and reprice the
    unit's energy (leakage energy/FLOP grows with the derate);
  * ``corrupt``   — a transient NaN-burst on the cheap unit: bounded retry
    with backoff must ride it out on the same fleet, committing no
    corrupted token, still losing nothing.

Appends one record to ``results/resilience_bench.json`` per run; the CI
guard watches ``completed_frac`` (any lost request drags it below the
floor and fails the build — it is asserted to 1.0 here first anyway).

Run: PYTHONPATH=src python benchmarks/resilience_bench.py
"""
import time

import jax
import numpy as np

from repro.configs.base import get_config
from repro.core import chip
from repro.core.energy_model import calibrate
from repro.core.formats import FP32, FP8_E4M3
from repro.core.fpu_arch import FABRICATED
from repro.faults import FaultEvent, FaultInjector, FaultKind
from repro.models import LM
from repro.serve.engine import Request, greedy_decode
from repro.serve.resilience import ResilienceConfig, ResilientServer

from bench_lib import append_trajectory, emit

ARCH = "tinyllama-1.1b"
SLOTS = 4
MAX_LEN = 64
N_REQUESTS = 8
NEW_TOKENS = 12
DISPATCH_TOKENS = 4
PROMPT_LENS = (4, 7, 5, 9, 6, 8, 4, 7)
TICK_S = 0.05  # simulated seconds per step (== synthetic dispatch time)


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _unit(name, fmt, rel_err, e_pj):
    metrics = dict(freq_ghz=1.0, cycle_ns=1.0, p_total_mw=2e3 * e_pj,
                   area_mm2=0.01, gflops_per_w=1.0 / (e_pj * 1e-3),
                   gflops_per_mm2=200.0, e_eff_pj=e_pj, rel_err=rel_err,
                   avg_latency_penalty=0.0)
    return chip.ChipUnit(name, FABRICATED["sp_cma"], 0.8, 1.2,
                         metrics=metrics, fmt=fmt)


def make_requests(cfg):
    rng = np.random.default_rng(7)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        PROMPT_LENS[i % len(PROMPT_LENS)]
                                        ).astype(np.int32),
                    max_new_tokens=NEW_TOKENS, accuracy_slo=5e-2)
            for i in range(N_REQUESTS)]


def run_scenario(model, params, cfg, events, *, probe=None,
                 max_steps=400):
    """One chaos run; returns (server, requests, sim seconds)."""
    spec = chip.ChipSpec("tiered", (_unit("decode_eco", FP8_E4M3, 1e-2, 0.5),
                                    _unit("decode_gold", FP32, 1e-8, 4.0)))
    policy = chip.ChipPolicy(spec, calibrate())
    clock = _Clock()
    injector = FaultInjector(events, seed=7) if events else None
    server = ResilientServer(
        model, params, slots=SLOTS, max_len=MAX_LEN, chip_policy=policy,
        accuracy_fleets=(5e-2, 1e-7), dispatch_tokens=DISPATCH_TOKENS,
        clock=clock, injector=injector,
        resilience=ResilienceConfig(synthetic_dispatch_s=TICK_S,
                                    probe_interval_s=probe))
    reqs = make_requests(cfg)
    for r in reqs:
        server.submit(r)
    for _ in range(max_steps):
        clock.t += TICK_S
        server.step()
        if server.idle():
            break
    return server, reqs, clock.t


def run():
    cfg = get_config(ARCH).reduced()
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    refs = [greedy_decode(model, params, r.prompt, NEW_TOKENS,
                          max_len=MAX_LEN)
            for r in make_requests(cfg)]

    def check(tag, server, reqs):
        done = {r.uid for r in server.finished if r.done}
        lost = [r.uid for r in reqs if r.uid not in done]
        assert not lost, f"{tag}: requests lost: {lost}"
        for r, ref in zip(reqs, refs):
            assert r.output == ref, \
                f"{tag}: uid {r.uid} diverged from greedy_decode"
        return len(done) / len(reqs)

    # --- baseline: fault-free energy reference
    base_srv, base_reqs, _ = run_scenario(model, params, cfg, ())
    check("baseline", base_srv, base_reqs)
    base_j = sum(r.energy_j for r in base_reqs)
    emit("resilience_bench.baseline", 0.0, f"energy_j={base_j:.3e}")

    # --- kill: cheap fleet dies mid-run, traffic in flight
    kill_srv, kill_reqs, _ = run_scenario(
        model, params, cfg,
        (FaultEvent(at_s=3 * TICK_S, unit="decode_eco",
                    kind=FaultKind.KILL),))
    completed_frac = check("kill", kill_srv, kill_reqs)
    rep = kill_srv.resilience_report()
    kill_j = sum(r.energy_j for r in kill_reqs)
    overhead = kill_j / base_j - 1.0
    recovery_s = rep["recovery_latency_s"]["max"]
    migrated = sum(1 for r in kill_reqs if r.requeues)
    assert rep["recovery_latency_s"]["n"] >= 1, "kill never detected"
    emit("resilience_bench.kill", recovery_s * 1e6,
         f"recovery_s={recovery_s:.3f};migrated={migrated};"
         f"energy_overhead={overhead:.2f}")

    # --- throttle: thermal derate detected from timings, energy repriced
    thr_srv, thr_reqs, _ = run_scenario(
        model, params, cfg,
        (FaultEvent(at_s=3 * TICK_S, unit="decode_eco",
                    kind=FaultKind.THROTTLE, magnitude=0.4),))
    check("throttle", thr_srv, thr_reqs)
    thr_rep = thr_srv.resilience_report()
    throttles = [r for r in thr_rep["fault_log"]
                 if r["kind"] == FaultKind.THROTTLE]
    assert throttles, "throttle never detected by the watchdog"
    eco_scale = thr_rep["health"]["decode_eco"]["energy_scale"]
    assert eco_scale > 1.0, "throttle detected but energy not repriced"
    emit("resilience_bench.throttle", 0.0,
         f"detected={len(throttles)};energy_scale={eco_scale:.2f}")

    # --- corrupt: transient NaN burst ridden out by bounded retry
    cor_srv, cor_reqs, _ = run_scenario(
        model, params, cfg,
        (FaultEvent(at_s=3 * TICK_S, unit="decode_eco",
                    kind=FaultKind.CORRUPT, duration_s=4 * TICK_S,
                    magnitude=1.0),),
        probe=1.0)
    check("corrupt", cor_srv, cor_reqs)
    cor_rep = cor_srv.resilience_report()
    n_corrupt = sum(cor_rep["corrupt_dispatches"].values())
    assert n_corrupt >= 1, "corruption never observed"
    emit("resilience_bench.corrupt", 0.0,
         f"corrupt_dispatches={n_corrupt};"
         f"wasted_j={cor_srv.wasted_energy_j:.3e}")

    path = append_trajectory("resilience_bench.json", dict(
        ts=time.strftime("%Y-%m-%dT%H:%M:%S"),
        arch=ARCH, slots=SLOTS, requests=N_REQUESTS,
        new_tokens=NEW_TOKENS, dispatch_tokens=DISPATCH_TOKENS,
        requests_lost=0,
        completed_frac=completed_frac,
        outputs_identical=True,
        kill_recovery_latency_s=recovery_s,
        kill_requests_migrated=migrated,
        degraded_energy_overhead_frac=overhead,
        throttle_energy_scale=eco_scale,
        corrupt_dispatches=n_corrupt,
        corrupt_wasted_energy_j=cor_srv.wasted_energy_j,
    ))
    emit("resilience_bench.trajectory", 0.0, f"appended={path}")
    return completed_frac


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
