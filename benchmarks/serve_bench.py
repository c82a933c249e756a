"""Serving hot-path benchmark: device-resident fused engine vs the seed
per-token engine, plus the chunked-prefill long-prompt storm.

Drives identical request waves through ``ReferenceServer`` (the seed: one
host sync + one energy charge per decoded token, eager single-prompt
prefill, full cache rebuild per admission) and ``BatchedServer`` (fused
N-token decode dispatches over donated device-resident state, bucketed
batched prefill).  Measures:

  * warm decode tokens/sec at 8 slots (the headline: the fused engine must
    sustain >=5x the seed);
  * host syncs per decoded token (the fused engine budgets <=1 per N-token
    dispatch plus one per admitted batch);
  * output equivalence — both engines must produce bit-identical token
    streams for every request;
  * **long-prompt storm** — a mixed trace of interactive shorts and long
    prompts replayed in deterministic simulated time (``StepCost``: the
    clock advances by each step's measured token work) against monolithic
    admission vs chunked prefill (``prefill_chunk=16``).  Both engines
    must produce bitwise-identical streams; chunked must cut the
    interactive class's p99 time-to-first-token by >= 3x (monolithic
    admission serializes a whole long prefill ahead of every lane;
    chunking bounds the blocking quantum at one chunk).  Records
    ``p99_ttft_s`` and ``decode_stall_frac`` for the regression guard.

Appends one record to ``results/serve_bench.json`` per run.

Run: PYTHONPATH=src python benchmarks/serve_bench.py
"""
import time

import jax
import numpy as np

from repro.cluster import SimClock, StepCost, latency_stats
from repro.cluster.loadgen import Arrival, replay
from repro.configs.base import get_config
from repro.models import LM
from repro.serve.engine import BatchedServer, ReferenceServer, Request

from bench_lib import append_trajectory, emit

ARCH = "tinyllama-1.1b"
SLOTS = 8
MAX_LEN = 64
N_REQUESTS = 16
NEW_TOKENS = 24
DISPATCH_TOKENS = 12
PROMPT_LENS = (5, 9, 6, 12, 7, 11, 8, 10)  # two admission buckets


def make_requests(cfg, uid0=0):
    rng = np.random.default_rng(uid0 + 1)
    return [Request(uid=uid0 + i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        PROMPT_LENS[i % len(PROMPT_LENS)]
                                        ).astype(np.int32),
                    max_new_tokens=NEW_TOKENS)
            for i in range(N_REQUESTS)]


def drive(server, reqs, *, dispatch_tokens=None):
    """Submit one wave and serve it to completion; returns (tokens, secs)."""
    for r in reqs:
        server.submit(r)
    t0 = time.perf_counter()
    if dispatch_tokens is None:  # seed engine: per-token steps
        for _ in range(10_000):
            if server.step() == 0:
                break
    else:
        server.run(dispatch_tokens=dispatch_tokens)
    dt = time.perf_counter() - t0
    assert all(r.done for r in reqs)
    return sum(len(r.output) for r in reqs), dt


# --- long-prompt storm (chunked prefill vs monolithic admission) ----------
STORM_SLOTS = 10
STORM_MAX_LEN = 512
STORM_CHUNK = 16
STORM_DISPATCH = 4
STORM_LONG_LEN = 448
STORM_LONG_AT = (0.1, 0.4, 0.7, 1.0)
STORM_SHORT_LENS = (5, 6, 7, 8)
STORM_SHORTS = 12
STORM_SHORT_EVERY_S = 0.1
STORM_NEW_TOKENS = 8
STORM_TICK_S = 2e-3
STORM_COST = StepCost(t_prefill_token_s=1e-3, t_decode_token_s=1e-3)


def storm_trace(cfg):
    """The seeded mixed trace: long prompts landing on top of a steady
    interactive stream, with short arrivals co-timed with the long ones so
    the monolithic engine's admission-blocking quantum is deterministically
    observed (a short submitted in the same step as a long admission eats
    the whole long prefill in its TTFT).  Returns (arrivals, interactive
    uids)."""
    rng = np.random.default_rng(42)
    arrivals, uid = [], 0
    for at in STORM_LONG_AT:
        req = Request(uid=uid, max_new_tokens=STORM_NEW_TOKENS,
                      prompt=rng.integers(0, cfg.vocab_size, STORM_LONG_LEN)
                      .astype(np.int32))
        arrivals.append(Arrival(at_s=at, cls="long", request=req))
        uid += 1
    for i in range(STORM_SHORTS):
        plen = STORM_SHORT_LENS[i % len(STORM_SHORT_LENS)]
        req = Request(uid=uid, max_new_tokens=STORM_NEW_TOKENS,
                      prompt=rng.integers(0, cfg.vocab_size, plen)
                      .astype(np.int32))
        arrivals.append(Arrival(at_s=(i + 1) * STORM_SHORT_EVERY_S,
                                cls="short", request=req))
        uid += 1
    shorts = {a.request.uid for a in arrivals if a.cls == "short"}
    return arrivals, shorts


def run_storm(model, cfg, params):
    """Replay the storm against monolithic and chunked engines; returns the
    metrics dict (bitwise equality hard-asserted)."""
    out = {}
    for mode, kw in [("mono", {}),
                     ("chunked", dict(prefill_chunk=STORM_CHUNK))]:
        clock = SimClock()
        server = BatchedServer(model, params, slots=STORM_SLOTS,
                               max_len=STORM_MAX_LEN,
                               dispatch_tokens=STORM_DISPATCH,
                               clock=clock, **kw)
        arrivals, shorts = storm_trace(cfg)
        rep = replay(server, arrivals, clock, tick_s=STORM_TICK_S,
                     dispatch_tokens=STORM_DISPATCH, cost=STORM_COST)
        assert not rep["rejected"] and not rep["expired"]
        assert len(rep["finished"]) == len(arrivals)
        st = latency_stats(
            rep["latency_s"],
            {u: t for u, t in rep["ttft_s"].items() if u in shorts})
        out[mode] = dict(
            outputs={r.uid: tuple(r.output) for r in rep["finished"]},
            p99_ttft_s=st["p99_ttft_s"],
            stall=server.decode_stall_frac)
    assert out["mono"]["outputs"] == out["chunked"]["outputs"], \
        "chunked prefill diverged from the monolithic token streams"
    gain = out["mono"]["p99_ttft_s"] / max(out["chunked"]["p99_ttft_s"],
                                           1e-12)
    emit("serve_bench.storm", out["chunked"]["p99_ttft_s"] * 1e6,
         f"p99_ttft_chunked_s={out['chunked']['p99_ttft_s']:.4f};"
         f"p99_ttft_mono_s={out['mono']['p99_ttft_s']:.4f};"
         f"ttft_gain={gain:.2f}x;"
         f"stall_chunked={out['chunked']['stall']:.3f};"
         f"stall_mono={out['mono']['stall']:.3f}")
    assert gain >= 3.0, (
        f"chunked prefill must cut interactive p99 TTFT >= 3x "
        f"(got {gain:.2f}x)")
    assert out["chunked"]["stall"] < out["mono"]["stall"]
    return dict(
        p99_ttft_s=out["chunked"]["p99_ttft_s"],
        decode_stall_frac=out["chunked"]["stall"],
        p99_ttft_mono_s=out["mono"]["p99_ttft_s"],
        decode_stall_frac_mono=out["mono"]["stall"],
        ttft_gain=gain, prefill_chunk=STORM_CHUNK,
        storm_long_len=STORM_LONG_LEN, storm_shorts=STORM_SHORTS)


def run():
    cfg = get_config(ARCH).reduced()
    model = LM(cfg)
    params = model.init(jax.random.key(0))

    # --- seed per-token engine: cold wave compiles, then warm waves
    ref = ReferenceServer(model, params, slots=SLOTS, max_len=MAX_LEN)
    ref_out = {r.uid % 100: r.output
               for r in (lambda rs: (drive(ref, rs), rs)[1])(
                   make_requests(cfg))}
    ref_tps = 0.0
    for wave in (100, 200):
        toks, dt = drive(ref, make_requests(cfg, wave))
        ref_tps = max(ref_tps, toks / dt)
    emit("serve_bench.reference_warm", 1e6 / ref_tps,
         f"tok_per_s={ref_tps:.1f};slots={SLOTS}")

    # --- fused device-resident engine
    fused = BatchedServer(model, params, slots=SLOTS, max_len=MAX_LEN,
                          dispatch_tokens=DISPATCH_TOKENS)
    cold = make_requests(cfg)
    drive(fused, cold, dispatch_tokens=DISPATCH_TOKENS)
    fused_out = {r.uid % 100: r.output for r in cold}
    fused_tps, syncs_per_tok = 0.0, 0.0
    for wave in (100, 200):
        s0, t0 = fused.host_syncs, fused.tokens_decoded
        toks, dt = drive(fused, make_requests(cfg, wave),
                         dispatch_tokens=DISPATCH_TOKENS)
        if toks / dt > fused_tps:
            fused_tps = toks / dt
            syncs_per_tok = (fused.host_syncs - s0) / (fused.tokens_decoded
                                                       - t0)
    emit("serve_bench.fused_warm", 1e6 / fused_tps,
         f"tok_per_s={fused_tps:.1f};dispatch_tokens={DISPATCH_TOKENS};"
         f"host_syncs_per_token={syncs_per_tok:.3f}")

    identical = ref_out == fused_out
    speedup = fused_tps / ref_tps
    emit("serve_bench.speedup", 0.0,
         f"speedup={speedup:.1f}x;outputs_identical={identical}")
    assert identical, "fused engine diverged from the seed token streams"

    storm = run_storm(model, cfg, params)

    path = append_trajectory("serve_bench.json", dict(
        ts=time.strftime("%Y-%m-%dT%H:%M:%S"),
        arch=ARCH, slots=SLOTS, max_len=MAX_LEN,
        requests=N_REQUESTS, new_tokens=NEW_TOKENS,
        dispatch_tokens=DISPATCH_TOKENS,
        reference_tok_per_s=ref_tps,
        fused_tok_per_s=fused_tps,
        speedup_warm=speedup,
        host_syncs_per_token=syncs_per_tok,
        outputs_identical=bool(identical),
        **storm,
    ))
    emit("serve_bench.trajectory", 0.0, f"appended={path}")
    return speedup


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
