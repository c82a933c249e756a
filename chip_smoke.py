"""Bring-up smoke test: the serving path and the transprecision kernels on a TPU.

  python chip_smoke.py              # one chip: serving, energy, kernels
  python chip_smoke.py --chips 4    # four chips: ClusterRouter, 4 dies vs 1

The one-chip run builds zamba2-1.2b at its published config (random bf16
weights from ``--seed``) and serves seeded requests through ``BatchedServer``
with the fabricated FPMax die's chip policy; checks the chunked prefill's
first-token logits against ``LM.prefill``; and runs every transprecision
kernel of ``repro.numerics`` with ``impl="auto"`` at model widths, checking
that each program holds a Mosaic kernel and agrees with its jnp twin.  The
``--chips 4`` run serves the same requests through a 4-die ``ClusterRouter``,
one die per device, and through a 1-die router, and compares the outputs.

Progress goes to stdout line by line.  The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed.  A failed phase makes the script exit
non-zero after the remaining phases ran; without a TPU it exits at once.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "zamba2-1.2b"
# bf16 keeps 8 significant bits (unit roundoff u = 2**-9).  Chunked and
# monolithic prefill run the same layers as different XLA programs, whose f32
# sums may be associated differently and rounded to bf16 at different points:
# a few roundings per layer, compounding over 38 layers: 2.7% of the logits'
# norm on a TPU v5e at seed 0.  A lost carry moves them further: the phase
# prints, as a control, how far the prompt without its first 256-token chunk
# lands (9.7% on the same run), and 5% sits between the two.
PREFILL_LOGIT_RTOL = 5e-2


class Phases:
    """Runs phases in order; records failures instead of stopping at one."""

    def __init__(self):
        self.failed: list[str] = []

    def run(self, name, fn, *args, **kw):
        print(f"== {name}", flush=True)
        t0 = time.perf_counter()
        try:
            out = fn(*args, **kw)
        except Exception:  # report and go on: the script still exits 1
            traceback.print_exc(file=sys.stdout)
            self.failed.append(name)
            print(f"== {name}: FAILED", flush=True)
            return None
        print(f"== {name}: ok in {time.perf_counter() - t0:.1f}s", flush=True)
        return out


def check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")


class CompileCounter:
    """Counts XLA backend compiles and their seconds, process-wide."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.n, self.seconds = 0, 0.0
        event = dispatch.BACKEND_COMPILE_EVENT

        def listen(name, secs, **_):
            if name == event:
                self.n += 1
                self.seconds += secs

        jax.monitoring.register_event_duration_secs_listener(listen)


def normwise_rel(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-300))


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def make_requests(vocab: int, *, n: int, lengths: tuple, new_tokens: int,
                  seed: int):
    """``n`` seeded requests, prompt lengths uniform in ``lengths``, the sp/dp
    precision mix of examples/serve_decode.py."""
    import numpy as np

    from repro.serve.engine import Request
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, vocab, int(rng.integers(
                        lengths[0], lengths[1] + 1))).astype(np.int32),
                    max_new_tokens=new_tokens,
                    precision="dp" if i % 3 == 0 else "sp")
            for i in range(n)]


def serving_phase(model, params, tech, *, slots: int, max_len: int,
                  prefill_chunk: int, dispatch_tokens: int, requests):
    """Serve ``requests`` through ``BatchedServer`` under the fabricated
    FPMax die's chip policy; gate on completion and on the energy report."""
    from repro.core.chip import ChipPolicy, fabricated_chip
    from repro.serve.engine import BatchedServer

    policy = ChipPolicy(fabricated_chip(None, tech), tech)
    server = BatchedServer(model, params, slots=slots, max_len=max_len,
                           chip_policy=policy, prefill_chunk=prefill_chunk,
                           dispatch_tokens=dispatch_tokens)
    for r in requests:
        server.submit(r)
    t0 = time.perf_counter()
    finished = server.run(max_steps=10_000)
    wall = time.perf_counter() - t0
    n_tok = sum(len(r.output) for r in requests)
    print(f"served {len(finished)}/{len(requests)} requests, {n_tok} tokens, "
          f"prompts {[len(r.prompt) for r in requests]}, "
          f"{server.dispatches} decode dispatches, {server.host_syncs} host "
          f"syncs, wall {wall:.2f}s (compiles included)")
    check(len(finished) == len(requests), "every request finished")
    for r in requests:
        check(len(r.output) == r.max_new_tokens,
              f"request {r.uid}: {len(r.output)} tokens, "
              f"want {r.max_new_tokens}")
    rep = server.energy_report()
    print(f"energy: total {rep['total_j']!r} J, {rep['j_per_token']!r} "
          f"J/token, per unit {rep['per_unit_j']}")
    check(math.isfinite(rep["total_j"]) and rep["total_j"] > 0,
          "energy total finite and positive")
    check(all(math.isfinite(v) and v > 0 for v in rep["per_unit_j"].values()),
          "per-unit energies finite and positive")
    return {r.uid: list(r.output) for r in requests}


def prefill_logits_phase(model, params, prompt, *, chunk: int):
    """First-token logits of the chunked prefill vs ``LM.prefill``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tokens = jnp.asarray(prompt[None])
    mono = jax.jit(model.prefill)(params, tokens)[0]
    chunked = jax.jit(model.prefill_chunked, static_argnums=2)(
        params, tokens, chunk)[0]
    mono = np.asarray(mono, np.float32)
    chunked = np.asarray(chunked, np.float32)
    rel = normwise_rel(chunked, mono)
    print(f"prefill logits, prompt {len(prompt)} tokens in chunks of {chunk}: "
          f"normwise rel err {rel!r} (tol {PREFILL_LOGIT_RTOL}), max abs "
          f"{float(np.abs(chunked - mono).max())!r}, |logits|max "
          f"{float(np.abs(mono).max())!r}, bitwise "
          f"{bool(np.array_equal(chunked, mono))}, same argmax "
          f"{bool(np.argmax(chunked) == np.argmax(mono))}")
    # what a lost carry looks like: the same prompt without its first chunk
    lost = np.asarray(jax.jit(model.prefill)(params, tokens[:, chunk:])[0],
                      np.float32)
    print(f"control, prompt without its first chunk: normwise rel err "
          f"{normwise_rel(lost, mono)!r}")
    check(np.isfinite(mono).all() and np.isfinite(chunked).all(),
          "finite logits")
    check(rel <= PREFILL_LOGIT_RTOL, "chunked prefill logits agree")


def greedy_share_phase(model, params, requests, outputs, *, max_len: int):
    """Share of served tokens equal to ``greedy_decode``'s (a finding: the
    server and the reference run differently batched bf16 programs)."""
    from repro.serve.engine import greedy_decode
    same = total = 0
    for r in requests:
        ref = greedy_decode(model, params, r.prompt, r.max_new_tokens,
                            max_len=max_len)
        got = outputs[r.uid]
        n = sum(a == b for a, b in zip(got, ref))
        first = next((i for i, (a, b) in enumerate(zip(got, ref)) if a != b),
                     None)
        print(f"request {r.uid} ({len(r.prompt)} prompt tokens): {n}/"
              f"{len(ref)} tokens equal to greedy_decode, first mismatch at "
              f"{first}")
        same += n
        total += len(ref)
    print(f"tokens equal to greedy_decode: {same}/{total} "
          f"({same / max(total, 1):.3f})")


# ---------------------------------------------------------------------------
# transprecision kernels
# ---------------------------------------------------------------------------
def _compare(name, compiled, twin, args, *, rtol: float, bitwise_meant: bool):
    """Run a compiled program and its jnp twin on the same arguments."""
    import jax
    import numpy as np
    got = jax.tree.leaves(compiled(*args))
    want = jax.tree.leaves(twin(*args))
    rec = {"name": name, "kernel": "tpu_custom_call" in compiled.as_text()}
    rels, same = [], True
    for g, w in zip(got, want):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        check(np.isfinite(g).all(), f"{name}: finite output")
        same &= bool(np.array_equal(g.view(np.uint32), w.view(np.uint32)))
        rels.append(normwise_rel(g, w))
    rec.update(bitwise=same, rel=max(rels))
    print(f"{name}: mosaic kernel {rec['kernel']}, bitwise "
          f"{'(meant) ' if bitwise_meant else ''}{same}, normwise rel "
          f"{rec['rel']!r} (tol {rtol!r})")
    check(rec["rel"] <= rtol, f"{name}: agrees with its twin")
    return rec


def transprecision_phase(*, qmm, flash, ssm, quant, seed: int):
    """Every ``repro.numerics`` transprecision entry point, ``impl='auto'``,
    against its jnp twin.  Shapes: qmm ((B, M, K), (K, N)); flash
    (B, S, H, D); ssm (B, S, D, N); quant (M, N)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.formats import BF16, FP8_E4M3
    from repro.numerics import (emulated_flash_attention, emulated_matmul,
                                emulated_ssm_scan, quantize_tensor)

    rng = np.random.default_rng(seed)
    recs = []

    def lower(fn, *args):
        return jax.jit(fn).lower(*args).compile()

    (B, M, K), (_, N) = qmm
    a = jnp.asarray(rng.standard_normal(qmm[0]), jnp.float32)
    b = jnp.asarray(rng.standard_normal(qmm[1]), jnp.float32)
    # scaled data sits in one binade, [2**-8, 2**-7), below fp8_e4m3's
    # normal range, so the kernel scales it up; every 128x128 tile then takes
    # the scale of the whole matrix, which is what the single-tile twin
    # applies.  It is positive so that the sums, which cascade rounds to the
    # format, stay clear of fp8's flush-to-zero range.
    sa = jnp.asarray(rng.uniform(1, 2, qmm[0]) * 2.0 ** -8, jnp.float32)
    sb = jnp.asarray(rng.uniform(1, 2, qmm[1]) * 2.0 ** -8, jnp.float32)
    for fmt in (BF16, FP8_E4M3):
        for style in ("fused", "cascade"):
            for scaled in (False, True):
                x, y = (sa, sb) if scaled else (a, b)

                def auto(x, y, fmt=fmt, style=style, scaled=scaled):
                    return emulated_matmul(x, y, fmt=fmt, style=style,
                                           scaled=scaled, impl="auto")

                def twin(x, y, fmt=fmt, style=style, scaled=scaled):
                    return emulated_matmul(x, y, fmt=fmt, style=style,
                                           scaled=scaled, impl="ref")
                # operands are on the format's grid, so products are exact
                # in f32 and the two differ only in f32 summation order;
                # cascade can then flip a rounding by one ulp of fmt in an
                # element now and then, far below one ulp normwise
                recs.append(_compare(
                    f"qmm {fmt.name} {style} scaled={scaled}",
                    lower(auto, x, y), jax.jit(twin), (x, y),
                    rtol=2.0 ** -fmt.man_bits, bitwise_meant=False))

    q = jnp.asarray(rng.standard_normal(flash), jnp.float32)
    k = jnp.asarray(rng.standard_normal(flash), jnp.float32)
    v = jnp.asarray(rng.standard_normal(flash), jnp.float32)
    for fmt in (BF16, FP8_E4M3):
        def fa(q, k, v, fmt=fmt):
            return emulated_flash_attention(q, k, v, fmt=fmt, impl="auto")

        def fa_twin(q, k, v, fmt=fmt):
            return emulated_flash_attention(q, k, v, fmt=fmt, impl="scan")
        # same block schedule; exp and the probability rounding to fmt may
        # differ by an ulp of fmt, which bounds the output error normwise
        recs.append(_compare(f"flash {fmt.name}", lower(fa, q, k, v),
                             jax.jit(fa_twin), (q, k, v),
                             rtol=2.0 ** -fmt.man_bits, bitwise_meant=False))

    sa_ = jnp.asarray(rng.uniform(0.05, 0.95, ssm), jnp.float32)
    sb_ = jnp.asarray(rng.standard_normal(ssm), jnp.float32)
    sc_ = jnp.asarray(rng.standard_normal(ssm[:2] + ssm[3:]), jnp.float32)
    for fmt in (BF16, FP8_E4M3):
        def sc(a, b, c, fmt=fmt):
            return emulated_ssm_scan(a, b, c, fmt=fmt, impl="auto")

        def sc_twin(a, b, c, fmt=fmt):
            return emulated_ssm_scan(a, b, c, fmt=fmt, impl="ref")
        # elementwise recurrence, then an N-term f32 readout sum whose
        # order may differ: N * 2**-24 relative, under 1e-5 for N <= 128
        recs.append(_compare(f"ssm_scan {fmt.name}", lower(sc, sa_, sb_, sc_),
                             jax.jit(sc_twin), (sa_, sb_, sc_), rtol=1e-5,
                             bitwise_meant=True))

    xq = jnp.asarray(rng.standard_normal(quant) * 40.0, jnp.float32)
    for fmt in (BF16, FP8_E4M3):
        def qt(x, fmt=fmt):
            return quantize_tensor(x, fmt=fmt, impl="auto")

        def qt_twin(x, fmt=fmt):
            return quantize_tensor(x, fmt=fmt, impl="ref")
        recs.append(_compare(f"quantize {fmt.name}", lower(qt, xq),
                             jax.jit(qt_twin), (xq,), rtol=0.0,
                             bitwise_meant=True))
    return recs


# ---------------------------------------------------------------------------
# four chips: one die per device behind a ClusterRouter
# ---------------------------------------------------------------------------
def cluster_phase(model, params, tech, devices, *, slots: int, max_len: int,
                  prefill_chunk: int, dispatch_tokens: int, make):
    """Serve ``make()``'s requests on a ``len(devices)``-die router, one die
    per device, and on a 1-die router; gate on equal outputs and on every
    device having served a share."""
    import dataclasses

    from repro.cluster import ClusterRouter, ClusterSpec
    from repro.core.chip import fabricated_chip

    die = fabricated_chip("sp", tech)

    def serve(n_dies):
        spec = ClusterSpec(f"{n_dies}die", tuple(
            dataclasses.replace(die, name=f"die{i}") for i in range(n_dies)))
        router = ClusterRouter(model, params, spec, slots=slots,
                               max_len=max_len, tech_params=tech,
                               devices=devices[:n_dies],
                               prefill_chunk=prefill_chunk,
                               dispatch_tokens=dispatch_tokens)
        reqs = make()
        placed = {r.uid: router.submit(r) for r in reqs}
        t0 = time.perf_counter()
        done = router.run(max_steps=10_000)
        wall = time.perf_counter() - t0
        check(len(done) == len(reqs), f"{n_dies} dies: every request done")
        for name, srv in router.servers.items():
            (dev,) = srv.cache.data["h"].devices()
            uids = sorted(u for u, d in placed.items() if d == name)
            print(f"  {n_dies} dies: {name} on {dev}: requests {uids}, "
                  f"{srv.tokens_decoded} tokens, {srv.dispatches} decode "
                  f"dispatches, peak_bytes_in_use "
                  f"{(dev.memory_stats() or {}).get('peak_bytes_in_use')}")
        print(f"  {n_dies} dies: wall {wall:.2f}s (compiles included)")
        return router, {r.uid: list(r.output) for r in reqs}

    router4, out4 = serve(len(devices))
    used = set()
    for name, srv in router4.servers.items():
        (dev,) = srv.cache.data["h"].devices()
        check(srv.tokens_decoded > 0, f"{name} on {dev} decoded tokens")
        used.add(dev.id)
    check(len(used) == len(devices), f"dies on {len(devices)} distinct devices")
    _, out1 = serve(1)
    same = sum(a == b for u in out1 for a, b in zip(out4[u], out1[u]))
    total = sum(len(v) for v in out1.values())
    print(f"{len(devices)}-die vs 1-die outputs: {same}/{total} tokens equal")
    check(out4 == out1, f"{len(devices)}-die outputs equal the 1-die outputs")


# ---------------------------------------------------------------------------
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip cluster phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, prompts and kernel inputs")
    args = ap.parse_args()

    import jax
    import numpy as np

    from repro.core.energy_model import calibrate
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.serve import load_model

    cache_dir = use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found {dev.platform}")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips}: JAX found {len(devices)}")
    print(f"device {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
          f"compile cache {cache_dir}", flush=True)
    compiles = CompileCounter()
    phases = Phases()

    t0 = time.perf_counter()
    model, params = load_model(ARCH, reduced=False, seed=args.seed)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"{ARCH}: {model.cfg.n_layers} layers, d_model {model.cfg.d_model}, "
          f"{n_params} params ({model.dtype}) built in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    tech = phases.run("calibrate energy model", calibrate)

    if args.chips == 4:
        def make():
            # one prompt length: every die and the 1-die router then run the
            # same (lanes, chunk) programs, so outputs can match token for
            # token
            from repro.serve.engine import Request
            rng = np.random.default_rng(args.seed)
            return [Request(uid=i, prompt=rng.integers(
                0, model.cfg.vocab_size, 256).astype(np.int32),
                max_new_tokens=16) for i in range(8)]
        phases.run("cluster: 4 dies on 4 devices vs 1 die", cluster_phase,
                   model, params, tech, devices[:4], slots=2, max_len=1024,
                   prefill_chunk=256, dispatch_tokens=8, make=make)
    else:
        requests = make_requests(model.cfg.vocab_size, n=8, lengths=(100, 700),
                                 new_tokens=32, seed=args.seed)
        outputs = phases.run("serve", serving_phase, model, params, tech,
                             slots=8, max_len=1024, prefill_chunk=256,
                             dispatch_tokens=8, requests=requests)
        longest = max(requests, key=lambda r: len(r.prompt))
        phases.run("chunked prefill logits", prefill_logits_phase, model,
                   params, longest.prompt, chunk=256)
        if outputs is not None:
            phases.run("greedy reference", greedy_share_phase, model, params,
                       requests, outputs, max_len=1024)
        recs = phases.run("transprecision kernels", transprecision_phase,
                          qmm=((1, 256, 2048), (2048, 8192)),
                          flash=(1, 512, 32, 64), ssm=(1, 256, 4096, 64),
                          quant=(2048, 2048), seed=args.seed)
        if recs is not None:
            missing = [r["name"] for r in recs if not r["kernel"]]
            if missing:
                print(f"no tpu_custom_call in: {missing}")
                phases.failed.append("transprecision kernels: mosaic")

    stats = dev.memory_stats() or {}
    print(f"compiled programs {compiles.n}, compile seconds "
          f"{compiles.seconds:.1f}, peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use')}, bytes_limit "
          f"{stats.get('bytes_limit')}")
    if phases.failed:
        print(f"FAILED phases: {phases.failed}")
        sys.exit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
