"""Run one cell of BENCHMARK.json once, on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (bench/configs/<config>.json: the model's
sizes and the engine's settings) and a traffic mix
(bench/traffic/<mix>.json).  The run makes the weights and the requests from
``--seed``, builds the served path, runs every program the mix can call once
(set-up), then offers the traffic for ``--seconds`` on the wall clock and
checks what the served path produced against the configuration's float32
reference.  ``--trace 1`` runs the same window under the JAX profiler and
reports the cell's per-layer metrics (bench/metrics/<metric>.py) instead of
its end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), and last ``checks``, each number compared beside its limit;
the same comparisons are the last lines of standard error.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def load_spec(cell: str, root: Path = ROOT) -> dict:
    """The cell's entry, configuration, traffic mix and metrics, from
    BENCHMARK.json and the files it names."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell not in cells:
        raise SystemExit(f"no workload {cell!r} in BENCHMARK.json; have "
                         f"{sorted(cells)}")
    w = cells[cell]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return dict(
        cell=w,
        config=json.loads((root / conf["file"]).read_text()),
        mix=json.loads((root / "bench" / "traffic"
                        / f"{w['traffic']}.json").read_text()),
        end_to_end=e2e, per_layer=layer)


def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def use_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache/`` in the checkout (a fixed path: the path
    is part of the cache key).  Every program is cached, however quickly it
    compiled, so a second run compiles nothing."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache


def model_for(config: dict):
    import dataclasses

    from repro.configs.base import get_config
    from repro.models import LM
    cfg = dataclasses.replace(get_config(config["arch"]), **config["model"])
    return LM(cfg)


def free(stack) -> None:
    """Delete the serving state (cache and slot state) so that the
    reference finds the chip's memory free; the weights stay for it."""
    import jax
    for srv in stack.servers:
        for x in jax.tree.leaves([srv.cache, srv._next_tok,
                                  srv._active_mask, srv._budget]):
            x.delete()
    gc.collect()


def judge(gaps, *, unfinished: int, wrong_length: int, limit: float):
    """The comparison that decides ``correct``: each number beside its
    limit, and whether every one is within it.  The control's gaps go
    through the same comparison."""
    checks = {
        "logit_gap_max": {"value": float(gaps.max()) if gaps.size
                          else float("nan"), "limit": limit},
        "unfinished_requests": {"value": unfinished, "limit": 0},
        "wrong_length_outputs": {"value": wrong_length, "limit": 0},
    }
    correct = bool(gaps.size) and all(c["value"] <= c["limit"]
                                      for c in checks.values())
    return checks, correct


def run(spec: dict, *, seed: int, seconds: float, trace: bool, devices,
        t_start: float = T_START, model=None, control: bool = False) -> dict:
    """One run of the cell ``spec`` (see ``load_spec``) on ``devices``;
    returns the result object.  ``model`` reuses an ``LM`` built for the
    same configuration (its jitted programs with it); ``control`` also
    reads the fp8 control's widest gap on the same sample, under the
    result's ``control`` key (bench/control.py: the benchmark's own runs
    never do)."""
    import jax

    from harness import check, serve, traffic
    from harness.context import Context, percentile
    from harness.trace import read_xplane, reduce
    from harness.weights import make_params
    from peaks import peaks
    from repro.core.energy_model import calibrate

    config, mix, cell = spec["config"], spec["mix"], spec["cell"]
    engine, sizes = config["engine"], config["model"]
    chips = cell["chips"]
    used = list(devices[:chips])
    kind = used[0].device_kind
    peak = peaks(kind) if used[0].platform == "tpu" else None

    model = model or model_for(config)
    layout = jax.eval_shape(model.init, jax.random.key(0))
    params = make_params(layout, seed, used[0])
    jax.block_until_ready(params)
    tech = calibrate()
    tracer = None
    if trace:
        from repro.telemetry import Tracer
        tracer = Tracer()
    stack = serve.build(model, params, engine, used, tech, tracer=tracer)
    n_prog = serve.warm_up(stack, mix)
    offers = traffic.offers(mix, seconds=seconds, seed=seed,
                            vocab=sizes["vocab_size"])
    calls = serve.CallLog(stack.servers) if trace else None
    logdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    compiles = serve.CompileCounter()
    marks = {}

    def on_open():
        if trace:
            jax.profiler.start_trace(logdir)
            calls.on = True
        marks["compiles"] = compiles.n
        marks["open"] = time.perf_counter()

    def on_close():
        marks["compiles_in_window"] = compiles.n - marks["compiles"]
        if trace:
            calls.on = False
            jax.profiler.stop_trace()

    try:
        with serve.no_gc():
            window = serve.drive(stack, offers, seconds=seconds,
                                 drain_s=mix["drain_s"], annotate=trace,
                                 on_open=on_open, on_close=on_close)
    finally:
        if calls is not None:
            calls.close()
    setup_s = marks["open"] - t_start
    in_window = [r for r in window.served if r.arrival_s < seconds]
    late = [r.submitted_s - r.arrival_s for r in in_window]
    started = [r for r in window.served if r.n > 0 or r.rejected]
    print(f"device {kind} x{len(used)} ({used[0].platform}); {n_prog} "
          f"programs warmed; compiles inside the window "
          f"{marks['compiles_in_window']}", flush=True)
    print(f"window {window.window_s!r} s, {window.steps} steps, "
          f"{len(window.served)} requests offered, {len(started)} started, "
          f"{sum(r.done for r in window.served)} finished, "
          f"{window.tokens_in_window} tokens inside the window; generator "
          f"lateness p50 {percentile(late, 50)!r} s, p95 "
          f"{percentile(late, 95)!r} s, max {max(late, default=None)!r} s",
          flush=True)
    rep = stack.target.energy_report()
    print(f"modelled energy (FPMax model, not a measurement): "
          f"{rep['total_j']!r} J, {rep['j_per_token']!r} J/token", flush=True)

    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in used)
    finished = [r.req for r in window.served if r.done]
    free(stack)
    del stack
    lim = config["check"]
    sample = check.pick(finished, seed, min_tokens=lim["min_tokens"],
                        max_requests=lim["max_requests"])
    t_ref = time.perf_counter()
    gaps = check.gaps(config["reference"], params, sizes, sample,
                      block=lim["block"])
    log(f"reference over {len(sample)} requests, {gaps.size} served tokens "
        f"in {time.perf_counter() - t_ref:.1f} s")
    unfinished = sum(1 for r in in_window if not (r.done or r.rejected))
    wrong_length = sum(1 for r in window.served
                       if r.done and r.n != r.max_new_tokens)
    checks, correct = judge(gaps, unfinished=unfinished,
                            wrong_length=wrong_length,
                            limit=lim["logit_gap_limit"])
    low = None
    if control:
        low = check.gaps(config["reference"], params, sizes, sample,
                         block=lim["block"], control=True)
        low_checks, low_correct = judge(low, unfinished=unfinished,
                                        wrong_length=wrong_length,
                                        limit=lim["logit_gap_limit"])

    reduced = None
    if trace:
        t_read = time.perf_counter()
        table = read_xplane(logdir)
        shutil.rmtree(logdir, ignore_errors=True)
        log(f"trace read in {time.perf_counter() - t_read:.1f} s; planes: "
            + "; ".join(
            f"{name} [{', '.join(lines)}]" for name, lines in table["planes"]))
        reduced = reduce(table, [d.id for d in used])
    ctx = Context(sizes=sizes,
                  counts=importlib.import_module(f"counts.{config['counts']}"),
                  peaks=peak, chips=chips, window=window, seconds=seconds,
                  trace=reduced, calls=calls.calls if calls else [],
                  tracer=tracer, setup_s=setup_s, drain_s=mix["drain_s"])
    metrics = {}
    for m in (spec["per_layer"] if trace else spec["end_to_end"]):
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": used[0].platform, "kind": kind, "count": len(used),
              "memory_peak_bytes": int(memory_peak)}
    out = {"correct": correct, "attempted": len(started),
           "failed": sum(r.rejected for r in window.served) + unfinished,
           "metrics": metrics, "device": device}
    if trace and reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    if low is not None:
        out["control"] = {"correct": low_correct, "tokens": int(low.size),
                          "checks": low_checks}
    out["checks"] = checks
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    spec = load_spec(args.workload)

    import jax
    cache = use_compile_cache()
    devices = jax.devices()
    chips = spec["cell"]["chips"]
    if devices[0].platform != "tpu":
        log(f"bench/run.py needs a TPU; JAX found {devices[0].platform}")
        sys.exit(2)
    if len(devices) < chips:
        log(f"{args.workload} needs {chips} chips; JAX found {len(devices)}")
        sys.exit(2)
    log(f"{args.workload}: seed {args.seed}, {args.seconds} s, trace "
        f"{args.trace}, compile cache {cache}")
    out = run(spec, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), devices=devices)
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
