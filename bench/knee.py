"""Rate sweep of an open-loop cell, once, on the chip: where is the knee?

  python3 bench/knee.py --workload <cell> --seed <n> --seconds <s> \
      --rates 1,2,3,...

One process builds the cell's stack once and offers its traffic mix at each
base rate in turn (every other parameter as in the mix file), following each
window's requests until they finish before the next rate.  For each rate it
prints one JSON line: the mean offered rate, requests finished inside the
window per second, requests still waiting for their first token when the
window closed, and the TTFT and TPOT percentiles.  The sweep stops after
the first rate whose window's requests are not all finished ``--drain``
seconds after it closed.  The knee is the highest rate at which completions
keep up and the queue does not grow over the window; a cell runs at a fixed
share of it, written into its mix file.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT / "bench")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated base rates, requests/s")
    ap.add_argument("--drain", type=float, default=None,
                    help="seconds to follow a window's requests after it "
                         "closes (default: the mix's drain_s)")
    args = ap.parse_args()

    import jax

    import run
    from harness import serve, traffic
    from harness.context import percentile
    from harness.weights import make_params
    from repro.core.energy_model import calibrate

    run.use_compile_cache()
    if jax.devices()[0].platform != "tpu":
        sys.exit("bench/knee.py needs a TPU")
    spec = run.load_spec(args.workload)
    config, mix = spec["config"], dict(spec["mix"])
    used = jax.devices()[:spec["cell"]["chips"]]
    model = run.model_for(config)
    params = make_params(jax.eval_shape(model.init, jax.random.key(0)),
                         args.seed, used[0])
    stack = serve.build(model, params, config["engine"], used, calibrate())
    serve.warm_up(stack, mix)
    burst = mix["burst"]
    duty = burst["mean_on_s"] / (burst["mean_on_s"] + burst["mean_off_s"])
    for rate in (float(r) for r in args.rates.split(",")):
        mix["rate_rps"] = rate
        offers = traffic.offers(mix, seconds=args.seconds, seed=args.seed,
                                vocab=config["model"]["vocab_size"])
        w = serve.drive(stack, offers, seconds=args.seconds, drain_s=mix["drain_s"] if args.drain is None
                        else args.drain)
        s = args.seconds
        rows = [r for r in w.served if r.arrival_s < s]
        print(json.dumps(dict(
            rate_rps=rate,
            mean_offered_rps=rate * (1 + duty * (burst["multiplier"] - 1)),
            arrived=len(rows),
            finished_in_window_rps=sum(
                1 for r in rows if r.done and r.last_s <= s) / s,
            waiting_at_close=sum(1 for r in rows
                                 if r.first_s is None or r.first_s > s),
            drained=w.drained,
            ttft_p50_s=percentile([r.first_s - r.arrival_s for r in rows
                                   if r.first_s is not None], 50),
            ttft_p95_s=percentile([r.first_s - r.arrival_s for r in rows
                                   if r.first_s is not None], 95),
            tpot_p95_ms=percentile([(r.last_s - r.first_s) / (r.n - 1) * 1e3
                                    for r in rows if r.n >= 2], 95),
            output_tok_s=w.tokens_in_window / w.window_s)), flush=True)
        if not w.drained:
            break


if __name__ == "__main__":
    main()
