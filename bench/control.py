"""The control of a cell's comparison, on the chip: the program's widest
logit gap and the fp8 control's, seed after seed, in one process.

  python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

Each seed is a whole run of the cell (bench/run.py's ``run``) at its own
size and load with a window of ``--seconds``; after the program's gaps, the
reference computed in float8_e4m3 products is put in the program's place
on the same sample, and the float32 reference's gap of the token it puts
first is read, and judged by the run's own comparison against the
configuration's limit.  One JSON line per seed.  The lower reading of the limit is
the largest program gap over the seeds, the upper the smallest control gap.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT / "bench")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()

    import jax

    import run
    run.use_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit("bench/control.py needs a TPU")
    spec = run.load_spec(args.workload)
    model = run.model_for(spec["config"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = run.run(spec, seed=seed, seconds=args.seconds, trace=False,
                      devices=devices, t_start=t, model=model, control=True)
        print(json.dumps(dict(
            seed=seed, correct=out["correct"],
            program=out["checks"]["logit_gap_max"]["value"],
            control_correct=out["control"]["correct"],
            control=out["control"]["checks"]["logit_gap_max"]["value"],
            tokens=out["control"]["tokens"],
            seconds=time.perf_counter() - t)), flush=True)


if __name__ == "__main__":
    main()
