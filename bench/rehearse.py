"""Compile rehearsal, without the chip: each cell's step programs compiled for
a described TPU v5e, with the memory each would take.

  JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload <cell>] [--all]

For every cell (or one), it compiles the fused decode dispatch and the
largest prefill-chunk program the cell's mix can call (``--all``: every
one) for one chip of a described ``v5e:2x2``, at the configuration's slots
and ``max_len``, and prints ``memory_analysis()``: the arguments (weights,
cache, slot state) and the temporaries, and how many ``remat`` names its
HLO holds: one that fits only by rematerialising holds many and runs far
slower.  The compiler refuses a program
that does not fit the chip's HBM (``RESOURCE_EXHAUSTED``), so a program that
compiles fits by the compiler's own count; the sum of the two figures
counts more than the compiler's peak (it held 22.8 GB for a program the
compiler placed in 15.75 GiB).  It exits non-zero where a program does not
compile.  A compile that passes is not a chip run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT / "bench")):
    if p not in sys.path:
        sys.path.insert(0, p)


def rehearse(cell: str, every: bool, one_chip) -> bool:
    import jax
    import jax.numpy as jnp

    import run
    from harness.serve import chunk_shapes
    from repro.models.model import DecodeCache
    from repro.serve.engine import _chunk_jit, _dispatch_jit

    spec = run.load_spec(cell)
    eng = spec["config"]["engine"]
    model = run.model_for(spec["config"])
    slots, max_len = eng["slots"], eng["max_len"]

    def state():
        cache = model.init_cache(slots, max_len)
        return (DecodeCache(cache.data, jnp.zeros(slots, jnp.int32)),
                jnp.zeros((slots, 1), jnp.int32), jnp.zeros(slots, bool),
                jnp.zeros(slots, jnp.int32))

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.key(0)))
    cache, tok, active, budget = on_chip(jax.eval_shape(state))

    class Lanes:  # what chunk_shapes reads of a server
        prefill_chunk = -(-eng["prefill_chunk"]
                          // model.cfg.ssm_scan_chunk) \
            * model.cfg.ssm_scan_chunk

    Lanes.slots = slots
    shapes = chunk_shapes(Lanes, spec["mix"])
    if not every:
        top = max(m for m, _ in shapes)
        shapes = [(top, max(w for _, w in shapes))]
    progs = [("decode dispatch", lambda: _dispatch_jit.lower(
        model, 0, eng["dispatch_tokens"], (), params, cache, tok, active,
        budget))]
    for m, w in shapes:
        def lower(m=m, w=w):
            ints = [jax.ShapeDtypeStruct((m,), jnp.int32, sharding=one_chip)
                    ] * 5
            toks = jax.ShapeDtypeStruct((m, w), jnp.int32, sharding=one_chip)
            return _chunk_jit.lower(model, params, cache, tok, active,
                                    budget, toks, *ints)
        progs.append((f"prefill chunk {m} lanes x {w}", lower))
    ok = True
    for name, lower in progs:
        try:
            compiled = lower().compile()
            mem = compiled.memory_analysis()
        except Exception as e:  # report every program, then fail
            print(f"{cell}: {name}: does not compile: {e}", flush=True)
            ok = False
            continue
        # a program that fits only by rematerialising runs far slower
        # (16 slots x 2560 of zamba2: 59 of them, 306 ms a token on a v5e)
        remat = compiled.as_text().count("remat")
        print(f"{cell}: {name}: compiles; arguments "
              f"{mem.argument_size_in_bytes} B, temporaries "
              f"{mem.temp_size_in_bytes} B, `remat` names in the HLO "
              f"{remat}", flush=True)
    return ok


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", help="one cell (default: every cell)")
    ap.add_argument("--all", action="store_true",
                    help="every chunk program, not only the largest")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    cells = [args.workload] if args.workload else [
        w["name"] for w in json.loads(
            (ROOT / "BENCHMARK.json").read_text())["workloads"]]
    ok = all([rehearse(c, args.all, one_chip) for c in cells])
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
