"""Perf hillclimb driver: hypothesis -> change -> re-lower -> record, for the
three selected cells.

Each iteration re-runs the dry-run cell with a configuration override and
records the three roofline terms + the fused-kernel memory term.  Results are
appended to results/perf_iterations.json.

MUST be run as a script/module: the device-count flag below executes before
any jax import (jax locks the device count at first init).  The generic
local-search engine this driver's accept/reject loop grew into lives in
``repro.core.localsearch`` — importable anywhere, no env side effects.
"""
import os

from repro.launch.xla_flags import force_host_device_count

force_host_device_count(512)

import argparse
import json
import time

import jax

from repro.configs.base import SHAPES, get_config
from repro.launch.dryrun import CPU_COMPILER_OPTIONS
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import make_cell
from repro.parallel import sharding as sh
from repro.roofline.analysis import analyze
from repro.roofline.fused_model import fused_memory_term


def measure(arch: str, shape: str, *, microbatches: int, label: str,
            hypothesis: str = "", triangle_skip: bool = False):
    mesh = make_production_mesh()
    ctx = sh.make_context(mesh)
    t0 = time.time()
    with sh.use_mesh(ctx):
        cell = make_cell(arch, shape, ctx, microbatches=microbatches,
                         triangle_skip=triangle_skip)
        donate = (0,) if cell.kind == "train" else ()
        compiled = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                           donate_argnums=donate).lower(
            *cell.arg_shapes).compile(compiler_options=CPU_COMPILER_OPTIONS)
    rep = analyze(arch, shape, "pod16x16", mesh.size, compiled,
                  get_config(arch), SHAPES[shape])
    mem = compiled.memory_analysis()
    hbm = (mem.temp_size_in_bytes + mem.argument_size_in_bytes) / 1e9
    t_mem_fused, fused_info = fused_memory_term(
        rep.bytes_per_device, compiled.as_text())
    bound_fused = max(rep.t_compute, t_mem_fused, rep.t_collective)
    frac_fused = rep.model_flops / (rep.chips * rep.peak_flops * bound_fused)
    row = dict(
        arch=arch, shape=shape, label=label, hypothesis=hypothesis,
        microbatches=microbatches,
        t_compute=rep.t_compute, t_memory=rep.t_memory,
        t_collective=rep.t_collective, bottleneck=rep.bottleneck,
        roofline_fraction=rep.roofline_fraction,
        t_memory_fused=t_mem_fused,
        roofline_fraction_fused=frac_fused,
        removed_gb=fused_info["removed_bytes"] / 1e9,
        hbm_gb=hbm, compile_s=round(time.time() - t0, 1),
        fits_16gb=hbm <= 16.0)
    print(json.dumps(row, indent=None), flush=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results/perf_iterations.json")
    args = ap.parse_args()
    rows = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            rows = json.load(f)

    plan = [
        # (arch, shape, mb, label, hypothesis)
        ("deepseek-67b", "train_4k", 16, "baseline",
         "paper-faithful framework baseline (mb=16 to fit pre-SP memory)"),
        ("deepseek-67b", "train_4k", 4, "mb4",
         "collective bytes ~ mb x layers x dW: 4x fewer microbatches cuts "
         "the dW all-reduce term ~4x; SP-sharded remat carries keep memory "
         "under 16GB"),
        ("deepseek-67b", "train_4k", 2, "mb2",
         "continue halving mb until memory budget binds"),
        ("mixtral-8x7b", "train_4k", 8, "baseline", ""),
        ("mixtral-8x7b", "train_4k", 4, "mb4",
         "same dW-reduce scaling as deepseek"),
        ("mixtral-8x7b", "train_4k", 2, "mb2", "knee check"),
        ("falcon-mamba-7b", "train_4k", 8, "baseline", ""),
        ("falcon-mamba-7b", "train_4k", 2, "mb2",
         "memory term dominated by per-pass state expansion; fewer "
         "microbatches reduce remat multiplicity"),
        ("falcon-mamba-7b", "train_4k", 1, "mb1", "knee check"),
    ]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    done = {(r["arch"], r["shape"], r["label"]) for r in rows}
    for arch, shape, mb, label, hyp in plan:
        if (arch, shape, label) in done:
            continue
        try:
            rows.append(measure(arch, shape, microbatches=mb, label=label,
                                hypothesis=hyp))
        except Exception as e:  # noqa: BLE001
            rows.append(dict(arch=arch, shape=shape, label=label,
                             error=str(e)))
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
