"""Transprecision numerics benchmark: the accuracy/energy trade in numbers.

Three claims, one record per run appended to ``results/numerics_bench.json``:

  * **accuracy-constrained tuning cost** — a format-joint tune sweeps
    ``n_formats x`` the structural grid through the same
    ``SweepExecutableCache``; cold pays one XLA compile of the bigger
    tensor, warm re-tunes are dispatch-only (``speedup_warm`` is the
    machine-normalized ratio scripts/check_bench_regression.py guards);
  * **the downshift win** — a loose-SLO throughput tune picks a sub-SP
    format and its GFLOPS/W gain over the FP32-pinned optimum is recorded
    (``downshift_gain``), while a tight SLO keeps FP32 bit-identically;
  * **emulation overhead** — emulated (bf16/fused) vs native f32 matmul
    wall time at smoke scale, the cost of numerics-faithful model studies.

Run: PYTHONPATH=src python benchmarks/numerics_bench.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.numerics as rn
from repro.core import autotune as at
from repro.core import latency_sim
from repro.core.energy_model import SweepExecutableCache, calibrate

from bench_lib import append_trajectory, emit, timed

#: the accuracy classes the demo tunes against: loose enough for the fp8
#: tiers vs tight enough that only FP32 qualifies on the oracle workload
LOOSE_SLO = 5e-2
TIGHT_SLO = 1e-7


def run():
    params = calibrate()
    cache = SweepExecutableCache()
    latency_sim.clear_penalty_cache()
    oracle = rn.AccuracyModel()  # fresh: its Fraction cost lands in "cold"

    # --- cold vs warm accuracy-constrained tune (the guarded warm path)
    kw = dict(params=params, cache=cache, accuracy_slo=LOOSE_SLO,
              accuracy_model=oracle)
    cold, cold_us = timed(at.autotune, at.GEMM_STREAM, "sp", **kw)
    warm_runs = [timed(at.autotune, at.GEMM_STREAM, "sp", **kw)
                 for _ in range(3)]
    warm, warm_us = min(warm_runs, key=lambda r: r[1])
    speedup = cold_us / warm_us
    emit("numerics_bench.cold_tune", cold_us,
         f"n_points={cold.n_points};chosen={cold.key};fmt={cold.fmt.name}")
    emit("numerics_bench.warm_tune", warm_us,
         f"speedup={speedup:.0f}x;cache={cache.stats}")

    # --- the downshift: loose SLO vs FP32-pinned vs tight SLO
    base = at.autotune(at.GEMM_STREAM, "sp", params=params, cache=cache)
    tight = at.autotune(at.GEMM_STREAM, "sp", params=params, cache=cache,
                        accuracy_slo=TIGHT_SLO, accuracy_model=oracle)
    gain = cold.metrics["gflops_per_w"] / base.metrics["gflops_per_w"]
    tight_is_base = (tight.design.name, tight.vdd, tight.vbb) == \
        (base.design.name, base.vdd, base.vbb)
    emit("numerics_bench.downshift", 0.0,
         f"loose_fmt={cold.fmt.name};"
         f"gflops_per_w={cold.metrics['gflops_per_w']:.0f}"
         f";fp32_gflops_per_w={base.metrics['gflops_per_w']:.0f};"
         f"gain={gain:.2f}x;tight_refuses={tight_is_base}")

    # --- emulated vs native matmul (smoke scale, CPU reference path)
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    emu_fn = jax.jit(lambda x, y: rn.emulated_matmul(
        x, y, fmt="bf16", style="fused"))
    nat_fn = jax.jit(jnp.matmul)
    jax.block_until_ready(emu_fn(a, b))  # compile
    jax.block_until_ready(nat_fn(a, b))
    _, emu_us = timed(lambda: jax.block_until_ready(emu_fn(a, b)))
    _, nat_us = timed(lambda: jax.block_until_ready(nat_fn(a, b)))
    emit("numerics_bench.matmul_256", emu_us,
         f"native_us={nat_us:.0f};overhead={emu_us / nat_us:.1f}x")

    path = append_trajectory("numerics_bench.json", dict(
        ts=time.strftime("%Y-%m-%dT%H:%M:%S"),
        n_points=cold.n_points,
        n_formats=len(rn.REGISTRY.formats_for("sp")),
        cold_s=cold_us / 1e6,
        warm_s=warm_us / 1e6,
        speedup_warm=speedup,
        cache=dict(cache.stats),
        loose_slo=LOOSE_SLO,
        tight_slo=TIGHT_SLO,
        loose_choice=cold.as_dict(),
        fp32_choice=base.as_dict(),
        tight_choice=tight.as_dict(),
        downshift_gain=float(gain),
        tight_refuses_downshift=bool(tight_is_base),
        emulated_matmul_us=emu_us,
        native_matmul_us=nat_us,
        emulation_overhead=float(emu_us / nat_us),
    ))
    emit("numerics_bench.trajectory", 0.0, f"appended={path}")
    return speedup


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
