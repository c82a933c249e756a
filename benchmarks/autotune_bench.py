"""Workload-aware autotuner benchmark: cold vs warm tuning time and
chosen-point efficiency.

Cold = first tune in the process (pays one XLA compile of the sweep
executable plus the penalty-simulator compile); warm = same-shape re-tune
(dispatches the AOT-cached executable, penalty cache hot).  Also measures
cross-design-space executable reuse (DP tune after SP pays no compile), the
throughput-vs-latency design split on the full expanded grid, and the
Fig. 4 low-activity adaptive-body-bias saving.  Appends one record to
``results/autotune_bench.json`` so the tuning-time trajectory is visible
per PR.

Run: PYTHONPATH=src python benchmarks/autotune_bench.py
"""
import time

from repro.core import autotune as at
from repro.core import latency_sim
from repro.core import objective as obj
from repro.core.energy_model import SweepExecutableCache, calibrate

from bench_lib import append_trajectory, emit, timed


def run():
    params = calibrate()  # one-time model fit, excluded from tuning times
    cache = SweepExecutableCache()
    latency_sim.clear_penalty_cache()

    # --- cold vs warm same-shape tuning (the compile-cache claim)
    cold, cold_us = timed(at.autotune, at.GEMM_STREAM, "sp", params=params,
                          cache=cache)
    warm_runs = [timed(at.autotune, at.GEMM_STREAM, "sp", params=params,
                       cache=cache) for _ in range(3)]
    warm, warm_us = min(warm_runs, key=lambda r: r[1])  # steady-state
    speedup = cold_us / warm_us
    emit("autotune_bench.cold", cold_us,
         f"n_points={cold.n_points};chosen={cold.key};"
         f"gflops_per_w={cold.metrics['gflops_per_w']:.0f};"
         f"e_eff_pj={cold.metrics['e_eff_pj']:.2f}")
    emit("autotune_bench.warm_same_shape", warm_us,
         f"speedup={speedup:.0f}x;cache_hits={cache.hits};"
         f"cache_misses={cache.misses}")

    # --- cross-design-space reuse: DP pads to the same bucket as SP
    misses_before = cache.misses
    dp, dp_us = timed(at.autotune, at.GEMM_STREAM, "dp", params=params,
                      cache=cache)
    emit("autotune_bench.warm_cross_space_dp", dp_us,
         f"recompiled={cache.misses != misses_before};chosen={dp.key}")

    # --- the Table I split on the full expanded grid
    lat, lat_us = timed(at.autotune, at.DEPENDENT_CHAIN, "sp", params=params,
                        cache=cache)
    distinct = lat.design.name != cold.design.name
    emit("autotune_bench.latency_mix", lat_us,
         f"chosen={lat.key};distinct_from_throughput={distinct};"
         f"avg_delay_ns={lat.metrics['avg_delay_ns']:.2f}")

    # --- Fig. 4: low-activity adaptive body bias at iso-frequency
    cons = (obj.Constraint("freq_ghz", lo=1.0),)
    low, low_us = timed(at.autotune, at.GEMM_LOW_ACTIVITY, "sp",
                        params=params, cache=cache, constraints=cons)
    bb_saving = at.static_bb_energy(low) / low.metrics["e_eff_pj"]
    emit("autotune_bench.low_activity_bb", low_us,
         f"chosen={low.key};adaptive_bb_saving={bb_saving:.2f}x;paper=~2x")

    path = append_trajectory("autotune_bench.json", dict(
        ts=time.strftime("%Y-%m-%dT%H:%M:%S"),
        n_points=cold.n_points,
        cold_s=cold_us / 1e6,
        warm_s=warm_us / 1e6,
        speedup_warm=speedup,
        warm_speedup_ge_10x=bool(speedup >= 10.0),
        cross_space_dp_s=dp_us / 1e6,
        cache=dict(cache.stats),
        throughput_choice=cold.as_dict(),
        latency_choice=lat.as_dict(),
        distinct_designs=bool(distinct),
        low_activity_choice=low.as_dict(),
        adaptive_bb_saving=float(bb_saving),
    ))
    emit("autotune_bench.trajectory", 0.0, f"appended={path}")
    return speedup


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
