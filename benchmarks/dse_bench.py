"""Old-vs-new DSE sweep benchmark: seed per-point loop vs the batched
structure-of-arrays pipeline.

Measures wall-clock and points/sec for the full SP+DP ``sweep()`` with
latency penalties (the Fig. 3/4 hot path), verifies the two paths produce
identical metrics (bitwise for the numpy backend, allclose for the XLA
backend) and identical Pareto frontiers, and appends one record to the
``results/dse_bench.json`` trajectory so speedups are tracked across PRs.

Run: PYTHONPATH=src python benchmarks/dse_bench.py
"""
import time

import numpy as np

from repro.core import latency_sim
from repro.core.dse import (enumerate_structures, latency_pareto,
                            sweep_arrays, sweep_loop, throughput_pareto)
from repro.core.energy_model import calibrate
from repro.core.latency_sim import calibrated_spec_mix

from bench_lib import append_trajectory, emit, timed


def _frontier_keys(obj):
    if isinstance(obj, list):  # legacy DsePoint list
        return {(p.design.name, p.vdd, p.vbb) for p in obj}
    return {(obj.design_of(i).name, float(obj.vdd[i]), float(obj.vbb[i]))
            for i in range(len(obj))}


def run():
    params = calibrate()

    # --- mixture calibration: batched vs (estimated) sequential seed cost
    calibrated_spec_mix.cache_clear()
    mix, mix_us = timed(calibrated_spec_mix)
    # seed baseline: per candidate, three separate scalar _simulate calls
    # on a freshly sampled trace (no batching, no cache) — what the seed's
    # sequential grid search did per mixture.
    import jax.numpy as jnp
    n_probe = 5
    t0 = time.perf_counter()
    for seed in range(n_probe):
        types, dists = latency_sim.SpecMix(0.3, 0.1, 0.2, 0.5, n_ops=20_000,
                                           seed=seed).sample()
        for acc, mul in ((2, 4), (4, 4), (5, 5)):
            float(latency_sim._simulate(jnp.asarray(types),
                                        jnp.asarray(dists),
                                        jnp.int32(acc), jnp.int32(mul)))
    seq_per_cand_s = (time.perf_counter() - t0) / n_probe
    emit("dse_bench.mix_calibration", mix_us,
         f"candidates=270;batched_s={mix_us / 1e6:.2f};"
         f"seq_estimate_s={seq_per_cand_s * 270:.1f};"
         f"est_speedup={seq_per_cand_s * 270 / (mix_us / 1e6):.0f}x")

    # --- full SP+DP sweep with latency penalties
    designs = enumerate_structures("sp") + enumerate_structures("dp")

    latency_sim.clear_penalty_cache()
    legacy, legacy_us = timed(sweep_loop, designs, params,
                              with_latency=True, mix=mix)
    latency_sim.clear_penalty_cache()
    _, cold_us = timed(sweep_arrays, designs, params,
                       with_latency=True, mix=mix)
    # warm dispatch is ~ms-scale: take the min over repeats so the recorded
    # speedup (guarded by scripts/check_bench_regression.py) is not noise
    warm_runs = [timed(sweep_arrays, designs, params,
                       with_latency=True, mix=mix) for _ in range(3)]
    res, warm_us = min(warm_runs, key=lambda r: r[1])
    res_np, np_us = timed(sweep_arrays, designs, params, with_latency=True,
                          mix=mix, backend="numpy")
    n = len(legacy)
    assert n == len(res) == len(res_np)

    # --- equivalence: metrics and Pareto frontiers
    keys = list(legacy[0].metrics)
    legacy_cols = {k: np.array([p.metrics[k] for p in legacy]) for k in keys}
    bitwise = all(np.array_equal(legacy_cols[k], res_np.metrics[k])
                  for k in keys)
    close = all(np.allclose(legacy_cols[k], res.metrics[k],
                            rtol=1e-12, atol=0) for k in keys)
    tp_same = (_frontier_keys(throughput_pareto(legacy))
               == _frontier_keys(throughput_pareto(res)))
    lp_same = (_frontier_keys(latency_pareto(legacy))
               == _frontier_keys(latency_pareto(res)))

    speedup_warm = legacy_us / warm_us
    speedup_cold = legacy_us / cold_us
    emit("dse_bench.sweep_legacy", legacy_us,
         f"n_points={n};points_per_s={n / (legacy_us / 1e6):.0f}")
    emit("dse_bench.sweep_vector_cold", cold_us,
         f"n_points={n};points_per_s={n / (cold_us / 1e6):.0f};"
         f"speedup={speedup_cold:.1f}x")
    emit("dse_bench.sweep_vector_warm", warm_us,
         f"n_points={n};points_per_s={n / (warm_us / 1e6):.0f};"
         f"speedup={speedup_warm:.1f}x")
    emit("dse_bench.equivalence", 0.0,
         f"numpy_bitwise={bitwise};jax_allclose={close};"
         f"throughput_pareto_identical={tp_same};"
         f"latency_pareto_identical={lp_same}")

    path = append_trajectory("dse_bench.json", dict(
        ts=time.strftime("%Y-%m-%dT%H:%M:%S"),
        n_points=n,
        legacy_s=legacy_us / 1e6,
        vector_cold_s=cold_us / 1e6,
        vector_warm_s=warm_us / 1e6,
        vector_numpy_s=np_us / 1e6,
        speedup_cold=speedup_cold,
        speedup_warm=speedup_warm,
        mix_calibration_s=mix_us / 1e6,
        numpy_bitwise=bool(bitwise),
        jax_allclose=bool(close),
        pareto_identical=bool(tp_same and lp_same),
    ))
    emit("dse_bench.trajectory", 0.0, f"appended={path}")
    return speedup_warm


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
