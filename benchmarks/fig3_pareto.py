"""Paper Fig. 3: throughput trade-offs for SP/DP FMAs — peak energy- and
area-efficiency operating points across the (V_DD, V_BB) space, anchored to
silicon.  Paper endpoints: SP FMA 289 GFLOPS/W (low-energy) / 278 GFLOPS/mm^2
(high-perf); DP FMA 117 GFLOPS/W / 111 GFLOPS/mm^2.

Array path: both designs' full (V_DD x V_BB) grids are evaluated in one
anchored ``predict_batch`` dispatch; the peak points are argmaxes over the
metric tensor (row-major, so ties resolve identically to the old loop)."""
import numpy as np

from repro.core.dse import enumerate_structures, sweep_arrays, throughput_pareto
from repro.core.energy_model import calibrate, predict_batch
from repro.core.fpu_arch import DP_FMA, SP_FMA, TABLE_I

from bench_lib import emit, timed

# paper measurements span ~0.55V (low-energy) to ~1.15V (high-perf)
VDD_GRID = np.round(np.arange(0.55, 1.16, 0.025), 3)
VBB_GRID = np.round(np.arange(0.0, 1.21, 0.2), 2)


def peak_points(designs, params):
    """Per design: (low-energy point, high-perf point) as
    (gflops_per_w, gflops_per_mm2, vdd, vbb) tuples."""
    out = predict_batch(designs, params, VDD_GRID, VBB_GRID, anchored=True)
    gw = np.where(out["freq_ghz"] > 0, out["gflops_per_w"], -np.inf)
    gm = np.where(out["freq_ghz"] > 0, out["gflops_per_mm2"], -np.inf)
    peaks = []
    for i in range(len(designs)):
        iw = np.unravel_index(np.argmax(gw[i]), gw[i].shape)
        im = np.unravel_index(np.argmax(gm[i]), gm[i].shape)
        best_w = (out["gflops_per_w"][i][iw], out["gflops_per_mm2"][i][iw],
                  VDD_GRID[iw[0]], VBB_GRID[iw[1]])
        best_mm2 = (out["gflops_per_w"][i][im], out["gflops_per_mm2"][i][im],
                    VDD_GRID[im[0]], VBB_GRID[im[1]])
        peaks.append((best_w, best_mm2))
    return peaks


def run():
    params = calibrate()
    designs, names = [SP_FMA, DP_FMA], ["sp_fma", "dp_fma"]
    peaks, us = timed(peak_points, designs, params)
    for (bw, bm), name in zip(peaks, names):
        m = TABLE_I[name]
        emit(f"fig3.{name}.low_energy_point", us / 4,
             f"gflops_per_w={bw[0]:.0f};at_gflops_per_mm2={bw[1]:.0f};"
             f"vdd={bw[2]};paper_max_gflops_per_w={m.max_gflops_per_w}")
        emit(f"fig3.{name}.high_perf_point", us / 4,
             f"gflops_per_mm2={bm[1]:.0f};at_gflops_per_w={bm[0]:.0f};"
             f"vdd={bm[2]};paper_max_gflops_per_mm2={m.max_gflops_per_mm2}")

    # architectural pareto at 1V (the paper's triangle curve, FPGen sim)
    res, us = timed(sweep_arrays, enumerate_structures("sp", styles=("fma",)),
                    params, np.array([1.0]), np.array([0.0]))
    front = throughput_pareto(res)
    emit("fig3.sp_arch_pareto_1v", us,
         f"n_points={len(res)};n_pareto={len(front)};"
         f"best_w={front.metrics['gflops_per_w'].max():.0f};"
         f"best_mm2={front.metrics['gflops_per_mm2'].max():.0f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
