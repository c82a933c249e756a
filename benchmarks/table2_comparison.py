"""Paper Table II: SP FMA vs published designs (feature-size/FO4 scaled).

Competitor numbers are the paper's own scaled values; ours comes from the
calibrated model at the nominal point (and should match the paper's 217
GFLOPS/mm^2 / 106 GFLOPS/W row)."""
from repro.core.energy_model import calibrate, predict_points
from repro.core.fpu_arch import SP_FMA, TABLE_I

from bench_lib import emit, timed

PUBLISHED = {
    "variable_precision_fma_kaul_isscc12": (62.5, 52.8),
    "resonant_fma_kao_asscc10": (142.0, 54.9),
    "cell_fma_oh_jssc06": (384.0, 66.0),
    "reconfig_fpu_jain_vlsi10": (0.8, 33.7),
}


def run():
    params = calibrate()
    m = TABLE_I["sp_fma"]
    batch, us = timed(predict_points, [SP_FMA], params,
                      vdd=[m.vdd], vbb=[m.vbb])
    p = {k: float(v[0]) for k, v in batch.items()}
    emit("table2.sp_fma_ours", us,
         f"area_eff={p['gflops_per_mm2']:.1f};energy_eff={p['gflops_per_w']:.1f};"
         f"paper_area_eff={m.gflops_per_mm2};paper_energy_eff={m.gflops_per_w}")
    for name, (ae, ee) in PUBLISHED.items():
        emit(f"table2.{name}", 0.0, f"area_eff={ae};energy_eff={ee}")
    return p


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
