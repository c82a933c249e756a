"""Paper Table I: performance summary of the four fabricated FPUs.

Reports, per unit: model-predicted vs measured frequency / power / area and
the normalized efficiencies (GFLOPS/W, GFLOPS/mm^2) — the validation that our
recalibrated FPGen cost model reproduces the silicon.  All four units are
evaluated in one batched ``predict_points`` dispatch inside
``calibration_report``."""
from repro.core.energy_model import calibrate, calibration_report
from repro.core.fpu_arch import TABLE_I

from bench_lib import emit, timed


def run():
    params = calibrate()  # one-time fit, excluded from the report timing
    rep, us = timed(calibration_report, params)
    for name, row in rep.items():
        m = TABLE_I[name]
        derived = (
            f"gflops_per_w_pred={row['gflops_per_w_pred']:.1f};"
            f"gflops_per_w_meas={m.gflops_per_w:.1f};"
            f"gflops_per_mm2_pred={row['gflops_per_mm2_pred']:.1f};"
            f"gflops_per_mm2_meas={m.gflops_per_mm2:.1f};"
            f"freq_err={row['freq_rel_err']:+.2f};"
            f"power_err={row['power_rel_err']:+.2f};"
            f"area_err={row['area_rel_err']:+.2f}")
        emit(f"table1.{name}", us / 4, derived)
    return rep


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
