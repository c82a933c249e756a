"""Generated-kernel model check: measured vs roofline-predicted throughput.

Calibrates a ``MachineModel`` on this host, runs the default ``KernelSpec``
sweep (every fused op x the format ladder x accumulation styles), and holds
each measured kernel time against its analytic prediction.  The fraction of
specs landing within the model tolerance is machine-normalized (prediction
and measurement share the calibrated clock), so it is guarded as a CI
trajectory in ``results/benchgen_bench.json``: a materialized intermediate
or a lost fusion in the generated kernels shifts measured/predicted by an
order of magnitude and trips the guard on any runner.
"""
import time

from repro.benchgen import calibrate, default_specs, validate

from bench_lib import append_trajectory, emit

#: floor asserted before the record is appended — the committed trajectory
#: can then never silently degrade below it
MIN_FRAC_WITHIN_TOL = 0.85


def run():
    machine = calibrate()
    emit("benchgen.machine", 0.0,
         f"backend={machine.name};mxu_gflops={machine.mxu_flops / 1e9:.1f};"
         f"quant_gelems={machine.quant_rate / 1e9:.2f}")

    out = validate(default_specs(), machine)
    for row in out["rows"]:
        emit(f"benchgen.{row['spec']['name']}", row["t_meas_s"] * 1e6,
             f"pred_us={row['t_pred_s'] * 1e6:.1f};"
             f"ratio={row['ratio']:.2f};within={row['within_tol']};"
             f"bottleneck={row['bottleneck']}")

    s = out["summary"]
    emit("benchgen.summary", 0.0,
         f"frac_within_tol={s['frac_within_tol']:.3f};"
         f"worst_ratio={s['worst_ratio']:.2f};"
         f"geomean_ratio={s['geomean_ratio']:.2f};n={s['n_specs']}")
    assert s["frac_within_tol"] >= MIN_FRAC_WITHIN_TOL, (
        f"generated kernels drifted from the machine model: "
        f"{s['frac_within_tol']:.2f} < {MIN_FRAC_WITHIN_TOL}")

    path = append_trajectory("benchgen_bench.json", dict(
        ts=time.strftime("%Y-%m-%dT%H:%M:%S"),
        machine=machine.as_dict(),
        tol=out["tol"],
        n_specs=s["n_specs"],
        frac_within_tol=s["frac_within_tol"],
        worst_ratio=s["worst_ratio"],
        geomean_ratio=s["geomean_ratio"],
        rows=[{k: r[k] for k in ("t_pred_s", "t_meas_s", "ratio",
                                 "within_tol", "bottleneck")}
              | {"name": r["spec"]["name"]} for r in out["rows"]],
    ))
    emit("benchgen.trajectory", 0.0, f"appended={path}")
    return s["frac_within_tol"]


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
