"""Paper Fig. 4: latency-unit energy vs utilization under body-bias
policies.  Claims validated: ~20% energy saving at 100% activity (13% power),
3x energy/op at 10% utilization with static BB, brought to ~1.5x by adaptive
BB.  The utilization curves are array-native (broadcast over the whole
utilization axis), so the full-resolution sweep is a single timed call."""
import numpy as np

from repro.core.body_bias import bb_study, energy_vs_utilization
from repro.core.fpu_arch import DP_CMA, SP_CMA

from bench_lib import emit, timed


def run():
    for design, name in ((DP_CMA, "dp_cma"), (SP_CMA, "sp_cma")):
        s, us = timed(bb_study, design, vdd=0.6)
        emit(f"fig4.{name}", us,
             f"bb_saving={s['bb_energy_saving']:.2%};"
             f"static_10pct_ratio={s['low_util_static_ratio']:.2f};"
             f"adaptive_10pct_ratio={s['low_util_adaptive_ratio']:.2f};"
             f"paper=20%/3x/1.5x")
    (utils, static, adaptive), us = timed(
        energy_vs_utilization, DP_CMA, utils=np.geomspace(0.01, 1.0, 200))
    emit("fig4.dp_cma.curve", us,
         f"n_points={utils.size};util_min={utils[0]:.2f};"
         f"static_ratio_at_min={static[0] / static[-1]:.1f};"
         f"adaptive_ratio_at_min={adaptive[0] / adaptive[-1]:.1f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
