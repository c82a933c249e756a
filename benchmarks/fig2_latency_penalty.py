"""Paper Fig. 2(c): average latency penalty, CMA vs FMA w/ and w/o
un-rounded-result forwarding — on the calibrated SPEC-FP-like mixture AND on
real dependency traces extracted from our models' jaxprs."""
import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core.fpu_arch import DP_CMA, get_design
from repro.core.latency_sim import calibrated_spec_mix, fig2c_penalties
from repro.core.trace import profile_fn, trace_penalty
from repro.models import LM

from bench_lib import emit, timed


def run():
    r, us = timed(lambda: fig2c_penalties(calibrated_spec_mix()))
    emit("fig2c.spec_mix", us,
         f"cma={r['dp_cma']:.3f};fma_fwd={r['fma5_fwd']:.3f};"
         f"fma_nofwd={r['fma5_nofwd']:.3f};"
         f"reduction_vs_fwd={r['reduction_vs_fwd']:.2%};"
         f"reduction_vs_nofwd={r['reduction_vs_nofwd']:.2%};"
         f"paper=37%/57%")

    # real model workloads: train-step jaxprs of two assigned archs
    for arch in ("tinyllama-1.1b", "falcon-mamba-7b"):
        cfg = get_config(arch).reduced()
        model = LM(cfg)
        params = model.init(jax.random.key(0))
        batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
                 "labels": jnp.zeros((2, 32), jnp.int32)}

        def loss(p):
            return model.loss_fn(p, batch)[0]

        prof, us2 = timed(profile_fn, loss, params)
        cma = trace_penalty(DP_CMA, prof)
        fma = trace_penalty(get_design("dp_fma"), prof)
        emit(f"fig2c.jaxpr_trace.{arch}", us2,
             f"cma_penalty={cma:.3f};fma_penalty={fma:.3f};"
             f"reduction={1 - cma / max(fma, 1e-9):.2%}")
    return r


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
