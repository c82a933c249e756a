"""Kernel micro-bench: emulated-precision matmul cost per accumulation
style vs the native matmul, the fused transprecision kernels
(``repro.kernels.fused``), and the quantize pipe.

The guarded trajectory metric (``results/kernel_bench.json``) is
``overhead_fused_vs_native`` — the warm cost of the fused quantize->dot->
dequant path relative to the same-shape native matmul *on the same run*.
Absolute runner speed cancels out of the ratio, so a regression (an extra
dispatch, a de-fused quantize chain, a new materialized intermediate on the
hot path) trips the guard on any machine.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import BF16, FP8_E4M3
from repro.kernels.fused import fused_qmm_ref, ssm_scan_quantized_ref
from repro.kernels.ops import emulated_matmul, quantize_tensor

from bench_lib import append_trajectory, emit


def _time(fn, *args, n=5):
    fn(*args).block_until_ready()  # compile+warm
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / n * 1e6


def run():
    rng = np.random.default_rng(0)
    m = k = n = 512
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)

    native = _time(jax.jit(lambda a, b: a @ b), a, b)
    emit("kernel.native_matmul_512", native, "style=native")
    for style in ("fused", "cascade", "cascade_fwd"):
        fn = jax.jit(lambda a, b, s=style: emulated_matmul(
            a, b, fmt=BF16, style=s, impl="ref"))
        us = _time(fn, a, b)
        emit(f"kernel.fma_emu_512.{style}", us,
             f"overhead_vs_native={us / max(native, 1e-9):.1f}x")

    # fused transprecision path: quantize -> dot -> dequant in one program
    fused_us = _time(lambda a, b: fused_qmm_ref(a, b, fmt=BF16), a, b)
    overhead = fused_us / max(native, 1e-9)
    emit("kernel.fused_qmm_512.bf16", fused_us,
         f"overhead_vs_native={overhead:.1f}x")
    scaled_us = _time(lambda a, b: fused_qmm_ref(
        a, b, fmt=FP8_E4M3, style="cascade", scaled=True), a, b)
    emit("kernel.fused_qmm_512.fp8_scaled", scaled_us,
         f"overhead_vs_native={scaled_us / max(native, 1e-9):.1f}x")

    sa = jnp.asarray(rng.uniform(0.05, 0.95, (1, 128, 256, 16)), jnp.float32)
    sb = jnp.asarray(rng.standard_normal((1, 128, 256, 16)), jnp.float32)
    sc = jnp.asarray(rng.standard_normal((1, 128, 16)), jnp.float32)
    ssm_us = _time(lambda a_, b_, c_: ssm_scan_quantized_ref(
        a_, b_, c_, fmt=FP8_E4M3)[0], sa, sb, sc)
    emit("kernel.ssm_scan_quant.fp8", ssm_us, "shape=1x128x256x16")

    q = _time(jax.jit(lambda x: quantize_tensor(x, fmt="bf16", impl="ref")), a)
    emit("kernel.quantize_512", q, "fmt=bf16")

    path = append_trajectory("kernel_bench.json", dict(
        ts=time.strftime("%Y-%m-%dT%H:%M:%S"),
        native_matmul_us=native,
        fused_qmm_bf16_us=fused_us,
        fused_qmm_fp8_scaled_us=scaled_us,
        ssm_scan_quant_us=ssm_us,
        quantize_us=q,
        overhead_fused_vs_native=overhead,
    ))
    emit("kernel.trajectory", 0.0, f"appended={path}")
    return overhead


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
