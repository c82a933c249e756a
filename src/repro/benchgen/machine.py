"""Machine model: sustained per-pipe rates the predictions divide by.

A ``MachineModel`` is the benchgen analogue of the paper's synthesis corner:
the handful of sustained rates that turn a spec's analytic op counts into a
time.  ``calibrate()`` *measures* them on the current backend with four tiny
probes (dot / elementwise / round-to-format / exp) plus a streaming copy —
so predictions and measurements share one clock and the validate() ratio is
machine-normalized.  ``paper_machine()`` carries the nominal accelerator
constants of ``repro.launch.mesh`` for offline what-if reports.
"""
from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import HBM_BW, PEAK_FLOPS_BF16


@dataclasses.dataclass(frozen=True)
class MachineModel:
    """Sustained rates: flops/s, elements/s, bytes/s — all f32 pipes."""

    name: str
    mxu_flops: float    # dot-product contraction flops/s
    vpu_flops: float    # elementwise mul/add flops/s
    quant_rate: float   # round-to-format elements/s (the quantize() chain)
    exp_rate: float     # transcendental exp() elements/s
    hbm_bw: float       # streaming interface bytes/s

    def as_dict(self) -> Dict[str, float]:
        return dict(name=self.name, mxu_flops=self.mxu_flops,
                    vpu_flops=self.vpu_flops, quant_rate=self.quant_rate,
                    exp_rate=self.exp_rate, hbm_bw=self.hbm_bw)


def paper_machine() -> MachineModel:
    """Nominal TPU-chip corner from ``repro.launch.mesh`` constants.

    VPU-class rates are the usual ~1/50 of the MXU peak; the round-to-format
    chain is ~12 VPU ops/element and exp ~8.  Indicative only — use
    ``calibrate()`` whenever a real backend is attached.
    """
    vpu = PEAK_FLOPS_BF16 / 50.0
    return MachineModel(name="tpu_paper", mxu_flops=PEAK_FLOPS_BF16,
                        vpu_flops=vpu, quant_rate=vpu / 12.0,
                        exp_rate=vpu / 8.0, hbm_bw=HBM_BW)


def _rate(fn: Callable, work: float, *args, n: int = 5) -> float:
    """work-units/s for a jitted ``fn``: warm once, median of ``n`` runs."""
    fn(*args).block_until_ready()
    samples = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        samples.append(time.perf_counter() - t0)
    return work / max(statistics.median(samples), 1e-12)


def calibrate(seed: int = 0, n: int = 5) -> MachineModel:
    """Measure the five pipe rates on the current jax default backend."""
    from repro.numerics.emulate import _on_tpu, quantize_tensor
    from repro.core.formats import BF16

    rng = np.random.default_rng(seed)
    sq = jnp.asarray(rng.standard_normal((512, 512)), jnp.float32)
    big = jnp.asarray(rng.standard_normal((2048, 2048)), jnp.float32)

    mxu = _rate(jax.jit(lambda a, b: a @ b), 2.0 * 512 ** 3, sq, sq, n=n)

    reps = 16  # chained FMAs so dispatch overhead amortizes out

    def _fma_chain(x):
        y = x
        for _ in range(reps):
            y = y * 1.0009765625 + 0.5  # exact-f32 constants
        return y

    vpu = _rate(jax.jit(_fma_chain), 2.0 * reps * big.size, big, n=n)

    q_impl = "pallas" if _on_tpu() else "ref"
    quant = _rate(jax.jit(lambda x: quantize_tensor(x, fmt=BF16,
                                                    impl=q_impl)),
                  float(big.size), big, n=n)

    expr = _rate(jax.jit(jnp.exp), float(big.size), big, n=n)

    hbm = _rate(jax.jit(lambda x: x + 1.0), 2.0 * 4.0 * big.size, big, n=n)

    return MachineModel(
        name=f"calibrated:{jax.default_backend()}", mxu_flops=mxu,
        vpu_flops=vpu, quant_rate=quant, exp_rate=expr, hbm_bw=hbm)
