"""Compile rehearsals for one TPU v5e chip, without the chip.

The TPU compiler is installed even where no TPU is attached, and it compiles
for a described ``v5e:2x2`` topology.  These tests compile each Pallas
kernel at zamba2-1.2b widths, asserting that the program holds the Mosaic
kernel (``tpu_custom_call``), and the serving engine's decode and
prefill-chunk programs for zamba2-1.2b at its published config and for one
16-layer stage of falcon-mamba-7b at its published widths, asserting that
they fit the chip's 16 GB, and that zamba2's decode dispatch carries its
cache in place (temporaries below the K/V cache).  They catch what
interpret mode cannot: tiling and VMEM refusals, unsupported ops,
programs that do not fit, copies the compiler adds.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.formats import BF16, FP32, FP8_E4M3
from repro.kernels import fma_emu, fused, quantize_kernel, ssm_scan

HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


def _shapes(sharding, *shapes, dtype=jnp.float32):
    return [jax.ShapeDtypeStruct(s, dtype, sharding=sharding) for s in shapes]


QMM = ((1, 256, 2048), (2048, 8192))
FLASH = ((1, 512, 32, 64),) * 3
SSM = ((1, 256, 4096, 64), (1, 256, 4096, 64), (1, 256, 64))

KERNELS = {
    **{f"fused_qmm-{fmt.name}-{style}": (
        lambda a, b, fmt=fmt, style=style: fused.fused_qmm(
            a, b, fmt=fmt, style=style), QMM)
       for fmt in (BF16, FP8_E4M3, FP32) for style in ("fused", "cascade")},
    **{f"fused_qmm-{fmt.name}-scaled": (
        lambda a, b, fmt=fmt: fused.fused_qmm(a, b, fmt=fmt, scaled=True),
        QMM) for fmt in (BF16, FP8_E4M3)},
    "fused_flash-bf16-scaled": (
        lambda q, k, v: fused.fused_flash_attention(q, k, v, fmt=BF16),
        FLASH),
    "fused_flash-native": (
        lambda q, k, v: fused.fused_flash_attention(q, k, v, fmt=None),
        FLASH),
    "ssm_scan_quantized-bf16": (
        lambda a, b, c: fused.ssm_scan_quantized(a, b, c, fmt=BF16), SSM),
    "ssm_scan": (lambda a, b, c: ssm_scan.ssm_scan(a, b, c), SSM),
    "quantize_2d-bf16": (
        lambda x: quantize_kernel.quantize_2d(x, fmt=BF16), ((2048, 2048),)),
    "fma_emu-bf16": (
        lambda a, b: fma_emu.fma_emu_matmul(a, b, fmt=BF16),
        ((256, 2048), (2048, 2048))),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    compiled = jax.jit(fn).lower(*_shapes(one_chip, *shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _fits(compiled):
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert need < HBM_BYTES, m
    return need


def _served(model, one_chip, slots, max_len):
    """Params, a ``slots``-slot decode cache of ``max_len`` and the slot
    state of ``model``, all as shapes on one chip."""
    from repro.models.model import DecodeCache

    def state():
        cache = model.init_cache(slots, max_len)
        return (DecodeCache(cache.data, jnp.zeros(slots, jnp.int32)),
                jnp.zeros((slots, 1), jnp.int32), jnp.zeros(slots, bool),
                jnp.zeros(slots, jnp.int32))

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.key(0)))
    return model, params, on_chip(jax.eval_shape(state)), slots


@pytest.fixture(scope="module")
def zamba2(one_chip):
    """zamba2-1.2b at its published config: params, an 8-slot 1024-token
    decode cache, and the slot state, all as shapes on one chip."""
    from repro.configs.base import get_config
    from repro.models import LM
    return _served(LM(get_config("zamba2-1.2b")), one_chip, 8, 1024)


@pytest.fixture(scope="module")
def falcon_stage(one_chip):
    """falcon-mamba-7b cut to one 16-layer pipeline stage at every
    published width (the benchmark's configuration), 8 slots."""
    import dataclasses

    from repro.configs.base import get_config
    from repro.models import LM
    cfg = dataclasses.replace(get_config("falcon-mamba-7b"), n_layers=16)
    return _served(LM(cfg), one_chip, 8, 4352)


def _decode_scan(served):
    from repro.serve.engine import _dispatch_jit
    model, params, (cache, tok, active, budget), _ = served
    return _dispatch_jit.lower(model, 0, 8, (), params, cache, tok, active,
                               budget).compile()


@pytest.fixture(scope="module")
def zamba2_decode_scan(zamba2):
    return _decode_scan(zamba2)


def _prefill_chunk_fits(served, one_chip):
    from repro.serve.engine import _chunk_jit
    model, params, (cache, tok, active, budget), slots = served
    lanes, chunk = slots, 256
    tokens = _shapes(one_chip, (lanes, chunk), dtype=jnp.int32)[0]
    per_lane = _shapes(one_chip, *[(lanes,)] * 5, dtype=jnp.int32)
    compiled = _chunk_jit.lower(model, params, cache, tok, active, budget,
                                tokens, *per_lane).compile()
    _fits(compiled)


def test_zamba2_decode_scan_fits_v5e(zamba2_decode_scan):
    _fits(zamba2_decode_scan)


def test_zamba2_decode_scan_temporaries_below_kv_cache(zamba2,
                                                      zamba2_decode_scan):
    """The dispatch updates its carried cache in place: its temporaries
    stay below the K/V cache it carries (a whole-cache select, a restack or
    a relayout of the cache around the token scan each take more)."""
    _, _, (cache, _, _, _), _ = zamba2
    kv = sum(cache.data[n].size * cache.data[n].dtype.itemsize
             for n in ("k", "v"))
    temp = zamba2_decode_scan.memory_analysis().temp_size_in_bytes
    assert temp < kv, (temp, kv)


def test_zamba2_prefill_chunk_fits_v5e(zamba2, one_chip):
    _prefill_chunk_fits(zamba2, one_chip)


def test_falcon_stage_decode_scan_fits_v5e(falcon_stage):
    _fits(_decode_scan(falcon_stage))


def test_falcon_stage_prefill_chunk_fits_v5e(falcon_stage, one_chip):
    _prefill_chunk_fits(falcon_stage, one_chip)
