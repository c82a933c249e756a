"""HLO analyzer: trip-count multiplication, flops/collective exactness."""
import numpy as np
import pytest

from helpers import run_multidevice
from repro.roofline.analysis import RooflineReport, model_flops_estimate
from repro.roofline.hlo_parse import _shape_bytes, analyze_hlo, parse_module


def test_shape_bytes():
    assert _shape_bytes("bf16[128,4096]{1,0}") == 128 * 4096 * 2
    assert _shape_bytes("(f32[2,3], s32[4])") == 24 + 16
    assert _shape_bytes("f8e4m3fn[10]") == 10
    assert _shape_bytes("pred[]") == 1


@pytest.mark.slow
def test_scan_flops_and_collectives_exact():
    run_multidevice("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.mesh import make_host_mesh
        from repro.roofline.hlo_parse import analyze_hlo
        mesh = make_host_mesh(data=2, model=2)
        W = jax.ShapeDtypeStruct((2048, 2048), jnp.float32)
        x = jax.ShapeDtypeStruct((256, 2048), jnp.float32)
        def f(w, x):
            def body(c, _):
                y = c @ w
                y = jax.lax.with_sharding_constraint(
                    y, NamedSharding(mesh, P("data", "model")))
                return y, None
            out, _ = jax.lax.scan(body, x, None, length=7)
            return out.sum()
        c = jax.jit(f, in_shardings=(
            NamedSharding(mesh, P(None, "model")),
            NamedSharding(mesh, P("data", None)))).lower(W, x).compile()
        cost = analyze_hlo(c.as_text())
        exp = 2 * 256 * 2048 * 2048 * 7 / 4  # per device, x7 trips
        assert abs(cost.flops / exp - 1) < 0.02, cost.flops
        ag = cost.collective_bytes.get("all-gather", 0)
        assert abs(ag - 7 * 128 * 2048 * 4) < 1e-6, ag
        print("OK")
    """, n_devices=4)


def test_roofline_report_terms():
    from repro.configs.base import SHAPES, get_config
    cfg = get_config("tinyllama-1.1b")
    r = RooflineReport(
        arch="tinyllama-1.1b", shape="train_4k", mesh="m", chips=256,
        flops_per_device=1e14, bytes_per_device=1e12,
        collective_bytes_per_device=1e11, collective_breakdown={},
        model_flops=model_flops_estimate(cfg, SHAPES["train_4k"]))
    assert abs(r.t_compute - 1e14 / 197e12) < 1e-9
    assert abs(r.t_memory - 1e12 / 819e9) < 1e-9
    assert abs(r.t_collective - 1e11 / 50e9) < 1e-9
    assert r.bottleneck == "collective"
    assert 0 < r.roofline_fraction < 1
    # model flops: 6 * N * tokens
    n = cfg.param_count()
    assert abs(r.model_flops - 6 * n * 4096 * 256) / r.model_flops < 1e-9


def test_moe_model_flops_uses_active_params():
    from repro.configs.base import SHAPES, get_config
    cfg = get_config("mixtral-8x7b")
    mf = model_flops_estimate(cfg, SHAPES["train_4k"])
    assert mf < 6 * cfg.param_count() * 4096 * 256  # < dense-total count
    assert mf == 6 * cfg.active_param_count() * 4096 * 256


def test_dryrun_results_exist_and_pass():
    """The committed dry-run sweeps must show every runnable cell OK on both
    the single-pod and the multi-pod mesh (deliverable (e))."""
    import json, os
    for mesh in ("pod16x16", "pod2x16x16"):
        path = os.path.join("results", f"dryrun_{mesh}.json")
        if not os.path.exists(path):
            pytest.skip("dry-run results not generated yet")
        data = json.load(open(path))
        assert len(data) == 33, (mesh, len(data))
        bad = {k: v.get("status") for k, v in data.items()
               if v.get("status") != "ok"}
        assert not bad, bad
