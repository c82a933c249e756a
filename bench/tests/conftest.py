"""Tests of the benchmark, on the CPU at small sizes.

  python -m pytest bench/tests

They import the harness as ``bench/run.py`` does: with ``bench/`` and the
program's ``src/`` on the path.
"""
import copy
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_HYBRID = dict(family="hybrid", n_layers=4, d_model=64, n_heads=4,
                   n_kv_heads=4, head_dim=16, d_ff=128, vocab_size=256,
                   ssm_version=2, ssm_state=8, ssm_expand=2, ssm_head_dim=16,
                   ssm_conv=4, ssm_scan_chunk=32, shared_attn_every=2,
                   mlp_act="gelu", rope_style="full", rope_theta=10000.0,
                   dtype="bfloat16", kv_cache_dtype="")


def tiny_spec(limit=0.02):
    """The cell at CPU size: the harness's own files, small sizes.  At this
    size the program's widest gap reads 0.002-0.003 and the fp8 control's
    0.05-0.09 (three seeds): the limit lies between."""
    import run
    spec = copy.deepcopy(run.load_spec("zamba2-chat-burst"))
    cfg = spec["config"]
    cfg["model"] = dict(TINY_HYBRID)
    cfg["engine"].update(slots=4, max_len=256, prefill_chunk=64,
                         dispatch_tokens=4)
    cfg["check"].update(logit_gap_limit=limit, min_tokens=120,
                        max_requests=16, block=8)
    mix = spec["mix"]
    mix.update(drain_s=60, rate_rps=3.0,
               burst={"multiplier": 3.0, "mean_on_s": 0.5, "mean_off_s": 1.0})
    mix["prompt"] = {"dist": "lognormal", "median": 48, "sigma": 0.5,
                     "min": 32, "max": 128, "round_up": 32}
    mix["output"] = {"dist": "uniform", "min": 4, "max": 12}
    return spec


@pytest.fixture
def cpu_devices():
    import jax
    return jax.devices("cpu")
