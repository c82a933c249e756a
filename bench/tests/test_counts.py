"""Counts of operations and bytes, from the configurations' sizes."""
import json
import os

from conftest import BENCH


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_param_bytes_match_the_program_layout():
    import importlib

    import jax

    import run
    cfg = _config("zamba2-1.2b")
    counts = importlib.import_module(f"counts.{cfg['counts']}")
    layout = jax.eval_shape(run.model_for(cfg).init, jax.random.key(0))
    want = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(layout))
    assert counts.param_bytes(cfg["model"]) == want


def test_zamba2_decode_step_bytes():
    from counts import hybrid_mamba2 as c
    s = _config("zamba2-1.2b")["model"]
    # one lane, one step at live length 99: weights, state, 100 KV rows
    flops, byts = c.decode_work(s, [(99, 1)])
    kv_row = 6 * 2 * 32 * 64 * 2  # 48 KiB per position
    assert kv_row == 48 * 1024
    assert byts == c.param_bytes(s) + c._state_bytes(s) + 100 * kv_row
    # two steps: the weights twice, the context one longer
    flops2, byts2 = c.decode_work(s, [(99, 2)])
    assert byts2 == 2 * byts + kv_row
    assert flops2 > 2 * flops


def test_chunk_work_scales_with_tokens():
    from counts import hybrid_mamba2 as c
    s = _config("zamba2-1.2b")["model"]
    f1, b1 = c.chunk_work(s, [(0, 256)])
    f2, b2 = c.chunk_work(s, [(0, 256), (0, 256)])
    assert b2 > b1 and 1.9 < f2 / f1 < 2.01
    # a chunk at an offset reads the cache before it: more of both
    f3, b3 = c.chunk_work(s, [(1024, 256)])
    assert f3 > f1 and b3 > b1
