"""The float32 references against the program's ``LM`` at a small size,
both in float32: they compute the same equations."""
import dataclasses

import numpy as np
import pytest

from conftest import TINY_HYBRID


@pytest.mark.parametrize("length", [77, 130])
def test_reference_matches_lm_in_float32(length):
    import jax

    from harness.check import reference
    from harness.weights import make_params
    from repro.configs.base import get_config
    from repro.models import LM

    f32 = dict(TINY_HYBRID, dtype="float32")
    model = LM(dataclasses.replace(get_config("zamba2-1.2b"), **f32))
    layout = jax.eval_shape(model.init, jax.random.key(0))
    params = make_params(layout, 3, jax.devices("cpu")[0])
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, f32["vocab_size"], (2, length)).astype(np.int32)
    want = np.asarray(jax.jit(model.apply)(params, tokens)[0])
    pos = np.tile(np.arange(length, dtype=np.int32), (2, 1))
    got = np.asarray(reference("hybrid_mamba2").logits_at(params, f32,
                                                          tokens, pos))
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err < 1e-4, err
