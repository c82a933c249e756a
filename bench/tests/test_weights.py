"""The benchmark's own weights fill the program's layout from a seed."""
import dataclasses

import numpy as np

from conftest import TINY_HYBRID


def test_weights_follow_the_layout_and_the_seed():
    import jax

    from harness.weights import make_params
    from repro.configs.base import get_config
    from repro.models import LM
    model = LM(dataclasses.replace(get_config("zamba2-1.2b"), **TINY_HYBRID))
    layout = jax.eval_shape(model.init, jax.random.key(0))
    dev = jax.devices("cpu")[0]
    a = make_params(layout, 2 ** 33 + 5, dev)
    b = make_params(layout, 2 ** 33 + 5, dev)
    c = make_params(layout, 6, dev)
    assert jax.tree.structure(a) == jax.tree.structure(layout)
    for x, s in zip(jax.tree.leaves(a), jax.tree.leaves(layout)):
        assert x.shape == s.shape and x.dtype == s.dtype
    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not np.array_equal(la[0], lc[0])
    # Mamba-2 decay rates are negative, steps in [1e-3, 1e-1]
    m = a["layers"]["mamba"]
    A = -np.exp(np.asarray(m["A_log"]))
    dt = np.log1p(np.exp(np.asarray(m["dt_bias"])))
    assert (A < 0).all() and dt.min() > 9e-4 and dt.max() < 0.11
    # the stacked layers differ from each other
    w = np.asarray(m["in_proj"], np.float32)
    assert not np.array_equal(w[0], w[1])
