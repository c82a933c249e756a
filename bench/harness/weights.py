"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights itself, so the reference reads nothing that
the program made.  It takes from the program only the layout of its
parameters (names, shapes, dtypes: ``jax.eval_shape`` of its ``init``) and
fills every leaf by a rule of its own:

* ``embed`` (V, d): N(0, 0.02^2);
* a norm's ``scale``: uniform in [0.9, 1.1];
* ``conv_w`` (K, C): N(0, 1/K^2); ``conv_b``: N(0, 0.02^2);
* ``A_log`` (H,): log of uniform [1, 16];
* ``dt_bias``: softplus^-1 of dt, log-uniform in [1e-3, 1e-1];
* ``D``: ones;
* any other leaf of 2 or more dims: N(0, 1/fan_in), fan_in = shape[-2];
  any other vector: N(0, 0.02^2).

Leaves under ``layers`` carry the layer on axis 0 and are drawn one layer
at a time inside the call (``lax.map``), so no leaf's float32 draw of all
layers is ever live.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import SingleDeviceSharding


def jax_key(seed: int):
    """A JAX key from any whole number (seeds may pass 2**31)."""
    word = np.random.default_rng(seed % 2 ** 64).integers(2 ** 31)
    return jax.random.key(int(word))


def _leaf(name: str, shape, key):
    """One leaf (or one layer of a stacked leaf), float32."""
    if name == "embed":
        return 0.02 * jax.random.normal(key, shape)
    if name == "scale":
        return jax.random.uniform(key, shape, minval=0.9, maxval=1.1)
    if name == "conv_w":
        return jax.random.normal(key, shape) / shape[-2]
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, minval=1.0,
                                          maxval=16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, minval=np.log(1e-3),
                                        maxval=np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "D":
        return jnp.ones(shape)
    if len(shape) >= 2:
        return jax.random.normal(key, shape) * shape[-2] ** -0.5
    return 0.02 * jax.random.normal(key, shape)


def make_params(layout, seed: int, device):
    """Parameters shaped as ``layout`` (a pytree of ShapeDtypeStructs),
    drawn from ``seed``, placed on ``device`` in their layout's dtypes."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(layout)

    def build(key):
        leaves = []
        for i, (path, sd) in enumerate(flat):
            names = [getattr(p, "key", None) for p in path]
            k = jax.random.fold_in(key, i)
            name = names[-1]
            if names[0] == "layers":
                leaf = lax.map(
                    lambda j, k=k, name=name, shape=sd.shape[1:], dt=sd.dtype:
                    _leaf(name, shape, jax.random.fold_in(k, j)).astype(dt),
                    jnp.arange(sd.shape[0]))
            else:
                leaf = _leaf(name, sd.shape, k).astype(sd.dtype)
            leaves.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    out = SingleDeviceSharding(device)
    return jax.jit(build, out_shardings=out)(jax_key(seed))
