"""Float32 building blocks shared by the reference forward passes.

Every matrix product runs at ``Precision.HIGHEST`` (on a TPU a float32
product otherwise runs in bf16 passes).  ``quant="fp8"`` rounds both operands
of every product to float8_e4m3 first, with one scale per row of the left
operand and per column of the right one: the control, the same model one
precision step below the bf16 the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
E4M3_MAX = 448.0


def fp8_round(x, axis):
    """``x`` rounded to float8_e4m3 with one absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def mm(x, w, quant=None):
    """x (..., K) @ w (K, N) in float32."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = fp8_round(x, -1), fp8_round(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def einsum(spec, a, b, quant=None, axes=(-1, -1)):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if quant == "fp8":
        a, b = fp8_round(a, axes[0]), fp8_round(b, axes[1])
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rmsnorm(x, scale, eps=1e-6):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def silu(x):
    return x * jax.nn.sigmoid(x)


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def causal_conv(x, w, b):
    """Depthwise causal convolution: x (B, S, C), w (K, C), b (C,);
    out[t] = b + sum_k w[k] * x[t - (K - 1) + k], zeros before the start."""
    K = w.shape[0]
    S = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = b.astype(jnp.float32)
    for k in range(K):
        out = out + xp[:, k:k + S] * w[k].astype(jnp.float32)
    return out


def rope(x, positions, theta):
    """Rotary embedding over the whole head: the head's first and second
    halves are the two coordinates of each rotated pair.  x (B, S, H, D),
    positions (S,)."""
    D = x.shape[-1]
    half = D // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv  # (S, half)
    sin = jnp.sin(ang)[None, :, None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


ROWS = 64  # rows of one unembedding call: one program for every run


@functools.partial(jax.jit, static_argnames=("quant",))
def _unembed_rows(scale, embed, rows, *, quant):
    return mm(rmsnorm(rows, scale), embed.T, quant)


def unembed(norm_scale, embed, x, positions, quant=None) -> np.ndarray:
    """Final norm and unembedding of the rows ``positions`` (B, K) of x
    (B, S, d) against the tied embedding table (V, d): (B, K, V) float32,
    on the host.  The rows are gathered on the host and unembedded
    ``ROWS`` at a time, so every run calls one program of one shape: one
    device program over each run's own (B, S, K) did not finish on a v5e
    for some of them."""
    positions = np.asarray(positions)
    B, K = positions.shape
    x = np.asarray(x)
    rows = x[np.arange(B)[:, None], positions].reshape(B * K, x.shape[-1])
    rows = np.concatenate([rows, np.zeros((-len(rows) % ROWS, rows.shape[1]),
                                          rows.dtype)])
    out = [np.asarray(_unembed_rows(norm_scale, embed,
                                    jnp.asarray(rows[i:i + ROWS]),
                                    quant=quant))
           for i in range(0, len(rows), ROWS)]
    return np.concatenate(out)[:B * K].reshape(B, K, -1)


def layer_slice(layers, i):
    """Layer ``i`` of a stack whose leaves carry the layer on axis 0."""
    return jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, i, 0, False),
                        layers)
