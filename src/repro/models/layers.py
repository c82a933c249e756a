"""Shared model layers: norms, RoPE, MLPs, embeddings.

Pure-functional: params are nested dicts of jnp arrays; every layer is an
``init(key, ...) -> params`` plus an ``apply(params, x, ...)`` pair.  Matmuls
route through ``repro.models.numerics.matmul`` so the FPMax numerics policy
(emulated formats / accumulation styles) applies uniformly.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.numerics import matmul


def dense_init(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = (d_in ** -0.5) if scale is None else scale
    return (jax.random.normal(key, (d_in, d_out), jnp.float32)
            * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------
def rmsnorm_init(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def unit_rms(x, eps: float = 1e-6):
    """x in float32, scaled to unit RMS over its last axis."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(var + eps)


def rmsnorm(params, x, eps: float = 1e-6):
    y = unit_rms(x, eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float, rotate_dims: int):
    """inv_freq for the rotated prefix of the head dim."""
    half = rotate_dims // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float = 10000.0, style: str = "full"):
    """x: (..., S, H, D). style 'half' rotates only the first D/2 dims
    (ChatGLM's 2d RoPE); 'full' rotates all D dims pairwise."""
    if style == "none":
        return x
    d = x.shape[-1]
    rot = d if style == "full" else d // 2
    inv_freq = rope_frequencies(d, theta, rot)
    # positions: (..., S)
    ang = positions[..., None].astype(jnp.float32) * inv_freq  # (..., S, rot/2)
    sin = jnp.sin(ang)[..., None, :]  # (..., S, 1, rot/2)
    cos = jnp.cos(ang)[..., None, :]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    out = jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)
    if rot < d:
        out = jnp.concatenate([out, x_pass], axis=-1)
    return out


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------
def mlp_init(key, d: int, d_ff: int, act: str, dtype):
    ks = jax.random.split(key, 3)
    if act == "swiglu":
        return {"w_gate": dense_init(ks[0], d, d_ff, dtype),
                "w_up": dense_init(ks[1], d, d_ff, dtype),
                "w_down": dense_init(ks[2], d_ff, d, dtype)}
    return {"w_up": dense_init(ks[0], d, d_ff, dtype),
            "w_down": dense_init(ks[1], d_ff, d, dtype)}


def mlp_apply(params, x, act: str, policy=None):
    if act == "swiglu":
        gate = matmul(x, params["w_gate"], policy)
        up = matmul(x, params["w_up"], policy)
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    else:
        up = matmul(x, params["w_up"], policy)
        h = jax.nn.gelu(up.astype(jnp.float32)).astype(x.dtype)
    return matmul(h, params["w_down"], policy)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def embed_apply(table, tokens):
    return jnp.take(table, tokens, axis=0)


def unembed_apply(table, x, policy=None):
    """Logits in f32 (loss stability; f32 accumulation on the MXU)."""
    if policy is not None and getattr(policy, "emulate", False):
        return matmul(x, table.T, policy).astype(jnp.float32)
    return jnp.einsum("...d,vd->...v", x, table,
                      preferred_element_type=jnp.float32)
