"""Zamba2-style hybrid in plain float32 (zamba2-1.2b).

x = embed[tokens]; the Mamba-2 layers run in order, and after every
``shared_attn_every``-th one the single shared transformer block runs
(x = x + Attn(rmsnorm(x)); x = x + MLP(rmsnorm(x))); logits = rmsnorm(x) @
embed.T (tied).  Mamba2 layer: in_proj -> (z, xBC, dt); xBC through a
depthwise causal conv and SiLU; per head h_t = exp(dt_t A) h_{t-1} +
dt_t x_t B_t^T with a scalar A = -exp(A_log) per head; y_t = h_t C_t + D
x_t; out = rmsnorm(y * SiLU(z)) @ out_proj.  Attention is causal
multi-head attention with rotary positions over all of each head; the MLP is
GELU (tanh form).  The recurrence runs token by token.

Departures of the served model from the published Zamba2-1.2B, followed
here because they are the program's equations: the shared block reads the
residual stream alone (the published one reads it concatenated with the
original embeddings, so its attention is 2 x d_model wide, head size 128);
no per-application LoRA on the shared block; tanh GELU; RMSNorm epsilon
1e-6; one B/C group.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from reference.common import (causal_conv, einsum, gelu_tanh, layer_slice, mm,
                              rmsnorm, rope, silu, softplus, unembed)


@functools.partial(jax.jit,
                   static_argnames=("d_state", "head_dim", "quant"))
def _mamba2(layers, i, x, *, d_state, head_dim, quant):
    lp = layer_slice(layers, i)
    m = lp["mamba"]
    d_in = m["out_proj"].shape[0]
    H = d_in // head_dim
    N = d_state
    B, S, _ = x.shape
    proj = mm(rmsnorm(x, lp["ln"]["scale"]), m["in_proj"], quant)
    z = proj[..., :d_in]
    xbc = silu(causal_conv(proj[..., d_in:2 * d_in + 2 * N], m["conv_w"],
                           m["conv_b"]))
    dt = softplus(proj[..., 2 * d_in + 2 * N:]
                  + m["dt_bias"].astype(jnp.float32))  # (B, S, H)
    xs = xbc[..., :d_in].reshape(B, S, H, head_dim)
    Bm = xbc[..., d_in:d_in + N]
    Cm = xbc[..., d_in + N:]
    A = -jnp.exp(m["A_log"].astype(jnp.float32))  # (H,)

    def step(hs, inp):
        x_t, dt_t, b_t, c_t = inp  # (B,H,P) (B,H) (B,N) (B,N)
        hs = jnp.exp(dt_t * A)[:, :, None, None] * hs \
            + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :]
        return hs, jnp.sum(hs * c_t[:, None, None, :], axis=-1)

    h0 = jnp.zeros((B, H, head_dim, N), jnp.float32)
    seq = tuple(a.swapaxes(0, 1) for a in (xs, dt, Bm, Cm))
    _, y = lax.scan(step, h0, seq)
    y = y.swapaxes(0, 1) + m["D"].astype(jnp.float32)[:, None] * xs
    y = rmsnorm(y.reshape(B, S, d_in) * silu(z), m["norm"]["scale"])
    return x + mm(y, m["out_proj"], quant)


@functools.partial(jax.jit, static_argnames=("sizes_t", "quant"))
def _shared(p, x, *, sizes_t, quant):
    sizes = dict(sizes_t)
    H, Hkv, D = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
    B, S, _ = x.shape
    h = rmsnorm(x, p["ln1"]["scale"])
    pos = jnp.arange(S)
    q = rope(mm(h, p["wq"], quant).reshape(B, S, H, D), pos,
             sizes["rope_theta"])
    k = rope(mm(h, p["wk"], quant).reshape(B, S, Hkv, D), pos,
             sizes["rope_theta"])
    v = mm(h, p["wv"], quant).reshape(B, S, Hkv, D)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def one(qkv):  # one sequence at a time keeps the scores (H, S, S)
        q1, k1, v1 = qkv
        s = einsum("qhd,khd->hqk", q1, k1, quant) / math.sqrt(D)
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return einsum("hqk,khd->qhd", pr, v1, quant, axes=(-1, 0))

    att = lax.map(one, (q, k, v)).reshape(B, S, H * D)
    x = x + mm(att, p["wo"], quant)
    up = mm(rmsnorm(x, p["ln2"]["scale"]), p["mlp"]["w_up"], quant)
    return x + mm(gelu_tanh(up), p["mlp"]["w_down"], quant)


def logits_at(params, sizes, tokens, positions, quant=None):
    """Logits (B, K, V), float32 on the host, at ``positions`` (B, K) of
    the right-padded token rows ``tokens`` (B, S), layer by layer."""
    keys = ("n_heads", "n_kv_heads", "head_dim", "rope_theta")
    sizes_t = tuple((k, sizes[k]) for k in keys)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0).astype(
            jnp.float32)
        for i in range(sizes["n_layers"]):
            x = _mamba2(params["layers"], i, x, d_state=sizes["ssm_state"],
                        head_dim=sizes["ssm_head_dim"], quant=quant)
            if (i + 1) % sizes["shared_attn_every"] == 0:
                x = _shared(params["shared_attn"], x, sizes_t=sizes_t,
                            quant=quant)
        return unembed(params["final_norm"]["scale"], params["embed"], x,
                       positions, quant)
