"""Falcon-Mamba in plain float32 (falcon-mamba-7b).

x = embed[tokens]; the Mamba-1 layers run in order, each
x = x + Mixer(rmsnorm(x)); logits = rmsnorm(x) @ embed.T (tied).  Mixer:
in_proj -> (x, z); x through a depthwise causal conv and SiLU;
x_proj(x) -> (Δ_low, B, C), each scaled to unit RMS over its last axis
(epsilon ``MIXER_RMS_EPS``, the published ``mixer_rms_eps``, no scale
parameter: Falcon-Mamba's addition to Mamba-1); Δ = softplus(Δ_low @ dt_proj + dt_bias); per channel
and state entry h_t = exp(Δ_t A) h_{t-1} + Δ_t x_t B_t with
A = -exp(A_log) of shape (d_inner, N); y_t = h_t C_t + D x_t;
out = (y * SiLU(z)) @ out_proj.  The recurrence runs token by token.

Departures of the served model from the published Falcon-Mamba-7B,
followed here because they are the program's equations: the head is tied
to the embedding (the published model has a separate one); the block
RMSNorm epsilon is 1e-6 (published 1e-5); the residual stream is the
program's bf16 (published residual_in_fp32), which a float32 reference
does not round.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from reference.common import (causal_conv, layer_slice, mm, rmsnorm, silu,
                              softplus, unembed)


MIXER_RMS_EPS = 1e-6


def unit_rms(x):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                         + MIXER_RMS_EPS)


@functools.partial(jax.jit, static_argnames=("d_state", "quant"))
def _mamba1(layers, i, x, *, d_state, quant):
    lp = layer_slice(layers, i)
    m = lp["mamba"]
    d_in = m["out_proj"].shape[0]
    R = m["dt_proj"].shape[0]
    N = d_state
    proj = mm(rmsnorm(x, lp["ln"]["scale"]), m["in_proj"], quant)
    z = proj[..., d_in:]
    xs = silu(causal_conv(proj[..., :d_in], m["conv_w"], m["conv_b"]))
    dbc = mm(xs, m["x_proj"], quant)
    dt_low, Bm, Cm = dbc[..., :R], dbc[..., R:R + N], dbc[..., R + N:]
    dt_low, Bm, Cm = (unit_rms(t) for t in (dt_low, Bm, Cm))
    dt = softplus(mm(dt_low, m["dt_proj"], quant)
                  + m["dt_bias"].astype(jnp.float32))  # (B, S, d_in)
    A = -jnp.exp(m["A_log"].astype(jnp.float32))  # (d_in, N)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp  # (B,d_in) (B,d_in) (B,N) (B,N)
        h = jnp.exp(dt_t[..., None] * A) * h \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    h0 = jnp.zeros((x.shape[0], d_in, N), jnp.float32)
    seq = tuple(a.swapaxes(0, 1) for a in (xs, dt, Bm, Cm))
    _, y = lax.scan(step, h0, seq)
    y = y.swapaxes(0, 1) + m["D"].astype(jnp.float32) * xs
    return x + mm(y * silu(z), m["out_proj"], quant)


def logits_at(params, sizes, tokens, positions, quant=None):
    """Logits (B, K, V), float32 on the host, at ``positions`` (B, K) of
    the right-padded token rows ``tokens`` (B, S), layer by layer."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0).astype(
            jnp.float32)
        for i in range(sizes["n_layers"]):
            x = _mamba1(params["layers"], i, x, d_state=sizes["ssm_state"],
                        quant=quant)
        return unembed(params["final_norm"]["scale"], params["embed"], x,
                       positions, quant)
