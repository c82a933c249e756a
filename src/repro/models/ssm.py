"""Selective state-space blocks: Mamba-1 (falcon-mamba) and Mamba-2 (zamba2).

Training/prefill runs a sequential lax.scan over chunks of tokens, carrying
the state, so the full (B,S,*state) tensor is never live; only one chunk's
work is, and the checkpointed chunk body is recomputed in the backward pass.
Inside a chunk the two versions differ, because their decays differ:

* Mamba-1 decays per channel and state entry (A is (d_in, N)).  Its chunk
  body expands the per-token (d_in, N) transitions and runs an associative
  scan over them: h_t = a_t * h_{t-1} + b_t, combine
  (aL,bL)∘(aR,bR) = (aL*aR, bL*aR + bR).  The mixer is Falcon-Mamba's
  (arXiv:2410.05355), the only Mamba-1 model registered: after ``x_proj``
  Δ_low (before ``dt_proj``), B and C are each scaled to unit RMS over
  their last axis by a weightless norm (``MIXER_RMS_EPS``), in float32 and
  cast back, alike in the chunk body and the one-token decode update.
* Mamba-2 decays by one scalar per head (A is (H,)) and shares B and C
  across heads, so its chunk body is the chunked SSD form of Dao & Gu
  (arXiv:2405.21060, section 6): small matmuls over the chunk's (Q x Q)
  decay-masked C·Bᵀ and its inputs, never the per-token (H, P, N)
  expansion.

Decode is a single O(1) state update in both.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import dense_init, rmsnorm, rmsnorm_init, unit_rms


#: Falcon-Mamba's ``mixer_rms_eps``: epsilon of the Δ/B/C norms.
MIXER_RMS_EPS = 1e-6


def _combine(left, right):
    aL, bL = left
    aR, bR = right
    return aL * aR, bL * aR + bR


def chunked_linear_scan(a, b, h0, chunk: int = 64):
    """a,b: (B,S,...state dims); h0: (B,...state). Returns (h_seq, h_last).

    The chunk step is jax.checkpoint'ed: the backward pass recomputes each
    chunk's associative scan instead of saving every per-token (d_inner x
    d_state) expansion — bounding training memory to one chunk plus the
    chunk-boundary carries (the standard selective-scan recompute trick)."""
    B, S = a.shape[0], a.shape[1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2),
                    constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad)) + ((0, 0),) * (b.ndim - 2))
    n = (S + pad) // chunk
    a_c = a.reshape((B, n, chunk) + a.shape[2:]).swapaxes(0, 1)
    b_c = b.reshape((B, n, chunk) + b.shape[2:]).swapaxes(0, 1)

    @jax.checkpoint
    def step(h, ab):
        a_k, b_k = ab  # (B, chunk, ...)
        pa, pb = lax.associative_scan(_combine, (a_k, b_k), axis=1)
        h_seq = pb + pa * h[:, None]
        return h_seq[:, -1], h_seq

    h_last, h_all = lax.scan(step, h0, (a_c, b_c))
    # h_all: (n, B, chunk, *state) — state dims follow b (a may broadcast)
    h_all = h_all.swapaxes(0, 1).reshape((B, n * chunk) + h_all.shape[3:])
    return h_all[:, :S], h_last


# ---------------------------------------------------------------------------
# Depthwise causal conv (the short conv in both mamba versions)
# ---------------------------------------------------------------------------
def causal_conv1d(x, w, b, carry=None):
    """x: (B,S,C); w: (K,C) depthwise; carry: (B,K-1,C) past inputs."""
    K = w.shape[0]
    if carry is None:
        carry = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xc = jnp.concatenate([carry, x], axis=1)
    out = jnp.zeros_like(x)
    for i in range(K):
        out = out + xc[:, i:i + x.shape[1]] * w[i]
    new_carry = xc[:, -(K - 1):] if K > 1 else carry
    return out + b, new_carry


# ---------------------------------------------------------------------------
# Mamba-1 (falcon-mamba-7b)
# ---------------------------------------------------------------------------
def mamba1_init(key, d_model: int, *, d_state: int, expand: int, conv: int,
                dtype) -> Dict:
    d_in = expand * d_model
    dt_rank = max(d_model // 16, 1)
    ks = jax.random.split(key, 8)
    return {
        "in_proj": dense_init(ks[0], d_model, 2 * d_in, dtype),
        "conv_w": (jax.random.normal(ks[1], (conv, d_in), jnp.float32)
                   * (1.0 / conv)).astype(dtype),
        "conv_b": jnp.zeros((d_in,), dtype),
        "x_proj": dense_init(ks[2], d_in, dt_rank + 2 * d_state, dtype),
        "dt_proj": dense_init(ks[3], dt_rank, d_in, dtype, scale=dt_rank**-0.5),
        "dt_bias": jnp.full((d_in,), -4.6, jnp.float32),  # softplus ~ 0.01
        "A_log": jnp.log(jnp.tile(
            jnp.arange(1, d_state + 1, dtype=jnp.float32)[None], (d_in, 1))),
        "D": jnp.ones((d_in,), jnp.float32),
        "out_proj": dense_init(ks[4], d_in, d_model, dtype),
    }


def _mamba1_core(p, xc, d_state: int):
    """xc: (B,S,d_in) post-conv. Returns per-step (a, b, C, x) tensors."""
    dt_rank = p["dt_proj"].shape[0]
    proj = xc @ p["x_proj"]
    dt_low, Bm, Cm = jnp.split(proj, [dt_rank, dt_rank + d_state], axis=-1)
    dt_low, Bm, Cm = (unit_rms(t, MIXER_RMS_EPS).astype(t.dtype)
                      for t in (dt_low, Bm, Cm))
    dt = jax.nn.softplus((dt_low @ p["dt_proj"]).astype(jnp.float32)
                         + p["dt_bias"])  # (B,S,d_in)
    A = -jnp.exp(p["A_log"])  # (d_in, n)
    a = jnp.exp(dt[..., None] * A)  # (B,S,d_in,n)
    bx = (dt * xc.astype(jnp.float32))[..., None] \
        * Bm.astype(jnp.float32)[..., None, :]  # (B,S,d_in,n)
    return a, bx, Cm.astype(jnp.float32)


def _chunked_ssm(inputs, h0, expand_fn, chunk: int):
    """Generic chunked selective scan that never materializes the full
    (B,S,*state) expansion: ``expand_fn`` maps a chunk of raw per-token
    inputs to (a, bx, readout_fn) *inside* the (checkpointed) chunk body,
    so only one chunk's expansion is ever live (fwd AND bwd).

    inputs: pytree of (B,S,...) tensors; returns (y (B,S,...), h_last)."""
    leaves = jax.tree.leaves(inputs)
    B, S = leaves[0].shape[0], leaves[0].shape[1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    mask = jnp.ones((B, S), jnp.float32)
    if pad:
        inputs = jax.tree.map(
            lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)),
            inputs)
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = (S + pad) // chunk
    chunked = jax.tree.map(
        lambda t: t.reshape((B, n, chunk) + t.shape[2:]).swapaxes(0, 1),
        (inputs, mask))

    @jax.checkpoint
    def step(h, chunk_and_mask):
        # named scope -> HLO metadata for fused-kernel traffic attribution
        with jax.named_scope("selective_scan_kernel"):
            return _scan_chunk(h, chunk_and_mask)

    def _scan_chunk(h, chunk_and_mask):
        chunk_inputs, m = chunk_and_mask
        a_k, bx_k, readout = expand_fn(chunk_inputs)
        # padded positions are identity transitions (a=1, b=0)
        me = m.reshape(m.shape + (1,) * (a_k.ndim - 2))
        a_k = a_k * me + (1.0 - me)
        bx_k = bx_k * m.reshape(m.shape + (1,) * (bx_k.ndim - 2))
        pa, pb = lax.associative_scan(_combine, (a_k, bx_k), axis=1)
        h_seq = pb + pa * h[:, None]
        y_k = readout(h_seq)
        return h_seq[:, -1], y_k

    h_last, y = lax.scan(step, h0, chunked)
    y = y.swapaxes(0, 1).reshape((B, n * chunk) + y.shape[3:])
    return y[:, :S], h_last


def mamba1_apply(p, x, *, d_state: int, chunk: int = 64,
                 state: Tuple | None = None, return_state: bool = False):
    """x: (B,S,d). state: (conv_carry, h) for stepwise decode."""
    B, S, _ = x.shape
    d_in = p["out_proj"].shape[0]
    xz = x @ p["in_proj"]
    x_in, z = jnp.split(xz, 2, axis=-1)
    conv_carry = None if state is None else state[0]
    xc, new_conv = causal_conv1d(x_in, p["conv_w"], p["conv_b"], conv_carry)
    xc = jax.nn.silu(xc.astype(jnp.float32)).astype(x.dtype)
    h0 = (jnp.zeros((B, d_in, d_state), jnp.float32) if state is None
          else state[1])
    if S == 1:  # decode fast path: one state update
        a, bx, Cm = _mamba1_core(p, xc, d_state)
        h_last = a[:, 0] * h0 + bx[:, 0]
        y = jnp.einsum("bdn,bn->bd", h_last, Cm[:, 0])[:, None]
    else:
        def expand(xc_k):
            a, bx, Cm = _mamba1_core(p, xc_k, d_state)
            return a, bx, (lambda h_seq:
                           jnp.einsum("bsdn,bsn->bsd", h_seq, Cm))

        y, h_last = _chunked_ssm(xc, h0, expand, chunk)
    y = y + p["D"] * xc.astype(jnp.float32)
    y = y.astype(x.dtype) * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    out = y @ p["out_proj"]
    if return_state:
        return out, (new_conv, h_last)
    return out


# ---------------------------------------------------------------------------
# Mamba-2 (zamba2-1.2b)
# ---------------------------------------------------------------------------
def mamba2_init(key, d_model: int, *, d_state: int, expand: int, conv: int,
                head_dim: int, dtype) -> Dict:
    d_in = expand * d_model
    n_heads = d_in // head_dim
    ks = jax.random.split(key, 6)
    d_conv_in = d_in + 2 * d_state  # x, B, C go through the conv
    return {
        "in_proj": dense_init(ks[0], d_model,
                              2 * d_in + 2 * d_state + n_heads, dtype),
        "conv_w": (jax.random.normal(ks[1], (conv, d_conv_in), jnp.float32)
                   * (1.0 / conv)).astype(dtype),
        "conv_b": jnp.zeros((d_conv_in,), dtype),
        "A_log": jnp.zeros((n_heads,), jnp.float32),
        "dt_bias": jnp.full((n_heads,), -4.6, jnp.float32),
        "D": jnp.ones((n_heads,), jnp.float32),
        "norm": rmsnorm_init(d_in, dtype),
        "out_proj": dense_init(ks[2], d_in, d_model, dtype),
    }


def _ssd_scan(xh, Bm, Cm, dt, A, h0, chunk: int):
    """Mamba-2's selective scan in chunked SSD (matmul) form.

    xh: (B,S,H,P) inputs; Bm, Cm: (B,S,N); dt: (B,S,H) f32; A: (H,) f32
    decay rates; h0: (B,H,P,N) f32.  Returns (y (B,S,H,P) f32 without the
    D skip, h_last).  Within a chunk of Q tokens, with cum = cumsum(dt·A):
      y_t   = Σ_{s<=t} exp(cum_t - cum_s)·(C_t·B_s)·dt_s·x_s
              + exp(cum_t)·C_t·h_prev
      h_new = exp(cum_Q)·h_prev + Σ_s exp(cum_Q - cum_s)·dt_s·x_s ⊗ B_s
    The largest live tensor is the (B,H,Q,Q) decay mask.  Every chunk of a
    call runs the same body on the same carry, so a prefill split at
    multiples of ``chunk`` equals the unsplit one bit for bit.  The state
    and every product stay float32 (``Precision.HIGHEST``: the TPU's
    default would round operands to bfloat16)."""
    B, S = dt.shape[:2]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:  # padded positions get dt = 0: decay 1, input 0
        xh, Bm, Cm, dt = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (xh, Bm, Cm, dt))
    n = (S + pad) // chunk
    chunked = jax.tree.map(
        lambda t: t.reshape((B, n, chunk) + t.shape[2:]).swapaxes(0, 1),
        (xh, Bm, Cm, dt))
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    mm = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)

    @jax.checkpoint
    def step(h, inputs):
        # named scope -> HLO metadata for fused-kernel traffic attribution
        with jax.named_scope("selective_scan_kernel"):
            x, b, c, dt_k = jax.tree.map(
                lambda t: t.astype(jnp.float32), inputs)
            dt_h = dt_k.swapaxes(1, 2)  # (B,H,Q)
            cum = jnp.cumsum(dt_h * A[:, None], axis=-1)
            # exp of the masked (upper) half would overflow: mask first
            decay = jnp.exp(jnp.where(
                causal, cum[..., :, None] - cum[..., None, :], -jnp.inf))
            cb = mm("bqn,bsn->bqs", c, b)
            m = decay * cb[:, None] * dt_h[:, :, None, :]  # (B,H,Q,Q)
            y = (mm("bhqs,bshp->bqhp", m, x)
                 + mm("bqn,bhpn->bqhp", c, h)
                 * jnp.exp(cum).swapaxes(1, 2)[..., None])
            w = (jnp.exp(cum[..., -1:] - cum) * dt_h).swapaxes(1, 2)
            h_new = (jnp.exp(cum[..., -1])[..., None, None] * h
                     + mm("bshp,bsn->bhpn", x * w[..., None], b))
            return h_new, y

    h_last, y = lax.scan(step, h0, chunked)
    y = y.swapaxes(0, 1).reshape((B, n * chunk) + y.shape[3:])
    return y[:, :S], h_last


def mamba2_apply(p, x, *, d_state: int, head_dim: int, chunk: int = 64,
                 state: Tuple | None = None, return_state: bool = False):
    B, S, _ = x.shape
    d_in = p["out_proj"].shape[0]
    H = d_in // head_dim
    proj = x @ p["in_proj"]
    z, xbc, dt_raw = jnp.split(proj, [d_in, 2 * d_in + 2 * d_state], axis=-1)
    x_part = xbc[..., :d_in]
    bc_part = xbc[..., d_in:]
    conv_in = jnp.concatenate([x_part, bc_part], axis=-1)
    conv_carry = None if state is None else state[0]
    xc_all, new_conv = causal_conv1d(conv_in, p["conv_w"], p["conv_b"],
                                     conv_carry)
    xc_all = jax.nn.silu(xc_all.astype(jnp.float32)).astype(x.dtype)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    A = -jnp.exp(p["A_log"])  # (H,)

    def parts(xc_k, dt_k):
        xh = xc_k[..., :d_in].reshape(xc_k.shape[0], -1, H, head_dim)
        Bm = xc_k[..., d_in:d_in + d_state].astype(jnp.float32)
        Cm = xc_k[..., d_in + d_state:].astype(jnp.float32)
        a = jnp.exp(dt_k * A)[..., None, None]  # (B,s,H,1,1)
        bx = (dt_k[..., None] * xh.astype(jnp.float32))[..., None] \
            * Bm[..., None, None, :]  # (B,s,H,P,N)
        return xh, a, bx, Cm

    h0 = (jnp.zeros((B, H, head_dim, d_state), jnp.float32) if state is None
          else state[1])
    if S == 1:
        xh1, a, bx, Cm = parts(xc_all, dt)
        h_last = a[:, 0] * h0 + bx[:, 0]
        y = jnp.einsum("bhpn,bn->bhp", h_last, Cm[:, 0])[:, None]
        xh = xh1
    else:
        xh = xc_all[..., :d_in].reshape(B, S, H, head_dim)
        y, h_last = _ssd_scan(xh, xc_all[..., d_in:d_in + d_state],
                              xc_all[..., d_in + d_state:], dt, A, h0, chunk)
    y = y + p["D"][:, None] * xh.astype(jnp.float32)
    y = y.reshape(B, S, d_in).astype(x.dtype)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype))
    out = y @ p["out_proj"]
    if return_state:
        return out, (new_conv, h_last)
    return out


def mamba_state_shapes(cfg, batch: int):
    """ShapeDtypeStructs of the per-layer decode state."""
    d_in = cfg.ssm_expand * cfg.d_model
    conv_c = d_in if cfg.ssm_version == 1 else d_in + 2 * cfg.ssm_state
    conv = jax.ShapeDtypeStruct((batch, cfg.ssm_conv - 1, conv_c),
                                jnp.dtype(cfg.dtype))
    if cfg.ssm_version == 1:
        h = jax.ShapeDtypeStruct((batch, d_in, cfg.ssm_state), jnp.float32)
    else:
        H = d_in // cfg.ssm_head_dim
        h = jax.ShapeDtypeStruct((batch, H, cfg.ssm_head_dim, cfg.ssm_state),
                                 jnp.float32)
    return conv, h
