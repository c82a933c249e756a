"""Pallas-native fused transprecision kernels (quantize -> compute -> dequant).

`repro.numerics` emulation historically ran as composed XLA ops: quantize the
operands to the generated FPU format, run the contraction, round the result —
with every low-precision intermediate materialized to HBM.  That trades
emulation fidelity against serving speed.  The kernels here close that gap:
each one keeps the whole transprecision schedule inside a single
``pallas_call`` — operands are rounded to the target format in VMEM (the
operand registers of the FPMax unit), the contraction runs on the MXU, and
the dequantized/rounded result is the only tensor that touches HBM.

Three kernels, one (format, accumulation-style, scaling) vocabulary:

  * ``fused_qmm``       — quantize+matmul+dequant with the accumulation style
                          from ``numerics.accum_style_for`` ('fused' /
                          'cascade' / 'cascade_fwd', the FMA/CMA k-block
                          mapping of kernels/fma_emu.py), batched in one
                          ``pallas_call`` (no vmap of per-slice calls), with
                          optional per-tile power-of-two scaling so fp8
                          operands use their full dynamic range;
  * ``fused_flash_attention`` — blockwise flash attention with per-block
                          quantization of q/k/v (and the probability operand)
                          and per-block dequant of each partial dot, the
                          fp8/bf16 variant of ``models/flash_vjp``'s schedule;
  * ``ssm_scan_quantized`` — the selective-scan kernel with operands rounded
                          to the format on VMEM entry (the state stays in the
                          wide f32 accumulator, as in the hardware unit).

Scaling is power-of-two only (``_pow2_scale``): the scale is built from
exponent bits, so scaling/descaling is *exact* — quantization error comes
only from mantissa rounding, and a scaled kernel agrees with the unscaled
one everywhere the unscaled dynamic range suffices.

Every kernel has a bitwise reference twin (``*_ref``) that replays the exact
tile schedule in pure jnp; tests/test_fused_kernels.py asserts interpret-mode
equality for every registry format (the f32 quantizer hosts everything up to
fp32; fp64 is the softfloat/dp path).  Consumers reach these through
``repro.numerics.emulate`` (``emulated_matmul(impl='fused')``,
``emulated_flash_attention``, ``emulated_ssm_scan``) — never directly.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import FloatFormat, _unbiased_exp_f32, quantize

STYLES = ("fused", "cascade", "cascade_fwd")
NEG_INF = -1.0e30


# ---------------------------------------------------------------------------
# Exact power-of-two block scaling
# ---------------------------------------------------------------------------
def _pow2_scale(x: jax.Array, fmt: FloatFormat):
    """(scale, inv_scale) moving ``x``'s max magnitude into the format's
    normal range when (and only when) it falls outside it.

    The target binade is ``clip(e, emin, emax - 1)``: blocks already in
    range get scale 1 (mantissa rounding is scale-invariant, so rescaling
    in-range data buys nothing and scaling near the top would overflow the
    f32 partial dot for wide-exponent formats); too-large blocks scale down
    to binade ``emax - 1`` (one binade of headroom — the scaled maximum
    stays < 2**emax <= max_finite and can never round to inf); too-small
    blocks scale up out of the subnormal flush zone.

    Both factors are exact powers of two built from exponent bits, so
    ``x * inv`` and ``part * scale`` are exact f32 operations: per-tile
    dequant adds no rounding of its own.  They are (1, 1) arrays, not
    scalars: the TPU compiler bitcasts vectors only.
    """
    amax = jnp.max(jnp.max(jnp.abs(x), axis=1, keepdims=True), axis=0,
                   keepdims=True)
    e = _unbiased_exp_f32(amax)
    scale_exp = jnp.clip(e - jnp.clip(e, fmt.emin, fmt.emax - 1), -126, 126)
    scale = lax.bitcast_convert_type(
        ((scale_exp + 127).astype(jnp.uint32) << jnp.uint32(23)), jnp.float32)
    inv = lax.bitcast_convert_type(
        ((127 - scale_exp).astype(jnp.uint32) << jnp.uint32(23)), jnp.float32)
    return scale, inv


def _quantize_block(x: jax.Array, fmt: FloatFormat, scaled: bool):
    """Round a VMEM tile to ``fmt``; returns (q, dequant_scale)."""
    if not scaled:
        return quantize(x, fmt), None
    scale, inv = _pow2_scale(x, fmt)
    return quantize(x * inv, fmt), scale


# ---------------------------------------------------------------------------
# fused_qmm: quantize + matmul + dequant, one pallas_call, batched
# ---------------------------------------------------------------------------
def _qmm_block_update(acc, a_t, b_t, *, fmt: FloatFormat, style: str,
                      scaled: bool):
    """One k-block step shared bitwise by the kernel and its ref twin."""
    qa, sa = _quantize_block(a_t, fmt, scaled)
    qb, sb = _quantize_block(b_t, fmt, scaled)
    part = jnp.dot(qa, qb, preferred_element_type=jnp.float32)
    if scaled:
        part = part * (sa * sb)
    if style == "fused":
        return acc + part
    if style == "cascade_fwd":
        return acc + quantize(part, fmt)
    if style == "cascade":
        return quantize(acc + quantize(part, fmt), fmt)
    raise ValueError(f"style must be one of {STYLES}, got {style!r}")


def _fused_qmm_kernel(a_ref, b_ref, o_ref, acc_ref, *, fmt: FloatFormat,
                      style: str, nk: int, out_fmt: FloatFormat | None,
                      scaled: bool):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] = _qmm_block_update(acc_ref[...], a_ref[0], b_ref[...],
                                     fmt=fmt, style=style, scaled=scaled)

    @pl.when(k == nk - 1)
    def _flush():
        acc = acc_ref[...]
        if out_fmt is not None:
            acc = quantize(acc, out_fmt)
        o_ref[0] = acc


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "style", "out_fmt", "scaled", "bm", "bn", "bk",
                     "interpret"),
)
def fused_qmm(
    a: jax.Array,
    b: jax.Array,
    *,
    fmt: FloatFormat,
    style: str = "fused",
    out_fmt: FloatFormat | None = None,
    scaled: bool = False,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """(B?, M, K) @ (K, N) fully fused: quantize -> MXU dot -> dequant.

    Unlike ``fma_emu_matmul`` this accepts a leading batch dim directly (one
    ``pallas_call``, grid over batch — no per-slice vmap), and ``scaled=True``
    applies exact per-tile power-of-two scaling with the dequant fused into
    the accumulation (the fp8 dynamic-range mode).  ``scaled=False`` is
    bitwise-identical to the kernels/ref.py k-block schedule.
    """
    if style not in STYLES:
        raise ValueError(f"style must be one of {STYLES}, got {style!r}")
    batched = a.ndim == 3
    a3 = a if batched else a[None]
    if a3.ndim != 3 or b.ndim != 2 or a3.shape[2] != b.shape[0]:
        raise ValueError(f"bad qmm shapes {a.shape} @ {b.shape}")
    nb, m, kdim = a3.shape
    _, n = b.shape

    pm, pn, pk = (-m) % bm, (-n) % bn, (-kdim) % bk
    a_p = jnp.pad(a3.astype(jnp.float32), ((0, 0), (0, pm), (0, pk)))
    b_p = jnp.pad(b.astype(jnp.float32), ((0, pk), (0, pn)))
    gm, gn, gk = (m + pm) // bm, (n + pn) // bn, (kdim + pk) // bk

    kernel = functools.partial(_fused_qmm_kernel, fmt=fmt, style=style,
                               nk=gk, out_fmt=out_fmt, scaled=scaled)
    out = pl.pallas_call(
        kernel,
        grid=(nb, gm, gn, gk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda bb, i, j, k: (bb, i, k)),
            pl.BlockSpec((bk, bn), lambda bb, i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda bb, i, j, k: (bb, i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, m + pm, n + pn), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
    )(a_p, b_p)
    out = out[:, :m, :n]
    return out if batched else out[0]


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "style", "out_fmt", "scaled", "bm", "bn", "bk"),
)
def fused_qmm_ref(
    a: jax.Array,
    b: jax.Array,
    *,
    fmt: FloatFormat,
    style: str = "fused",
    out_fmt: FloatFormat | None = None,
    scaled: bool = False,
    bm: int | None = None,
    bn: int | None = None,
    bk: int = 128,
) -> jax.Array:
    """Bitwise ref twin of ``fused_qmm``: same tiles, same op order, pure jnp.

    ``bm``/``bn`` default to the full output (one tile), matching the
    bitwise-contract shapes of tests; pass the kernel's tiling to replay any
    grid exactly.  With ``scaled=False`` and a single (bm, bn) tile this is
    expression-identical to ``ref.fma_emu_matmul_ref``.  Jitted: the bitwise
    contract is between two *compiled* programs (XLA:CPU fuses eager
    elementwise chains differently, which can drift the last ulp).
    """
    batched = a.ndim == 3
    a3 = a if batched else a[None]
    nb, m, kdim = a3.shape
    _, n = b.shape
    bm = m if bm is None else bm
    bn = n if bn is None else bn
    pm, pn, pk = (-m) % bm, (-n) % bn, (-kdim) % bk
    a_p = jnp.pad(a3.astype(jnp.float32), ((0, 0), (0, pm), (0, pk)))
    b_p = jnp.pad(b.astype(jnp.float32), ((0, pk), (0, pn)))
    gm, gn, gk = (m + pm) // bm, (n + pn) // bn, (kdim + pk) // bk

    rows = []
    for bb in range(nb):
        row_tiles = []
        for i in range(gm):
            col_tiles = []
            for j in range(gn):
                acc = jnp.zeros((bm, bn), jnp.float32)
                for k in range(gk):
                    a_t = a_p[bb, i * bm:(i + 1) * bm, k * bk:(k + 1) * bk]
                    b_t = b_p[k * bk:(k + 1) * bk, j * bn:(j + 1) * bn]
                    acc = _qmm_block_update(acc, a_t, b_t, fmt=fmt,
                                            style=style, scaled=scaled)
                if out_fmt is not None:
                    acc = quantize(acc, out_fmt)
                col_tiles.append(acc)
            row_tiles.append(jnp.concatenate(col_tiles, axis=1))
        rows.append(jnp.concatenate(row_tiles, axis=0))
    out = jnp.stack(rows)[:, :m, :n]
    return out if batched else out[0]


# ---------------------------------------------------------------------------
# fused_flash_attention: blockwise attention with per-block dequant
# ---------------------------------------------------------------------------
def _flash_block_update(carry, q_blk, k_blk, v_blk, mask, *, scale: float,
                        fmt: FloatFormat | None, scaled: bool):
    """One (q-block, kv-block) online-softmax update, shared bitwise by the
    kernel and its ref twin.

    q/k/v blocks are (bq|bk, D) f32 for one (batch, head); ``mask`` is
    (bq, bk); the running max ``m`` and denominator ``l`` are (bq, 1)
    columns, which keep every value 2-D for the TPU's vector layouts.  With
    ``fmt`` set, q/k/v are rounded to the format per block (with optional
    exact pow2 scaling) and each partial dot is dequantized
    before it enters the f32 online-softmax state — the low-precision tensors
    never leave the block.
    """
    m, l, acc = carry
    if fmt is not None:
        qq, sq = _quantize_block(q_blk, fmt, scaled)
        qk, sk = _quantize_block(k_blk, fmt, scaled)
        qv, sv = _quantize_block(v_blk, fmt, scaled)
    else:
        qq, qk, qv = q_blk, k_blk, v_blk
        sq = sk = sv = None
    s = lax.dot_general(qq, qk, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    if sq is not None:
        s = s * (sq * sk)
    s = s * scale
    s_m = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s_m, axis=-1, keepdims=True))
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe) * mask
    corr = jnp.exp(jnp.minimum(m - m_safe, 0.0)) * (m > NEG_INF / 2)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    if fmt is not None:
        # the probability operand register: p is in [0, 1], no scale needed
        p = quantize(p, fmt)
    pv = jnp.dot(p, qv, preferred_element_type=jnp.float32)
    if sv is not None:
        pv = pv * sv
    acc_new = acc * corr + pv
    return m_new, l_new, acc_new


def _flash_mask(q_pos, k_pos, *, causal: bool, window: int, kv_len: int):
    """(bq, bk) mask from a (bq, 1) column of query and a (1, bk) row of key
    positions."""
    m = k_pos < kv_len
    if causal:
        m = m & (k_pos <= q_pos)
    if window:
        m = m & (k_pos > q_pos - window)
    return m


def _fused_flash_kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
                        fmt, scaled, scale, causal, window, kv_len,
                        q_offset, bq, bk, nk, out_fmt):
    qi, kj = pl.program_id(2), pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q_pos = q_offset + qi * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    k_pos = kj * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    mask = _flash_mask(q_pos, k_pos, causal=causal, window=window,
                       kv_len=kv_len)
    carry = (m_s[:, :1], l_s[:, :1], acc_s[...])
    m_new, l_new, acc_new = _flash_block_update(
        carry, q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], mask,
        scale=scale, fmt=fmt, scaled=scaled)
    m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
    l_s[...] = jnp.broadcast_to(l_new, l_s.shape)
    acc_s[...] = acc_new

    @pl.when(kj == nk - 1)
    def _flush():
        out = acc_s[...] / jnp.maximum(l_s[:, :1], 1e-30)
        if out_fmt is not None:
            out = quantize(out, out_fmt)
        o_ref[0, 0] = out


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "scaled", "causal", "window", "kv_len",
                     "q_offset", "out_fmt", "block_q", "block_k",
                     "interpret"),
)
def fused_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    fmt: FloatFormat | None,
    scaled: bool = True,
    causal: bool = True,
    window: int = 0,
    kv_len: int | None = None,
    q_offset: int = 0,
    out_fmt: FloatFormat | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Blockwise flash attention with per-block quantize/dequant, one kernel.

    q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D) -> (B, Sq, Hq, D), the
    ``models/flash_vjp`` forward schedule with the transprecision operand
    path fused in: every q/k/v block is rounded to ``fmt`` in VMEM (exact
    pow2 scaling when ``scaled``) and each partial dot dequantized into the
    f32 online-softmax state.  GQA is handled in the BlockSpec index map
    (kv head = q head // G) — no KV repetition is materialized.
    ``fmt=None`` runs the same schedule without rounding (the native path).
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    kv_len = Sk if kv_len is None else kv_len
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    pq, pk = (-Sq) % bq, (-Sk) % bk
    # head-major layout so a (1, 1, bq|bk, D) block is one head's tile
    qh = jnp.pad(q.astype(jnp.float32),
                 ((0, 0), (0, pq), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    kh = jnp.pad(k.astype(jnp.float32),
                 ((0, 0), (0, pk), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    vh = jnp.pad(v.astype(jnp.float32),
                 ((0, 0), (0, pk), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    nq, nk = (Sq + pq) // bq, (Sk + pk) // bk

    kernel = functools.partial(
        _fused_flash_kernel, fmt=fmt, scaled=scaled, scale=scale,
        causal=causal, window=window, kv_len=kv_len, q_offset=q_offset,
        bq=bq, bk=bk, nk=nk, out_fmt=out_fmt)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq + pq, D), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),  # running max (lane-bcast)
            pltpu.VMEM((bq, 128), jnp.float32),  # running denom
            pltpu.VMEM((bq, D), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
    )(qh, kh, vh)
    return out.transpose(0, 2, 1, 3)[:, :Sq].astype(q.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "scaled", "causal", "window", "kv_len",
                     "q_offset", "out_fmt", "block_q", "block_k"),
)
def fused_flash_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    fmt: FloatFormat | None,
    scaled: bool = True,
    causal: bool = True,
    window: int = 0,
    kv_len: int | None = None,
    q_offset: int = 0,
    out_fmt: FloatFormat | None = None,
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    """Bitwise ref twin: replays the kernel's per-(batch, head) block
    schedule with python loops (test-scale shapes only).  Jitted — see
    ``fused_qmm_ref`` on why the bitwise contract needs compiled-vs-compiled."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    kv_len = Sk if kv_len is None else kv_len
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    pq, pk = (-Sq) % bq, (-Sk) % bk
    qp = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = (Sq + pq) // bq, (Sk + pk) // bk

    heads = []
    for h in range(Hq):
        hk = h // G
        q_rows = []
        for qi in range(nq):
            q_pos = q_offset + qi * bq + jnp.arange(bq)[:, None]
            m = jnp.full((B, bq, 1), NEG_INF, jnp.float32)
            l = jnp.zeros((B, bq, 1), jnp.float32)
            acc = jnp.zeros((B, bq, D), jnp.float32)
            for kj in range(nk):
                k_pos = kj * bk + jnp.arange(bk)[None, :]
                mask = _flash_mask(q_pos, k_pos, causal=causal,
                                   window=window, kv_len=kv_len)
                for bb in range(B):
                    mb, lb, ab = _flash_block_update(
                        (m[bb], l[bb], acc[bb]),
                        qp[bb, qi * bq:(qi + 1) * bq, h],
                        kp[bb, kj * bk:(kj + 1) * bk, hk],
                        vp[bb, kj * bk:(kj + 1) * bk, hk],
                        mask, scale=scale, fmt=fmt, scaled=scaled)
                    m = m.at[bb].set(mb)
                    l = l.at[bb].set(lb)
                    acc = acc.at[bb].set(ab)
            out = acc / jnp.maximum(l, 1e-30)
            if out_fmt is not None:
                out = quantize(out, out_fmt)
            q_rows.append(out)
        heads.append(jnp.concatenate(q_rows, axis=1))
    out = jnp.stack(heads, axis=2)[:, :Sq]  # (B, Sq, Hq, D)
    return out.astype(q.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "scaled", "causal", "window", "kv_len",
                     "q_offset", "out_fmt", "block_q", "block_k"),
)
def fused_flash_scan(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    fmt: FloatFormat | None,
    scaled: bool = True,
    causal: bool = True,
    window: int = 0,
    kv_len: int | None = None,
    q_offset: int = 0,
    out_fmt: FloatFormat | None = None,
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    """Fast jnp twin (lax.scan over blocks, vmapped over batch x head): the
    CPU serving path and the benchgen measurement target.  Same block
    schedule and per-block math as the kernel; batched dots may reassociate,
    so agreement is to f32 tolerance rather than bitwise."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    kv_len_ = Sk if kv_len is None else kv_len
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    pq, pk = (-Sq) % bq, (-Sk) % bk
    qp = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = (Sq + pq) // bq, (Sk + pk) // bk
    # (B*Hq, nq, bq, D) / kv repeated to q heads (CPU path: the repeat is
    # cheap relative to the contraction; the Pallas kernel avoids it)
    qf = qp.transpose(0, 2, 1, 3).reshape(B * Hq, nq, bq, D)
    kf = jnp.repeat(kp.transpose(0, 2, 1, 3), G, axis=1
                    ).reshape(B * Hq, nk, bk, D)
    vf = jnp.repeat(vp.transpose(0, 2, 1, 3), G, axis=1
                    ).reshape(B * Hq, nk, bk, D)

    def one_head(qh, kh, vh):
        def q_step(_, qi_blk):
            qi, q_blk = qi_blk
            q_pos = q_offset + qi * bq + jnp.arange(bq)[:, None]

            def kv_step(carry, kj_blk):
                kj, k_blk, v_blk = kj_blk
                k_pos = kj * bk + jnp.arange(bk)[None, :]
                mask = _flash_mask(q_pos, k_pos, causal=causal,
                                   window=window, kv_len=kv_len_)
                return _flash_block_update(carry, q_blk, k_blk, v_blk, mask,
                                           scale=scale, fmt=fmt,
                                           scaled=scaled), None

            init = (jnp.full((bq, 1), NEG_INF, jnp.float32),
                    jnp.zeros((bq, 1), jnp.float32),
                    jnp.zeros((bq, D), jnp.float32))
            (m, l, acc), _ = lax.scan(kv_step, init,
                                      (jnp.arange(nk), kh, vh))
            out = acc / jnp.maximum(l, 1e-30)
            if out_fmt is not None:
                out = quantize(out, out_fmt)
            return None, out

        _, outs = lax.scan(q_step, None, (jnp.arange(nq), qh))
        return outs  # (nq, bq, D)

    outs = jax.vmap(one_head)(qf, kf, vf)
    out = outs.reshape(B, Hq, (Sq + pq), D).transpose(0, 2, 1, 3)[:, :Sq]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# ssm_scan_quantized: the selective scan with format-rounded operands
# ---------------------------------------------------------------------------
def _ssm_scan_quant_kernel(a_ref, b_ref, c_ref, y_ref, h_ref, hstate, *,
                           fmt: FloatFormat | None,
                           out_fmt: FloatFormat | None,
                           nchunks: int, chunk: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        hstate[...] = jnp.zeros_like(hstate)

    def step(i, h):
        a_i, b_i, c_i = a_ref[0, i], b_ref[0, i], c_ref[0, i]
        if fmt is not None:
            a_i = quantize(a_i, fmt)
            b_i = quantize(b_i, fmt)
            c_i = quantize(c_i, fmt)
        h = a_i * h + b_i
        y = jnp.sum(h * c_i[None, :], axis=-1)
        if out_fmt is not None:
            y = quantize(y, out_fmt)
        y_ref[0, i, :] = y
        return h

    hstate[...] = jax.lax.fori_loop(0, chunk, step, hstate[...])

    @pl.when(t == nchunks - 1)
    def _flush():
        h_ref[0] = hstate[...]


@functools.partial(jax.jit, static_argnames=("fmt", "out_fmt", "chunk", "bd",
                                             "interpret"))
def ssm_scan_quantized(a, b, c, *, fmt: FloatFormat | None,
                       out_fmt: FloatFormat | None = None, chunk: int = 16,
                       bd: int = 256, interpret: bool = False):
    """Quantized selective scan: operands rounded to ``fmt`` on VMEM entry.

    a, b: (B, S, D, N); c: (B, S, N) -> (y (B, S, D), h_last (B, D, N)).
    The recurrence state stays in the wide f32 accumulator (the hardware
    unit's extended accumulator); only the per-token operands a/b/c pass
    through the format's operand registers, and ``out_fmt`` optionally
    rounds the readout.  Rounding is elementwise, so — unlike the matmul
    kernels — the quantization is tiling-independent and the bitwise ref is
    ``ssm_scan_quantized_ref`` regardless of (chunk, bd).  The default
    tiles fit v5e's scoped VMEM as in ``kernels/ssm_scan.ssm_scan``.
    """
    B, S, D, N = a.shape
    bd = min(bd, D)
    if S % chunk or D % bd:
        raise ValueError(f"S={S} % chunk={chunk} or D={D} % bd={bd} != 0")
    nchunks = S // chunk
    kernel = functools.partial(_ssm_scan_quant_kernel, fmt=fmt,
                               out_fmt=out_fmt, nchunks=nchunks, chunk=chunk)
    y, h = pl.pallas_call(
        kernel,
        grid=(B, D // bd, nchunks),
        in_specs=[
            pl.BlockSpec((1, chunk, bd, N), lambda i, j, t: (i, t, j, 0)),
            pl.BlockSpec((1, chunk, bd, N), lambda i, j, t: (i, t, j, 0)),
            pl.BlockSpec((1, chunk, N), lambda i, j, t: (i, t, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, bd), lambda i, j, t: (i, t, j)),
            pl.BlockSpec((1, bd, N), lambda i, j, t: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, D), jnp.float32),
            jax.ShapeDtypeStruct((B, D, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a.astype(jnp.float32), b.astype(jnp.float32), c.astype(jnp.float32))
    return y, h


@functools.partial(jax.jit, static_argnames=("fmt", "out_fmt"))
def ssm_scan_quantized_ref(a, b, c, *, fmt: FloatFormat | None,
                           out_fmt: FloatFormat | None = None):
    """Bitwise ref twin: sequential recurrence with the same per-step ops
    (quantized operands, f32 state, mult+sum readout — no einsum, whose
    reduction order could differ from the kernel's)."""
    def step(h, inp):
        a_t, b_t, c_t = inp
        if fmt is not None:
            a_t = quantize(a_t, fmt)
            b_t = quantize(b_t, fmt)
            c_t = quantize(c_t, fmt)
        h = a_t * h + b_t
        y = jnp.sum(h * c_t[:, None, :], axis=-1)
        if out_fmt is not None:
            y = quantize(y, out_fmt)
        return h, y

    B, S, D, N = a.shape
    h0 = jnp.zeros((B, D, N), jnp.float32)
    h_last, ys = jax.lax.scan(
        step, h0,
        (a.astype(jnp.float32).swapaxes(0, 1),
         b.astype(jnp.float32).swapaxes(0, 1),
         c.astype(jnp.float32).swapaxes(0, 1)))
    return ys.swapaxes(0, 1), h_last
