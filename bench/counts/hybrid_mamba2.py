"""Counts for the Zamba2-style hybrid (see reference/hybrid_mamba2.py):
Mamba-2 layers and one shared attention + MLP block applied every
``shared_attn_every`` layers, weights bf16 except A_log, dt_bias and D
(float32); KV cache bf16; SSM state float32."""
from __future__ import annotations

from counts import vocab_padded

BF16, F32 = 2, 4


def _dims(s):
    d = s["d_model"]
    d_in = s["ssm_expand"] * d
    N, P = s["ssm_state"], s["ssm_head_dim"]
    H = d_in // P
    return d, d_in, N, P, H


def n_apps(s) -> int:
    return s["n_layers"] // s["shared_attn_every"]


def _matmul_params(s):
    """(per-token matmul weights of the stack, unembed weights)."""
    d, d_in, N, P, H = _dims(s)
    hd, Hq, Hkv = s["head_dim"], s["n_heads"], s["n_kv_heads"]
    mamba = d * (2 * d_in + 2 * N + H) + d_in * d
    attn = d * hd * (Hq + 2 * Hkv) + Hq * hd * d + 2 * d * s["d_ff"]
    return (s["n_layers"] * mamba + n_apps(s) * attn,
            vocab_padded(s["vocab_size"]) * d)


def param_bytes(s) -> int:
    d, d_in, N, P, H = _dims(s)
    hd, Hq, Hkv = s["head_dim"], s["n_heads"], s["n_kv_heads"]
    C = d_in + 2 * N
    mamba = (d * (2 * d_in + 2 * N + H) + s["ssm_conv"] * C + C + d_in
             + d_in * d + d) * BF16 + 3 * H * F32
    shared = (d * hd * (Hq + 2 * Hkv) + Hq * hd * d + 2 * d * s["d_ff"]
              + 2 * d) * BF16
    return (s["n_layers"] * mamba + shared
            + (vocab_padded(s["vocab_size"]) * d + d) * BF16)


def _state_bytes(s) -> int:
    """One lane's SSM and conv state, read once and written once."""
    d, d_in, N, P, H = _dims(s)
    return 2 * s["n_layers"] * (H * P * N * F32
                                + (s["ssm_conv"] - 1) * (d_in + 2 * N) * BF16)


def _kv_token_bytes(s) -> int:
    return n_apps(s) * 2 * s["n_kv_heads"] * s["head_dim"] * BF16


def _scan_flops(s) -> int:
    d, d_in, N, P, H = _dims(s)
    return 5 * s["n_layers"] * H * P * N


def _attn_flops(s, ctx: int) -> int:
    """One query against ``ctx`` positions, over every application."""
    return 4 * n_apps(s) * ctx * s["n_heads"] * s["head_dim"]


def decode_work(s, lanes):
    stack, unembed = _matmul_params(s)
    pb = param_bytes(s)
    flops = byts = 0
    steps = max((n for _, n in lanes), default=0)
    for t in range(steps):
        live = [(c, n) for c, n in lanes if n > t]
        byts += pb
        for c, _ in live:
            ctx = c + t + 1
            flops += 2 * (stack + unembed) + _scan_flops(s) \
                + _attn_flops(s, ctx)
            byts += _state_bytes(s) + _kv_token_bytes(s) * (ctx - 1) \
                + _kv_token_bytes(s)
    return flops, byts


def chunk_work(s, lanes):
    stack, unembed = _matmul_params(s)
    flops, byts = 0, param_bytes(s) if lanes else 0
    for o, c in lanes:
        flops += 2 * stack * c + 2 * unembed + _scan_flops(s) * c \
            + 4 * n_apps(s) * s["n_heads"] * s["head_dim"] \
            * (c * o + c * (c + 1) // 2)
        byts += _state_bytes(s) + _kv_token_bytes(s) * (o + c)
    return flops, byts
