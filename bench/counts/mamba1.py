"""Counts for Falcon-Mamba (see reference/mamba1.py): Mamba-1 layers,
weights bf16 except A_log, dt_bias and D (float32); h state float32, conv
state bf16; no KV cache.

With d = d_model, E = expand·d, N = ssm_state, R = d // 16 (the Δ rank),
K = ssm_conv, L = n_layers and V the padded vocabulary:

* matmul weights a token: W = L·(2·d·E + E·(R + 2N) + R·E + E·d)
  (in_proj, x_proj, dt_proj, out_proj), the head V·d;
* selective scan, 7·E·N FLOPs a token a layer: Δ·A, its exp, (Δ·x)·B,
  a·h, + b, h·C and the sum over N.  This is the recurrence; the program's
  chunk body expands and associative-scans it, which is not counted;
* state, one lane: L·(E·N·4 + (K - 1)·E·2) bytes, read once and written
  once per program;
* a decode step, per live lane: 2·(W + V·d) + 7·L·E·N FLOPs; the weights
  once a step, the state, the token's embedding row (d·2 B) and its
  logits (V·4 B);
* a prefill chunk, per lane of c tokens: 2·W·c + 2·V·d (the head at the
  chunk's last position) + 7·L·E·N·c FLOPs; the weights once a program,
  the state, c embedding rows and one row of logits.
"""
from __future__ import annotations

from counts import vocab_padded

BF16, F32 = 2, 4


def _dims(s):
    d = s["d_model"]
    E = s["ssm_expand"] * d
    return d, E, s["ssm_state"], max(d // 16, 1), s["ssm_conv"]


def _matmul_params(s):
    """(per-token matmul weights of the stack, unembed weights)."""
    d, E, N, R, K = _dims(s)
    layer = 2 * d * E + E * (R + 2 * N) + R * E + E * d
    return s["n_layers"] * layer, vocab_padded(s["vocab_size"]) * d


def param_bytes(s) -> int:
    d, E, N, R, K = _dims(s)
    layer = (2 * d * E + K * E + E + E * (R + 2 * N) + R * E + E * d
             + d) * BF16 + (E + E * N + E) * F32
    return (s["n_layers"] * layer
            + (vocab_padded(s["vocab_size"]) * d + d) * BF16)


def _state_bytes(s) -> int:
    """One lane's h and conv state, read once and written once."""
    d, E, N, R, K = _dims(s)
    return 2 * s["n_layers"] * (E * N * F32 + (K - 1) * E * BF16)


def _scan_flops(s) -> int:
    d, E, N, R, K = _dims(s)
    return 7 * s["n_layers"] * E * N


def _io_bytes(s, tokens: int) -> int:
    """``tokens`` embedding rows read and one row of logits written."""
    return tokens * s["d_model"] * BF16 + vocab_padded(s["vocab_size"]) * F32


def decode_work(s, lanes):
    stack, unembed = _matmul_params(s)
    pb = param_bytes(s)
    flops = byts = 0
    steps = max((n for _, n in lanes), default=0)
    for t in range(steps):
        live = sum(1 for _, n in lanes if n > t)
        byts += pb + live * (_state_bytes(s) + _io_bytes(s, 1))
        flops += live * (2 * (stack + unembed) + _scan_flops(s))
    return flops, byts


def chunk_work(s, lanes):
    stack, unembed = _matmul_params(s)
    flops, byts = 0, param_bytes(s) if lanes else 0
    for _, c in lanes:
        flops += 2 * stack * c + 2 * unembed + _scan_flops(s) * c
        byts += _state_bytes(s) + _io_bytes(s, c)
    return flops, byts
