"""The comparison that decides ``correct``.

After the window, a sample of the finished requests (drawn from the run's
seed, always holding the longest) is run through the configuration's plain
float32 reference, once over each prompt followed by its served tokens.  At
each served position the reference's best logit minus its logit for the
served token is the gap; the widest gap is the number compared with the
configuration's limit.  Greedy decoding serves the argmax, so a correct
bf16 program reads gaps near 0: only where two logits nearly tie can bf16
rounding pick the reference's second best.

The control puts the reference, computed in float8_e4m3 products
(``quant="fp8"``), in the program's place: at each position of the same
sequences its argmax is read against the float32 reference the same way.
"""
from __future__ import annotations

import importlib
from typing import List, Sequence

import numpy as np


PAD = 256


def reference(name: str):
    return importlib.import_module(f"reference.{name}")


def pick(finished: Sequence, seed: int, *, min_tokens: int,
         max_requests: int) -> List:
    """The sample: the longest finished request (prompt + output), then
    others in an order drawn from ``seed``, until ``min_tokens`` served
    tokens or ``max_requests`` requests."""
    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i].prompt) + len(finished[i].output))
    rng = np.random.default_rng((seed + 0x5EED) % 2 ** 64)
    order = [longest] + [int(i) for i in rng.permutation(len(finished))
                         if i != longest]
    out, tokens = [], 0
    for i in order:
        if tokens >= min_tokens or len(out) >= max_requests:
            break
        out.append(finished[i])
        tokens += len(finished[i].output)
    return out


def _rows(block):
    """Right-padded token rows (prompt + output[:-1]) and the positions
    whose logits predicted each served token."""
    # rows padded to a multiple of PAD: the reference's layer programs
    # come in few shapes, each compiled once into the persistent cache
    S = -(-max(len(r.prompt) + len(r.output) - 1 for r in block) // PAD) * PAD
    K = max(len(r.output) for r in block)
    tokens = np.zeros((len(block), S), np.int32)
    pos = np.zeros((len(block), K), np.int32)
    served = np.zeros((len(block), K), np.int32)
    valid = np.zeros((len(block), K), bool)
    for b, r in enumerate(block):
        seq = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(r.output[:-1], np.int32)])
        tokens[b, :len(seq)] = seq
        n = len(r.output)
        pos[b, :n] = len(r.prompt) - 1 + np.arange(n)
        pos[b, n:] = pos[b, n - 1]
        served[b, :n] = r.output
        valid[b, :n] = True
    return tokens, pos, served, valid


def _gaps(ref_logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """Reference best minus the reference's logit of ``chosen``."""
    got = np.take_along_axis(ref_logits, chosen[..., None], axis=-1)[..., 0]
    return ref_logits.max(axis=-1) - got


def gaps(ref_name: str, params, sizes: dict, sample, *, block: int,
         control: bool = False) -> np.ndarray:
    """Gap of every served token of ``sample`` (or, with ``control``, of
    the token the fp8 reference puts first at the same positions), flat."""
    ref = reference(ref_name)
    out = []
    for i in range(0, len(sample), block):
        tokens, pos, served, valid = _rows(sample[i:i + block])
        full = ref.logits_at(params, sizes, tokens, pos)
        if control:
            low = ref.logits_at(params, sizes, tokens, pos, quant="fp8")
            chosen = low.argmax(axis=-1)
            del low
        else:
            chosen = served
        g = _gaps(full, chosen)
        del full
        out.append(g[valid])
    return np.concatenate(out) if out else np.zeros(0)
