"""The one traffic generator: a mix file's parameters -> requests.

A mix (bench/traffic/<mix>.json) gives the loop, the rates and bursts and
the length distributions.  Its ``layout_seed`` fixes the work: the arrival
times and each arrival's prompt and output lengths.  The run's ``--seed``
draws only the token ids, so every seed offers the same schedule of work:
the window's tail is the tail of one fixed schedule, not of whichever
lengths a seed happens to put into a burst.

Arrivals over the run's window come from a 2-state Markov-modulated
Poisson process (a background rate, multiplied inside bursts with
exponential holding times), drawn by Lewis-Shedler thinning; the arithmetic
is that of ``repro.cluster.loadgen.generate`` without its diurnal envelope.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Offer:
    at_s: float          # scheduled arrival, seconds after the window opens
    prompt: np.ndarray   # (S,) int32
    max_new_tokens: int


def _rng(seed: int):
    return np.random.default_rng(seed % 2 ** 64)


def draw_lengths(spec: dict, rng, n: int) -> np.ndarray:
    """``n`` lengths from a distribution spec: lognormal (median, sigma) or
    uniform (min, max, inclusive), clipped to [min, max], then rounded up to
    a multiple of ``round_up`` (1 when absent)."""
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        x = rng.integers(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = np.clip(np.ceil(x), spec["min"], spec["max"]).astype(np.int64)
    r = int(spec.get("round_up", 1))
    return np.minimum(-(-x // r) * r, spec["max"] // r * r)


def possible_lengths(spec: dict) -> np.ndarray:
    """Every length ``draw_lengths`` can return for this spec."""
    r = int(spec.get("round_up", 1))
    lo = -(-int(spec["min"]) // r) * r
    return np.arange(lo, spec["max"] // r * r + 1, r)


def mmpp_arrivals(rate: float, burst: dict, horizon_s: float, rng
                  ) -> np.ndarray:
    """Arrival times in [0, horizon_s): rate ``rate`` outside bursts and
    ``rate * multiplier`` inside them."""
    on = []
    t, is_on = 0.0, False
    while t < horizon_s:
        dur = rng.exponential(burst["mean_on_s"] if is_on
                              else burst["mean_off_s"])
        if is_on:
            on.append((t, min(t + dur, horizon_s)))
        t += dur
        is_on = not is_on
    peak = rate * burst["multiplier"]
    out, t = [], 0.0
    while True:
        t += rng.exponential(1.0 / peak)
        if t >= horizon_s:
            break
        r = peak if any(a <= t < b for a, b in on) else rate
        if rng.random() * peak <= r:
            out.append(t)
    return np.asarray(out)


def offers(mix: dict, *, seconds: float, seed: int, vocab: int
           ) -> List[Offer]:
    """The run's requests, ordered by scheduled arrival."""
    layout = _rng(mix["layout_seed"])
    if mix["loop"] != "open":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    times = mmpp_arrivals(mix["rate_rps"], mix["burst"], seconds, layout)
    n = len(times)
    prompts = draw_lengths(mix["prompt"], layout, n)
    outputs = draw_lengths(mix["output"], layout, n)
    rng = _rng(seed)
    return [Offer(float(times[i]),
                  rng.integers(0, vocab, int(prompts[i])).astype(np.int32),
                  int(outputs[i]))
            for i in range(n)]
