"""What a per-layer metric's reader reads (bench/metrics/<metric>.py).

A reader is ``read(ctx) -> float | None``: None where the run holds nothing
for it to read, and the harness then leaves the metric out.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


def percentile(values, q: float) -> Optional[float]:
    """``np.percentile`` (linear), the arithmetic of
    ``repro.cluster.loadgen.latency_stats``; None for no values."""
    v = np.asarray(list(values), float)
    return float(np.percentile(v, q)) if v.size else None


@dataclasses.dataclass
class Context:
    sizes: dict            # the configuration file's model sizes
    counts: object         # bench/counts/<family>.py
    peaks: dict            # bench/peaks.py entry of this device kind
    chips: int
    window: object         # harness.serve.Window
    seconds: float         # the run's --seconds
    trace: Optional[dict]  # harness.trace.reduce of the traced window
    calls: List[dict]      # harness.serve.CallLog calls in the window
    tracer: object         # repro.telemetry Tracer of the traced run
    setup_s: float         # process start to the window's opening
    drain_s: float         # how long the run follows requests after it

    # -- device time per program -------------------------------------
    def program_seconds(self, program: str) -> List[float]:
        """Device seconds of each run of ``program`` in the traced window
        (``_dispatch_jit``, ``_chunk_jit``)."""
        if not self.trace:
            return []
        return [d for _, d, _ in self.trace["modules"].get(program, [])]

    def roofline(self, kind: str, program: str) -> Optional[float]:
        """Least time of the ``kind`` calls' work at the chip's peaks, over
        their device time, in %.  The k-th call the host made on a device
        is the k-th run of the program on that device; where the counts
        differ nothing is read."""
        if not self.trace or not self.peaks:
            return None
        runs = self.trace["modules"].get(program, [])
        calls = [c for c in self.calls if c["kind"] == kind]
        if not runs or not calls:
            return None
        least = spent = 0.0
        for dev in {c["device"] for c in calls} | {r[2] for r in runs}:
            dc = [c for c in calls if c["device"] == dev]
            dr = [r for r in runs if r[2] == dev]
            if len(dc) != len(dr):
                return None
            for c, (_, secs, _) in zip(dc, dr):
                least += self.least_seconds(c)
                spent += secs
        return 100.0 * least / spent if spent else None

    def work(self, call: dict):
        fn = (self.counts.decode_work if call["kind"] == "dispatch"
              else self.counts.chunk_work)
        return fn(self.sizes, call["lanes"])

    def least_seconds(self, call: dict) -> float:
        flops, byts = self.work(call)
        return max(flops / self.peaks["flops_bf16"],
                   byts / self.peaks["hbm_bytes_per_s"])

    def mfu(self) -> Optional[float]:
        """Model FLOPs of every prompt and output token the window's
        programs processed, over window x chips x peak, in %."""
        if not self.calls or not self.window.window_s or not self.peaks:
            return None
        flops = sum(self.work(c)[0] for c in self.calls)
        return 100.0 * flops / (self.window.window_s * self.chips
                                * self.peaks["flops_bf16"])

    def idle_frac(self) -> Optional[float]:
        if not self.trace or not self.trace["window_s"]:
            return None
        return 1.0 - self.trace["busy_s"] / self.trace["window_s"]

    def queue_waits(self) -> List[float]:
        """Seconds from each in-window request's scheduled arrival to its
        first SEAT event (the repro Tracer, on the same wall clock)."""
        if self.tracer is None:
            return []
        seat = {}
        for span in self.tracer.spans:
            for ev in span.events:
                if ev[0] == "seat" and span.uid not in seat:
                    seat[span.uid] = ev[1]
        t0 = self.window.t0
        return [seat[r.uid] - t0 - r.arrival_s for r in self.window.served
                if r.arrival_s < self.seconds and r.uid in seat]
