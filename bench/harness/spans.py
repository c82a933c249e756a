"""What the program's own step spans say (``repro.telemetry.Tracer``'s
``step_spans``, on the engine's clock: ``time.perf_counter``, the clock of
``Window.t0`` and of the harness's token stamps).

A tracer that records no step spans, as a program without them has none,
gives nothing to read: each function then returns an empty list.
"""
from __future__ import annotations

from typing import List


def step_spans(ctx) -> list:
    return list(getattr(ctx.tracer, "step_spans", None) or [])


def steps_in_window(ctx) -> list:
    """The closed ``engine.step`` spans that start inside the window."""
    w = ctx.window
    if not w.window_s:
        return []
    lo, hi = w.t0, w.t0 + w.window_s
    return [s for s in step_spans(ctx) if s.name == "engine.step"
            and s.end_s is not None and lo <= s.start_s < hi]


def _covered(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, float("-inf")
    for a, b in sorted(intervals):
        a = max(a, reach)
        if b > a:
            total += b - a
            reach = b
    return total


def host_step_seconds(ctx) -> List[float]:
    """Each in-window step's duration less the time its ``engine.sync``
    descendants cover (each instant once): the host's own work."""
    spans = step_spans(ctx)
    kids = {}
    for s in spans:
        kids.setdefault(s.parent_id, []).append(s)
    out = []
    for step in steps_in_window(ctx):
        syncs, todo = [], list(kids.get(step.span_id, []))
        while todo:
            s = todo.pop()
            if s.name == "engine.sync" and s.end_s is not None:
                syncs.append((max(s.start_s, step.start_s),
                              min(s.end_s, step.end_s)))
            todo.extend(kids.get(s.span_id, []))
        out.append(step.duration_s - _covered(syncs))
    return out


def prefill_seconds(ctx) -> List[float]:
    """For each request scheduled in the window that got a first token:
    its ``first_token_s`` minus its first SEAT event.  An engine that
    records no step spans stamps the first token at the start of the step
    whose chunk made it, a chunk early: nothing is read there."""
    if not step_spans(ctx):
        return []
    seat = {}
    for span in ctx.tracer.spans:
        for ev in span.events:
            if ev[0] == "seat":
                seat[span.uid] = min(ev[1], seat.get(span.uid, ev[1]))
    return [r.req.first_token_s - seat[r.uid] for r in ctx.window.served
            if r.arrival_s < ctx.seconds and r.uid in seat
            and r.req.first_token_s is not None]
