"""Prefill chunks, per token: device seconds of the in-window
``_chunk_jit`` runs (trace, ``XLA Modules``, runs wholly inside
``bench.window``) over the live prompt tokens of the ``engine.chunk``
spans wholly inside the window (the sum of their lanes' chunk lengths),
in us.  A chunk span ends after its program's host sync, so a span inside
the window holds a device run inside it.  None without chunk spans."""
from harness.spans import step_spans


def chunk_tokens(ctx):
    """Live prompt tokens of the ``engine.chunk`` spans that start and end
    inside the window; None where there is none."""
    w = ctx.window
    if not w.window_s:
        return None
    lo, hi = w.t0, w.t0 + w.window_s
    spans = [s for s in step_spans(ctx) if s.name == "engine.chunk"
             and s.end_s is not None and lo <= s.start_s and s.end_s <= hi]
    if not spans:
        return None
    return sum(c for s in spans for _, c in s.attrs["lanes"])


def read(ctx):
    secs = ctx.program_seconds("_chunk_jit")
    tokens = chunk_tokens(ctx)
    if not secs or not tokens:
        return None
    return sum(secs) / tokens * 1e6
