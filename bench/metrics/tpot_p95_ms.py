"""95th percentile, over the window's requests with two or more output
tokens, of (last token's commit time - first token's) / (tokens - 1)."""
from harness.context import percentile


def read(ctx):
    return percentile([(r.last_s - r.first_s) / (r.n - 1) * 1e3
                       for r in ctx.window.served
                       if r.arrival_s < ctx.seconds and r.n >= 2], 95)
