"""95th percentile, over every request scheduled to arrive in the window,
of its first token's commit time minus its scheduled arrival.  A request
that was rejected or had no first token when the run stopped following it
counts as the whole time it was followed (window + drain): a lower bound of
its wait, and the run is then not correct."""
from harness.context import percentile


def read(ctx):
    cap = ctx.seconds + ctx.drain_s
    return percentile([cap if r.first_s is None else r.first_s - r.arrival_s
                       for r in ctx.window.served
                       if r.arrival_s < ctx.seconds], 95)
