"""Scheduler: 95th percentile of the wait from a request's scheduled
arrival to its seating in a slot (the Tracer's SEAT event)."""
from harness.context import percentile


def read(ctx):
    return percentile(ctx.queue_waits(), 95)
