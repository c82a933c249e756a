"""Scheduler step: 95th percentile of the duration of the ``engine.step``
spans that start inside the window: what an arrival waits behind before
the harness can submit it."""
from harness.context import percentile
from harness.spans import steps_in_window


def read(ctx):
    return percentile([s.duration_s for s in steps_in_window(ctx)], 95)
