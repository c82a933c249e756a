"""Engine host loop: mean, over the ``engine.step`` spans that start inside
the window, of the step's duration less the time its nested
``engine.sync`` spans (the host blocked on the device) cover, in ms."""
import numpy as np

from harness.spans import host_step_seconds


def read(ctx):
    t = host_step_seconds(ctx)
    return float(np.mean(t)) * 1e3 if t else None
