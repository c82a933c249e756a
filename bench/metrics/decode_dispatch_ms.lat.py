"""Step programs: mean device time of one fused decode dispatch
(``jit__dispatch_jit`` in the profiler trace), in ms."""
import numpy as np


def read(ctx):
    t = ctx.program_seconds("_dispatch_jit")
    return float(np.mean(t)) * 1e3 if t else None
