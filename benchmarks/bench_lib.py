"""Shared benchmark utilities: timing and CSV emission."""
import time


def timed(fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, (time.perf_counter() - t0) * 1e6


def emit(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}")
