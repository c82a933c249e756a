"""Profiler trace -> device busy and idle time, per-program device time,
and the breakdown.

``read_xplane`` turns the ``.xplane.pb`` the JAX profiler wrote into a plain
event table; ``reduce`` works on that table alone, so tests can check it on
a recorded one.

Table: ``{"devices": {id: {"modules": [[name, start_ns, dur_ns], ...],
"ops": [...]}}, "host": [[name, start_ns, dur_ns], ...]}``.  Device
planes are ``/device:TPU:<id>``; their ``XLA Modules`` line holds one event
per program run (named after the jitted function, e.g.
``jit__dispatch_jit(…)``) and their ``XLA Ops`` line one per operation.
Host events are the benchmark's own ``bench.*`` annotations.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def read_xplane(logdir: str) -> dict:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices: Dict[int, dict] = {}
    host: List[list] = []
    planes = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        lines = []
        for line in plane.lines:
            lines.append(line.name)
            if m:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key is None:
                    continue
                dev = devices.setdefault(int(m.group(1)),
                                         {"modules": [], "ops": []})
                dev[key].extend([e.name, e.start_ns, e.duration_ns]
                                for e in line.events)
            elif plane.name.startswith("/host:"):
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith("bench."))
        planes.append((plane.name, lines))
    return {"devices": devices, "host": host, "planes": planes}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(ev, lo, hi):
    a, b = ev[1], ev[1] + ev[2]
    return max(a, lo), min(b, hi)


def reduce(table: dict, devices: List[int], *, top: int = 10) -> Optional[dict]:
    """Busy and idle time over the ``bench.window`` span on each device in
    ``devices``, each program's device times, the operations that took
    most time and the longest idle gaps, named by the host span that
    overlaps each most.  None where the table holds no window or no device
    event."""
    win = [e for e in table["host"] if e[0] == "bench.window"]
    if not win:
        return None
    lo, hi = win[0][1], win[0][1] + win[0][2]
    window_s = (hi - lo) * 1e-9
    busy, modules, op_time = {}, {}, {}
    gaps = []
    host = [e for e in table["host"] if e[0] != "bench.window"]
    for d in devices:
        dev = table["devices"].get(d) or table["devices"].get(str(d))
        if not dev:
            continue
        evs = dev["ops"] or dev["modules"]
        iv = [_clip(e, lo, hi) for e in evs]
        iv = _union([(a, b) for a, b in iv if b > a])
        busy[d] = sum(b - a for a, b in iv) * 1e-9
        for e in dev["modules"]:
            if lo <= e[1] and e[1] + e[2] <= hi:
                modules.setdefault(program_name(e[0]), []).append(
                    (e[1], e[2] * 1e-9, d))
        runs = sorted(dev["modules"], key=lambda e: e[1])
        starts = [e[1] for e in runs]
        for e in dev["ops"]:
            a, b = _clip(e, lo, hi)
            if b > a:
                i = bisect.bisect_right(starts, e[1]) - 1
                name = op_name(e[0], e[1], runs[i] if i >= 0 else None)
                op_time[name] = op_time.get(name, 0.0) + (b - a) * 1e-9
        if d == devices[0]:
            edges = [lo] + [x for ab in iv for x in ab] + [hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps.append((_name_gap(host, a, b), (b - a) * 1e-9))
    if not busy:
        return None
    for v in modules.values():
        v.sort()
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])
    return dict(window_s=window_s,
                busy_s=sum(busy.values()) / len(busy),
                busy_by_device=busy,
                modules=modules,
                device_ops=[[n, s] for n, s in ops[:top]],
                idle_gaps=[[n, s] for n, s in gaps[:top]])


def program_name(event_name: str) -> str:
    """``jit__dispatch_jit(123)`` -> ``_dispatch_jit``."""
    name = event_name.split("(")[0]
    return name[len("jit_"):] if name.startswith("jit_") else name


def op_name(event_name: str, start: int, run) -> str:
    """An operation's name in the breakdown: its program (the enclosing
    ``XLA Modules`` event) and the HLO instruction's name, without the
    instruction's text (``%while.12 = (...) while(...)`` -> ``%while.12``):
    ``_dispatch_jit:%while.12``."""
    op = event_name.split(" = ")[0]
    if run is None or not run[1] <= start < run[1] + run[2]:
        return op
    return f"{program_name(run[0])}:{op}"


def _name_gap(host, a, b) -> str:
    best, name = 0, "host:outside bench spans"
    for e in host:
        ov = min(b, e[1] + e[2]) - max(a, e[1])
        if ov > best:
            best, name = ov, "host:" + e[0]
    return name
