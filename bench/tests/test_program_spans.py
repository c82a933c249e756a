"""The per-layer metrics that read the program's step spans
(``prefill_p95_s``, ``step_p95_s``, ``host_step_ms``), on a hand-built
tracer and window, and the spans' live work against ``CallLog``'s."""
import types

import pytest

from conftest import tiny_spec


class Clock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def _ctx(tracer, served=(), t0=100.0, window_s=10.0, seconds=10.0):
    from harness.context import Context
    from harness.serve import Window
    window = Window(list(served), window_s, 0, True, 0, t0)
    return Context(sizes={}, counts=None, peaks=None, chips=1, window=window,
                   seconds=seconds, trace=None, calls=[], tracer=tracer,
                   setup_s=0.0, drain_s=0.0)


def _step(tr, clock, start, dur, syncs=()):
    """One ``engine.step`` from ``start`` lasting ``dur``; each sync is
    ``(parent_name, start, end)``, inside a phase span of that name."""
    clock.t = start
    with tr.span("engine.step", clock):
        for parent, a, b in syncs:
            clock.t = a
            with tr.span(parent, clock):
                with tr.span("engine.sync", clock):
                    clock.t = b
        clock.t = start + dur


def _read(name, ctx):
    import run
    return run.reader(name)(ctx)


def test_steps_outside_the_window_are_cut():
    from repro.telemetry import Tracer
    tr, clock = Tracer(), Clock()
    _step(tr, clock, 99.0, 5.0)             # starts before the window
    for i in range(10):
        _step(tr, clock, 100.0 + i * 0.5, 0.1 * (i + 1))
    _step(tr, clock, 110.0, 7.0)            # starts as the window closes
    ctx = _ctx(tr)
    durs = [0.1 * (i + 1) for i in range(10)]
    import numpy as np
    assert _read("step_p95_s", ctx) == pytest.approx(
        float(np.percentile(durs, 95)))
    assert _read("host_step_ms", ctx) == pytest.approx(
        float(np.mean(durs)) * 1e3)


def test_host_step_subtracts_nested_syncs_once():
    from repro.telemetry import Tracer
    tr, clock = Tracer(), Clock()
    # a 1 s step: a chunk's sync 0.1-0.3, the dispatch's 0.5-0.9
    _step(tr, clock, 100.0, 1.0, [("engine.chunk", 100.1, 100.3),
                                  ("engine.dispatch", 100.5, 100.9)])
    # a sync inside a sync still counts its instants once: 0.2-0.6
    clock.t = 102.0
    with tr.span("engine.step", clock):
        with tr.span("engine.dispatch", clock):
            clock.t = 102.2
            with tr.span("engine.sync", clock):
                with tr.span("engine.sync", clock):
                    clock.t = 102.6
        clock.t = 103.0
    assert _read("host_step_ms", _ctx(tr)) == pytest.approx(
        (0.4 + 0.6) / 2 * 1e3)


def _served(uid, arrival_s, first_token_s):
    from harness.serve import Served
    req = types.SimpleNamespace(first_token_s=first_token_s)
    return Served(uid, arrival_s, arrival_s, 8, req)


def test_prefill_reads_first_token_less_first_seat():
    from repro.telemetry import Event, Tracer
    tr, clock = Tracer(), Clock()
    _step(tr, clock, 100.0, 1.0)
    seats = {0: 101.0, 1: 102.0, 2: 103.0, 3: 104.0}
    for uid, t in seats.items():
        tr.request_begin(uid, t - 0.5)
        tr.begin_attempt(uid, t)
        tr.event(uid, Event.SEAT, t)
    tr.begin_attempt(0, 106.0)               # re-seated: the first counts
    tr.event(0, Event.SEAT, 106.0)
    served = [_served(0, 0.5, 104.0),        # 3 s after its first seat
              _served(1, 1.5, 103.0),        # 1 s
              _served(2, 2.5, None),         # no first token: left out
              _served(3, 12.0, 110.0)]       # arrives after the window
    import numpy as np
    assert _read("prefill_p95_s", _ctx(tr, served)) == pytest.approx(
        float(np.percentile([3.0, 1.0], 95)))


def test_a_program_without_step_spans_reads_nothing():
    """The parent's tracer has request spans and first-token stamps but no
    step spans: every reader gives None, and nothing raises."""
    from repro.telemetry import Event

    class OldTracer:                         # what a program without
        def __init__(self):                  # step spans records
            self.spans = []

    tr = OldTracer()
    tr.spans.append(types.SimpleNamespace(
        uid=0, events=[(Event.SEAT, 101.0, {})]))
    ctx = _ctx(tr, [_served(0, 0.5, 104.0)])
    for name in ("prefill_p95_s", "step_p95_s", "host_step_ms"):
        assert _read(name, ctx) is None
        assert _read(name, _ctx(None)) is None


def test_spans_give_the_call_logs_lanes(cpu_devices):
    """Driven through the harness with ``CallLog`` on, the
    ``engine.chunk``/``engine.dispatch`` spans give, call by call, the
    lanes that ``CallLog`` rebuilds from the server's state."""
    import jax

    import run
    from harness import serve, traffic
    from harness.weights import make_params
    from repro.core.energy_model import calibrate
    from repro.telemetry import Tracer

    spec = tiny_spec()
    config, mix = spec["config"], spec["mix"]
    model = run.model_for(config)
    layout = jax.eval_shape(model.init, jax.random.key(0))
    params = make_params(layout, 11, cpu_devices[0])
    tracer = Tracer()
    stack = serve.build(model, params, config["engine"], cpu_devices[:1],
                        calibrate(), tracer=tracer)
    offers = traffic.offers(mix, seconds=2.0, seed=11,
                            vocab=config["model"]["vocab_size"])
    calls = serve.CallLog(stack.servers)
    calls.on = True
    try:
        window = serve.drive(stack, offers, seconds=2.0,
                             drain_s=mix["drain_s"])
    finally:
        calls.close()
    assert window.drained
    spans = [s for s in tracer.step_spans
             if s.name in ("engine.chunk", "engine.dispatch")]
    kinds = {"engine.chunk": "chunk", "engine.dispatch": "dispatch"}
    assert {c["kind"] for c in calls.calls} == {"chunk", "dispatch"}
    assert len(spans) == len(calls.calls)
    for s, c in zip(spans, calls.calls):
        assert kinds[s.name] == c["kind"]
        assert [tuple(lane) for lane in s.attrs["lanes"]] == c["lanes"]
        if c["kind"] == "chunk":
            assert tuple(s.attrs["shape"]) == c["shape"]
        else:
            assert s.attrs["n"] == c["n"]
