"""The reduction from a profiler trace's event table to busy and idle
time, per-program device time and the breakdown."""
import pytest

from harness import trace

MS = 1_000_000  # ns


def _table():
    # a 100 ms window; device 0 busy 10-30 ms (decode) and 50-60 ms (chunk)
    return {
        "host": [["bench.window", 0, 100 * MS],
                 ["bench.step", 5 * MS, 30 * MS],
                 ["bench.commit", 35 * MS, 10 * MS],
                 ["bench.wait", 62 * MS, 38 * MS]],
        "devices": {0: {
            "modules": [["jit__dispatch_jit(7)", 10 * MS, 20 * MS],
                        ["jit__chunk_jit(9)", 50 * MS, 10 * MS]],
            "ops": [["fusion.1", 10 * MS, 15 * MS],
                    ["fusion.2", 25 * MS, 5 * MS],
                    ["fusion.1", 50 * MS, 10 * MS]]}},
    }


def test_busy_idle_and_programs():
    r = trace.reduce(_table(), [0])
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.03)
    assert [d for _, d, _ in r["modules"]["_dispatch_jit"]] \
        == pytest.approx([0.02])
    assert [d for _, d, _ in r["modules"]["_chunk_jit"]] \
        == pytest.approx([0.01])
    assert r["device_ops"] == [["_dispatch_jit:fusion.1", pytest.approx(0.015)],
                               ["_chunk_jit:fusion.1", pytest.approx(0.01)],
                               ["_dispatch_jit:fusion.2", pytest.approx(0.005)]]
    # gaps: 0-10 (step), 30-50 (commit overlaps 35-45), 60-100 (wait)
    assert r["idle_gaps"][0] == ["host:bench.wait", pytest.approx(0.04)]
    assert r["idle_gaps"][1] == ["host:bench.commit", pytest.approx(0.02)]
    assert r["idle_gaps"][2] == ["host:bench.step", pytest.approx(0.01)]


def test_events_outside_the_window_are_cut():
    t = _table()
    t["devices"][0]["ops"].append(["fusion.3", 95 * MS, 20 * MS])
    r = trace.reduce(t, [0])
    assert r["busy_s"] == pytest.approx(0.035)


def test_nothing_to_read():
    assert trace.reduce({"host": [], "devices": {}}, [0]) is None
    t = _table()
    assert trace.reduce(t, [1]) is None


def test_op_name():
    run = ["jit__chunk_jit(9)", 50 * MS, 10 * MS]
    hlo = "%while.12 = (s32[], f32[4,64]) while((s32[], f32[4,64]) %t), body=%b"
    assert trace.op_name(hlo, 55 * MS, run) == "_chunk_jit:%while.12"
    assert trace.op_name(hlo, 65 * MS, run) == "%while.12"
    assert trace.op_name("fusion.3", 0, None) == "fusion.3"


def test_program_name():
    assert trace.program_name("jit__dispatch_jit(12)") == "_dispatch_jit"
    assert trace.program_name("jit_foo") == "foo"
