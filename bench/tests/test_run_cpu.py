"""The whole run, on the CPU at a small size: it serves, stays correct, and
compiles nothing inside its window."""
import pytest

from conftest import tiny_spec


@pytest.mark.parametrize("seed", [2 ** 31 + 7, 5])
def test_run_is_correct_on_cpu(seed, cpu_devices, capsys):
    import run
    out = run.run(tiny_spec(), seed=seed, seconds=2.0, trace=False,
                  devices=cpu_devices)
    assert out["correct"], out
    assert out["failed"] == 0
    assert out["checks"]["logit_gap_max"]["value"] < 0.05
    assert "compiles inside the window 0" in capsys.readouterr().out
    assert {"ttft_p95_s", "tpot_p95_ms", "setup_s"} <= set(out["metrics"])
    assert list(out)[-1] == "checks"
