"""The comparison that decides ``correct`` fails what it must.

At a small size on the CPU: the fp8 control, judged by the run's own
comparison, comes out not correct where the program is correct, and a whole
run with the timed path broken underneath (a
token altered where it is produced, a decode step that returns its state
unchanged, half of a prefill batch left out) comes out not correct.  The
harness's look for a chip is skipped: these call ``run.run`` directly.
"""
import jax
import jax.numpy as jnp
import pytest

from conftest import tiny_spec

SEED = 2 ** 31 + 3


def test_control_is_not_correct(cpu_devices):
    import run
    out = run.run(tiny_spec(), seed=SEED, seconds=2.0, trace=False,
                  devices=cpu_devices, control=True)
    assert out["correct"], out["checks"]
    program = out["checks"]["logit_gap_max"]["value"]
    low = out["control"]["checks"]["logit_gap_max"]
    assert not out["control"]["correct"], low
    assert low["value"] > low["limit"] > program
    assert low["value"] > 3 * max(program, 1e-3)


def _token_altered(orig):
    def f(*a):
        cache, tok, active, budget, toks, emitted = orig(*a)
        return cache, tok, active, budget, (toks + 1) % 256, emitted
    return "_dispatch_jit", f


def _state_unchanged(orig):
    def f(model, pad_id, n, stops, params, cache, *rest):
        kept = jax.tree.map(jnp.copy, cache)
        out = orig(model, pad_id, n, stops, params, cache, *rest)
        return (kept,) + tuple(out[1:])
    return "_dispatch_jit", f


def _half_batch_out(orig):
    def f(model, params, cache, next_tok, active, budget, tokens, offsets,
          chunk_lens, slot_ids, final_ids, budgets):
        n_slots = active.shape[0]
        valid = slot_ids < n_slots
        # the first half of the lanes, rounded down: a lone lane goes
        keep = jnp.cumsum(valid) <= valid.sum() // 2
        slot_ids = jnp.where(keep, slot_ids, n_slots)
        return orig(model, params, cache, next_tok, active, budget, tokens,
                    offsets, chunk_lens, slot_ids, final_ids, budgets)
    return "_chunk_jit", f


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch_out])
def test_broken_timed_path_is_not_correct(fault, cpu_devices, monkeypatch):
    import run
    from repro.serve import engine
    name, broken = fault(getattr(engine, name_of(fault)))
    monkeypatch.setattr(engine, name, broken)
    out = run.run(tiny_spec(), seed=SEED, seconds=2.0, trace=False,
                  devices=cpu_devices)
    assert not out["correct"], out["checks"]


def name_of(fault):
    return fault(None)[0]
