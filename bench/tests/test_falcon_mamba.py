"""falcon-mamba-7b at a small size on the CPU: the float32 reference
(``reference/mamba1.py``) against the program's ``LM`` and against the
served path, the Δ/B/C norms that tell Falcon-Mamba from plain Mamba-1,
the counts (``counts/mamba1.py``), the live prompt tokens that
``prefill_us_per_token.lat`` reads off the ``engine.chunk`` spans' lanes,
and a whole run of the cell."""
import copy
import dataclasses
import json
import os

import numpy as np
import pytest

from conftest import BENCH
from test_program_spans import Clock, _read

TINY_FALCON = dict(family="ssm", n_layers=2, d_model=64, n_heads=0,
                   n_kv_heads=0, d_ff=0, vocab_size=256, ssm_version=1,
                   ssm_state=8, ssm_expand=2, ssm_conv=4, ssm_scan_chunk=16,
                   rope_style="none",
                   dtype="bfloat16", kv_cache_dtype="")


def falcon_spec(limit=0.006):
    """The cell at CPU size: its own files, small sizes and a light load.
    At this size the program's widest gap read 0-0.0032 (six seeds) and
    the fp8 control's 0.034-0.097 (five): the limit lies between."""
    import run
    spec = copy.deepcopy(run.load_spec("falcon-mamba-longprompt"))
    cfg = spec["config"]
    cfg["model"] = dict(TINY_FALCON)
    cfg["engine"].update(slots=4, max_len=288, prefill_chunk=64,
                         dispatch_tokens=4)
    cfg["check"].update(logit_gap_limit=limit, min_tokens=120,
                        max_requests=16, block=8)
    mix = spec["mix"]
    mix.update(drain_s=60, rate_rps=4.0)
    mix["prompt"] = {"dist": "lognormal", "median": 96, "sigma": 0.6,
                     "min": 32, "max": 256, "round_up": 16}
    mix["output"] = {"dist": "uniform", "min": 8, "max": 24}
    return spec


def _model(dtype="float32"):
    from repro.configs.base import get_config
    from repro.models import LM
    sizes = dict(TINY_FALCON, dtype=dtype)
    return LM(dataclasses.replace(get_config("falcon-mamba-7b"), **sizes)), \
        sizes


def _params(model, seed):
    import jax

    from harness.weights import make_params
    layout = jax.eval_shape(model.init, jax.random.key(0))
    return make_params(layout, seed, jax.devices("cpu")[0])


def _lm_vs_reference(model, sizes, params, length):
    import jax

    from harness.check import reference
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, sizes["vocab_size"], (2, length)).astype(
        np.int32)
    want = np.asarray(jax.jit(model.apply)(params, tokens)[0])
    pos = np.tile(np.arange(length, dtype=np.int32), (2, 1))
    got = np.asarray(reference("mamba1").logits_at(params, sizes, tokens,
                                                   pos))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("length", [77, 130])
def test_mamba1_reference_matches_lm_in_float32(length):
    """Both in float32, the same equations: they differ only in the order
    of sums (an associative scan against a token loop), 1e-6 relative."""
    model, sizes = _model()
    err = _lm_vs_reference(model, sizes, _params(model, 3), length)
    assert err < 1e-4, err


def test_program_without_the_norms_is_not_falcon_mamba(monkeypatch):
    """The Δ/B/C norms decide that agreement: with them taken out of the
    reference, on the same weights, the program is off by far more than
    the float32 agreement (1e-4), so a program without them fails the
    test above."""
    import functools

    import jax

    from harness.check import reference
    model, sizes = _model()
    params = _params(model, 3)
    assert _lm_vs_reference(model, sizes, params, 77) < 1e-4
    ref = reference("mamba1")
    monkeypatch.setattr(ref, "unit_rms", lambda t: t)
    # a new function, so jit traces it afresh, with the norms out
    layer = functools.partial(ref._mamba1.__wrapped__)
    monkeypatch.setattr(ref, "_mamba1", jax.jit(
        layer, static_argnames=("d_state", "quant")))
    assert _lm_vs_reference(model, sizes, params, 77) > 1e-2


def test_served_path_matches_reference_logit_by_logit():
    """Through ``BatchedServer`` (chunked prefill over several chunks,
    then the fused decode through the h/conv slot state) in float32:
    every served token is the reference's argmax at its position, and
    from the state a finished lane holds, the next step's logits equal
    the reference's full forward pass at that position, every one of V.
    Tolerances: float32 on both sides, sums in another order, 1e-4."""
    import jax
    import jax.numpy as jnp

    from harness.check import reference
    from repro.models.model import DecodeCache
    from repro.serve.engine import BatchedServer, Request

    model, sizes = _model()
    params = _params(model, 5)
    srv = BatchedServer(model, params, slots=4, max_len=256,
                        prefill_chunk=32, dispatch_tokens=4)
    rng = np.random.default_rng(1)
    reqs = [Request(uid=i, prompt=rng.integers(0, 256, n).astype(np.int32),
                    max_new_tokens=m)
            for i, (n, m) in enumerate([(77, 9), (130, 6), (33, 12)])]
    for r in reqs:
        srv.submit(r)
    slot_of = {}
    while not srv.idle():
        srv.step(4)
        for s, r in enumerate(srv._active):
            if r is not None:
                slot_of[r.uid] = s
    ref = reference("mamba1")
    for r in reqs:
        assert len(r.output) == r.max_new_tokens
        seq = np.concatenate([r.prompt, np.asarray(r.output, np.int32)])
        n = len(r.prompt)
        pos = np.arange(n - 1, len(seq), dtype=np.int32)[None]
        want = ref.logits_at(params, sizes, seq[None], pos)[0]
        gaps = want[:-1].max(-1) - want[np.arange(len(r.output)),
                                        r.output][:len(r.output)]
        assert gaps.max() <= 1e-4 * np.abs(want).max(), gaps
        s = slot_of[r.uid]
        lane = DecodeCache({k: v[:, s:s + 1] for k, v in
                            srv.cache.data.items()}, jnp.int32(len(seq) - 1))
        got, _ = model.decode_step(params, lane,
                                   jnp.asarray([[r.output[-1]]], jnp.int32))
        got = np.asarray(got)[0, 0, :sizes["vocab_size"]]
        err = np.linalg.norm(got - want[-1]) / np.linalg.norm(want[-1])
        assert err < 1e-4, err


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_param_bytes_match_the_program_layout():
    import jax

    import run
    from counts import mamba1 as c
    cfg = _config("falcon-mamba-7b")
    layout = jax.eval_shape(run.model_for(cfg).init, jax.random.key(0))
    want = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(layout))
    assert c.param_bytes(cfg["model"]) == want == 3907395584


def test_mamba1_counts_by_hand():
    """d 64, E 128, N 8, R 4, K 4, 2 layers, V 256."""
    from counts import mamba1 as c
    s = TINY_FALCON
    W = 2 * (2 * 64 * 128 + 128 * (4 + 16) + 4 * 128 + 128 * 64)
    head = 256 * 64
    scan = 7 * 2 * 128 * 8
    state = 2 * 2 * (128 * 8 * 4 + 3 * 128 * 2)
    pb = (2 * ((2 * 64 * 128 + 4 * 128 + 128 + 128 * 20 + 4 * 128
                + 128 * 64 + 64) * 2 + (128 + 128 * 8 + 128) * 4)
          + (256 * 64 + 64) * 2)
    assert c.param_bytes(s) == pb
    # one chunk program: lanes of 40 and 16 tokens at any offsets
    f, b = c.chunk_work(s, [(0, 40), (64, 16)])
    assert f == 2 * W * 56 + 2 * 2 * head + scan * 56
    assert b == pb + 2 * state + 56 * 64 * 2 + 2 * 256 * 4
    # a 3-step dispatch, one lane for 3 steps and one for 1
    f, b = c.decode_work(s, [(99, 3), (10, 1)])
    tok = 2 * (W + head) + scan
    assert f == 4 * tok
    assert b == 3 * pb + 4 * (state + 64 * 2 + 256 * 4)
    assert c.chunk_work(s, []) == (0, 0)


def _ctx(tracer, trace, t0=100.0, window_s=10.0):
    from harness.context import Context
    from harness.serve import Window
    window = Window([], window_s, 0, True, 0, t0)
    return Context(sizes={}, counts=None, peaks=None, chips=1, window=window,
                   seconds=window_s, trace=trace, calls=[], tracer=tracer,
                   setup_s=0.0, drain_s=0.0)


def test_prefill_us_per_token_reads_spans_and_trace():
    """Device seconds of the in-window ``_chunk_jit`` runs over the live
    prompt tokens (the lanes' chunk lengths) of the chunk spans that lie
    wholly inside the window; None without chunk spans or trace."""
    from repro.telemetry import Tracer
    tr, clock = Tracer(), Clock()
    # (start, end, lanes): before the open, two inside, one open at the
    # close (its tokens would be counted without its device run), after
    for start, end, lanes in ((99.0, 99.2, [[0, 999]]),
                              (101.0, 101.2, [[0, 256]]),
                              (103.0, 103.2, [[256, 192], [0, 256]]),
                              (109.9, 110.3, [[0, 999]]),
                              (110.5, 110.7, [[0, 999]])):
        clock.t = start
        with tr.span("engine.step", clock):
            with tr.span("engine.chunk", clock, lanes=lanes):
                clock.t = end
    trace = {"modules": {"_chunk_jit": [(1, 0.25, 0), (2, 0.5, 0)],
                         "_dispatch_jit": [(3, 9.0, 0)]}}
    got = _read("prefill_us_per_token.lat", _ctx(tr, trace))
    assert got == pytest.approx(0.75 / (256 + 448) * 1e6)
    bare = Tracer()                        # no chunk span in the window
    clock.t = 101.0
    with bare.span("engine.step", clock):
        clock.t = 101.2
    assert _read("prefill_us_per_token.lat", _ctx(bare, trace)) is None
    assert _read("prefill_us_per_token.lat", _ctx(None, trace)) is None
    assert _read("prefill_us_per_token.lat", _ctx(tr, None)) is None


def test_chunk_tokens_equal_the_call_logs(cpu_devices):
    """Driven through the harness with ``CallLog`` on, the live prompt
    tokens that ``prefill_us_per_token.lat`` reads off the chunk spans are
    the chunk lengths ``CallLog`` rebuilds, and every prompt token of the
    run."""
    import jax

    import run
    from harness import serve, traffic
    from harness.weights import make_params
    from repro.core.energy_model import calibrate
    from repro.telemetry import Tracer

    spec = falcon_spec()
    config, mix = spec["config"], spec["mix"]
    model = run.model_for(config)
    layout = jax.eval_shape(model.init, jax.random.key(0))
    params = make_params(layout, 13, cpu_devices[0])
    tracer = Tracer()
    stack = serve.build(model, params, config["engine"], cpu_devices[:1],
                        calibrate(), tracer=tracer)
    offers = traffic.offers(mix, seconds=2.0, seed=13,
                            vocab=config["model"]["vocab_size"])
    calls = serve.CallLog(stack.servers)
    calls.on = True
    try:
        window = serve.drive(stack, offers, seconds=2.0,
                             drain_s=mix["drain_s"])
    finally:
        calls.close()
    assert window.drained
    chunks = [c for c in calls.calls if c["kind"] == "chunk"]
    assert len(chunks) > 1
    want = sum(n for c in chunks for _, n in c["lanes"])
    assert want == sum(len(r.req.prompt) for r in window.served)
    # one second of chunk device time over a window that holds every span
    trace = {"modules": {"_chunk_jit": [(0, 1.0, 0)]}}
    got = _read("prefill_us_per_token.lat",
                _ctx(tracer, trace, t0=0.0, window_s=1e9))
    assert got == pytest.approx(1e6 / want)


@pytest.mark.parametrize("seed", [2 ** 31 + 11, 7])
def test_falcon_run_is_correct_on_cpu(seed, cpu_devices, capsys):
    import run
    out = run.run(falcon_spec(), seed=seed, seconds=2.0, trace=False,
                  devices=cpu_devices, control=True)
    assert out["correct"], out
    assert out["failed"] == 0
    assert "compiles inside the window 0" in capsys.readouterr().out
    assert {"ttft_p95_s", "tpot_p95_ms", "setup_s"} <= set(out["metrics"])
    program = out["checks"]["logit_gap_max"]["value"]
    low = out["control"]["checks"]["logit_gap_max"]
    assert not out["control"]["correct"], low
    assert low["value"] > low["limit"] > program
