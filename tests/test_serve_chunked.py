"""Chunked prefill + continuous batching: bitwise parity and scheduler
semantics.

The core contract under test: splitting a prompt's prefill into chunks —
at the model layer (``LM.prefill_chunk``/``prefill_chunked``) and through
the serving scheduler (``BatchedServer(prefill_chunk=...)``) — produces
token streams bitwise-identical to the monolithic ``prefill`` /
``greedy_decode`` path, across attention, sliding-window (ring cache),
SSM, and hybrid families, for chunk sizes that don't divide the prompt,
prompts longer than the attention window, and first-token EOS.  On top of
that: TTFT stamps, token-weighted ``load_report``, ``latency_stats`` TTFT
percentiles, mid-prefill drain/continuation, and transparent
``ClusterRouter`` inheritance.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import LM
from repro.models.model import DecodeCache
from repro.serve.engine import BatchedServer, Request, greedy_decode

from helpers import FakeClock

MAX_LEN = 48


def _family(arch, **repl):
    cfg = get_config(arch).reduced()
    if repl:
        cfg = dataclasses.replace(cfg, **repl)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def dense():
    return _family("tinyllama-1.1b")


@pytest.fixture(scope="module")
def windowed():
    return _family("mixtral-8x7b")  # reduced window = 16, ring KV cache


@pytest.fixture(scope="module")
def ring_dense():
    return _family("tinyllama-1.1b", window=16)  # dense, ring KV cache


@pytest.fixture(scope="module")
def ssm():
    # tiny internal scan chunk so serving-size chunks hit real resume
    # boundaries at smoke scale
    return _family("falcon-mamba-7b", ssm_scan_chunk=4)


@pytest.fixture(scope="module")
def hybrid():
    return _family("zamba2-1.2b", ssm_scan_chunk=4)


def _toks(cfg, shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, shape), jnp.int32)


# ---------------------------------------------------------------------------
# Model-layer parity: prefill_chunked == prefill, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("chunk", [3, 4, 16])
def test_chunked_prefill_matches_monolithic_dense(dense, chunk):
    """Attention family: any chunk size (buckets pad exactly), including a
    chunk that doesn't divide the prompt and one prompt-sized chunk."""
    cfg, model, params = dense
    toks = _toks(cfg, (2, 10))
    last_m, cache_m = model.prefill(params, toks, max_len=MAX_LEN)
    last_c, cache_c = model.prefill_chunked(params, toks, chunk,
                                            max_len=MAX_LEN)
    assert jnp.array_equal(last_m, last_c)
    assert jnp.array_equal(cache_m.data["k"][:, :, :10],
                           cache_c.data["k"][:, :, :10])
    assert jnp.array_equal(cache_m.data["v"][:, :, :10],
                           cache_c.data["v"][:, :, :10])
    assert np.all(np.asarray(cache_c.length) == 10)


@pytest.mark.parametrize("chunk", [5, 8])
def test_chunked_prefill_matches_ring_window(windowed, chunk):
    """Sliding-window ring cache, prompt (24) > window (16): history read
    back across the ring seam, chunk writes ring-aligned, final cache
    identical to the monolithic roll."""
    cfg, model, params = windowed
    assert cfg.window and 24 > cfg.window
    toks = _toks(cfg, (2, 24), seed=1)
    last_m, cache_m = model.prefill(params, toks, max_len=40)
    last_c, cache_c = model.prefill_chunked(params, toks, chunk, max_len=40)
    assert jnp.array_equal(last_m, last_c)
    assert jnp.array_equal(cache_m.data["k"], cache_c.data["k"])
    assert jnp.array_equal(cache_m.data["v"], cache_c.data["v"])


@pytest.mark.parametrize("fixture,chunk", [("ssm", 4), ("ssm", 8),
                                           ("hybrid", 4), ("hybrid", 8)])
def test_chunked_prefill_matches_recurrent(fixture, chunk, request):
    """SSM / hybrid: chunk boundaries on ``ssm_scan_chunk`` multiples carry
    (conv, h) bitwise; final partial chunk of any length is exempt (11 and
    10 are not multiples of 4)."""
    cfg, model, params = request.getfixturevalue(fixture)
    S = 11 if fixture == "ssm" else 10
    toks = _toks(cfg, (2, S), seed=2)
    last_m, cache_m = model.prefill(params, toks, max_len=24)
    last_c, cache_c = model.prefill_chunked(params, toks, chunk, max_len=24)
    assert jnp.array_equal(last_m, last_c)
    assert jnp.array_equal(cache_m.data["conv"], cache_c.data["conv"])
    assert jnp.array_equal(cache_m.data["h"], cache_c.data["h"])
    if "k" in cache_m.data:  # hybrid shared-attention KV
        assert jnp.array_equal(cache_m.data["k"][:, :, :S],
                               cache_c.data["k"][:, :, :S])


def test_single_chunk_and_length_one_tail(dense):
    """Degenerate chunking: prompt shorter than the chunk (one chunk) and a
    final chunk of exactly one token (S % chunk == 1)."""
    cfg, model, params = dense
    for S, chunk in [(3, 16), (9, 4)]:
        toks = _toks(cfg, (1, S), seed=3)
        last_m, _ = model.prefill(params, toks, max_len=MAX_LEN)
        last_c, _ = model.prefill_chunked(params, toks, chunk,
                                          max_len=MAX_LEN)
        assert jnp.array_equal(last_m, last_c), (S, chunk)


# ---------------------------------------------------------------------------
# Engine: chunked continuous batching == greedy_decode, bit for bit
# ---------------------------------------------------------------------------
def _requests(cfg, plens, new_tokens=6, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, n)
                    .astype(np.int32),
                    max_new_tokens=new_tokens)
            for i, n in enumerate(plens)]


@pytest.mark.parametrize("fixture,plens,chunk", [
    ("dense", (7, 13, 5, 1), 4),
    ("windowed", (24, 9, 3), 5),     # first prompt exceeds the window
    ("ssm", (11, 6, 4), 4),
    ("hybrid", (10, 7, 3), 4),
])
def test_server_chunked_bitwise_vs_greedy(fixture, plens, chunk, request):
    cfg, model, params = request.getfixturevalue(fixture)
    reqs = _requests(cfg, plens)
    server = BatchedServer(model, params, slots=4, max_len=MAX_LEN,
                           prefill_chunk=chunk)
    for r in reqs:
        server.submit(r)
    done = server.run(dispatch_tokens=3)
    assert len(done) == len(reqs)
    for r in done:
        ref = greedy_decode(model, params, r.prompt, r.max_new_tokens,
                            max_len=MAX_LEN)
        assert r.output == ref, r.uid


def test_first_token_eos_frees_lane(dense):
    """A request whose very first token is a stop id finishes at its final
    chunk without ever joining decode, and the lane is recycled."""
    cfg, model, params = dense
    req0 = _requests(cfg, (9,), new_tokens=8)[0]
    eos = greedy_decode(model, params, req0.prompt, 1, max_len=MAX_LEN)[0]
    server = BatchedServer(model, params, slots=1, max_len=MAX_LEN,
                           prefill_chunk=4, stop_tokens=(eos,))
    follow = _requests(cfg, (5,), seed=1)[0]
    follow.uid = 1
    server.submit(req0)
    server.submit(follow)
    done = server.run(dispatch_tokens=3)
    assert [r.uid for r in done][0] == 0
    assert req0.output == [eos]
    assert follow.output == greedy_decode(model, params, follow.prompt,
                                          follow.max_new_tokens,
                                          max_len=MAX_LEN,
                                          stop_tokens=(eos,))


def test_chunked_requires_exact_cache_dtype(dense):
    """Chunked prefill reads KV history back from the cache: a lossy cache
    dtype breaks the bitwise contract and is rejected at construction."""
    cfg, model, params = dense
    lossy = dataclasses.replace(cfg, kv_cache_dtype="float8_e4m3fn")
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        BatchedServer(LM(lossy), params, slots=2, max_len=MAX_LEN,
                      prefill_chunk=4)
    with pytest.raises(ValueError, match="prefill_chunk"):
        BatchedServer(model, params, slots=2, max_len=MAX_LEN,
                      prefill_chunk=0)


def test_ssm_chunk_rounded_to_scan_boundary(ssm):
    """The engine rounds the chunk up to the internal scan chunk so every
    non-final boundary is a bitwise-exact resume point."""
    cfg, model, params = ssm
    server = BatchedServer(model, params, slots=2, max_len=MAX_LEN,
                           prefill_chunk=3)
    assert server.prefill_chunk == cfg.ssm_scan_chunk
    server = BatchedServer(model, params, slots=2, max_len=MAX_LEN,
                           prefill_chunk=5)
    assert server.prefill_chunk == 2 * cfg.ssm_scan_chunk


# ---------------------------------------------------------------------------
# Fused decode dispatch: inactive lanes, one of them mid chunked prefill
# ---------------------------------------------------------------------------
def _bits(a):
    a = np.asarray(a)
    return a.view(f"u{a.itemsize}") if a.dtype.kind == "f" or \
        a.dtype.name == "bfloat16" else a


def _select_decode_scan(model, params, cache, tok, active, budget, n):
    """The fused decode as it froze inactive lanes before: a step over
    every lane, then a select of the old cache over every leaf."""
    toks = []
    for _ in range(n):
        logits, stepped = model.decode_step(params, cache, tok)
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        data = jax.tree.map(
            lambda new, old: jnp.where(
                active.reshape((1, -1) + (1,) * (new.ndim - 2)), new, old),
            stepped.data, cache.data)
        cache = DecodeCache(data, jnp.where(active, stepped.length,
                                            cache.length))
        toks.append(jnp.where(active, nxt, 0))
        tok = jnp.where(active[:, None], nxt[:, None], tok)
        budget = budget - active.astype(budget.dtype)
        active = active & (budget > 0)
    return cache, jnp.stack(toks)


@pytest.mark.parametrize("fixture", ["ring_dense", "ssm", "hybrid"])
def test_dispatch_leaves_inactive_lanes_verbatim(fixture, request):
    """One ``_dispatch_jit`` over four lanes: two decode (one runs out of
    budget halfway, one wraps its ring), one is mid chunked prefill and one
    was never seated.  The inactive lanes' K/V, conv and h bits come out as
    they went in, and the tokens and the whole cache equal a step-by-step
    ``decode_step`` frozen by the old whole-cache select."""
    from repro.serve.engine import _dispatch_jit
    cfg, model, params = request.getfixturevalue(fixture)
    cache = model.init_cache(4, MAX_LEN)
    cache = DecodeCache(cache.data, jnp.zeros(4, jnp.int32))
    tok = np.zeros((4, 1), np.int32)
    # lane: (prompt length, tokens prefilled); lane 3 is never seated
    for lane, (plen, upto) in enumerate([(20, 20), (7, 7), (24, 8)]):
        prompt = _toks(cfg, (1, plen), seed=lane + 5)
        for off in range(0, upto, 4):
            n = min(4, upto - off)
            last, cache = model.prefill_chunk(
                params, cache, prompt[:, off:off + n], [off], [n], [lane])
        tok[lane] = np.argmax(last, -1)
    tok = jnp.asarray(tok)
    active = jnp.array([True, True, False, False])
    budget = jnp.array([6, 2, 0, 0], jnp.int32)
    before = jax.tree.map(np.asarray, cache)
    want_cache, want_toks = _select_decode_scan(model, params, cache, tok,
                                                active, budget, 4)
    want = jax.tree.map(np.asarray, (want_cache, want_toks))
    got_cache, _, _, _, toks, emitted = _dispatch_jit(
        model, 0, 4, (), params, cache, tok, active, budget)
    assert np.array_equal(np.asarray(toks), want[1])
    assert np.asarray(emitted)[:, 1].tolist() == [True, True, False, False]
    for name, leaf in got_cache.data.items():
        assert np.array_equal(_bits(leaf), _bits(want[0].data[name])), name
        for lane in (2, 3):  # lane axis 1 in every leaf
            assert np.array_equal(_bits(leaf[:, lane]),
                                  _bits(before.data[name][:, lane])), \
                (name, lane)
    assert np.asarray(got_cache.length).tolist() == [24, 9, 8, 0]


# ---------------------------------------------------------------------------
# Scheduler semantics: TTFT, load, stall accounting, drain
# ---------------------------------------------------------------------------
def test_ttft_stamps_under_fake_clock(dense):
    """submitted_s is stamped at submit(), first_token_s at the step whose
    chunk produced the first output token — later for longer prompts."""
    cfg, model, params = dense
    clock = FakeClock(10.0)
    server = BatchedServer(model, params, slots=2, max_len=MAX_LEN,
                           prefill_chunk=4, clock=clock)
    short, long_ = _requests(cfg, (4, 13), new_tokens=4)
    server.submit(short)
    server.submit(long_)
    assert short.submitted_s == 10.0 and long_.submitted_s == 10.0
    while not server.idle():
        clock.t += 1.0
        server.step(2)
    assert short.first_token_s is not None
    assert long_.first_token_s is not None
    # 13 tokens at chunk 4 = 4 steps of prefill vs 1 for the short prompt
    assert long_.first_token_s > short.first_token_s
    assert short.first_token_s - short.submitted_s == 1.0


class TickingClock:
    """A clock that moves one second on every read."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1.0
        return self.t


def test_first_token_stamp_follows_the_chunk_that_made_it(dense):
    """With a clock that ticks on every read, the first token is stamped
    after the step's start (its first clock read) and no later than the
    step's end: the stamp is read once the final chunk's token is on the
    host, not when the step began."""
    cfg, model, params = dense
    clock = TickingClock()
    server = BatchedServer(model, params, slots=2, max_len=MAX_LEN,
                           prefill_chunk=4, clock=clock)
    reqs = _requests(cfg, (4, 13), new_tokens=4)
    for r in reqs:
        server.submit(r)
    window = {}
    while not server.idle():
        before = clock.t
        server.step(2)
        for r in reqs:
            if r.first_token_s is not None and r.uid not in window:
                window[r.uid] = (before + 1.0, clock.t)
    assert set(window) == {r.uid for r in reqs}
    for r in reqs:
        start, end = window[r.uid]
        assert start < r.first_token_s <= end


def test_load_report_counts_remaining_tokens(dense):
    """Backlog weights prompt + decode *tokens*: a queued long prompt must
    outweigh a queued short one even at equal request counts."""
    cfg, model, params = dense
    server = BatchedServer(model, params, slots=1, max_len=MAX_LEN)
    # occupy the only slot so submissions stay queued
    busy = _requests(cfg, (4,), new_tokens=8)[0]
    server.submit(busy)
    server.step()
    rep0 = server.load_report()
    long_ = _requests(cfg, (30,), new_tokens=8, seed=1)[0]
    long_.uid = 1
    server.submit(long_)
    rep1 = server.load_report()
    assert rep1["backlog_tokens"] - rep0["backlog_tokens"] == 30 + 8
    # a seated mid-prefill lane reports its un-prefilled prompt tokens too
    chunked = BatchedServer(model, params, slots=1, max_len=MAX_LEN,
                            prefill_chunk=4)
    chunked.submit(_requests(cfg, (13,), new_tokens=8)[0])
    chunked.step()  # seated, one 4-token chunk done, 9 prompt tokens left
    rep = chunked.load_report()
    assert rep["active"] == 1
    assert rep["backlog_tokens"] >= 9


def test_decode_stall_frac_discriminates(dense):
    """Monolithic admission of a long prompt while decode lanes are live
    stalls them (high decode_stall_frac); chunked interleaving decodes
    through the same prefill (strictly lower)."""
    cfg, model, params = dense
    fracs = {}
    for mode, kw in [("mono", {}), ("chunked", dict(prefill_chunk=4))]:
        server = BatchedServer(model, params, slots=2, max_len=64, **kw)
        first = _requests(cfg, (4,), new_tokens=24)[0]
        server.submit(first)
        server.step(2)  # first request decoding: lanes are now live
        long_ = _requests(cfg, (40,), new_tokens=4, seed=1)[0]
        long_.uid = 1
        server.submit(long_)
        while not server.idle():
            server.step(2)
        fracs[mode] = server.decode_stall_frac
    assert 0.0 <= fracs["chunked"] < fracs["mono"] <= 1.0


def test_mid_prefill_drain_resumes_bitwise(dense):
    """Evacuating a server mid-prefill hands the request back as a
    continuation; re-admitting it (fresh server, same params) restarts the
    chunked prefill and the stream still matches greedy_decode."""
    cfg, model, params = dense
    req = _requests(cfg, (13,), new_tokens=5)[0]
    server = BatchedServer(model, params, slots=1, max_len=MAX_LEN,
                           prefill_chunk=4)
    server.submit(req)
    server.step(2)  # seated, first chunk done, prompt NOT finished
    assert req.output == []  # no token committed yet
    (drained,) = server.evacuate()
    assert drained is req
    assert server.idle()
    second = BatchedServer(model, params, slots=1, max_len=MAX_LEN,
                           prefill_chunk=4)
    second.requeue(req)
    done = second.run(dispatch_tokens=2)
    assert done[0].output == greedy_decode(model, params, req.prompt,
                                           req.max_new_tokens,
                                           max_len=MAX_LEN)


def test_latency_stats_reports_ttft_separately():
    from repro.cluster import latency_stats
    lat = {0: 2.0, 1: 4.0}
    ttft = {0: 0.5, 1: 1.5}
    st = latency_stats(lat, ttft)
    assert st["n"] == 2 and st["n_ttft"] == 2
    assert st["p50_ttft_s"] == pytest.approx(1.0)
    assert st["max_ttft_s"] == 1.5
    # backwards compatible: no ttft arg -> no ttft keys
    assert "p99_ttft_s" not in latency_stats(lat)
    assert latency_stats({}, {})["p99_ttft_s"] == 0.0


def test_cluster_router_inherits_chunked_prefill(dense):
    """ClusterRouter passes prefill_chunk through to every die replica and
    the served streams stay bitwise-identical to the monolithic path."""
    from repro.cluster import ClusterRouter, SimClock, homogeneous
    from repro.core import chip
    from repro.core.formats import FP32
    from helpers import make_chip_unit
    cfg, model, params = dense
    die = chip.ChipSpec("d", (make_chip_unit("decode", FP32, 1e-8, 1.0),))
    cluster = homogeneous(die, 2)
    outs = {}
    for mode, kw in [("mono", {}), ("chunked", dict(prefill_chunk=4))]:
        clock = SimClock()
        router = ClusterRouter(model, params, cluster, slots=2,
                               max_len=MAX_LEN, clock=clock,
                               dispatch_tokens=3, **kw)
        reqs = _requests(cfg, (7, 13, 5, 9), new_tokens=5)
        for r in reqs:
            router.submit(r)
        for _ in range(200):
            clock.t += 0.01
            router.step()
            if router.idle():
                break
        assert router.idle()
        outs[mode] = {r.uid: r.output for r in router.drain_finished()}
    assert outs["mono"] == outs["chunked"]
    assert all(v for v in outs["mono"].values())
