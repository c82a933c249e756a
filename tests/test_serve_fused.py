"""Device-resident serving hot path: fused multi-token decode must be
bitwise-identical to per-sample greedy decoding across prompt-length
buckets and model families, bucketed prefill must share compiled programs,
bulk (dispatch-boundary) energy charging must match the seed per-token
accounting, run() must collect finished work, and chip-aware admission
must route requests to per-unit fleets."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import chip
from repro.core.energy_model import calibrate
from repro.models import LM
from repro.serve.engine import (BatchedServer, Request, bucket_length,
                                greedy_decode)

from helpers import FakeClock


@pytest.fixture(scope="module")
def dense():
    cfg = get_config("tinyllama-1.1b").reduced()
    model = LM(cfg)
    return cfg, model, model.init(jax.random.key(3))


def _prompts(cfg, lens, seed=11):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


# ------------------------------------------------------------- equivalence
def test_bucket_length():
    assert [bucket_length(n) for n in (1, 8, 9, 16, 17, 100)] == \
        [8, 8, 16, 16, 32, 128]


def test_fused_decode_bitwise_matches_greedy_across_buckets(dense):
    """Prompt lengths spanning three pad buckets, more requests than slots
    (churn), multi-token dispatches: every output must equal the
    single-sequence reference decoder token for token."""
    cfg, model, params = dense
    prompts = _prompts(cfg, (3, 8, 9, 15, 17, 30))
    refs = [greedy_decode(model, params, p, 7, max_len=64) for p in prompts]
    server = BatchedServer(model, params, slots=4, max_len=64,
                           dispatch_tokens=3)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=7)
            for i, p in enumerate(prompts)]
    for r in reqs:
        server.submit(r)
    finished = server.run(max_steps=100)
    assert sorted(r.uid for r in finished) == list(range(len(reqs)))
    for r, ref in zip(reqs, refs):
        assert r.output == ref, (r.uid, r.output, ref)


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "falcon-mamba-7b",
                                  "zamba2-1.2b"])
def test_fused_decode_matches_greedy_other_families(arch):
    """Sliding-window ring caches (incl. a prompt longer than the window)
    and exact-length SSM/hybrid batching through the fused path."""
    cfg = get_config(arch).reduced()
    model = LM(cfg)
    params = model.init(jax.random.key(5))
    lens = (5, 20, 7) if cfg.window else (5, 7, 7)
    prompts = _prompts(cfg, lens)
    refs = [greedy_decode(model, params, p, 5, max_len=48) for p in prompts]
    server = BatchedServer(model, params, slots=2, max_len=48,
                           dispatch_tokens=4)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=5)
            for i, p in enumerate(prompts)]
    for r in reqs:
        server.submit(r)
    server.run(max_steps=100)
    for r, ref in zip(reqs, refs):
        assert r.output == ref, (r.uid, r.output, ref)


def _stablehlo_ops(text, op):
    """(operand dims, result dims) of every ``stablehlo.<op>`` in text."""
    for line in text.splitlines():
        if f"stablehlo.{op} " not in line:
            continue
        dims = [tuple(int(d) for d in t.split("x")[:-1])
                for t in re.findall(r"tensor<([^>]*)>", line)]
        yield dims[:-1], dims[-1]


def test_hybrid_decode_scan_copies_nothing():
    """The lowered decode dispatch of a hybrid with three segments and two
    shared-block applications reads weights, SSM state and K/V where they
    lie: no select whose result is a whole K/V, conv or h stack, no
    concatenate that rebuilds one, and no static slice of a stacked
    (n_layers, ...) array, weights or state.  The token scan holds the
    whole program, so the whole program is searched."""
    from repro.models.model import DecodeCache
    from repro.serve.engine import _dispatch_jit
    cfg = dataclasses.replace(get_config("zamba2-1.2b").reduced(),
                              n_layers=5, shared_attn_every=2)
    model = LM(cfg)

    def state():
        cache = model.init_cache(3, 32)
        return (DecodeCache(cache.data, jnp.zeros(3, jnp.int32)),
                jnp.zeros((3, 1), jnp.int32), jnp.zeros(3, bool),
                jnp.zeros(3, jnp.int32))

    params = jax.eval_shape(model.init, jax.random.key(0))
    cache, tok, active, budget = jax.eval_shape(state)
    stacks = {a.shape for a in cache.data.values()}
    assert len(stacks) == 3 and cache.data["k"].shape[0] == 2
    text = _dispatch_jit.lower(model, 0, 4, (), params, cache, tok, active,
                               budget).as_text()
    selects = [r for _, r in _stablehlo_ops(text, "select") if r in stacks]
    concats = [r for _, r in _stablehlo_ops(text, "concatenate")
               if r in stacks]
    slices = [ops[0] for ops, _ in _stablehlo_ops(text, "slice")
              if ops and ops[0][:1] == (cfg.n_layers,)]
    assert (selects, concats, slices) == ([], [], [])


def test_cache_capped_request_finishes_at_dispatch_boundary(dense):
    """A request whose budget was capped by the cache capacity is finished
    the moment its device budget drains — no extra dead dispatch — and is
    marked done (truncated), not expired."""
    cfg, model, params = dense
    server = BatchedServer(model, params, slots=1, max_len=32,
                           dispatch_tokens=4)
    req = Request(uid=0, prompt=_prompts(cfg, (20,))[0], max_new_tokens=50)
    server.submit(req)
    steps = 0
    for _ in range(20):
        if server.step(4) == 0 and not any(server._queues.values()):
            break
        steps += 1
    assert req.done and not req.expired
    assert len(req.output) == 1 + (32 - 20)  # prefill token + capped budget
    assert steps == 3  # ceil(12 / 4) dispatches, none wasted


def test_run_returns_finished_and_expired_requests(dense):
    """Regression: run() used to return an always-empty list."""
    cfg, model, params = dense
    clock = FakeClock(0.0)
    server = BatchedServer(model, params, slots=2, max_len=32, clock=clock)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=3)
            for i, p in enumerate(_prompts(cfg, (4, 5, 6)))]
    reqs.append(Request(uid=3, prompt=_prompts(cfg, (4,))[0],
                        max_new_tokens=3, deadline_s=-1.0))  # expires queued
    for r in reqs:
        server.submit(r)
    finished = server.run(max_steps=50)
    assert sorted(r.uid for r in finished) == [0, 1, 2, 3]
    assert all(r.done for r in finished)
    assert [r.uid for r in finished if r.expired] == [3]
    # a second run has nothing new to report
    assert server.run(max_steps=5) == []


def test_bucketed_prefill_shares_compiled_programs(dense):
    """Two admission waves with different prompt lengths in the same
    power-of-two bucket must reuse one compiled prefill program."""
    cfg, model, params = dense
    from repro.serve import engine as eng
    server = BatchedServer(model, params, slots=2, max_len=64)
    base = eng._admit_jit._cache_size()
    for wave, lens in enumerate(((9, 11), (13, 16))):  # all bucket 16
        reqs = [Request(uid=10 * wave + i, prompt=p, max_new_tokens=2)
                for i, p in enumerate(_prompts(cfg, lens))]
        for r in reqs:
            server.submit(r)
        server.run(max_steps=20)
        assert all(r.done for r in reqs)
    assert eng._admit_jit._cache_size() - base == 1


def test_slot_churn_under_mixed_deadlines(dense):
    """Expiring and surviving requests interleave through the same slots;
    survivors' outputs stay bitwise-correct and every slot is recycled."""
    cfg, model, params = dense
    prompts = _prompts(cfg, (4, 6, 5, 7, 9, 8))
    clock = FakeClock(0.0)
    server = BatchedServer(model, params, slots=2, max_len=32, clock=clock)
    doomed = [Request(uid=i, prompt=prompts[i], max_new_tokens=50,
                      deadline_s=float(i + 1)) for i in range(3)]
    survivors = [Request(uid=10 + i, prompt=prompts[3 + i], max_new_tokens=4)
                 for i in range(3)]
    for a, b in zip(doomed, survivors):
        server.submit(a)
        server.submit(b)
    for _ in range(60):
        clock.t += 1.0  # every step expires the next doomed deadline
        if server.step() == 0 and not any(server._queues.values()):
            break
    assert all(r.done and r.expired for r in doomed)
    assert all(r.done and not r.expired for r in survivors)
    refs = [greedy_decode(model, params, r.prompt, 4, max_len=32)
            for r in survivors]
    for r, ref in zip(survivors, refs):
        assert r.output == ref
    assert server._active == [None, None]


# ------------------------------------------------------------------ energy
def test_bulk_energy_matches_per_token_reference(dense):
    """Dispatch-boundary (device-counted) charging == the seed's per-token
    charging in closed form: the prompt's FLOPs on the prefill unit, one
    token's FLOPs per decoded token after the first on the decode unit,
    per request and per unit, with outputs equal to ``greedy_decode``."""
    cfg, model, params = dense
    tech = calibrate()
    prompts = _prompts(cfg, (4, 9, 6, 12))
    fpt = 2.0 * cfg.active_param_count()
    prec = cfg.numerics_precision

    def serve(**kw):
        policy = chip.ChipPolicy(chip.fabricated_chip("sp", tech), tech)
        server = BatchedServer(model, params, slots=2, max_len=32,
                               chip_policy=policy, **kw)
        reqs = [Request(uid=i, prompt=p, max_new_tokens=6)
                for i, p in enumerate(prompts)]
        for r in reqs:
            server.submit(r)
        for _ in range(40):
            if server.step() == 0:
                break
        return server, reqs

    new_server, new_reqs = serve()
    # drain multi-token dispatches too: same totals at coarser granularity
    bulk_server, bulk_reqs = serve(dispatch_tokens=4)
    bulk_server.run(max_steps=10)
    policy = new_server.chip_policy
    want_units = {}
    for i, p in enumerate(prompts):
        ref = greedy_decode(model, params, p, 6, max_len=32)
        assert new_reqs[i].output == ref == bulk_reqs[i].output
        split = {}
        for ph, flops in (("prefill", fpt * len(p)),
                          ("decode", fpt * (len(ref) - 1))):
            unit = policy.unit_for_phase(ph, precision=prec).name
            e = policy.request_energy_j(ph, flops, precision=prec)
            split[unit] = split.get(unit, 0.0) + e
            want_units[unit] = want_units.get(unit, 0.0) + e
        for r in (new_reqs[i], bulk_reqs[i]):
            assert r.energy_j == pytest.approx(sum(split.values()), rel=1e-9)
            assert r.unit_energy_j == pytest.approx(split, rel=1e-9)
    for server in (new_server, bulk_server):
        rep = server.energy_report()
        assert rep["tokens_decoded"] == sum(len(r.output) for r in new_reqs)
        assert rep["per_unit_j"] == pytest.approx(want_units, rel=1e-9)


# ----------------------------------------------------------- fleet routing
def test_partition_slots_proportional():
    units = chip.fabricated_chip(None, calibrate()).units
    cma = [u for u in units if u.design.style == "cma"]
    fleets = chip.partition_slots(8, cma)
    assert sorted(fleets) == sorted(u.name for u in cma)
    all_slots = [s for ids in fleets.values() for s in ids]
    assert sorted(all_slots) == list(range(8))
    assert all(len(ids) >= 1 for ids in fleets.values())
    with pytest.raises(ValueError):
        chip.partition_slots(1, cma)


def test_admission_routing_by_precision(dense):
    """SP and DP requests land on their precision's decode fleet and are
    charged on that fleet's unit."""
    cfg, model, params = dense
    tech = calibrate()
    policy = chip.ChipPolicy(chip.fabricated_chip(None, tech), tech)
    server = BatchedServer(model, params, slots=4, max_len=32,
                           chip_policy=policy)
    assert sorted(server._fleets) == ["dp_cma", "sp_cma"]
    prompts = _prompts(cfg, (4, 5, 6, 7))
    reqs = [Request(uid=i, prompt=p, max_new_tokens=3,
                    precision="dp" if i % 2 else "sp")
            for i, p in enumerate(prompts)]
    for r in reqs:
        server.submit(r)
    server.run(max_steps=30)
    for r in reqs:
        want = "dp_cma" if r.uid % 2 else "sp_cma"
        assert r.routed_unit == want
        assert r.unit_energy_j[want] > 0
    rep = server.energy_report()
    assert rep["per_unit_j"]["sp_cma"] > 0
    assert rep["per_unit_j"]["dp_cma"] > 0
    fleets = server.fleet_report()
    assert set(fleets) == {"sp_cma", "dp_cma"}
    assert all(f["queued"] == 0 and f["active"] == 0
               for f in fleets.values())


def test_admission_routing_by_deadline_class(dense):
    """With deadline_routing on, deadline-bound traffic rides the
    latency-class (CMA) fleet and bulk traffic the throughput-class (FMA)
    fleet of the same precision."""
    cfg, model, params = dense
    tech = calibrate()
    policy = chip.ChipPolicy(chip.fabricated_chip("sp", tech), tech)
    assert [u.name for u in policy.decode_fleet_units(
        deadline_routing=True)] == ["sp_cma", "sp_fma"]
    clock = FakeClock(0.0)
    server = BatchedServer(model, params, slots=4, max_len=32,
                           chip_policy=policy, deadline_routing=True,
                           clock=clock)
    prompts = _prompts(cfg, (4, 5))
    interactive = Request(uid=0, prompt=prompts[0], max_new_tokens=3,
                          deadline_s=1e9)
    bulk = Request(uid=1, prompt=prompts[1], max_new_tokens=3)
    server.submit(interactive)
    server.submit(bulk)
    server.run(max_steps=20)
    assert interactive.routed_unit == "sp_cma"
    assert bulk.routed_unit == "sp_fma"
    assert interactive.unit_energy_j["sp_cma"] > 0
    assert bulk.unit_energy_j["sp_fma"] > 0


def test_stop_tokens_bitwise_parity_with_greedy(dense):
    """Satellite acceptance: EOS-class stop tokens freeze lanes inside the
    fused scan — per-request outputs must equal greedy_decode with the same
    stop set token for token, across dispatch-boundary positions."""
    cfg, model, params = dense
    prompts = _prompts(cfg, (3, 8, 9, 15))
    plain = [greedy_decode(model, params, p, 12, max_len=64)
             for p in prompts]
    # stop ids that actually occur mid-stream (one early, one late) so the
    # stop lands both inside a dispatch and at a dispatch boundary
    stops = (plain[0][3], plain[2][1])
    refs = [greedy_decode(model, params, p, 12, max_len=64,
                          stop_tokens=stops) for p in prompts]
    assert any(len(r) < 12 for r in refs)  # the stops really fire
    server = BatchedServer(model, params, slots=2, max_len=64,
                           dispatch_tokens=4, stop_tokens=stops)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=12)
            for i, p in enumerate(prompts)]
    for r in reqs:
        server.submit(r)
    finished = server.run(max_steps=100)
    assert sorted(r.uid for r in finished) == [0, 1, 2, 3]
    for r, ref in zip(reqs, refs):
        assert r.output == ref, (r.uid, r.output, ref)
        assert r.done and not r.expired


def test_stop_token_on_first_prefill_token(dense):
    """A prompt whose very first sampled token is a stop id finishes at
    admission without ever occupying a decode slot — and its device lane
    is freed too: later dispatches (driven here by a concurrent request)
    must not decode zombie tokens for the recycled slot."""
    cfg, model, params = dense
    p, other = _prompts(cfg, (6, 9))
    first = greedy_decode(model, params, p, 1, max_len=32)[0]
    other_ref = greedy_decode(model, params, other, 6, max_len=32)
    server = BatchedServer(model, params, slots=2, max_len=32,
                           dispatch_tokens=2, stop_tokens=(first,))
    req = Request(uid=0, prompt=p, max_new_tokens=8)
    longer = Request(uid=1, prompt=other, max_new_tokens=6)
    server.submit(req)
    server.submit(longer)
    server.run(max_steps=20)
    assert req.done and req.output == [first]
    assert longer.output == other_ref
    assert server._active == [None, None]
    # the EOS'd lane was deactivated on device at admission: every decoded
    # token is accounted to a live request, none to the zombie slot
    assert not bool(np.asarray(server._active_mask).any())
    assert server.tokens_decoded == len(req.output) + len(longer.output)


def test_admission_routing_by_accuracy_class(dense):
    """Requests carrying an accuracy SLO land on the cheapest fleet whose
    unit format meets it: loose-SLO traffic on the sub-SP (fp8) unit,
    tight-SLO traffic on the FP32 unit."""
    from helpers import make_chip_unit as unit
    from repro.core.formats import FP32, FP8_E4M3
    cfg, model, params = dense

    spec = chip.ChipSpec("tiered", (unit("decode_eco", FP8_E4M3, 1e-2, 0.5),
                                    unit("decode_gold", FP32, 1e-8, 4.0)))
    policy = chip.ChipPolicy(spec, calibrate())
    server = BatchedServer(model, params, slots=4, max_len=32,
                           chip_policy=policy,
                           accuracy_fleets=(5e-2, 1e-7))
    assert sorted(server._fleets) == ["decode_eco", "decode_gold"]
    prompts = _prompts(cfg, (4, 5, 6))
    loose = Request(uid=0, prompt=prompts[0], max_new_tokens=3,
                    accuracy_slo=5e-2)
    tight = Request(uid=1, prompt=prompts[1], max_new_tokens=3,
                    accuracy_slo=1e-7)
    dont_care = Request(uid=2, prompt=prompts[2], max_new_tokens=3)
    for r in (loose, tight, dont_care):
        server.submit(r)
    server.run(max_steps=30)
    assert loose.routed_unit == "decode_eco"
    assert tight.routed_unit == "decode_gold"
    assert dont_care.routed_unit == "decode_eco"  # class objective winner
    assert loose.unit_energy_j["decode_eco"] > 0
    assert tight.unit_energy_j["decode_gold"] > 0
    # the loose fleet's pJ/FLOP is the cheap one: same token count, less J
    assert loose.energy_j < tight.energy_j


def test_accuracy_fallback_picks_most_accurate_provisioned_fleet(dense):
    """When the chip routes an accuracy-tagged request to a unit no fleet
    was provisioned for, admission re-resolves against the provisioned
    units — most accurate available, never an arbitrary fleet."""
    from helpers import make_chip_unit as unit
    from repro.core.formats import BF16, FP32, FP8_E4M3
    cfg, model, params = dense
    spec = chip.ChipSpec("tri", (unit("decode_eco", FP8_E4M3, 1e-2, 0.5),
                                 unit("decode_mid", BF16, 1e-3, 1.0),
                                 unit("decode_gold", FP32, 1e-8, 4.0)))
    policy = chip.ChipPolicy(spec, calibrate())
    # fleets provisioned only for the loose classes: eco + mid
    server = BatchedServer(model, params, slots=4, max_len=32,
                           chip_policy=policy,
                           accuracy_fleets=(5e-2, 5e-3))
    assert sorted(server._fleets) == ["decode_eco", "decode_mid"]
    # tight request: the chip would route decode_gold (unprovisioned) —
    # admission must degrade to the most accurate *provisioned* fleet
    # (mid), not silently land on the fp8 fleet
    tight = Request(uid=0, prompt=_prompts(cfg, (4,))[0], max_new_tokens=3,
                    accuracy_slo=1e-7)
    # a mid-class request takes the cheapest fleet meeting its SLO
    mid = Request(uid=1, prompt=_prompts(cfg, (5,))[0], max_new_tokens=3,
                  accuracy_slo=5e-3)
    server.submit(tight)
    server.submit(mid)
    server.run(max_steps=20)
    assert tight.routed_unit == "decode_mid"
    assert mid.routed_unit == "decode_mid"
