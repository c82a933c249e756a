"""Batched serving engine: device-resident continuous batching over the
decode step, with chip-aware admission routing across per-unit slot fleets.

The engine drives the LM's prefill/decode steps with a fixed slot count
(= the compiled decode batch size).  Requests are admitted into free slots;
finished/expired slots are recycled without recompiling — the production
pattern for TPU serving (one compiled decode XLA program, rotating traffic).

Hot-path structure (the device-resident overhaul):

  * **Fused multi-token decode** — greedy sampling is fused into the jitted
    decode step and ``LM.decode_scan`` decodes up to N tokens per host
    dispatch, carrying the slot state (per-slot lengths, next token,
    remaining budget, done flags) as device arrays.  Host syncs drop from
    one per token to one per N-token dispatch.
  * **Donated cache buffers** — the batched decode cache and slot-state
    arrays are donated through the jitted admit/dispatch calls, so XLA
    updates them in place instead of re-materializing the cache per step.
  * **Bucketed batched prefill** — prompt lengths are padded up to
    power-of-two buckets (exact for causal attention: pads never enter a
    valid position's context) so prefill compiles O(log max_len) programs
    instead of one per length, and same-bucket queued requests are admitted
    in one batched prefill + scatter.  SSM/hybrid state carries run through
    pads, so those families batch at exact lengths instead.
  * **Bulk energy accounting** — per-slot decoded-token counts accumulate
    on device inside the dispatch; ``ChipPolicy`` energy is charged once
    per dispatch boundary instead of per token.
  * **Chip-aware admission routing** — with a ``ChipPolicy`` attached the
    slots are partitioned into per-unit fleets (``ChipPolicy.slot_fleets``)
    and every request is routed to the SP or DP fleet by its requested
    ``precision`` — and, with ``deadline_routing=True``, by its deadline
    class (deadline-bound -> latency-class unit, bulk -> throughput-class
    unit) — at admission.  Requests may also carry an ``accuracy_slo``
    (their accuracy *class*): admission then routes to the cheapest fleet
    whose unit operand format meets the SLO (``accuracy_fleets=`` lists
    the classes to provision fleets for), the transprecision
    energy-proportionality argument at serving time.
  * **EOS / stop tokens** — ``stop_tokens=`` freezes a lane *inside* the
    fused scan the moment it samples a stop id: the stop token is emitted,
    nothing after it is decoded or charged, and the slot is recycled at
    the dispatch boundary (bitwise parity with ``greedy_decode``'s
    stop-token semantics).  Energy is accounted on the fleet's unit; the
    prompt forward pass (including the logits that produce the first
    output token) on the prefill unit.  Expired requests release their
    slot and keep the partial energy accrued so far; ``energy_report()``
    aggregates chip-level.

Deadlines are evaluated against an injected ``clock`` (default
``time.monotonic``) at dispatch boundaries: a request that expired before a
step is released without decoding or charging another token; tokens decoded
in the dispatch during which the deadline passes are kept (the work was
done).

Greedy sampling only (deterministic).  ``greedy_decode`` below is the
reference: a single-sequence decoder that the tests compare every serving
path against bit for bit.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.faults import UnitFault
from repro.models import LM, DecodeCache
from repro.telemetry.tracer import NULL_SPAN, NULL_TRACER
from repro.telemetry.tracer import Event as TraceEvent


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int
    deadline_s: Optional[float] = None
    precision: Optional[str] = None  # requested fleet precision (sp/dp)
    #: requested accuracy class: max acceptable numerics error (normwise
    #: relative, the AccuracyModel scale).  Admission routes to the
    #: cheapest fleet whose unit format meets it; None = don't care.
    accuracy_slo: Optional[float] = None
    # filled by the engine
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    expired: bool = False
    #: structurally rejected (validation / backpressure / load shedding):
    #: never admitted, reason in ``reject_reason``
    rejected: bool = False
    reject_reason: str = ""
    routed_unit: str = ""  # chip unit serving this request's decode phase
    #: times this request was drained off a failing fleet and re-admitted
    #: as a continuation (prefill + decode-path replay) on a surviving one
    requeues: int = 0
    #: clock time ``submit()`` accepted the request (TTFT origin)
    submitted_s: Optional[float] = None
    #: clock time the first output token was committed, at its dispatch
    #: boundary (TTFT = first_token_s - submitted_s); survives requeues —
    #: a continuation keeps its original first-token stamp
    first_token_s: Optional[float] = None
    energy_j: float = 0.0  # total (partial if expired)
    unit_energy_j: Dict[str, float] = dataclasses.field(default_factory=dict)


class RequestRejected(ValueError):
    """Structured admission reject: ``submit()`` raises it *and* records
    the reject on the request (``rejected`` / ``reject_reason``) and in
    ``server.rejected`` — callers get an actionable error instead of a
    deep routing failure, telemetry gets a structured record."""

    def __init__(self, req: "Request", code: str, reason: str):
        super().__init__(f"request {req.uid}: [{code}] {reason}")
        self.req = req
        self.code = code
        self.reason = reason


def bucket_length(n: int, *, lo: int = 8) -> int:
    """Power-of-two prompt-length bucket (>= lo) — the prefill pad target."""
    b = lo
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# Jitted device kernels (module level: the compile cache is keyed on the LM
# instance, so fresh servers over the same model reuse warm executables)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3),
                   donate_argnums=(5, 6, 7, 8))
def _dispatch_jit(model, pad_id, n_steps, stop_tokens, params, cache,
                  next_tok, active, budget):
    """One fused N-token decode dispatch over all slots."""
    return model.decode_scan(params, cache, next_tok, active, budget,
                             n_steps, pad_id=pad_id,
                             stop_tokens=stop_tokens)


@functools.partial(jax.jit, static_argnums=(0, 1),
                   donate_argnums=(3, 4, 5, 6))
def _admit_jit(model, ring, params, cache, next_tok, active, budget,
               tokens, true_lens, slot_ids, budgets):
    """Batched same-bucket admission: one prefill forward over the admitted
    prompts + in-place scatter of KV/states and slot state into the batched
    cache (buffers donated -> XLA updates in place).

    Padded lanes carry ``slot_ids == n_slots`` (out of bounds) and are
    dropped by the scatters.  ``ring`` marks ring (sliding-window) KV
    caches, whose writes must be ring-aligned when a prompt exceeds the
    window.
    """
    last_logits, kv, states = model.prefill_batched(params, tokens,
                                                    true_lens)
    first = jnp.argmax(last_logits, -1).astype(jnp.int32)
    data = dict(cache.data)
    if kv is not None:
        k, v = kv  # (L_or_apps, M, Lb, Hkv, D), already cache dtype
        smax = data["k"].shape[2]
        Lb = k.shape[2]
        # a bucket wider than the cache can only be a ring (sliding-window)
        # cache: non-ring engines cap both the bucket and the prompt length
        # at the cache width
        if Lb <= smax:
            data["k"] = data["k"].at[:, slot_ids, :Lb].set(k, mode="drop")
            data["v"] = data["v"].at[:, slot_ids, :Lb].set(v, mode="drop")
        else:
            assert ring, "bucket wider than a non-ring cache"
            # keep the window tail, ring-aligned so position p sits at slot
            # p % smax (where decode writes next); clip handles short
            # prompts (their out-of-range slots are masked until decode
            # overwrites them)
            j = jnp.arange(smax)
            base = true_lens[:, None] - smax
            p = jnp.clip(base + ((j[None, :] - base) % smax), 0, Lb - 1)
            idx = p[None, :, :, None, None]
            data["k"] = data["k"].at[:, slot_ids].set(
                jnp.take_along_axis(k, idx, axis=2), mode="drop")
            data["v"] = data["v"].at[:, slot_ids].set(
                jnp.take_along_axis(v, idx, axis=2), mode="drop")
    if states is not None:
        conv, h = states
        data["conv"] = data["conv"].at[:, slot_ids].set(conv, mode="drop")
        data["h"] = data["h"].at[:, slot_ids].set(h, mode="drop")
    length = cache.length.at[slot_ids].set(true_lens, mode="drop")
    next_tok = next_tok.at[slot_ids, 0].set(first, mode="drop")
    budget = budget.at[slot_ids].set(budgets, mode="drop")
    active = active.at[slot_ids].set(budgets > 0, mode="drop")
    return DecodeCache(data, length), next_tok, active, budget, first


@functools.partial(jax.jit, static_argnums=(0,),
                   donate_argnums=(2, 3, 4, 5))
def _chunk_jit(model, params, cache, next_tok, active, budget, tokens,
               offsets, chunk_lens, slot_ids, final_ids, budgets):
    """One grouped prefill-chunk dispatch: advance M lanes' chunk-resumable
    prefills in place, then arm the decode slot state for the lanes whose
    prompt just completed (``final_ids``; non-final and pad lanes carry the
    out-of-bounds slot id and are dropped by the scatters).  ``first`` is
    only fetched by the host when final lanes exist — mid-prompt chunks
    cost zero host syncs."""
    last_logits, cache = model.prefill_chunk(params, cache, tokens,
                                             offsets, chunk_lens, slot_ids)
    first = jnp.argmax(last_logits, -1).astype(jnp.int32)
    next_tok = next_tok.at[final_ids, 0].set(first, mode="drop")
    budget = budget.at[final_ids].set(budgets, mode="drop")
    active = active.at[final_ids].set(budgets > 0, mode="drop")
    return cache, next_tok, active, budget, first


class BatchedServer:
    """Fixed-slot, device-resident continuous batching server around one LM.

    ``chip_policy`` (a ``repro.core.chip.ChipPolicy``) enables fleet
    routing and per-unit energy telemetry; ``flops_per_token`` defaults to
    ``2 * active params`` of the model config (the roofline inference
    estimate).  ``dispatch_tokens`` is the fused decode depth ``run()``
    uses per host dispatch; ``clock`` is the deadline time source
    (injectable for deterministic tests); ``deadline_routing`` splits each
    precision's traffic across latency-class (deadline-bound) and
    throughput-class (bulk) fleets.
    """

    def __init__(self, model: LM, params, *, slots: int, max_len: int,
                 pad_id: int = 0, chip_policy=None,
                 flops_per_token: Optional[float] = None,
                 dispatch_tokens: int = 8,
                 clock: Callable[[], float] = time.monotonic,
                 deadline_routing: bool = False,
                 accuracy_fleets: Tuple[float, ...] = (),
                 stop_tokens: Tuple[int, ...] = (),
                 min_bucket: int = 8,
                 prefill_chunk: Optional[int] = None,
                 prefill_token_budget: Optional[int] = None,
                 tracer=None):
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.pad_id = pad_id
        self.cfg = model.cfg
        self.chip_policy = chip_policy
        self.dispatch_tokens = dispatch_tokens
        self.min_bucket = min_bucket
        # --- chunked prefill + continuous batching ---------------------
        # prefill_chunk=N streams prompts through lanes N tokens per step
        # interleaved with decode dispatches (None = monolithic admission,
        # the pre-chunking behavior, bit for bit).  prefill_token_budget
        # caps the total chunk tokens per step (whole chunks, >= 1 lane).
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError("prefill_chunk must be >= 1")
            if model.cache_dtype != model.dtype:
                raise ValueError(
                    "chunked prefill reads KV history back from the cache "
                    "between chunks, so bitwise parity requires the cache "
                    "dtype to equal the compute dtype — unset "
                    f"kv_cache_dtype (cache {model.cache_dtype} != compute "
                    f"{model.dtype})")
            if self.cfg.family in ("ssm", "hybrid"):
                # bitwise-exact resume points only exist at the internal
                # selective-scan carry boundaries: round the chunk up
                sc = max(int(getattr(self.cfg, "ssm_scan_chunk", 64)), 1)
                prefill_chunk = -(-prefill_chunk // sc) * sc
        self.prefill_chunk = prefill_chunk
        self.prefill_token_budget = prefill_token_budget
        self._prefill_pos: Dict[int, int] = {}  # slot -> tokens prefilled
        self._slot_pf_budget = [0] * slots  # decode budget armed on finish
        self.prefill_tokens = 0  # cumulative prompt tokens prefilled
        # decode-stall accounting: prefill vs decode tokens processed on
        # steps where decode-ready lanes existed (see decode_stall_frac)
        self._stall_prefill_tokens = 0
        self._contended_decode_tokens = 0
        # EOS-class token ids: a lane freezes on device the moment it
        # samples one (the stop token is emitted, nothing after it)
        self.stop_tokens = tuple(int(s) for s in stop_tokens)
        self._stop_set = set(self.stop_tokens)
        self._clock = clock
        self._deadline_routing = deadline_routing
        # accuracy classes (SLOs) admission provisions fleets for, on top
        # of the don't-care class
        self._accuracy_fleets = tuple(accuracy_fleets)
        self._precision = getattr(self.cfg, "numerics_precision", None)
        if flops_per_token is None and hasattr(self.cfg,
                                               "active_param_count"):
            flops_per_token = 2.0 * self.cfg.active_param_count()
        self.flops_per_token = flops_per_token or 0.0
        self.tokens_decoded = 0
        self.dispatches = 0  # fused decode dispatches issued
        self.host_syncs = 0  # device->host fetches (admits + dispatches)
        self._unit_energy_j: Dict[str, float] = {}
        # SSM/hybrid decode states integrate every prompt token, so bucket
        # pads would perturb them: those families batch at exact lengths.
        self._bucketed = self.cfg.family not in ("ssm", "hybrid")
        # ring (sliding-window) KV caches wrap; everything else caps the
        # total per-slot length at the cache width
        self._ring = bool(self.cfg.window) and self.cfg.family != "hybrid"
        # device-resident slot state, on the device that holds the params
        # (a ClusterRouter places each die's replica on its own device)
        (device,) = jax.tree.leaves(params)[0].devices()
        with jax.default_device(device):
            cache = model.init_cache(slots, max_len)
            self.cache = DecodeCache(cache.data, jnp.zeros(slots, jnp.int32))
            self._next_tok = jnp.full((slots, 1), pad_id, jnp.int32)
            self._budget = jnp.zeros(slots, jnp.int32)
            self._active_mask = jnp.zeros(slots, bool)
        self._len_cap = None
        if "k" in cache.data and not self._ring:
            self._len_cap = cache.data["k"].shape[2]
        # host-side slot table / queues / fleet plan
        self._active: List[Optional[Request]] = [None] * slots
        # total tokens the slot's request will get (1 + its device budget;
        # below max_new_tokens when the cache capacity capped it)
        self._slot_quota = [0] * slots
        # committed tokens a re-admitted continuation still has to replay
        # through the decode path before commits resume (see _admit_batch)
        self._slot_replay = [0] * slots
        self.finished: List[Request] = []
        #: structurally rejected requests (validation / backpressure /
        #: shedding) — never admitted, never in ``finished``
        self.rejected: List[Request] = []
        #: fleets taken out of service (unit killed / quarantined) — the
        #: resilience layer drains them; admission never routes to them
        self._out_of_service: set = set()
        #: drained requests with no fleet in service to re-route to —
        #: parked (never dropped) until capacity returns
        self._parked: List[Request] = []
        if chip_policy is None:
            self._fleets: Dict[str, Tuple[int, ...]] = {
                "": tuple(range(slots))}
            self._fleet_units: Dict[str, object] = {"": None}
        else:
            self._fleets = chip_policy.slot_fleets(
                slots, deadline_routing=deadline_routing,
                accuracy_slos=(None,) + self._accuracy_fleets)
            self._fleet_units = {name: chip_policy.spec.unit(name)
                                 for name in self._fleets}
        self._queues: Dict[str, List[Request]] = {name: []
                                                  for name in self._fleets}
        self._slot_fleet = {s: name for name, ids in self._fleets.items()
                            for s in ids}
        # --- telemetry -------------------------------------------------
        # The tracer records span trees, step spans and metric timelines on
        # the injected clock (see repro.telemetry).  Default is the no-op
        # NULL_TRACER: every instrumentation site below is guarded by
        # ``tracer.enabled`` so the disabled hot path pays one attribute
        # read per site (and enters the shared NULL_SPAN around a step
        # phase).  The recording cost on a TPU v5e is in PERF.md.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        #: die/site label stamped on spans and metric samples (the cluster
        #: router sets it to the die name)
        self.trace_site = ""
        self.reset_run_counters()

    def _span(self, name: str, **attrs):
        """A step-phase span on the engine's clock and site (callers guard
        with ``tracer.enabled`` and enter ``NULL_SPAN`` otherwise)."""
        return self.tracer.span(name, self._clock, self.trace_site, **attrs)

    # ------------------------------------------------------- chip telemetry
    def _charge_unit(self, req: Request, unit, flops: float,
                     phase: str = "decode") -> None:
        """Account ``flops`` on ``unit`` (bulk form, dispatch-boundary),
        at the unit's *current* health pricing (a throttled unit's leakage
        energy per FLOP grows with the derate).

        This is the single energy choke point — every prefill, decode,
        replay, and wasted-corrupt-dispatch charge flows through here — so
        the tracer hook below makes span-attributed energy reconcile
        exactly against the ``_unit_energy_j`` chip ledger."""
        if self.chip_policy is None or not flops or unit is None:
            return
        e_j = self.chip_policy.unit_energy_j(unit, flops)
        req.energy_j += e_j
        req.unit_energy_j[unit.name] = \
            req.unit_energy_j.get(unit.name, 0.0) + e_j
        self._unit_energy_j[unit.name] = \
            self._unit_energy_j.get(unit.name, 0.0) + e_j
        if self.tracer.enabled:
            self.tracer.charge(req.uid, unit.name, e_j, flops,
                               self._clock(), phase=phase)

    def _prefill_unit(self, req: Request):
        if self.chip_policy is None:
            return None
        return self.chip_policy.unit_for_phase(
            "prefill", precision=req.precision or self._precision)

    def reset_run_counters(self) -> None:
        """Deterministically reset the per-run counters.

        ``run()`` calls this at entry so back-to-back runs don't leak
        scheduler state into each other's metrics: the decode-stall inputs
        (``_stall_prefill_tokens`` / ``_contended_decode_tokens``) are
        zeroed, and the cumulative counters (tokens, dispatches, syncs,
        energy) are snapshotted so ``run_report()`` exposes this run's
        deltas.  The cumulative surfaces (``energy_report()``,
        ``tokens_decoded`` ...) are *not* reset — they remain
        everything-served-so-far by contract.  Step-driven callers
        (``loadgen.replay``) may call this themselves to scope the stall
        fraction to a window."""
        self._stall_prefill_tokens = 0
        self._contended_decode_tokens = 0
        self._run_base = dict(
            tokens_decoded=self.tokens_decoded,
            prefill_tokens=self.prefill_tokens,
            dispatches=self.dispatches,
            host_syncs=self.host_syncs,
            energy_j=sum(self._unit_energy_j.values()))

    def run_report(self) -> Dict[str, float]:
        """Counters scoped to the current run (deltas since the last
        ``reset_run_counters()`` — which ``run()`` performs at entry)."""
        return dict(
            tokens_decoded=self.tokens_decoded
            - self._run_base["tokens_decoded"],
            prefill_tokens=self.prefill_tokens
            - self._run_base["prefill_tokens"],
            dispatches=self.dispatches - self._run_base["dispatches"],
            host_syncs=self.host_syncs - self._run_base["host_syncs"],
            energy_j=sum(self._unit_energy_j.values())
            - self._run_base["energy_j"],
            decode_stall_frac=self.decode_stall_frac)

    def energy_report(self) -> Dict[str, object]:
        """Chip-level energy aggregated over everything served so far
        (cumulative across runs; see ``run_report()`` for per-run
        deltas)."""
        total = sum(self._unit_energy_j.values())
        return dict(
            chip=self.chip_policy.spec.name if self.chip_policy else None,
            total_j=total,
            per_unit_j=dict(self._unit_energy_j),
            tokens_decoded=self.tokens_decoded,
            j_per_token=(total / self.tokens_decoded
                         if self.tokens_decoded else 0.0))

    # ------------------------------------------------------------------ api
    def fleet_report(self) -> Dict[str, Dict[str, object]]:
        """Per-fleet slot allocation and queue depth."""
        return {name or "(default)": dict(
            unit=name or None, slots=list(ids),
            queued=len(self._queues[name]),
            in_service=self._fleet_in_service(name),
            active=sum(1 for s in ids if self._active[s] is not None))
            for name, ids in self._fleets.items()}

    def load_report(self) -> Dict[str, float]:
        """Instantaneous load signal for cluster-level routing: queued /
        seated / parked request counts plus the token backlog — remaining
        *prefill + decode tokens* of the seated and queued requests, not a
        request count, so least-loaded placement doesn't steer long prompts
        onto already-prompt-heavy dies — normalized against the slots still
        in service.  Pure host-side bookkeeping — no device sync."""
        queued = sum(len(q) for q in self._queues.values())
        active_tokens = 0
        active = 0
        for s, req in enumerate(self._active):
            if req is None:
                continue
            active += 1
            active_tokens += max(self._slot_quota[s] - len(req.output), 0)
            if s in self._prefill_pos:  # prompt tokens still to prefill
                active_tokens += len(req.prompt) - self._prefill_pos[s]
        queued_tokens = sum(len(r.prompt) + r.max_new_tokens
                            for q in self._queues.values() for r in q)
        serving_slots = sum(len(ids) for n, ids in self._fleets.items()
                            if self._fleet_in_service(n))
        backlog = active_tokens + queued_tokens
        return dict(queued=queued, active=active, parked=len(self._parked),
                    slots=self.slots, serving_slots=serving_slots,
                    backlog_tokens=backlog,
                    load=backlog / max(serving_slots, 1))

    def evacuate(self) -> List[Request]:
        """Release every in-flight, queued, and parked request untouched
        (partial output and energy kept, device lanes deactivated) and hand
        them back — the cluster router's whole-die drain.  The requests are
        continuations: re-admitting them anywhere (``requeue`` on any
        server sharing this model+params) replays their committed tokens
        through the decode path and resumes the streams bitwise."""
        out: List[Request] = []
        released: List[int] = []
        for s, req in enumerate(self._active):
            if req is not None:
                out.append(req)
                released.append(s)
        self._release_slots(released)
        for name in self._queues:
            out.extend(self._queues[name])
            self._queues[name] = []
        out.extend(self._parked)
        self._parked = []
        return out

    def take_parked(self) -> List[Request]:
        """Hand over the parked requests (drained with no fleet in service)
        for placement elsewhere — the cluster router's rescue hook."""
        parked, self._parked = self._parked, []
        return parked

    def _fleet_in_service(self, name: str) -> bool:
        """A fleet is routable when the engine hasn't taken it out of
        service AND the chip's health model still lists its unit as
        serving (dead/quarantined units never take new admissions)."""
        if name in self._out_of_service:
            return False
        if self.chip_policy is not None and name in self._fleet_units \
                and self._fleet_units[name] is not None:
            return self.chip_policy.in_service(name)
        return True

    def _serving_fleets(self) -> List[str]:
        return [n for n in self._fleets if self._fleet_in_service(n)]

    def _route(self, req: Request) -> str:
        """Admission routing: which fleet serves this request's decode."""
        if self.chip_policy is None:
            return ""
        deadline_class = None
        if self._deadline_routing:
            deadline_class = ("interactive" if req.deadline_s is not None
                             else "bulk")
        try:
            unit = self.chip_policy.admission_unit(
                precision=req.precision or self._precision,
                deadline_class=deadline_class,
                accuracy_slo=req.accuracy_slo)
        except Exception:  # every unit out of service: degrade below
            unit = None
        if unit is not None and unit.name in self._fleets \
                and self._fleet_in_service(unit.name):
            return unit.name
        return self._degrade_route(req)

    def _degrade_route(self, req: Request) -> str:
        """Degrade-don't-drop re-resolution against the *provisioned,
        in-service* fleets — used when the chip routed a unit no fleet was
        provisioned for, or the preferred fleet is out of service.

        Candidate order: same-precision fleets when any survive (soft
        pre-filter, as in ``unit_for_phase``); then the cheapest fleet
        whose unit meets the request's accuracy requirement — the explicit
        ``accuracy_slo``, else the native error of its requested precision
        (falling back to a *more accurate* unit is always legal); else the
        most accurate survivor (never silently degrade harder than
        necessary).  With no fleet in service at all there is nothing to
        degrade to: ``repro.faults.UnitFault``."""
        units = [(n, u) for n, u in self._fleet_units.items()
                 if u is not None and self._fleet_in_service(n)]
        if not units:
            alive = self._serving_fleets()
            if alive:  # fleets without chip units (no-policy engines)
                return alive[0]
            from repro.faults import UnitFault
            raise UnitFault(
                f"request {req.uid}: no serving fleet in service "
                f"(out of service: {sorted(self._out_of_service)})")
        want_p = req.precision or self._precision
        if want_p is not None:
            same_p = [(n, u) for n, u in units
                      if u.design.precision == want_p]
            units = same_p or units
        ceiling = req.accuracy_slo
        if ceiling is None and req.precision is not None:
            # falling back across precisions: a surviving unit at least as
            # accurate as the requested precision's native format is legal
            try:
                from repro.numerics import (DEFAULT_ACCURACY_MODEL,
                                            native_format)
                ceiling = DEFAULT_ACCURACY_MODEL.rel_err(
                    native_format(req.precision), "fused")
            except Exception:
                ceiling = None
        pol = self.chip_policy

        def cost(nu):  # health-repriced pJ/FLOP: throttled fleets cost more
            return nu[1].e_per_flop_pj * pol.unit_energy_scale(nu[0])

        if ceiling is not None:
            ok = [(n, u) for n, u in units if u.rel_err() <= ceiling]
            if ok:
                return min(ok, key=cost)[0]
            return min(units, key=lambda nu: nu[1].rel_err())[0]
        return min(units, key=cost)[0]

    # ---------------------------------------------------------- validation
    def _reject(self, req: Request, code: str, reason: str):
        req.rejected = True
        req.reject_reason = f"[{code}] {reason}"
        self.rejected.append(req)
        if self.tracer.enabled:
            now = self._clock()
            self.tracer.request_begin(req.uid, now)
            self.tracer.event(req.uid, TraceEvent.REJECT, now, code=code,
                              site=self.trace_site)
            self.tracer.end_attempt(req.uid, now, "rejected")
            self.tracer.end_request(req.uid, now, "rejected")
        raise RequestRejected(req, code, reason)

    def validate(self, req: Request) -> None:
        """Admission validation: actionable, structured errors instead of
        deep routing/scatter failures.  Raises ``RequestRejected`` (and
        records the reject) on the first violation."""
        n = req.max_new_tokens
        if not isinstance(n, (int, np.integer)) or n < 1:
            self._reject(req, "bad_max_tokens",
                         f"max_new_tokens must be a positive int, got {n!r}")
        prompt = np.asarray(req.prompt)
        if prompt.ndim != 1 or prompt.size == 0:
            self._reject(req, "bad_prompt",
                         f"prompt must be a non-empty 1-D int array, got "
                         f"shape {prompt.shape}")
        if not np.issubdtype(prompt.dtype, np.integer):
            self._reject(req, "bad_prompt",
                         f"prompt dtype must be integer, got {prompt.dtype}")
        if self._len_cap is not None and len(prompt) > self._len_cap:
            self._reject(req, "prompt_too_long",
                         f"prompt length {len(prompt)} exceeds the engine "
                         f"cache capacity {self._len_cap}")
        if req.accuracy_slo is not None and req.accuracy_slo <= 0:
            self._reject(req, "bad_accuracy_slo",
                         f"accuracy_slo must be > 0, got {req.accuracy_slo}")
        if self.chip_policy is not None:
            die = self.chip_policy.spec.units
            if req.precision is not None:
                have = sorted({u.design.precision for u in die})
                if req.precision not in have:
                    self._reject(req, "unknown_precision",
                                 f"precision {req.precision!r} is not "
                                 f"fabricated on chip "
                                 f"{self.chip_policy.spec.name!r} "
                                 f"(have {have})")
            if req.accuracy_slo is not None:
                best = min(u.rel_err() for u in die)
                if best > req.accuracy_slo:
                    self._reject(
                        req, "accuracy_slo_unmeetable",
                        f"no unit on chip {self.chip_policy.spec.name!r} "
                        f"meets accuracy_slo={req.accuracy_slo:g} (best "
                        f"achievable rel_err={best:g})")

    def submit(self, req: Request):
        self.validate(req)
        if req.submitted_s is None:  # continuations keep their origin
            req.submitted_s = self._clock()
        fleet = self._route(req)
        if self.chip_policy is not None:
            req.routed_unit = fleet
        self._queues[fleet].append(req)
        if self.tracer.enabled:
            self.tracer.request_begin(
                req.uid, req.submitted_s,
                prompt_tokens=int(np.asarray(req.prompt).size),
                max_new_tokens=req.max_new_tokens,
                precision=req.precision, accuracy_slo=req.accuracy_slo,
                deadline_s=req.deadline_s)
            self.tracer.event(req.uid, TraceEvent.ADMIT, self._clock(),
                              site=self.trace_site, fleet=fleet)

    def _bucket(self, n: int) -> int:
        if not self._bucketed:
            return n  # exact-length batching for SSM/hybrid
        return min(bucket_length(n, lo=self.min_bucket), self._len_cap) \
            if self._len_cap is not None \
            else bucket_length(n, lo=self.min_bucket)

    def _finish(self, req: Request):
        req.done = True
        self.finished.append(req)
        if self.tracer.enabled:
            now = self._clock()
            status = "expired" if req.expired else "ok"
            self.tracer.event(
                req.uid,
                TraceEvent.EXPIRE if req.expired else TraceEvent.FINISH,
                now, site=self.trace_site, tokens_out=len(req.output))
            self.tracer.end_attempt(req.uid, now, status)
            self.tracer.end_request(req.uid, now, status)

    def _expire(self, req: Request):
        req.expired = True
        self._finish(req)

    # ------------------------------------------------ drain / re-admission
    def _release_slots(self, slots: List[int]) -> None:
        """Free engine+device slot state without touching the requests."""
        tr = self.tracer
        for s in slots:
            req = self._active[s]
            if req is not None and tr.enabled:
                now = self._clock()
                tr.event(req.uid, TraceEvent.DRAIN, now,
                         site=self.trace_site, slot=s)
                tr.end_attempt(req.uid, now, "drained")
            self._active[s] = None
            self._slot_replay[s] = 0
            self._prefill_pos.pop(s, None)
        if slots:
            self._active_mask = self._active_mask.at[
                np.asarray(slots, np.int32)].set(False)

    def requeue(self, req: Request) -> str:
        """Re-admit an in-flight request as a continuation: re-routed
        (health-aware) to a surviving fleet, queued at the *front* (drained
        traffic outranks new arrivals).  On admission the new fleet
        re-prefills the prompt and *replays* the committed tokens through
        the decode path — the same computation that produced them, so the
        stream resumes bitwise-identically (re-prefilling prompt+output
        instead would cross from the decode path to the prefill path,
        whose numerics are not bitwise-equal).  With *no* fleet in service
        the request is parked (never dropped): the next admission with
        restored capacity re-routes it.  Returns the new fleet ('' when
        parked)."""
        req.requeues += 1
        try:
            fleet = self._route(req)
        except UnitFault:
            self._parked.append(req)
            if self.tracer.enabled:
                self.tracer.event(req.uid, TraceEvent.PARK, self._clock(),
                                  site=self.trace_site)
            return ""
        if self.chip_policy is not None:
            req.routed_unit = fleet
        self._queues[fleet].insert(0, req)
        if self.tracer.enabled:
            self.tracer.event(req.uid, TraceEvent.REQUEUE, self._clock(),
                              site=self.trace_site, fleet=fleet,
                              requeues=req.requeues)
        return fleet

    def set_fleet_in_service(self, name: str, in_service: bool) -> None:
        if name not in self._fleets:
            raise KeyError(f"no fleet {name!r}; have {sorted(self._fleets)}")
        if in_service:
            self._out_of_service.discard(name)
        else:
            self._out_of_service.add(name)

    def drain_fleet(self, name: str, *, requeue: bool = True
                    ) -> List[Request]:
        """Take a fleet out of service and drain it: in-flight requests on
        its slots are released (device lanes deactivated, partial energy
        kept) and — with ``requeue=True`` — re-admitted as continuations on
        the cheapest surviving fleet that still meets their
        precision/accuracy class; its queued requests are re-routed the
        same way.  ``requeue=False`` force-drains: affected requests are
        finished as expired with whatever they produced (partial output +
        partial energy).  Returns the affected requests."""
        self.set_fleet_in_service(name, False)
        affected: List[Request] = []
        released: List[int] = []
        for s in self._fleets[name]:
            req = self._active[s]
            if req is None:
                continue
            affected.append(req)
            released.append(s)
        self._release_slots(released)
        queued, self._queues[name] = self._queues[name], []
        affected.extend(queued)
        for req in affected:
            if requeue:
                self.requeue(req)
            else:
                self._expire(req)
        return affected

    def _expire_active(self, now: float):
        """Release slots whose request expired before this step — no more
        tokens are decoded or charged for them."""
        released = []
        for s, req in enumerate(self._active):
            if req is not None and req.deadline_s is not None \
                    and now > req.deadline_s:
                self._expire(req)
                self._active[s] = None
                self._prefill_pos.pop(s, None)
                released.append(s)
        if released:
            self._active_mask = self._active_mask.at[
                np.asarray(released, np.int32)].set(False)

    def idle(self) -> bool:
        """Nothing queued, parked, or seated — the drain-loop exit test."""
        return not self._parked \
            and all(not q for q in self._queues.values()) \
            and all(r is None for r in self._active)

    # ---------------------------------------------------------- admission
    def _unpark(self):
        """Re-route parked requests (drained while no fleet was in
        service) now that capacity may have returned."""
        if not self._parked:
            return
        parked, self._parked = self._parked, []
        for req in parked:
            try:
                fleet = self._route(req)
            except UnitFault:
                self._parked.append(req)
                continue
            if self.chip_policy is not None:
                req.routed_unit = fleet
            self._queues[fleet].insert(0, req)
            if self.tracer.enabled:
                self.tracer.event(req.uid, TraceEvent.UNPARK,
                                  self._clock(), site=self.trace_site,
                                  fleet=fleet)

    def _admit(self, now: float) -> int:
        """Monolithic admission: batched prefill of queued requests into
        free lanes.  Returns how many were admitted."""
        self._unpark()
        admitted = 0
        for fleet, slot_ids in self._fleets.items():
            if not self._fleet_in_service(fleet):
                continue  # the resilience layer drains/re-routes its queue
            queue = self._queues[fleet]
            while queue:
                free = [s for s in slot_ids if self._active[s] is None]
                if not free:
                    break
                # drop requests already expired before admission: zero work,
                # zero charge
                batch: List[Request] = []
                bucket = None
                i = 0
                while i < len(queue) and len(batch) < len(free):
                    req = queue[i]
                    if req.deadline_s is not None and now > req.deadline_s:
                        queue.pop(i)
                        self._expire(req)
                        continue
                    b = self._bucket(len(req.prompt))
                    if bucket is None:
                        bucket = b
                    if b == bucket:  # batched same-bucket admission
                        batch.append(queue.pop(i))
                        continue
                    i += 1
                if not batch:
                    break
                self._admit_batch(batch, free[:len(batch)], bucket)
                admitted += len(batch)
        return admitted

    def _admit_batch(self, reqs: List[Request], slot_ids: List[int],
                     bucket: int):
        M = len(reqs)
        Mb = 1
        while Mb < M:  # pow2 batch pad bounds prefill compiles at
            Mb *= 2    # O(log slots x log max_len) programs
        tokens = np.full((Mb, bucket), self.pad_id, np.int32)
        true_lens = np.ones(Mb, np.int32)
        ids = np.full(Mb, self.slots, np.int32)  # OOB pad lanes: dropped
        budgets = np.zeros(Mb, np.int32)
        # continuations (requeued mid-flight) are admitted exactly like
        # fresh requests — original prompt, full budget — and *replay*
        # their committed tokens through the decode path (see the commit
        # loop): the decode scan recomputes them bit-for-bit, so the
        # stream resumes bitwise-identically on any fleet
        prompts = [np.asarray(r.prompt) for r in reqs]
        for j, (req, p, slot) in enumerate(zip(reqs, prompts, slot_ids)):
            tokens[j, :len(p)] = p
            true_lens[j] = len(p)
            ids[j] = slot
            cap = req.max_new_tokens - 1
            if self._len_cap is not None:
                cap = min(cap, self._len_cap - len(p))
            budgets[j] = max(cap, 0)
        (self.cache, self._next_tok, self._active_mask, self._budget,
         first) = _admit_jit(
            self.model, self._ring, self.params, self.cache, self._next_tok,
            self._active_mask, self._budget, jnp.asarray(tokens),
            jnp.asarray(true_lens), jnp.asarray(ids), jnp.asarray(budgets))
        tr = self.tracer
        with (self._span("engine.sync", program="_admit_jit")
              if tr.enabled else NULL_SPAN):
            first = np.asarray(first)  # one host sync per admitted batch
        self.host_syncs += 1
        now = self._clock()
        dead = []
        for j, (req, p, slot) in enumerate(zip(reqs, prompts, slot_ids)):
            if tr.enabled:
                tr.begin_attempt(req.uid, now, site=self.trace_site,
                                 fleet=self._slot_fleet.get(slot, ""),
                                 slot=slot)
                tr.event(req.uid, TraceEvent.SEAT, now, slot=slot)
                tr.event(req.uid, TraceEvent.PREFILL, now, tokens=len(p),
                         bucket=bucket, slot=slot)
                tr.count("bucket_hit", now,
                         1.0 if bucket == len(p) else 0.0, self.trace_site)
            # the prefill charge covers the whole prompt forward pass,
            # including the logits that produce the next output token —
            # decode charges start with the first fused decode step.  A
            # requeued continuation re-prefills the prompt and re-decodes
            # its committed tokens: that repeated work IS the energy
            # overhead of degraded routing, accounted honestly.
            self._charge_unit(req, self._prefill_unit(req),
                              self.flops_per_token * len(p),
                              phase="prefill")
            self.prefill_tokens += len(p)
            self.tokens_decoded += 1
            replay = len(req.output)  # committed tokens a continuation
            if not replay:            # must replay, not re-commit
                req.output.append(int(first[j]))
                if req.first_token_s is None:
                    req.first_token_s = now
                if tr.enabled:  # the prefill logits committed one token
                    tr.event(req.uid, TraceEvent.DECODE_DISPATCH, now,
                             tokens=1, slot=slot, first=True)
            if budgets[j] == 0 or (not replay
                                   and int(first[j]) in self._stop_set):
                # token budget already met by the prefill logits (or the
                # cache is full, or the very first token is an EOS):
                # finish without occupying the slot
                self._finish(req)
                if budgets[j] > 0:
                    # _admit_jit activated the lane from its budget; a
                    # first-token EOS must also free it on device or later
                    # dispatches decode zombie tokens for a slot the host
                    # already recycled
                    dead.append(slot)
            else:
                self._active[slot] = req
                # prefill already replayed the first committed token
                self._slot_replay[slot] = max(replay - 1, 0)
                self._slot_quota[slot] = 1 + int(budgets[j])
        if dead:
            self._active_mask = self._active_mask.at[
                np.asarray(dead, np.int32)].set(False)

    # --------------------------------------- continuous batching scheduler
    def _seat(self, now: float) -> int:
        """Continuous-batching admission: move queued requests into free
        lanes *immediately* (FIFO per in-service fleet) without touching
        device state — seated lanes prefill chunk by chunk via
        ``_advance_prefills`` and only join the decode dispatch once their
        final chunk arms the slot on device.  Returns how many were
        seated."""
        self._unpark()
        seated = 0
        for fleet, slot_ids in self._fleets.items():
            if not self._fleet_in_service(fleet):
                continue
            queue = self._queues[fleet]
            free = [s for s in slot_ids if self._active[s] is None]
            while queue and free:
                req = queue.pop(0)
                if req.deadline_s is not None and now > req.deadline_s:
                    self._expire(req)  # expired in queue: zero work
                    continue
                slot = free.pop(0)
                self._active[slot] = req
                self._prefill_pos[slot] = 0
                cap = req.max_new_tokens - 1
                if self._len_cap is not None:
                    cap = min(cap, self._len_cap - len(req.prompt))
                self._slot_pf_budget[slot] = max(cap, 0)
                self._slot_quota[slot] = 1 + self._slot_pf_budget[slot]
                self._slot_replay[slot] = 0
                seated += 1
                if self.tracer.enabled:
                    self.tracer.begin_attempt(
                        req.uid, now, site=self.trace_site,
                        fleet=self._slot_fleet.get(slot, ""), slot=slot)
                    self.tracer.event(req.uid, TraceEvent.SEAT, now,
                                      slot=slot)
        return seated

    def _advance_prefills(self, now: float) -> int:
        """Advance every mid-prefill lane by one chunk (<= prefill_chunk
        tokens), grouped by padded chunk width so same-shape chunks share
        one dispatch and one compiled program.  Attention families pad the
        final partial chunk up to a pow2 bucket (exact: pads are masked out
        of every valid row's context); SSM/hybrid chunks stay exact-length
        (the conv carry integrates raw inputs, so pads would corrupt it).
        A lane whose chunk completes the prompt gets its decode slot state
        armed in the same dispatch; its first output token is committed
        here (one host sync, only on steps with finishing lanes).  Returns
        how many lanes advanced."""
        C = self.prefill_chunk
        lanes = sorted(self._prefill_pos)
        if self.prefill_token_budget is not None and lanes:
            kept, total = [], 0
            for s in lanes:  # whole chunks in lane order, always >= 1
                clen = min(C, len(self._active[s].prompt)
                           - self._prefill_pos[s])
                if kept and total + clen > self.prefill_token_budget:
                    break
                kept.append(s)
                total += clen
            lanes = kept
        groups: Dict[int, List[int]] = {}
        for s in lanes:
            clen = min(C, len(self._active[s].prompt)
                       - self._prefill_pos[s])
            cb = min(bucket_length(clen, lo=self.min_bucket), C) \
                if self._bucketed else clen
            groups.setdefault(cb, []).append(s)
        tr = self.tracer
        for cb, slots in sorted(groups.items()):
            with (self._span("engine.chunk") if tr.enabled
                  else NULL_SPAN) as span:
                self._run_chunk_group(cb, slots, now, span)
        return len(lanes)

    def _run_chunk_group(self, cb: int, slots: List[int], now: float,
                         span) -> None:
        """One ``_chunk_jit`` call over the lanes ``slots``, all at padded
        chunk width ``cb``, and the first tokens of the lanes it finishes.
        ``span`` is the recording ``engine.chunk`` span, or None."""
        C = self.prefill_chunk
        M = len(slots)
        Mb = 1
        while Mb < M:  # pow2 lane pad: chunk programs are shared
            Mb *= 2    # across prompts and steps
        tokens = np.full((Mb, cb), self.pad_id, np.int32)
        offs = np.zeros(Mb, np.int32)
        clens = np.ones(Mb, np.int32)
        ids = np.full(Mb, self.slots, np.int32)  # OOB pads: dropped
        final_ids = np.full(Mb, self.slots, np.int32)
        budgets = np.zeros(Mb, np.int32)
        finals: List[int] = []
        for j, s in enumerate(slots):
            req = self._active[s]
            p = np.asarray(req.prompt)
            off = self._prefill_pos[s]
            clen = min(C, len(p) - off)
            tokens[j, :clen] = p[off:off + clen]
            offs[j] = off
            clens[j] = clen
            ids[j] = s
            if off + clen == len(p):
                final_ids[j] = s
                budgets[j] = self._slot_pf_budget[s]
                finals.append(j)
        tr = self.tracer
        if span is not None:
            span.attrs.update(shape=[Mb, cb], finals=len(finals),
                              lanes=[[int(offs[j]), int(clens[j])]
                                     for j in range(M)])
        (self.cache, self._next_tok, self._active_mask, self._budget,
         first) = _chunk_jit(
            self.model, self.params, self.cache, self._next_tok,
            self._active_mask, self._budget, jnp.asarray(tokens),
            jnp.asarray(offs), jnp.asarray(clens), jnp.asarray(ids),
            jnp.asarray(final_ids), jnp.asarray(budgets))
        t_first = now
        if finals:
            with (self._span("engine.sync", program="_chunk_jit")
                  if tr.enabled else NULL_SPAN):
                first = np.asarray(first)  # host sync only when lanes end
            self.host_syncs += 1
            # the first tokens exist once the sync returns: stamp them
            # then, as _admit_batch does, not at the step's start
            t_first = self._clock()
        dead = []
        for j, s in enumerate(slots):
            req = self._active[s]
            clen = int(clens[j])
            self.prefill_tokens += clen
            self._charge_unit(req, self._prefill_unit(req),
                              self.flops_per_token * clen,
                              phase="prefill")
            if tr.enabled:
                tr.event(req.uid, TraceEvent.PREFILL_CHUNK, now,
                         tokens=clen, offset=int(offs[j]), slot=s)
                tr.count("bucket_hit", now,
                         1.0 if cb == clen else 0.0, self.trace_site)
            if final_ids[j] == self.slots:
                self._prefill_pos[s] = int(offs[j]) + clen
                continue
            # final chunk: the prompt's last logits just produced the
            # first output token — same commit semantics as
            # _admit_batch (replay skip, first-token EOS, zero budget)
            del self._prefill_pos[s]
            self.tokens_decoded += 1
            replay = len(req.output)
            if not replay:
                req.output.append(int(first[j]))
                if req.first_token_s is None:
                    req.first_token_s = t_first
                if tr.enabled:  # final chunk committed one token
                    tr.event(req.uid, TraceEvent.DECODE_DISPATCH, t_first,
                             tokens=1, slot=s, first=True)
            if budgets[j] == 0 or (not replay
                                   and int(first[j]) in self._stop_set):
                self._finish(req)
                self._active[s] = None
                if budgets[j] > 0:
                    dead.append(s)  # free the armed lane on device
            else:
                self._slot_replay[s] = max(replay - 1, 0)
        if dead:
            self._active_mask = self._active_mask.at[
                np.asarray(dead, np.int32)].set(False)

    @property
    def decode_stall_frac(self) -> float:
        """Fraction of contended-step token work spent on prefill: over the
        steps that performed prefill work while decode-ready lanes existed
        (measured before admission), prefill tokens processed / (prefill +
        decode tokens processed in those same steps).  A monolithic 4k
        admission makes its step almost pure prefill (frac -> 1) while the
        live decode lanes crawl; a chunked engine caps each step's prefill
        share at roughly chunk / (chunk + dispatch work).  High values mean
        prompt admission starved live decode streams — exactly the
        utilization cliff chunked prefill removes.  Clock-free and
        deterministic.  Scoped to the current run: ``run()`` resets the
        input counters at entry (``reset_run_counters``); step-driven
        callers accumulate since the last explicit reset."""
        tot = self._stall_prefill_tokens + self._contended_decode_tokens
        return self._stall_prefill_tokens / max(tot, 1)

    def _filter_dispatch(self, active_slots: List[int], toks_np: np.ndarray,
                         emitted_np: np.ndarray, now: float,
                         dispatch_dt_s: float
                         ) -> Tuple[np.ndarray, np.ndarray]:
        """Symptom hook between the device fetch and token commit.  The base
        engine is fault-free: identity.  ``ResilientServer`` overrides this
        to apply injected fault symptoms (kills, corruption, inflated
        dispatch times), feed the health monitor, and drain slots whose
        fleet just went out of service — slots it drains are skipped by the
        commit loop."""
        return toks_np, emitted_np

    def _sample_metrics(self, now: float, n_seated: int,
                        decode_lanes: int) -> None:
        """One step's gauge samples into the tracer timelines (enabled
        tracers only — ``step`` guards the call)."""
        tr = self.tracer
        site = self.trace_site
        slots = max(self.slots, 1)
        tr.count("occupancy", now, n_seated / slots, site)
        tr.count("decode_occupancy", now, decode_lanes / slots, site)
        tr.count("prefill_occupancy", now,
                 len(self._prefill_pos) / slots, site)
        queued = sum(len(q) for q in self._queues.values())
        tr.count("queued", now, float(queued), site)
        tr.count("backlog_tokens", now,
                 float(sum(len(r.prompt) + r.max_new_tokens
                           for q in self._queues.values() for r in q)),
                 site)
        tr.count("decode_stall_frac", now, self.decode_stall_frac, site)

    # ------------------------------------------------------------ decoding
    def step(self, max_tokens: Optional[int] = None) -> int:
        """One scheduler step: admission (monolithic, or chunked-prefill
        advance under continuous batching), then one fused decode dispatch
        over the decode-ready slots (up to ``max_tokens`` tokens each,
        default 1).  Returns #seated slots (mid-prefill lanes count: the
        engine is not idle while they stream)."""
        tr = self.tracer
        with (self._span("engine.step") if tr.enabled else NULL_SPAN) as span:
            return self._step(max_tokens, span)

    def _step(self, max_tokens: Optional[int], span) -> int:
        """``step``'s body; ``span`` is the recording ``engine.step`` span,
        or None."""
        now = self._clock()
        self._expire_active(now)
        # decode-ready lanes BEFORE admission: if any exist, this step is
        # contended and its prefill/decode token split feeds
        # ``decode_stall_frac``
        decode_ready = sum(1 for s, r in enumerate(self._active)
                           if r is not None and s not in self._prefill_pos)
        pf0 = self.prefill_tokens
        tr = self.tracer
        with (self._span("engine.seat") if tr.enabled else NULL_SPAN) as seat:
            if self.prefill_chunk is not None:
                seated = self._seat(now)
            else:
                seated = self._admit(now)
        if seat is not None:
            seat.attrs["seated"] = seated
        prefill_lanes = self._advance_prefills(now) \
            if self.prefill_chunk is not None else seated
        pf_delta = self.prefill_tokens - pf0
        contended = decode_ready > 0 and pf_delta > 0
        if contended:
            self._stall_prefill_tokens += pf_delta
        n_seated = sum(1 for r in self._active if r is not None)
        active_slots = [s for s, r in enumerate(self._active)
                        if r is not None and s not in self._prefill_pos]
        if tr.enabled:
            span.attrs.update(seated=n_seated, decode_lanes=len(active_slots),
                              prefill_lanes=prefill_lanes)
            self._sample_metrics(now, n_seated, len(active_slots))
        if not active_slots:
            return n_seated
        n = 1 if max_tokens is None else max(1, int(max_tokens))
        t_dispatch = time.perf_counter()
        with (self._span("engine.dispatch", n=n,
                         lanes=self._dispatch_lanes(active_slots, n))
              if tr.enabled else NULL_SPAN):
            (self.cache, self._next_tok, self._active_mask, self._budget,
             toks, emitted) = _dispatch_jit(
                self.model, self.pad_id, n, self.stop_tokens, self.params,
                self.cache, self._next_tok, self._active_mask, self._budget)
            # THE host sync: one device_get per N-token dispatch
            with (self._span("engine.sync", program="_dispatch_jit")
                  if tr.enabled else NULL_SPAN):
                toks_np, emitted_np = jax.device_get((toks, emitted))
        self.dispatches += 1
        self.host_syncs += 1
        now = self._clock()
        with (self._span("engine.commit") if tr.enabled
              else NULL_SPAN) as commit:
            decode_emitted = self._commit_dispatch(
                active_slots, toks_np, emitted_np, n, now,
                time.perf_counter() - t_dispatch)
        if commit is not None:
            commit.attrs["tokens"] = decode_emitted
        if contended:
            self._contended_decode_tokens += decode_emitted
        return n_seated

    def _dispatch_lanes(self, active_slots: List[int],
                        n: int) -> List[List[int]]:
        """Each decode lane's live work in an ``n``-token dispatch: its
        cache length and the steps it may still emit."""
        lanes = []
        for s in active_slots:
            r = self._active[s]
            steps = min(n, self._slot_quota[s] - len(r.output))
            lanes.append([len(r.prompt) + len(r.output) - 1, max(steps, 0)])
        return lanes

    def _commit_dispatch(self, active_slots: List[int], toks_np, emitted_np,
                         n: int, now: float, dispatch_dt_s: float) -> int:
        """Commit one fetched dispatch: the resilience filter, then each
        lane's tokens, energy charge and finish or expiry.  Returns the
        tokens the dispatch emitted."""
        # resilience hook: fault symptoms are applied/detected on the
        # fetched arrays before any token is committed (identity here; the
        # ResilientServer overrides it and may drain slots)
        toks_np, emitted_np = self._filter_dispatch(
            active_slots, np.asarray(toks_np), np.asarray(emitted_np), now,
            dispatch_dt_s)
        released = []
        decode_emitted = 0
        tr = self.tracer
        for slot in active_slots:
            req = self._active[slot]
            if req is None:  # drained by the resilience filter mid-dispatch
                continue
            count = int(emitted_np[:, slot].sum())
            decode_emitted += count
            if tr.enabled and count:
                tr.event(req.uid, TraceEvent.DECODE_DISPATCH, now,
                         tokens=count, slot=slot)
            for t in range(n):
                if emitted_np[t, slot]:
                    if self._slot_replay[slot]:
                        # continuation replay: the decode path just
                        # recomputed an already-committed token — skip it
                        self._slot_replay[slot] -= 1
                    else:
                        req.output.append(int(toks_np[t, slot]))
            self.tokens_decoded += count
            self._charge_unit(req, self._fleet_units.get(req.routed_unit),
                              self.flops_per_token * count)
            if count < n or len(req.output) >= self._slot_quota[slot] \
                    or (count and int(toks_np[count - 1, slot])
                        in self._stop_set):
                # budget exhausted on device, or the lane sampled an EOS
                # token (a stop in the final scan step yields count == n
                # with the lane already frozen — finish it here instead of
                # wasting a dead dispatch); quota < max_new_tokens means
                # the cache capacity truncated the request
                self._finish(req)
            if not req.done and req.deadline_s is not None \
                    and now > req.deadline_s:
                # expired during this dispatch: its tokens were decoded and
                # stay charged, but the slot is released for queued traffic
                self._expire(req)
                released.append(slot)
            if req.done:
                self._active[slot] = None
        if released:
            self._active_mask = self._active_mask.at[
                np.asarray(released, np.int32)].set(False)
        return decode_emitted

    def run(self, max_steps: int = 10_000,
            dispatch_tokens: Optional[int] = None) -> List[Request]:
        """Serve until queues and slots drain (or ``max_steps`` dispatches);
        returns the requests finished (including expired) since the last
        ``run`` call.  Per-run counters (the ``decode_stall_frac`` inputs
        and the ``run_report()`` baselines) are reset at entry so
        back-to-back runs don't leak scheduler state into each other."""
        self.reset_run_counters()
        n = self.dispatch_tokens if dispatch_tokens is None \
            else dispatch_tokens
        for _ in range(max_steps):
            if self.idle():
                break
            self.step(n)
        out, self.finished = self.finished, []
        return out


def greedy_decode(model: LM, params, prompt: np.ndarray, n_new: int,
                  max_len: Optional[int] = None,
                  stop_tokens: Tuple[int, ...] = ()) -> List[int]:
    """Single-sequence reference decoder (tests compare server vs this).

    ``stop_tokens``: EOS-class ids — decoding stops after emitting one
    (the stop token is included in the output), the semantics the fused
    ``decode_scan`` implements on device.
    """
    stops = set(int(s) for s in stop_tokens)
    max_len = max_len or (len(prompt) + n_new)
    nxt, cache = _greedy_prefill_jit(model, max_len, params,
                                     jnp.asarray(prompt[None]))
    out = [int(nxt[0])]
    for _ in range(n_new - 1):
        if out[-1] in stops:
            break
        nxt, cache = _greedy_step_jit(model, params, cache, nxt[:, None])
        out.append(int(nxt[0]))
    return out


@functools.partial(jax.jit, static_argnums=(0, 1))
def _greedy_prefill_jit(model, max_len, params, tokens):
    last, cache = model.prefill(params, tokens, max_len=max_len)
    return jnp.argmax(last, -1).astype(jnp.int32), cache


@functools.partial(jax.jit, static_argnums=(0,))
def _greedy_step_jit(model, params, cache, tok):
    logits, cache = model.decode_step(params, cache, tok)
    return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), cache
