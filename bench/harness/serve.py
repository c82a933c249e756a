"""Build a cell's serving stack, warm up its programs, drive its window.

The stack is the served path users run: ``BatchedServer`` with chunked
prefill and fused multi-token decode under the fabricated FPMax die's
``ChipPolicy`` (one fleet, the per-dispatch energy charge on the path).
The window calls ``step()`` of the server and stamps each committed token
on the wall clock when ``step()`` returns.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness.traffic import possible_lengths


class CompileCounter:
    """Counts XLA backend compiles and their seconds, process-wide (the
    arithmetic of chip_smoke.CompileCounter)."""

    def __init__(self):
        from jax._src import dispatch
        self.n, self.seconds = 0, 0.0
        event = dispatch.BACKEND_COMPILE_EVENT

        def listen(name, secs, **_):
            if name == event:
                self.n += 1
                self.seconds += secs

        jax.monitoring.register_event_duration_secs_listener(listen)


@dataclasses.dataclass
class Stack:
    target: object            # the BatchedServer
    servers: List[object]     # [target]: the servers whose programs run
    dispatch_tokens: int


def build(model, params, engine: dict, devices, tech, tracer=None) -> Stack:
    """The cell's server, on the device that holds ``params``."""
    from repro.core.chip import ChipPolicy, fabricated_chip
    from repro.serve.engine import BatchedServer

    die = fabricated_chip(engine["chip_precision"], tech)
    srv = BatchedServer(model, params, chip_policy=ChipPolicy(die, tech),
                        tracer=tracer, slots=engine["slots"],
                        max_len=engine["max_len"],
                        prefill_chunk=engine["prefill_chunk"],
                        dispatch_tokens=engine["dispatch_tokens"],
                        clock=time.perf_counter)
    return Stack(srv, [srv], engine["dispatch_tokens"])


def chunk_shapes(server, mix: dict):
    """Every (lane pad, chunk width) prefill-chunk program the mix's prompt
    lengths can produce on ``server``: widths are the prompt's last partial
    chunk or a whole chunk (SSM and hybrid chunks run at exact lengths);
    lanes of one width are at most the slots, padded to a power of two."""
    C = server.prefill_chunk
    lengths = possible_lengths(mix["prompt"])
    widths = sorted({int((n - 1) % C) + 1 for n in lengths}
                    | ({C} if lengths.max() > C else set()))
    out = []
    for w in widths:
        m = 1
        while True:
            out.append((m, w))
            if m >= server.slots:
                break
            m *= 2
    return out


def warm_up(stack: Stack, mix: dict) -> int:
    """Run each program the window can call once, with every lane parked
    (out-of-range slot ids, no active lane), so that no state changes.
    Returns the number of programs run."""
    from repro.serve import engine as eng
    n = 0
    for srv in stack.servers:
        slots = srv.slots
        # the engine makes its slot state uncommitted, and its programs'
        # outputs are committed: commit the state first, or the warm-up
        # compiles programs for arguments the window never passes
        (dev,) = jax.tree.leaves(srv.params)[0].devices()
        (srv.cache, srv._next_tok, srv._active_mask,
         srv._budget) = jax.device_put(
            (srv.cache, srv._next_tok, srv._active_mask, srv._budget), dev)
        for m, w in chunk_shapes(srv, mix):
            pad = np.full(m, slots, np.int32)
            (srv.cache, srv._next_tok, srv._active_mask, srv._budget,
             first) = eng._chunk_jit(
                srv.model, srv.params, srv.cache, srv._next_tok,
                srv._active_mask, srv._budget,
                jnp.asarray(np.zeros((m, w), np.int32)),
                jnp.asarray(np.zeros(m, np.int32)),
                jnp.asarray(np.ones(m, np.int32)), jnp.asarray(pad),
                jnp.asarray(pad), jnp.asarray(np.zeros(m, np.int32)))
            np.asarray(first)
            n += 1
        (srv.cache, srv._next_tok, srv._active_mask, srv._budget, toks,
         emitted) = eng._dispatch_jit(
            srv.model, srv.pad_id, stack.dispatch_tokens, srv.stop_tokens,
            srv.params, srv.cache, srv._next_tok, srv._active_mask,
            srv._budget)
        jax.device_get((toks, emitted))
        n += 1
    jax.block_until_ready([s.cache for s in stack.servers])
    return n


# ---------------------------------------------------------------------------
# call records: the host's view of each step program, for the trace
# ---------------------------------------------------------------------------
class CallLog:
    """While ``on``, records every ``_dispatch_jit`` and ``_chunk_jit``
    call the engine makes: its device and the live work of its lanes
    (decode: each lane's cache length and steps; chunk: each lane's offset
    and chunk length).  Installed by wrapping the engine module's two
    jitted functions; the wrapper reads only host state, plus the chunk
    call's small lane arrays."""

    def __init__(self, servers):
        from repro.serve import engine as eng
        self.eng = eng
        self.servers = servers
        self.on = False
        self.calls: List[dict] = []
        self._orig = (eng._dispatch_jit, eng._chunk_jit)
        eng._dispatch_jit = self._dispatch
        eng._chunk_jit = self._chunk

    def close(self):
        self.eng._dispatch_jit, self.eng._chunk_jit = self._orig

    def _server(self, cache):
        for s in self.servers:
            if s.cache is cache:
                return s
        return None

    @staticmethod
    def _device(srv):
        (dev,) = jax.tree.leaves(srv.params)[0].devices()
        return dev.id

    def _dispatch(self, model, pad_id, n, stops, params, cache, *rest):
        srv = self._server(cache)
        if self.on and srv is not None:
            lanes = []
            for s, r in enumerate(srv._active):
                if r is None or s in srv._prefill_pos:
                    continue
                live = len(r.prompt) + len(r.output) - 1
                steps = min(n, srv._slot_quota[s] - len(r.output))
                lanes.append((live, max(steps, 0)))
            self.calls.append(dict(kind="dispatch", device=self._device(srv),
                                   t=time.perf_counter(), n=n, lanes=lanes))
        return self._orig[0](model, pad_id, n, stops, params, cache, *rest)

    def _chunk(self, model, params, cache, next_tok, active, budget, tokens,
               offsets, chunk_lens, slot_ids, final_ids, budgets):
        srv = self._server(cache)
        if self.on and srv is not None:
            offs, clens, ids = (np.asarray(a) for a in
                                (offsets, chunk_lens, slot_ids))
            lanes = [(int(o), int(c)) for o, c, i in zip(offs, clens, ids)
                     if i < srv.slots]
            self.calls.append(dict(kind="chunk", device=self._device(srv),
                                   t=time.perf_counter(),
                                   shape=tuple(tokens.shape), lanes=lanes))
        return self._orig[1](model, params, cache, next_tok, active, budget,
                             tokens, offsets, chunk_lens, slot_ids,
                             final_ids, budgets)


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Served:
    uid: int
    arrival_s: float          # scheduled, seconds after the window opened
    submitted_s: float        # when submit() was called
    max_new_tokens: int
    req: object               # the engine's Request
    first_s: Optional[float] = None
    last_s: Optional[float] = None
    n: int = 0                # tokens committed so far
    done: bool = False
    rejected: bool = False


@dataclasses.dataclass
class Window:
    served: List[Served]
    window_s: float           # measured window: open to close
    tokens_in_window: int     # output tokens committed inside it
    drained: bool             # every in-window request finished
    steps: int
    t0: float                 # perf_counter() when the window opened


@contextlib.contextmanager
def no_gc():
    """No garbage collection inside: a pass of the collector stalls the
    host at a moment that differs from run to run, and a step that ends
    later seats a later arrival with it, which changes every step after."""
    gc.collect()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


def _span(annotate: bool, name: str):
    if annotate:
        return jax.profiler.TraceAnnotation(name)
    return contextlib.nullcontext()


def drive(stack: Stack, offers, *, seconds: float, drain_s: float,
          annotate: bool = False, on_open=None, on_close=None) -> Window:
    """Offer ``offers`` to the stack and step it, open loop.

    Each offer is submitted once its scheduled time has come (arrivals stop
    when the window closes), then the stack is stepped until every
    submitted request has finished, or ``drain_s`` has passed.  ``on_open`` and
    ``on_close`` are called as the window opens and closes (with every step
    it started returned): the traced run starts and stops the profiler
    there, and ``annotate`` marks the window and the host's work in it for
    the trace (``bench.window``, ``bench.step`` ...).
    """
    from repro.serve.engine import Request, RequestRejected

    target = stack.target
    n_tok = stack.dispatch_tokens
    served: List[Served] = []
    live: Dict[int, Served] = {}
    clock = time.perf_counter

    def submit(i, off, t_sub):
        req = Request(uid=i, prompt=off.prompt,
                      max_new_tokens=off.max_new_tokens)
        rec = Served(i, off.at_s, t_sub, off.max_new_tokens, req)
        served.append(rec)
        try:
            target.submit(req)
            live[i] = rec
        except RequestRejected:
            rec.rejected = True

    pending = list(offers)
    nxt = 0
    if on_open is not None:
        on_open()
    mark = _span(annotate, "bench.window")
    mark.__enter__()
    t0 = clock()
    window_end = None
    tokens_in_window = 0
    steps = 0

    def close(t):
        mark.__exit__(None, None, None)
        if on_close is not None:
            on_close()
        return t

    while True:
        now = clock() - t0
        if window_end is None and now >= seconds:
            window_end = close(now)
        if window_end is not None and (not live or now > seconds + drain_s):
            break
        if window_end is None and nxt < len(pending) \
                and pending[nxt].at_s <= now:
            with _span(annotate, "bench.submit"):
                while nxt < len(pending) and pending[nxt].at_s <= now:
                    submit(nxt, pending[nxt], now)
                    nxt += 1
        if target.idle():
            if window_end is not None:
                break
            # nothing to serve: wait for the next arrival or the close
            until = pending[nxt].at_s if nxt < len(pending) else seconds
            with _span(annotate, "bench.wait"):
                time.sleep(max(min(until, seconds) - (clock() - t0), 0.0))
            continue
        with _span(annotate, "bench.step"):
            target.step(n_tok)
        t = clock() - t0
        steps += 1
        with _span(annotate, "bench.commit"):
            for uid in list(live):
                rec = live[uid]
                n = len(rec.req.output)
                if n > rec.n:
                    if rec.first_s is None:
                        rec.first_s = t
                    rec.last_s = t
                    if window_end is None:
                        tokens_in_window += n - rec.n
                    rec.n = n
                if rec.req.done:
                    rec.done = True
                    del live[uid]
    target.finished = []
    in_window = [r for r in served if r.arrival_s < seconds]
    drained = all(r.done or r.rejected for r in in_window)
    return Window(served, window_end, tokens_in_window, drained, steps, t0)

