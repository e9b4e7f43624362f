"""Asyncio continuous-batching orchestrator over the engine API seam.

The :class:`ThinKVEngine` is device-facing only (prefill / insert /
generate / free_resource — see ``serving/engine.py``); this module owns
the HOST LOOP, in the spirit of SHARK-Engine's ``BatchGenerateService``
/ ``WorkQueue``: one asyncio task drives the engine while per-request
consumers stream tokens concurrently.

OVERLAP MODEL.  Three transfers/computations overlap per tick:

  1. ``generate`` dispatches tick N and returns a ``ResultTokens`` whose
     D2H copies start immediately (``copy_to_host_async``) — the serve
     loop then parks in ``await run_in_executor(res.block)``, yielding
     the event loop;
  2. while tick N computes/transfers, CONSUMERS drain tick N-1's tokens
     from their stream queues (the ``put`` happened after tick N-1 was
     consumed, but queue waiters only get scheduled at the loop's next
     await point — which is after tick N's dispatch, so every delivery
     of tick N-1 lands INSIDE tick N's device window);
  3. admission prefills dispatch behind the in-flight work without a
     host sync (the loop yields once before each prefill so running
     requests' consumers drain first — a waiting request's prefill
     overlaps running requests' decode streams).

The interleave is observable: every submit/prefill/resume/dispatch/
consume/deliver/cancel/finish lands in ``events`` (a per-run metrics
log) with its tick index and a monotonic sequence number, and
``prefill_overlaps_decode()`` / ``stream_overlaps_dispatch()`` assert
the two overlap claims from that log — the serving-trace suite pins
both.

DECISION-ORDER EQUIVALENCE.  The loop replays the historical
synchronous ``run`` loop's decision order exactly — the same admission
sweeps, headroom checks, livelock valve, and rng split points — so a
streamed run emits bit-identical tokens/logits/audits/metrics to the
old monolithic loop on the same arrival pattern.  Per-request LOGITS
are schedule-invariant even across DIFFERENT arrival patterns
(preemption/resume is bit-exact and shared prefix blocks are
content-immutable), which is what lets the differential trace suite
compare a staggered streamed replay logit-for-logit against the batch
run.

CANCELLATION.  ``TokenStream.cancel()`` marks the stream (no further
token is ever yielded, effective immediately) and enqueues the request
for teardown at the loop's next boundary: a RUNNING request's slot is
``free_resource``'d (every pool reference released, slot reusable by
the very next admission sweep), a WAITING/PREEMPTED request leaves the
queue and ``drop_spill`` releases any shared-block references its
spill retained.  ``audit_pool`` runs after every teardown — cancelling
must never leak or double-free a block.

PACING.  Open-loop arrivals come in two flavors: ``schedule_arrival``
with ``after_tick=`` injects deterministically in TICK space (arrivals
independent of request completions — reproducible for gates/tests) and
``submit`` can be called from any concurrent task for wall-clock
arrivals.  The loop sleeps on an arrival event when idle, so a server
can keep ``serve(forever=True)`` parked between bursts.
"""
from __future__ import annotations

import asyncio
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.serving import tracing as TR
from repro.serving.scheduler import Request, RequestState

_END = object()        # stream sentinel: no further tokens


class TokenStream:
    """Per-request handle: ``async for token in stream`` + cancel.

    Returned by :meth:`Orchestrator.submit` / ``schedule_arrival``.  The
    orchestrator puts ``(tick, token)`` pairs in as they are generated;
    iteration yields bare tokens and logs a ``deliver`` event (the
    overlap witness).  After :meth:`cancel`, iteration stops immediately
    and PERMANENTLY — tokens already queued are dropped, not yielded.
    """

    def __init__(self, orch: "Orchestrator", request: Request):
        self._orch = orch
        self.request = request
        self._queue: asyncio.Queue = asyncio.Queue()
        self._done = asyncio.Event()
        self.cancelled = False

    def __aiter__(self):
        return self

    async def __anext__(self) -> int:
        if self.cancelled:
            raise StopAsyncIteration
        item = await self._queue.get()
        if item is _END or self.cancelled:
            raise StopAsyncIteration
        tick, tok = item
        self._orch._log("deliver", arrival=self.request.arrival, tick=tick)
        return tok

    def cancel(self) -> None:
        """Cancel mid-flight: never yields another token (immediate),
        releases the request's pool/queue resources at the serve loop's
        next boundary (audited)."""
        if self.request.done or self.cancelled:
            return
        self.cancelled = True
        self._orch._cancel_pending.append(self.request)
        self._queue.put_nowait(_END)      # wake any parked __anext__
        self._orch._arrival_event.set()   # wake an idle serve loop

    async def result(self) -> Request:
        """Wait for terminal state (FINISHED or CANCELLED)."""
        await self._done.wait()
        return self.request

    @property
    def metrics(self) -> Optional[Dict]:
        """Per-request timing summary (TTFT/TPOT/queue-wait); None until
        first token."""
        return self._orch.request_summary().get(self.request.arrival)


class Orchestrator:
    """Continuous-batching serve loop over one :class:`ThinKVEngine`.

    One orchestrator drives one serve episode (``engine.run()`` builds a
    fresh one per call, matching the old loop's per-call rng reset).
    Requests already sitting in the engine's scheduler — queued via
    ``engine.submit`` or left mid-flight by a previous episode — are
    adopted; they simply have no token streams attached.
    """

    def __init__(self, engine, audit_on_cancel: bool = True):
        self.engine = engine
        self.audit_on_cancel = audit_on_cancel
        self.streams: Dict[int, TokenStream] = {}     # arrival -> stream
        self._stream_of: Dict[int, TokenStream] = {}  # id(req) -> stream
        self.events: List[Dict] = []                  # the metrics log
        self.request_metrics: Dict[int, Dict] = {}    # arrival -> timings
        self._cancel_pending: List[Request] = []
        self._pending_forks: List[tuple] = []  # (parent_req, child_stream)
        self._tick_arrivals: List[tuple] = []  # (after_tick, seq, req, st)
        self._arrival_event = asyncio.Event()
        self._closed = False
        self._seq = 0
        self._rng = None
        self._t0 = None

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------

    def _make_request(self, prompt, max_new_tokens, eos_token, priority,
                      uid) -> TokenStream:
        req = Request(uid=self._seq if uid is None else uid,
                      prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=max_new_tokens, eos_token=eos_token,
                      priority=priority)
        self._seq += 1
        stream = TokenStream(self, req)
        stream.forks = []          # child streams (samples_per_slot > 1)
        self._stream_of[id(req)] = stream
        return stream

    def _attach_forks(self, stream: TokenStream,
                      samples_per_slot: int) -> None:
        """Create ``samples_per_slot - 1`` fork-child streams sharing the
        parent's prompt/limits.  Children never pass through the
        admission queue: once the parent is mid-decode and a slot is
        free, the engine COW-forks the parent's cache into the child's
        slot (:meth:`_try_forks`) and the child diverges from there —
        best-of-n over a shared prompt + chain-of-thought prefix."""
        req = stream.request
        for _ in range(max(0, int(samples_per_slot) - 1)):
            stream.forks.append(self._make_request(
                req.prompt, req.max_new_tokens, req.eos_token,
                req.priority, None))

    def _submit_now(self, stream: TokenStream) -> None:
        eng = self.engine
        req = stream.request
        eng.scheduler.submit(req)
        eng._queued_at[req.arrival] = eng.metrics["ticks"]
        self.streams[req.arrival] = stream
        self.request_metrics[req.arrival] = self._fresh_metrics()
        self._log("submit", arrival=req.arrival)
        # stamp fork children NOW, in submission order: the stamp seeds
        # each child's private sampling stream, so stamping at fork-LAND
        # time would make sampled tokens depend on when a slot freed up
        for child in stream.forks:
            creq = child.request
            eng.scheduler.stamp(creq)
            eng._queued_at[creq.arrival] = eng.metrics["ticks"]
            self.streams[creq.arrival] = child
            self.request_metrics[creq.arrival] = self._fresh_metrics()
            self._log("submit", arrival=creq.arrival,
                      fork_of=req.arrival)
            self._pending_forks.append((req, child))
        self._arrival_event.set()

    def _fresh_metrics(self) -> Dict:
        return {
            "submit_wall": time.perf_counter(),
            "submit_tick": int(self.engine.metrics["ticks"]),
            "admit_wall": None, "admit_tick": None,
            "first_token_wall": None, "first_token_tick": None,
            "last_token_wall": None, "tokens": 0, "token_ticks": []}

    def submit(self, prompt, max_new_tokens: int = 256,
               eos_token: Optional[int] = None, priority: int = 0,
               uid: Optional[int] = None,
               samples_per_slot: int = 1) -> TokenStream:
        """Submit one request now; returns its :class:`TokenStream`.
        Callable before ``serve`` starts or from any concurrent task
        while it runs (wall-clock open-loop arrivals).
        ``samples_per_slot=n`` attaches ``n - 1`` COW-forked sibling
        streams (``stream.forks``) sharing the prompt + CoT prefix."""
        stream = self._make_request(prompt, max_new_tokens, eos_token,
                                    priority, uid)
        self._attach_forks(stream, samples_per_slot)
        self._submit_now(stream)
        return stream

    def schedule_arrival(self, after_tick: int, prompt,
                         max_new_tokens: int = 256,
                         eos_token: Optional[int] = None,
                         priority: int = 0,
                         uid: Optional[int] = None,
                         samples_per_slot: int = 1) -> TokenStream:
        """Deterministic open-loop arrival: the serve loop itself submits
        the request once ``after_tick`` engine ticks have completed
        (tick-space pacing — independent of request completions and
        reproducible across runs/hosts, unlike wall-clock timers).  The
        stream handle is live immediately; it just yields nothing until
        the request lands."""
        stream = self._make_request(prompt, max_new_tokens, eos_token,
                                    priority, uid)
        self._attach_forks(stream, samples_per_slot)
        self._tick_arrivals.append((int(after_tick), len(self._tick_arrivals),
                                    stream))
        self._tick_arrivals.sort(key=lambda t: (t[0], t[1]))
        return stream

    def close(self) -> None:
        """No further external ``submit`` calls: ``serve`` returns once
        the queue drains (scheduled tick-arrivals still inject)."""
        self._closed = True
        self._arrival_event.set()

    # ------------------------------------------------------------------
    # the serve loop
    # ------------------------------------------------------------------

    def run_sync(self, max_ticks: int = 10_000) -> List[Request]:
        """Synchronous episode: serve everything already submitted (the
        ``engine.run()`` compatibility path).  Callable from inside a
        running event loop too (an async caller driving the sync
        wrapper): the episode then runs on a private loop in a worker
        thread, blocking the caller — the engine is not thread-safe, so
        the two loops must never drive it concurrently."""
        self.close()
        try:
            asyncio.get_running_loop()
        except RuntimeError:
            return asyncio.run(self.serve(max_ticks=max_ticks))
        import concurrent.futures
        with concurrent.futures.ThreadPoolExecutor(1) as ex:
            return ex.submit(
                asyncio.run, self.serve(max_ticks=max_ticks)).result()

    async def serve(self, max_ticks: int = 10_000) -> List[Request]:
        """Drive the engine until the queue drains (after :meth:`close`)
        or ``max_ticks`` loop iterations ran.  Returns finished requests.

        Mirrors the historical synchronous loop's decision order exactly:
        one admission sweep up front, then per iteration — cancellation
        boundary, headroom, tick dispatch, (overlapped) consume, token
        fan-out, admission sweep."""
        eng = self.engine
        sch = eng.scheduler
        self._rng = jax.random.PRNGKey(eng.cfg.seed)
        self._t0 = time.perf_counter()
        self._adopt_existing()
        self._inject_due_arrivals()
        self._process_cancellations()
        await self._admit_and_prefill()
        iters = 0
        while iters < max_ticks:
            self._inject_due_arrivals()
            self._process_cancellations()
            if not sch.busy():
                if self._pending_forks:
                    # idle with only fork children left: their parents
                    # are terminal, so land the prefill fallbacks now
                    self._try_forks()
                    if sch.busy():
                        continue
                if self._tick_arrivals:
                    # idle with only tick-scheduled arrivals left: ticks
                    # cannot advance, so inject the earliest batch now
                    self._inject_due_arrivals(force_next=True)
                    continue
                if self._closed:
                    break
                await self._wait_for_arrival()
                continue
            iters += 1
            with jax.profiler.StepTraceAnnotation(
                    TR.STEP, step_num=int(eng.metrics["ticks"]) + 1):
                await self._step()
        self._drain_retrace_events()   # events from trailing prefills
        eng.metrics["wall_s"] = time.perf_counter() - self._t0
        return sch.finished

    async def _step(self) -> None:
        """One iteration of the serve loop with work queued or running:
        admit into an empty batch, or dispatch a tick, wait for it,
        consume it, fan its tokens out and run an admission sweep."""
        eng = self.engine
        sch = eng.scheduler
        if not any(not s.free for s in sch.slots):
            await self._admit_and_prefill()
            if sch.queue and not any(not s.free for s in sch.slots):
                # last resort before declaring livelock: unpin spilled
                # requests' retained shared references (blocks co-held
                # by cache entries + spills deadlock decay against
                # preemption) and retry admission once
                if eng._demote_spilled_shared():
                    await self._admit_and_prefill()
            if sch.queue and not any(not s.free for s in sch.slots):
                # nothing running means every claimed block is pinned by
                # cache entries/spills the decay valve could not
                # release, and the watermark still refuses every queued
                # request; with no in-flight request the pool can never
                # change, so admission can never succeed and nothing is
                # preemptible — fail loudly instead of spinning
                # max_ticks and dropping requests
                raise RuntimeError(
                    f"admission livelock: {len(sch.queue)} queued "
                    f"request(s), nothing running or preemptible, and "
                    f"the global pool ({eng.num_pool_blocks} blocks) "
                    f"is below the smallest request's watermark "
                    f"estimate — the pool cannot serve even one "
                    f"request")
            return
        with TR.span(TR.DISPATCH):
            res, self._rng = eng.generate(self._rng)
        if res is None:
            return           # headroom preempted everything this round
        self._log("dispatch", tick=res.tick)
        # park off-thread while the tick computes + D2H copies land;
        # consumers woken by the previous iteration's puts run NOW, so
        # tick N-1's deliveries land inside tick N's window
        with TR.span(TR.WAIT):
            await asyncio.get_running_loop().run_in_executor(None,
                                                             res.block)
        eng.consume(res)
        self._log("consume", tick=res.tick)
        self._drain_retrace_events()
        with TR.span(TR.DELIVER):
            if getattr(res, "packed", False):
                # drain the multi-tick pack trip by trip — fan-out order
                # (and retirement timing) identical to trips separate
                # single-tick results; finished slots fall out of
                # active_slots() for the remaining trips
                toks, valid = res.tokens_host, res.valid_host
                logits = res.logits_host
                for t in range(res.trips_host):
                    tick_t = res.base_tick + t + 1
                    for slot in sch.active_slots():
                        if valid[t][slot.idx]:
                            self._record_logits(slot.request,
                                                logits[t][slot.idx])
                            self._finish_token(
                                slot, int(toks[t][slot.idx]), tick_t)
            else:
                toks, logits = res.tokens_host, res.logits_host
                for slot in sch.active_slots():
                    self._record_logits(slot.request, logits[slot.idx])
                    self._finish_token(slot, int(toks[slot.idx]),
                                       res.tick)
        await self._admit_and_prefill()

    async def _wait_for_arrival(self) -> None:
        self._arrival_event.clear()
        # re-check under the cleared flag: a submit/cancel between the
        # busy check and the clear would otherwise be missed
        if self.engine.scheduler.busy() or self._cancel_pending \
                or self._closed:
            return
        await self._arrival_event.wait()

    # ------------------------------------------------------------------
    # admission (mirrors the old loop's admit_and_prefill exactly)
    # ------------------------------------------------------------------

    def _try_forks(self) -> None:
        """Land pending ``samples_per_slot`` fork children.

        A child lands as soon as its parent is mid-decode (at least one
        token generated — there must be state to fork) AND a slot is
        free: the engine COW-forks the parent's cache/table into the
        slot (``fork_slot`` — refcount++, zero plane copies) and the
        child is placed mid-decode, inheriting the parent's emitted
        tokens.  Runs BEFORE each admission sweep, so a freed slot goes
        to a waiting fork ahead of the queue.  If the parent reached a
        terminal state first, the child falls back to a fresh prefill of
        the shared prompt through the normal queue (same greedy tokens,
        just without the shared-cache saving)."""
        eng = self.engine
        sch = eng.scheduler
        if not self._pending_forks:
            return
        still = []
        for parent_req, child_stream in self._pending_forks:
            child = child_stream.request
            if child_stream.cancelled or child.done:
                continue
            if parent_req.state in (RequestState.FINISHED,
                                    RequestState.CANCELLED):
                sch.enqueue_stamped(child)
                self._log("fork_fallback", arrival=child.arrival)
                continue
            pslot = next((s for s in sch.slots
                          if s.request is parent_req), None)
            if pslot is None or eng._slot_ntok[pslot.idx] == 0:
                still.append((parent_req, child_stream))
                continue        # parent queued/preempted or not started
            slot = next((s for s in sch.slots if s.free), None)
            if slot is None:
                still.append((parent_req, child_stream))
                continue
            eng.fork_slot(pslot.idx, slot.idx, child.arrival)
            sch.place(child, slot, tokens_out=pslot.tokens_out)
            child.output = list(parent_req.output)
            # the inherited prefix is part of the child's emitted
            # sequence: deliver it through the stream (and timing
            # metrics) at the fork tick, exactly once
            now = time.perf_counter()
            tick = eng.metrics["ticks"]
            rm = self.request_metrics.get(child.arrival)
            stream = self.streams.get(child.arrival)
            for tok in child.output:
                if rm is not None:
                    rm["tokens"] += 1
                    rm["token_ticks"].append(tick)
                    rm["last_token_wall"] = now
                    if rm["first_token_wall"] is None:
                        rm["first_token_wall"] = now
                        rm["first_token_tick"] = tick
                if stream is not None and not stream.cancelled:
                    stream._queue.put_nowait((tick, tok))
            eng.metrics["admissions"] += 1
            eng.metrics["queue_wait_ticks"] += \
                eng.metrics["ticks"] - eng._queued_at.pop(
                    child.arrival, eng.metrics["ticks"])
            self._mark_admitted(child)
            self._log("fork", arrival=child.arrival,
                      parent=parent_req.arrival,
                      at_tokens=int(pslot.tokens_out))
        self._pending_forks = still

    async def _admit_and_prefill(self) -> None:
        eng = self.engine
        sch = eng.scheduler
        with TR.span(TR.ADMIT):
            self._try_forks()
            # keep admitting while prefill can immediately retire requests
            while True:
                if not sch.queue or all(not s.free for s in sch.slots):
                    break       # gate construction syncs device state —
                                # skip it on the steady-state hot path
                newly = sch.admit(eng._admission_gate())
                if not newly:
                    break
                for slot in newly:
                    req = slot.request
                    if req is None:
                        continue    # vacated mid-sweep (defensive; started
                                    # slots only — pending ones can't be
                                    # victims, see _victim_exclude)
                    eng.metrics["admissions"] += 1
                    eng.metrics["queue_wait_ticks"] += \
                        eng.metrics["ticks"] - eng._queued_at.pop(
                            req.arrival, eng.metrics["ticks"])
                    self._mark_admitted(req)
                    st = eng._spilled.pop(req.arrival, None)
                    if st is not None:
                        self._log("resume", arrival=req.arrival)
                        if not eng._resume(slot, st):
                            # an earlier admission this sweep overclaimed
                            # past its estimate: re-spill, re-queue, and
                            # let the next sweep's gate see true counts
                            eng._spilled[req.arrival] = st
                            sch.preempt(slot)
                            eng._queued_at[req.arrival] = eng.metrics["ticks"]
                        continue
                    # yield once so running requests' consumers drain while
                    # this prefill dispatches (prefill overlaps decode)
                    await asyncio.sleep(0)
                    self._log("prefill", arrival=req.arrival,
                              decoding=sum(1 for s in sch.active_slots()
                                           if s is not slot
                                           and s.tokens_out > 0))
                    prefix, self._rng = eng.prefill(req.prompt, slot.idx,
                                                    self._rng,
                                                    arrival=req.arrival)
                    eng.insert(prefix, slot.idx)
                    self._record_logits(req, prefix.logits)
                    self._finish_token(slot, prefix.first_token,
                                       int(eng.metrics["ticks"]))
            self._try_forks()

    def _adopt_existing(self) -> None:
        """Requests submitted straight to the engine (``engine.submit``)
        or left mid-flight by a previous episode get metrics entries so
        token bookkeeping works; they have no streams attached."""
        eng = self.engine
        now = time.perf_counter()
        reqs = list(eng.scheduler.queue) + \
            [s.request for s in eng.scheduler.active_slots()]
        for req in reqs:
            self.request_metrics.setdefault(req.arrival, {
                "submit_wall": now,
                "submit_tick": int(eng.metrics["ticks"]),
                "admit_wall": None, "admit_tick": None,
                "first_token_wall": None, "first_token_tick": None,
                "last_token_wall": None, "tokens": 0, "token_ticks": []})

    def _inject_due_arrivals(self, force_next: bool = False) -> None:
        eng = self.engine
        due = [t for t in self._tick_arrivals
               if t[0] <= eng.metrics["ticks"]]
        if not due and force_next and self._tick_arrivals:
            due = [self._tick_arrivals[0]]
        for entry in due:
            self._tick_arrivals.remove(entry)
            stream = entry[2]
            if stream.cancelled:
                continue        # cancelled before it ever arrived
            self._submit_now(stream)

    # ------------------------------------------------------------------
    # per-token bookkeeping + streaming fan-out
    # ------------------------------------------------------------------

    def _finish_token(self, slot, tok: int, tick: int) -> bool:
        """Book-keeping for one generated token; returns done.  (The
        historical ``engine._finish_token``, plus stream delivery and
        per-request timing.)"""
        eng = self.engine
        req = slot.request
        req.output.append(tok)
        slot.tokens_out += 1
        eng._feed[slot.idx] = tok
        now = time.perf_counter()
        rm = self.request_metrics.get(req.arrival)
        if rm is not None:
            rm["tokens"] += 1
            rm["token_ticks"].append(tick)
            rm["last_token_wall"] = now
            if rm["first_token_wall"] is None:
                rm["first_token_wall"] = now
                rm["first_token_tick"] = tick
        stream = self.streams.get(req.arrival)
        if stream is not None and not stream.cancelled:
            stream._queue.put_nowait((tick, tok))
        done = slot.tokens_out >= req.max_new_tokens or \
            (req.eos_token is not None and tok == req.eos_token)
        if done:
            req.stats = eng.slot_stats(slot.idx)
            req.stats["preemptions"] = req.preemptions
            if getattr(eng, "drift_probe", False):
                # quality telemetry: replay the finished request through
                # the uncompressed dense forward and compare against the
                # serving-path logits recorded tick by tick
                drift = eng.measure_drift(
                    req.prompt, req.output,
                    eng.request_logits.get(req.arrival, []))
                req.stats["drift"] = drift
                self._log("drift", arrival=req.arrival, tick=tick, **drift)
            eng.scheduler.retire(slot)
            eng.free_resource(slot.idx)
            self._log("finish", arrival=req.arrival, tick=tick)
            if stream is not None:
                stream._queue.put_nowait(_END)
                stream._done.set()
        return done

    def _record_logits(self, req, logits) -> None:
        if self.engine.record_logits:
            self.engine.request_logits.setdefault(
                req.arrival, []).append(np.asarray(logits))

    def _mark_admitted(self, req) -> None:
        rm = self.request_metrics.get(req.arrival)
        if rm is not None and rm["admit_wall"] is None:
            rm["admit_wall"] = time.perf_counter()
            rm["admit_tick"] = int(self.engine.metrics["ticks"])

    # ------------------------------------------------------------------
    # cancellation teardown (audited)
    # ------------------------------------------------------------------

    def cancel_request(self, req: Request) -> None:
        """Queue a request for teardown at the next loop boundary — the
        streamless spelling of :meth:`TokenStream.cancel` (adopted
        requests, server-side disconnect handling)."""
        stream = self.streams.get(req.arrival)
        if stream is not None:
            stream.cancel()
            return
        if not req.done:
            self._cancel_pending.append(req)
            self._arrival_event.set()

    def _process_cancellations(self) -> None:
        eng = self.engine
        sch = eng.scheduler
        pending, self._cancel_pending = self._cancel_pending, []
        for req in pending:
            if req.done or req.state is RequestState.FINISHED:
                continue
            self._log("cancel", arrival=req.arrival)
            if req.state is RequestState.RUNNING:
                slot = next(s for s in sch.slots if s.request is req)
                sch.vacate(slot)
                eng.free_resource(slot.idx)    # slot reusable next sweep
            else:          # WAITING or PREEMPTED (or never arrived)
                sch.cancel(req)
                eng.drop_spill(req.arrival)    # retained shared refs
                req.state = RequestState.CANCELLED
                req.done = True
            eng._queued_at.pop(req.arrival, None)
            eng.metrics["cancellations"] += 1
            stream = self._stream_of.get(id(req))
            if stream is not None:
                stream.cancelled = True
                stream._queue.put_nowait(_END)
                stream._done.set()
            if self.audit_on_cancel:
                # teardown must leave claimed + free == pool_blocks with
                # no orphaned refcounts — raises on any leak
                eng.audit_pool()

    # ------------------------------------------------------------------
    # metrics log + derived summaries
    # ------------------------------------------------------------------

    def _log(self, kind: str, **kw) -> None:
        self.events.append({
            "seq": len(self.events), "kind": kind,
            "tick": kw.pop("tick", int(self.engine.metrics["ticks"])),
            "wall": time.perf_counter() - (self._t0 or time.perf_counter()),
            **kw})

    def _drain_retrace_events(self) -> None:
        """Fold ``analysis.RetraceGuard`` events into the metrics log.

        With a guard installed on the engine
        (``RetraceGuard(engine).install()``), every retrace an entry
        point performs mid-stream lands here as a ``kind="retrace"``
        event — steady-state serving must log NONE after warmup (the
        ``launch/audit.py --retrace`` gate and
        ``tests/test_analysis.py`` assert exactly that)."""
        guard = getattr(self.engine, "_retrace_guard", None)
        if guard is None:
            return
        for ev in guard.drain_new_events():
            self._log("retrace", entry=ev.entry,
                      call_index=ev.call_index, steady=ev.steady)

    def request_summary(self) -> Dict[int, Dict]:
        """Per-request {ttft_s, ttft_ticks, tpot_s, queue_wait_*, tokens}
        keyed by arrival stamp (completed first token only)."""
        out = {}
        for arrival, rm in self.request_metrics.items():
            if rm["first_token_wall"] is None:
                continue
            n = rm["tokens"]
            span = rm["last_token_wall"] - rm["first_token_wall"]
            out[arrival] = {
                "ttft_s": rm["first_token_wall"] - rm["submit_wall"],
                "ttft_ticks": rm["first_token_tick"] - rm["submit_tick"],
                "tpot_s": span / (n - 1) if n > 1 else 0.0,
                "queue_wait_s": (rm["admit_wall"] - rm["submit_wall"])
                if rm["admit_wall"] is not None else None,
                "queue_wait_ticks": (rm["admit_tick"] - rm["submit_tick"])
                if rm["admit_tick"] is not None else None,
                "tokens": n,
            }
        return out

    def percentiles(self, keys=("ttft_s", "tpot_s", "queue_wait_ticks"),
                    qs=(50, 99)) -> Dict[str, Dict[str, float]]:
        """p50/p99 over completed requests for the given summary keys."""
        summaries = list(self.request_summary().values())
        out = {}
        for key in keys:
            vals = [s[key] for s in summaries if s.get(key) is not None]
            if vals:
                out[key] = {f"p{q}": float(np.percentile(vals, q))
                            for q in qs}
        return out

    def prefill_overlaps_decode(self) -> bool:
        """True iff the log shows a waiting request's prefill landing
        strictly INSIDE another request's decode window: some other
        request generated tokens both at-or-before and after the prefill
        event's tick (it was mid-decode while the prefill ran)."""
        for ev in self.events:
            if ev["kind"] != "prefill":
                continue
            for arrival, rm in self.request_metrics.items():
                if arrival == ev.get("arrival"):
                    continue
                ticks = rm["token_ticks"]
                if any(t <= ev["tick"] for t in ticks) and \
                        any(t > ev["tick"] for t in ticks):
                    return True
        return False

    def stream_overlaps_dispatch(self) -> bool:
        """True iff some tick-N token was DELIVERED to a consumer after
        tick N+1 was dispatched but before it was consumed — i.e. token
        streaming genuinely overlapped the next device tick (the event
        log is totally ordered by ``seq``; the loop is single-threaded,
        so this ordering is exact, not racy)."""
        windows = {}           # tick -> (dispatch_seq, consume_seq)
        for ev in self.events:
            if ev["kind"] == "dispatch":
                windows[ev["tick"]] = [ev["seq"], None]
            elif ev["kind"] == "consume" and ev["tick"] in windows:
                windows[ev["tick"]][1] = ev["seq"]
        for ev in self.events:
            if ev["kind"] != "deliver":
                continue
            nxt = windows.get(ev["tick"] + 1)
            if nxt and nxt[1] is not None and nxt[0] < ev["seq"] < nxt[1]:
                return True
        return False
