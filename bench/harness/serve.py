"""Drive ``Orchestrator.serve()`` with clients on a wall-clock schedule.

One asyncio loop holds the orchestrator's serve task, one client task per
request (sleep until due, submit, stamp every token its stream yields) and
the run's own control flow: priming, the measured window, and the close.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Any, Callable, Dict, Optional

from harness.timeline import Record

#: Host spans around the engine's calls, written into the profiler's
#: trace in a traced run (they cost a few microseconds when it is off).
SPANS = {"generate": "engine.generate", "consume": "engine.consume",
         "prefill": "engine.prefill"}


def install_spans(eng) -> None:
    """Wrap the engine's calls in ``jax.profiler.TraceAnnotation``s."""
    import jax

    def wrap(name, fn):
        def wrapped(*a, **kw):
            with jax.profiler.TraceAnnotation(name):
                out = fn(*a, **kw)
            if name == "engine.generate" and out[0] is not None:
                res, block = out[0], out[0].block

                def waited():
                    with jax.profiler.TraceAnnotation("result.wait"):
                        return block()
                res.block = waited
            return out
        return wrapped

    for attr, name in SPANS.items():
        setattr(eng, attr, wrap(name, getattr(eng, attr)))


@dataclasses.dataclass
class Window:
    start: float = 0.0           # the schedule's time 0
    open: float = 0.0
    close: float = 0.0
    counters_open: Dict = dataclasses.field(default_factory=dict)
    counters_close: Dict = dataclasses.field(default_factory=dict)
    at_close: Any = None         # what ``at_close()`` kept at the close
    # slot -> index of the request it served at the close
    live: Dict[int, int] = dataclasses.field(default_factory=dict)


async def _guard(task: asyncio.Task, coro):
    """Await ``coro`` unless the serve task dies first (its error wins)."""
    other = asyncio.ensure_future(coro)
    done, _ = await asyncio.wait({task, other},
                                 return_when=asyncio.FIRST_COMPLETED)
    if task in done:
        other.cancel()
        task.result()
        raise RuntimeError("the serve loop ended before the window closed")
    return other.result()


async def serve(eng, reqs, *, seconds: float, prime: dict, budget: int,
                on_open: Callable[[], None],
                during: Optional[Callable[[], "asyncio.Future"]] = None,
                at_close: Optional[Callable[[], tuple]] = None) -> tuple:
    """Serve ``reqs`` (``traffic.Req``) and measure a window of
    ``seconds``; returns ``(records, Window)``.

    ``prime`` says when the window opens: ``cache_budget`` once every
    slot holds a request whose prompt plus output exceeds ``budget``;
    ``seconds`` after that many seconds of arrivals; otherwise at once.
    ``on_open`` runs just before the window opens (tracing starts there);
    ``during()`` runs alongside the window; the run waits for it.
    ``at_close()`` runs at the close, before anything else can run, and
    returns ``(state, {slot: engine request})``: the state is kept in
    ``Window.at_close`` and the slots in ``Window.live`` by request index.
    Every record gets the engine's own list of the tokens it produced."""
    from repro.serving.orchestrator import Orchestrator
    from repro.serving.scheduler import RequestState

    orch = Orchestrator(eng)
    slots = eng.cfg.max_seqs
    win = Window()
    closing = False
    recs = [Record(r.index, 0.0, len(r.prompt), r.max_new) for r in reqs]
    streams: Dict[int, object] = {}
    t_sched = win.start = time.perf_counter()
    for rec, r in zip(recs, reqs):
        rec.due = t_sched + r.due_s
    serve_task = asyncio.create_task(orch.serve(max_ticks=1 << 62))

    async def client(rec: Record, r) -> None:
        delay = rec.due - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        if closing:
            return
        rec.submit = time.perf_counter()
        st = orch.submit(r.prompt, max_new_tokens=r.max_new)
        streams[rec.index] = st
        async for tok in st:
            rec.stamps.append(time.perf_counter())
            rec.tokens.append(tok)
        rec.ended = time.perf_counter()
        rec.finished = st.request.state is RequestState.FINISHED
        rec.stats = dict(st.request.stats)

    clients = [asyncio.create_task(client(rec, r))
               for rec, r in zip(recs, reqs)]

    async def primed() -> None:
        kind = prime.get("kind", "none")
        if kind == "seconds":
            await asyncio.sleep(max(0.0, t_sched + prime["seconds"]
                                    - time.perf_counter()))
            return
        while kind == "cache_budget":
            # every slot busy (or every request left in one) and every
            # such request past the budget, or finishing short of it
            left = [r for r in recs if not r.finished]
            live = [r for r in left if r.stamps]
            if len(live) >= min(slots, len(left)) and all(
                    r.prompt_len + min(len(r.stamps), r.max_new) > budget
                    or r.prompt_len + r.max_new <= budget for r in live):
                return
            await asyncio.sleep(0.005)

    await _guard(serve_task, primed())
    on_open()
    win.counters_open = dict(eng.metrics)
    win.open = time.perf_counter()
    async def window() -> None:
        # the whole window, unless the mix runs out of work first (only
        # the benchmark's own small test batch does)
        end = win.open + seconds
        while time.perf_counter() < end:
            if all(r.ended is not None for r in recs):
                return
            await asyncio.sleep(min(0.01, max(0.0, end - time.perf_counter())))

    side = asyncio.ensure_future(during()) if during is not None else None
    await _guard(serve_task, window())
    win.close = time.perf_counter()
    win.counters_close = dict(eng.metrics)
    if at_close is not None:
        win.at_close, slot_reqs = at_close()
        index = {id(st.request): i for i, st in streams.items()}
        win.live = {s: index[id(q)] for s, q in slot_reqs.items()
                    if id(q) in index}
    # once the streams are cancelled the serve loop dispatches no further
    # tick for these requests, so the state kept at the close and each
    # request's served tokens end at the same tick
    closing = True
    for st in streams.values():
        st.cancel()
    orch.close()
    if side is not None:
        await side
    await serve_task
    for c in clients:
        c.cancel()
    await asyncio.gather(*clients, return_exceptions=True)
    for rec in recs:
        st = streams.get(rec.index)
        if st is not None:
            rec.served = list(st.request.output)
    return recs, win
