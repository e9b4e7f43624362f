"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json`` (see ``harness/spec.py``).  A run:

1. refuses to run without a TPU, or with fewer chips than the cell asks;
2. keeps JAX's compilation cache at ``<checkout>/.jax_cache``;
3. makes the weights on the device from the seed, builds the engine on
   the compiled kernel backend, and warms up every program the cell's
   traffic runs;
4. serves the mix through ``Orchestrator.serve()``, primes it as the mix
   says, and measures a window of ``--seconds`` (traced with ``--trace
   1``);
5. frees the engine and compares a sample of the requests in flight at
   the close with the configuration's plain reference
   (``harness/check.py``).

Earlier lines report compilations inside the window, how late the
generator ran, peak device memory and the engine's counters.  The last
lines of standard error and the ``checks`` key of the result line give
each number compared with its limit.  The last line of standard output is
the result: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device`` and, traced, ``breakdown``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))

import numpy as np  # noqa: E402

from harness import check, context, serve, spec, timeline  # noqa: E402
from harness import trace as T  # noqa: E402
from harness import traffic  # noqa: E402

#: Seconds of the window a ``--trace 1`` run records (from the window's
#: start); the trace of a whole window would be too large to read back,
#: and the profiler's buffers take device memory the engine also needs.
TRACE_SECONDS = 5.0
TRACE_DIR = ROOT / ".bench_trace"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def warm_up(eng, cfg: dict, seed: int) -> None:
    """Serve one request per slot, so that every program the window runs
    is compiled or loaded, and every slot index has been admitted,
    prefilled and retired once (the engine's host paths index slots with
    Python integers): a big prefill chunk, g-sized chunks (full and
    partial), decode ticks across a group commit, and retirement."""
    from repro.serving.orchestrator import Orchestrator
    g = cfg["engine"]["group_size"]
    rng = np.random.default_rng([seed, 1])
    orch = Orchestrator(eng)
    for i in range(eng.cfg.max_seqs):
        n = eng.prefill_chunk + g + 5 if i == 0 else g + 3
        orch.submit(rng.integers(0, cfg["vocab_size"], n).astype(np.int32),
                    max_new_tokens=g + 2)
    orch.run_sync()


class CompileCounter:
    """Counts traces and backend compiles JAX reports while armed.  JAX
    keeps its listeners for the life of the process, so there is one
    counter per process (:func:`compile_counter`)."""

    def __init__(self):
        import jax
        self.armed = False
        self.counts = {"traces": 0, "backend_compiles": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if not self.armed:
            return
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.counts["traces"] += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.counts["backend_compiles"] += 1


@functools.lru_cache(maxsize=None)
def compile_counter() -> CompileCounter:
    return CompileCounter()


def _cache_counts(records, group: int) -> dict:
    """Commits, refreshes and evicted token-slots (summed over layers) of
    the requests that finished, from the engine's slot statistics."""
    out = {"commits": 0, "refreshes": 0, "evicted_token_slots": 0}
    for r in records:
        st = r.stats
        if not st:
            continue
        c = st["committed_tokens"]
        out["commits"] += c // group
        out["refreshes"] += st["refreshes"]
        out["evicted_token_slots"] += sum(c - v for v in st["valid_tokens"])
    return out


def build(cfg: dict, mix: dict, seed: int, log=print) -> tuple:
    """Weights from the seed, on the device in one call, and the engine
    over them, warmed up: ``(weights, engine)``."""
    import jax
    ref = context.load_module("references", cfg["reference"])
    system = context.load_module("systems", cfg["system"])
    samp = mix.get("sampling", {})
    t0 = time.perf_counter()
    weights = ref.init_weights(cfg, seed)
    jax.block_until_ready(weights)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(weights))
    t1 = time.perf_counter()
    eng = system.build_engine(cfg, weights, seed,
                              float(samp.get("temperature", 0.0)),
                              float(samp.get("top_p", 1.0)))
    t2 = time.perf_counter()
    warm_up(eng, cfg, seed)
    log(f"weights: {nbytes} bytes float32 from seed {seed} in "
        f"{t1 - t0:.3f} s (from {t0 - T_START:.3f} s after start); engine "
        f"build {t2 - t1:.3f} s; warm-up {time.perf_counter() - t2:.3f} s")
    return weights, eng


def run_cell(cell: dict, cfg: dict, mix: dict, peaks: dict, *, seed: int,
             seconds: float, trace: bool, log=print, t_start: float = T_START,
             memory_peak=None, control: bool = False, fault=None,
             built=None) -> dict:
    """One run of one cell on whatever device JAX has.  Returns the
    result line's fields (``checks`` last).

    Only the benchmark's own tests and ``readings.py`` set the last
    three: ``control`` also reads the reference's lower-precision control
    on the same tokens and judges it as a run would be judged,
    ``fault(engine)`` breaks the engine's decode tick from the window's
    open, and ``built`` reuses ``build``'s weights and engine."""
    import jax

    ref = context.load_module("references", cfg["reference"])
    system = context.load_module("systems", cfg["system"])
    weights, eng = built if built is not None else build(cfg, mix, seed, log)
    tick = eng._tick
    from repro.analysis.retrace import RetraceGuard
    guard = RetraceGuard(eng, transfer_guard=False).install()
    guard.mark_steady()
    compiles = compile_counter()
    compiles.counts = dict.fromkeys(compiles.counts, 0)
    if trace:
        serve.install_spans(eng)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    reqs = traffic.make_requests(mix, cfg["vocab_size"], seed, seconds)
    e = cfg["engine"]
    dims = dict(ref.dims(cfg), max_seqs=e["max_seqs"],
                token_budget=e["token_budget"],
                prefill_chunk=int(eng.prefill_chunk))
    traced: dict = {}

    def on_open():
        if trace:
            traced["snap_open"] = system.cache_snapshot(eng)
            jax.profiler.start_trace(str(TRACE_DIR))
            traced["span"] = jax.profiler.TraceAnnotation(T.WINDOW_SPAN)
            traced["span"].__enter__()
            traced["counters_open"] = dict(eng.metrics)
            traced["t0"] = time.perf_counter()
        if fault is not None:
            fault(eng)
        compiles.armed = True

    async def during():
        if not trace:
            return
        await asyncio.sleep(min(TRACE_SECONDS, seconds))
        traced["t1"] = time.perf_counter()
        traced["counters_close"] = dict(eng.metrics)
        traced["span"].__exit__(None, None, None)
        # the cache state now, while every slot still serves: writing the
        # trace out can take longer than the rest of the window
        traced["snap_close"] = system.cache_snapshot(eng)
        await asyncio.get_running_loop().run_in_executor(
            None, jax.profiler.stop_trace)

    records, win = asyncio.run(serve.serve(
        eng, reqs, seconds=seconds, prime=mix.get("prime", {}),
        budget=e["token_budget"], on_open=on_open, during=during,
        at_close=functools.partial(system.at_close, eng)))
    compiles.armed = False
    state = system.held(win.at_close)
    win.at_close = None
    setup_s = win.open - t_start
    log(f"setup: {setup_s:.3f} s to window open, priming the last "
        f"{win.open - win.start:.3f} s of it; window "
        f"{win.close - win.open:.3f} s")
    log(f"compilations inside the window: {compiles.counts['traces']} "
        f"traces, {compiles.counts['backend_compiles']} backend compiles; "
        f"RetraceGuard steady-state retraces of entry points: "
        f"{guard.steady_retraces()}")
    log(f"generator lateness: {json.dumps(timeline.lateness(records))}")
    mem = memory_peak() if memory_peak else None
    log(f"peak device memory: {mem} bytes")
    keys = ("ticks", "tokens", "prefill_tokens", "prefill_chunks",
            "prefill_big_chunks", "admissions", "preemptions", "resumes")
    delta = {k: win.counters_close.get(k, 0) - win.counters_open.get(k, 0)
             for k in keys}
    in_win = [r for r in records if r.finished and r.stamps
              and win.open <= r.stamps[-1] < win.close]
    log(f"engine counters over the window: {json.dumps(delta)}; cache, over "
        f"the {len(in_win)} requests that finished in it: "
        f"{json.dumps(_cache_counts(in_win, e['group_size']))}")

    guard.uninstall()
    eng._tick = tick
    del eng, guard, built
    gc.collect()

    prompts = {r.index: r.prompt for r in reqs}
    cands, numbers = check.in_flight(state, win.live, records, prompts,
                                     e["token_budget"])
    numbers.update(check.whole_run(records, win.close))
    picked = check.sample(cands, mix["check"], seed)
    t3 = time.perf_counter()
    result = check.compare(ref, cfg, weights, picked,
                           check.prompt_pad(mix, ref.Q_BLOCK),
                           control=control)
    log(f"reference: {result['requests']} of {len(cands)} requests in "
        f"flight at the close, {result['positions']} served tokens "
        f"compared in {time.perf_counter() - t3:.3f} s")
    limit = cfg["limits"][check.GAP]
    correct, checks = check.verdict(result, numbers, limit,
                                    e["group_size"])

    run = context.Run(dims=dims, peaks=peaks, setup_s=setup_s,
                      records=records, open=win.open, close=win.close,
                      counters_open=win.counters_open,
                      counters_close=win.counters_close)
    breakdown = None
    device_extra = {}
    if trace:
        files = glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"),
                          recursive=True)
        events = T.load_xplane(max(files, key=os.path.getmtime))
        run.traced = context.Traced(
            events=events, t0=traced["t0"], t1=traced["t1"],
            counters_open=traced["counters_open"],
            counters_close=traced["counters_close"],
            snap_open=traced["snap_open"], snap_close=traced["snap_close"])
        lo, hi = T.window(events)
        device_extra = {"busy_s": T.busy_seconds(events),
                        "window_s": (hi - lo) / 1e9}
        breakdown = {"device_ops": T.top_ops(events),
                     "idle_gaps": T.idle_gaps_by_span(
                         events, list(serve.SPANS.values()) + ["result.wait"])}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.metrics_for(_bench(), cell["name"], kind):
        v = context.load_module("metrics", m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    # in play in the window: due before it closed, not done before it
    # opened; failed: its stream ended inside the window unfinished
    attempted = [r for r in records if r.due < win.close and
                 not (r.ended is not None and r.ended < win.open)]
    out = {"correct": bool(correct), "attempted": len(attempted),
           "failed": sum(1 for r in attempted if not r.finished and
                         r.ended is not None and r.ended < win.close),
           "metrics": metrics, "memory_peak_bytes": mem,
           "device_extra": device_extra}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    out["compared"] = dict(result, **numbers)
    if control:
        out["control_correct"], out["control_checks"] = check.verdict(
            result, numbers, limit, e["group_size"], gap="control_gap")
    return out


@functools.lru_cache(maxsize=None)
def _bench() -> dict:
    return spec.load_benchmark()


def open_chip(workload: str, tool: str):
    """Put JAX's compilation cache at ``<checkout>/.jax_cache`` and find
    the cell and its chips: ``(cell, devices)``, or ``None`` after saying
    why when JAX finds fewer TPU chips than the cell asks for."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    cell = spec.cell(_bench(), workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"{tool}: {workload} needs {cell['chips']} TPU chip(s); JAX "
              f"finds {len(devices)} {devices[0].platform} device(s); "
              f"nothing was run", file=sys.stderr)
        return None
    from repro.launch.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"device: {devices[0].platform} {devices[0].device_kind} x "
          f"{len(devices)}; compile cache {enable_compile_cache()}",
          flush=True)
    return cell, devices


def main(argv=None) -> int:
    args = parse(argv)
    found = open_chip(args.workload, "run.py")
    if found is None:
        return 2
    cell, devices = found
    dev = devices[0]
    peaks = spec.peaks(dev.device_kind)
    cfg = spec.config(cell["config"])
    mix = spec.mix(cell["traffic"])

    def memory_peak():
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices[:cell["chips"]])

    out = run_cell(cell, cfg, mix, peaks, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   memory_peak=memory_peak,
                   log=lambda s: print(s, flush=True))
    checks = out.pop("checks")
    compared = out.pop("compared")
    print(f"compared: {json.dumps(compared)}", flush=True)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"],
              "memory_peak_bytes": out.pop("memory_peak_bytes")}
    device.update(out.pop("device_extra"))
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"],
            "device": device}
    if "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
