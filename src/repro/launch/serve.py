"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Runs the ThinKV continuous-batching engine on synthetic reasoning prompts
and reports throughput + compression stats (the CPU-scale analogue of the
paper's Table 2 measurement loop).

Oversubscription knobs: ``--pool-blocks`` (absolute) or ``--pool-frac``
(fraction of the dense worst case ``slots * NB``) shrink the shared
physical block pool below worst-case demand; the engine then serves via
watermark admission + preemption (pause lowest-priority request, spill
its blocks to the host, resume later — no recompute, no dropped tokens).
``--priorities`` assigns request priorities (higher = served first,
preempted last).  ``--expect-all`` turns the run into a CI gate: exit
nonzero unless every request completes with its full token count.

Prefix-sharing knobs: ``--prefix-cache`` enables copy-on-write prefix
caching over the shared pool (requests whose prompt extends an already-
prefilled prefix map the cached blocks refcounted into their block table
and skip the covered prefill chunks); ``--shared-prefix-frac`` makes the
synthetic workload share that fraction of every prompt (1.0 = identical
prompts — the shared-system-prompt fleet shape).  ``--expect-prefix-hits``
gates on at least one hit, > 0 prefill tokens skipped, and a clean
refcount audit (``claimed + free == pool_blocks``, every reference
accounted).

Streaming knobs: ``--stream`` serves through the asyncio orchestrator
(``serving.orchestrator``) — per-request ``async for`` token streams,
prefill of waiting requests overlapped with decode of running ones, and
per-request TTFT/TPOT/queue-wait percentiles reported.
``--arrival-rate R`` makes the workload OPEN-LOOP: requests arrive by a
seeded Poisson process at R requests per engine tick (tick-space pacing
is deterministic across hosts, unlike wall-clock timers), independent of
completions.  ``--expect-stream-parity`` turns the run into the
orchestrator CI gate: a second engine replays the same requests through
the synchronous batch ``run()`` path and every request's per-step logits
must be BIT-IDENTICAL (greedy only — per-request logits are
schedule-invariant at temperature 0, so even staggered arrivals must
reproduce the batch run exactly), with both pool audits clean.

Mega-dispatch knobs: ``--ticks-per-dispatch N`` fuses up to N decode
ticks into ONE on-device ``lax.while_loop`` dispatch — sampling happens
on-device (``--temperature``/``--top-p``, per-request seeded streams)
and sampled tokens feed the next tick's embedding without visiting the
host; the loop exits early at scheduling events (a slot finishing, or
the host-precomputed claim-safe trip count).  ``--samples-per-slot n``
serves n samples per request by COW-forking the prompt + generated
prefix into n logical sequences (best-of-n reasoning; needs
``--stream``).  ``--expect-multi-tick`` turns the run into the
mega-dispatch CI gate: mean ticks/dispatch > 1 with >= 1 early exit,
clean pool audits, and bit-identical greedy tokens vs a second engine
serving one tick per dispatch (plus fork COW faults, shared refcounts
> 1, and fork/parent token identity when forking).

Tensor-parallel knobs: ``--mesh model=N`` shards the engine's pool
planes, TBQ buffers, and attention over N devices on the KV-head axis
(``kv_heads % N == 0`` — use ``--heads/--kv-heads`` to override the
smoke config; on CPU export
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` first).
``--expect-mesh-parity`` turns the run into the sharded-serving CI gate:
a second, UNSHARDED engine replays the identical trace and every
request's per-step logits must be BIT-IDENTICAL across the two
topologies, with both pool audits clean.
"""
from __future__ import annotations

import argparse
import dataclasses

import numpy as np

from repro.config import ServeConfig, ThinKVConfig
from repro.configs import get_config, get_smoke_config
from repro.core import ct_cache as CC
from repro.serving.engine import ThinKVEngine


def _run_streamed(eng, args, prompts, priorities):
    """Serve through the asyncio orchestrator: open-loop seeded Poisson
    arrivals in TICK space (deterministic), one consumer task per
    request draining its ``async for`` token stream concurrently.
    ``--samples-per-slot n`` attaches ``n - 1`` COW-forked sibling
    streams per request (best-of-n over the shared prompt + CoT prefix).
    Returns (finished requests, orchestrator, streamed token counts,
    parent streams)."""
    import asyncio

    from repro.serving.orchestrator import Orchestrator

    orch = Orchestrator(eng)
    spr = getattr(args, "samples_per_slot", 1)
    arr_rng = np.random.default_rng(1)
    if args.arrival_rate > 0:
        gaps = arr_rng.exponential(1.0 / args.arrival_rate, len(prompts))
        at_tick = np.floor(np.cumsum(gaps)).astype(int)
    else:
        at_tick = np.zeros(len(prompts), int)

    async def go():
        # fork children draw uids from the orchestrator's own counter,
        # so explicit parent uids would collide with them: let the
        # counter number everything when forking (still deterministic)
        streams = [
            orch.schedule_arrival(
                after_tick=int(at_tick[i]), prompt=p,
                max_new_tokens=args.max_new,
                priority=priorities[i] if priorities else 0,
                uid=i if spr == 1 else None, samples_per_slot=spr)
            for i, p in enumerate(prompts)]
        counts = {}

        async def consume(s):
            n = 0
            async for _tok in s:
                n += 1
            counts[s.request.uid] = n

        consumers = [asyncio.ensure_future(consume(s))
                     for parent in streams
                     for s in (parent, *parent.forks)]
        orch.close()
        done = await orch.serve()
        for c in consumers:
            await c
        return done, counts, streams

    done, counts, streams = asyncio.run(go())
    return done, orch, counts, streams


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="r1-llama-8b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--budget", type=int, default=64)
    ap.add_argument("--tau", type=int, default=16)
    ap.add_argument("--group", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = disabled); applied "
                         "on-device wherever tokens are sampled")
    ap.add_argument("--ticks-per-dispatch", type=int, default=1,
                    help="fuse up to N decode ticks into ONE on-device "
                         "while_loop dispatch (sampled tokens feed the "
                         "next tick without visiting the host; the loop "
                         "exits early at scheduling events)")
    ap.add_argument("--samples-per-slot", type=int, default=1,
                    help="serve n samples per request by COW-forking the "
                         "prompt + generated-prefix cache into n logical "
                         "sequences (best-of-n reasoning); needs --stream")
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "reference", "kernel"),
                    help="decode attention path: dense dequant (reference) "
                         "or the ct_paged_attention kernel")
    ap.add_argument("--pool-blocks", type=int, default=None,
                    help="physical blocks in the shared pool (default: the "
                         "dense worst case, slots * NB)")
    ap.add_argument("--pool-frac", type=float, default=None,
                    help="pool size as a fraction of the dense worst case "
                         "(e.g. 0.25 oversubscribes 4x; overrides "
                         "--pool-blocks)")
    ap.add_argument("--priorities", type=str, default=None,
                    help="comma-separated priority ints cycled over "
                         "requests (higher = served first, preempted last)")
    ap.add_argument("--expect-all", action="store_true",
                    help="CI gate: fail unless every request finishes with "
                         "its full --max-new tokens (preemptions are fine; "
                         "drops and deadlocks are not)")
    ap.add_argument("--expect-preemptions", action="store_true",
                    help="CI gate: fail unless at least one preemption + "
                         "resume happened (guards the spill/resume "
                         "machinery against vacuous oversubscription runs)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable copy-on-write prefix caching: requests "
                         "whose prompt extends a cached prefix share its "
                         "physical blocks (refcounted) and skip the "
                         "covered prefill chunks")
    ap.add_argument("--shared-prefix-frac", type=float, default=0.0,
                    help="fraction of every prompt shared across requests "
                         "(1.0 = identical prompts; models a shared "
                         "system-prompt fleet)")
    ap.add_argument("--expect-prefix-hits", action="store_true",
                    help="CI gate: fail unless the run scored >= 1 prefix "
                         "hit with > 0 prefill tokens skipped and a clean "
                         "pool refcount audit")
    ap.add_argument("--stream", action="store_true",
                    help="serve via the asyncio orchestrator: streaming "
                         "token delivery, overlapped prefill/decode, "
                         "per-request TTFT/TPOT/queue-wait percentiles")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="open-loop Poisson arrivals at this many requests "
                         "per engine TICK (0 = everything arrives up "
                         "front); needs --stream")
    ap.add_argument("--expect-stream-parity", action="store_true",
                    help="CI gate (needs --stream, greedy): replay the "
                         "same requests through the synchronous batch "
                         "run() on a second engine and fail unless every "
                         "request's per-step logits are bit-identical "
                         "and both pool audits are clean")
    ap.add_argument("--mesh", type=str, default=None,
                    help="device mesh spec for tensor-parallel serving, "
                         "e.g. model=8 (shards pool planes + attention "
                         "over the KV-head axis; kv_heads %% N == 0)")
    ap.add_argument("--heads", type=int, default=None,
                    help="override the arch's query-head count (e.g. to "
                         "make a smoke config head-shardable)")
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="override the arch's KV-head count")
    ap.add_argument("--expect-mesh-parity", action="store_true",
                    help="CI gate (needs --mesh): replay the identical "
                         "trace on an UNSHARDED engine and fail unless "
                         "every request's logits are bit-identical and "
                         "both pool audits are clean")
    ap.add_argument("--policy", default="thinkv",
                    choices=("thinkv", "rkv", "uniform"),
                    help="retention policy: the paper's thought-adaptive "
                         "rho/psi schedule (thinkv), redundancy-aware "
                         "farthest-point retention (rkv), or a uniform "
                         "4-bit recency baseline (uniform)")
    ap.add_argument("--drift-probe", action="store_true",
                    help="replay every finished request through an "
                         "uncompressed dense forward and report logit "
                         "drift vs the serving path (quality telemetry; "
                         "needs --stream)")
    ap.add_argument("--expect-drift", action="store_true",
                    help="CI gate (needs --drift-probe): fail unless "
                         "every finished request carries finite drift "
                         "stats with top-1 agreement recorded")
    ap.add_argument("--expect-multi-tick", action="store_true",
                    help="CI gate (needs --ticks-per-dispatch > 1, greedy):"
                         " fail unless mean ticks/dispatch > 1 with >= 1 "
                         "early pack exit, the pool audit is clean, and a "
                         "second engine replaying the workload one tick "
                         "per dispatch emits bit-identical tokens; with "
                         "--samples-per-slot > 1 additionally requires "
                         ">= 1 COW fork fault, shared refcounts > 1, and "
                         "fork outputs equal to their parents'")
    args = ap.parse_args()
    if args.expect_mesh_parity and not args.mesh:
        ap.error("--expect-mesh-parity requires --mesh")
    if (args.arrival_rate or args.expect_stream_parity) and not args.stream:
        ap.error("--arrival-rate/--expect-stream-parity require --stream")
    if args.expect_stream_parity and args.temperature > 0:
        ap.error("--expect-stream-parity needs --temperature 0: only "
                 "greedy per-request logits are schedule-invariant")
    if args.samples_per_slot > 1 and not args.stream:
        ap.error("--samples-per-slot > 1 requires --stream (forks land "
                 "through the orchestrator)")
    if args.expect_multi_tick and args.ticks_per_dispatch < 2:
        ap.error("--expect-multi-tick requires --ticks-per-dispatch > 1")
    if args.expect_multi_tick and args.temperature > 0:
        ap.error("--expect-multi-tick needs --temperature 0 for the "
                 "bit-exact per-tick parity replay")
    if args.drift_probe and not args.stream:
        ap.error("--drift-probe requires --stream (the probe fires from "
                 "the orchestrator's finish hook)")
    if args.expect_drift and not args.drift_probe:
        ap.error("--expect-drift requires --drift-probe")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    mcfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    if args.heads is not None:
        mcfg = dataclasses.replace(mcfg, num_heads=args.heads)
    if args.kv_heads is not None:
        mcfg = dataclasses.replace(mcfg, num_kv_heads=args.kv_heads)
    if mcfg.num_heads % mcfg.num_kv_heads != 0:
        ap.error(f"--heads/--kv-heads must keep num_heads divisible by "
                 f"num_kv_heads (got {mcfg.num_heads} / "
                 f"{mcfg.num_kv_heads})")
    tk = ThinKVConfig(refresh_interval=args.tau, group_size=args.group,
                      block_size=args.group, token_budget=args.budget,
                      retention_schedule=(32, 16, 8, 4), min_retention=4,
                      max_segments=256, kmeans_iters=4)
    cfg = ServeConfig(model=mcfg, thinkv=tk, max_seqs=args.slots,
                      temperature=args.temperature, top_p=args.top_p)
    dims = CC.make_dims(tk, mcfg.num_layers, mcfg.num_kv_heads,
                        mcfg.head_dim)
    worst_case = args.slots * dims.NB
    pool_blocks = args.pool_blocks
    if args.pool_frac is not None:
        pool_blocks = max(int(worst_case * args.pool_frac), 1)
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh(args.mesh)
    eng = ThinKVEngine(cfg, backend=args.backend, pool_blocks=pool_blocks,
                       prefix_cache=args.prefix_cache, mesh=mesh,
                       ticks_per_dispatch=args.ticks_per_dispatch,
                       allow_forks=args.samples_per_slot > 1,
                       policy=args.policy, drift_probe=args.drift_probe,
                       record_logits=(args.expect_mesh_parity or
                                      args.expect_stream_parity))
    rng = np.random.default_rng(0)
    shared_len = int(round(args.prompt_len * args.shared_prefix_frac))
    shared = rng.integers(0, mcfg.vocab_size, shared_len)
    prompts = [np.concatenate([
        shared, rng.integers(0, mcfg.vocab_size,
                             args.prompt_len - shared_len)]).astype(np.int64)
        for _ in range(args.requests)]
    priorities = None
    if args.priorities:
        cycle = [int(x) for x in args.priorities.split(",")]
        priorities = [cycle[i % len(cycle)] for i in range(args.requests)]
    orch = None
    streams = None
    if args.stream:
        done, orch, streamed_counts, streams = _run_streamed(
            eng, args, prompts, priorities)
    else:
        eng.submit(prompts, max_new_tokens=args.max_new,
                   priorities=priorities)
        done = eng.run()
    toks = eng.metrics["tokens"]
    wall = eng.metrics["wall_s"]
    fr = np.mean([r.stats["footprint_frac"] for r in done])
    bits = np.mean([r.stats["avg_bits"] for r in done])
    import jax
    dev = jax.devices()[0]
    print(f"served {len(done)} requests [policy={args.policy}] | {toks} "
          f"tokens in {wall:.1f}s wall on {dev.platform} "
          f"({dev.device_kind}, backend={eng.backend}, "
          f"{toks / wall:.1f} tok/s) | "
          f"mean footprint {fr * 100:.2f}% of FullKV | avg {bits:.2f} bits")
    if args.drift_probe:
        drifts = [r.stats["drift"] for r in done if "drift" in r.stats]
        if drifts:
            mx = max(d["max_abs"] for d in drifts)
            mean = np.mean([d["mean_abs"] for d in drifts])
            agree = np.mean([d["top1_agree"] for d in drifts])
            print(f"drift probe: {len(drifts)} requests vs uncompressed "
                  f"replay | max |dlogit| {mx:.4f} | mean |dlogit| "
                  f"{mean:.4f} | top-1 agreement {agree * 100:.1f}%")
    print(f"pool {eng.num_pool_blocks}/{worst_case} blocks "
          f"({100.0 * eng.num_pool_blocks / worst_case:.0f}% of worst case)"
          f" | {eng.metrics['preemptions']} preemptions, "
          f"{eng.metrics['resumes']} resumes | mean queue wait "
          f"{eng.metrics['queue_wait_ticks'] / max(eng.metrics['admissions'], 1):.1f}"
          f" ticks")
    if args.ticks_per_dispatch > 1 or args.samples_per_slot > 1:
        m = eng.metrics
        print(f"mega-dispatch: {m['dispatches']} dispatches for "
              f"{m['ticks']} ticks "
              f"({m['ticks'] / max(m['dispatches'], 1):.2f} ticks/dispatch"
              f", {m['dispatches'] / max(m['tokens'], 1):.3f} "
              f"dispatches/token) | early exits: "
              f"{m['early_exit_finish']} finish, "
              f"{m['early_exit_headroom']} headroom | {m['forks']} "
              f"fork(s), {m['fork_cow_faults']} fork COW faults, peak "
              f"refcount {m['peak_refcount']}")
    if args.stream:
        pct = orch.percentiles()
        parts = []
        for key, label, scale in (("ttft_s", "TTFT", 1e3),
                                  ("tpot_s", "TPOT", 1e3)):
            if key in pct:
                parts.append(f"{label} p50 {pct[key]['p50'] * scale:.0f}ms"
                             f" / p99 {pct[key]['p99'] * scale:.0f}ms")
        if "queue_wait_ticks" in pct:
            parts.append(f"queue wait p50 "
                         f"{pct['queue_wait_ticks']['p50']:.1f} / p99 "
                         f"{pct['queue_wait_ticks']['p99']:.1f} ticks")
        rate = f"{args.arrival_rate} req/tick" if args.arrival_rate \
            else "all-at-once"
        print(f"streamed ({rate} open-loop): {sum(streamed_counts.values())}"
              f" tokens delivered over {len(streamed_counts)} streams | "
              + " | ".join(parts))
        print(f"overlap: prefill-inside-decode="
              f"{orch.prefill_overlaps_decode()} "
              f"stream-inside-next-tick={orch.stream_overlaps_dispatch()}")
    if args.expect_all:
        want = args.requests * max(args.samples_per_slot, 1)
        short = [r for r in done if len(r.output) < args.max_new]
        if len(done) != want or short:
            raise SystemExit(
                f"oversubscription gate FAILED: {len(done)}/{want} "
                f"requests finished, {len(short)} with dropped tokens")
        print(f"oversubscription gate OK: {want}/{want} "
              f"requests completed with zero dropped tokens")
    if args.expect_preemptions:
        if eng.metrics["preemptions"] < 1 or \
                eng.metrics["resumes"] != eng.metrics["preemptions"]:
            raise SystemExit(
                f"preemption gate FAILED: {eng.metrics['preemptions']} "
                f"preemptions / {eng.metrics['resumes']} resumes — the "
                f"oversubscribed run never exercised spill/resume (or a "
                f"victim was never restored)")
        print(f"preemption gate OK: {eng.metrics['preemptions']} "
              f"preemption(s), every victim resumed")
    if args.prefix_cache:
        pc = eng.prefix_cache.stats()
        print(f"prefix cache: {eng.metrics['prefix_hits']} hits | "
              f"{eng.metrics['prefix_tokens_skipped']} prefill tokens "
              f"skipped | {eng.metrics['cow_faults']} COW faults | "
              f"{pc['entries']} entries, {pc['evictions']} evictions")
        try:
            eng.audit_pool()
        except AssertionError as e:
            raise SystemExit(f"pool refcount audit FAILED: {e}")
        print("pool refcount audit OK: every reference accounted, "
              "claimed + free == pool_blocks")
    if args.expect_prefix_hits:
        if not args.prefix_cache:
            raise SystemExit("--expect-prefix-hits requires --prefix-cache")
        if eng.metrics["prefix_hits"] < 1 or \
                eng.metrics["prefix_tokens_skipped"] <= 0:
            raise SystemExit(
                f"prefix gate FAILED: {eng.metrics['prefix_hits']} hits, "
                f"{eng.metrics['prefix_tokens_skipped']} tokens skipped — "
                f"the shared-prefix run never reused a cached prefix")
        print(f"prefix gate OK: {eng.metrics['prefix_hits']} hit(s), "
              f"{eng.metrics['prefix_tokens_skipped']} prefill tokens "
              f"skipped")
    if args.expect_stream_parity:
        ref = ThinKVEngine(cfg, params=eng.params, backend=args.backend,
                           pool_blocks=pool_blocks,
                           prefix_cache=args.prefix_cache,
                           policy=args.policy, record_logits=True)
        ref.submit([p.copy() for p in prompts],
                   max_new_tokens=args.max_new, priorities=priorities)
        ref_done = ref.run()
        mismatch = []
        if len(done) != len(ref_done):
            mismatch.append(f"completed {len(done)} vs {len(ref_done)}")
        # greedy per-request logits are schedule-invariant: the streamed
        # run's staggered arrivals must reproduce the batch run's logits
        # bit for bit, keyed by arrival stamp (both submit in uid order)
        if set(eng.request_logits) != set(ref.request_logits):
            mismatch.append("recorded-request sets differ")
        out_by_uid = {r.uid: r.output for r in done}
        mismatch += [
            s.uid for s in ref_done
            if out_by_uid.get(s.uid) != s.output]
        logit_steps = bad_steps = 0
        for key in set(eng.request_logits) & set(ref.request_logits):
            seq, ref_seq = eng.request_logits[key], ref.request_logits[key]
            if len(seq) != len(ref_seq):
                mismatch.append(f"arrival{key}:steps")
                continue
            for a, b in zip(seq, ref_seq):
                logit_steps += 1
                if a.shape != b.shape or not (a == b).all():
                    bad_steps += 1
        try:
            eng.audit_pool()
            ref.audit_pool()
        except AssertionError as e:
            raise SystemExit(f"stream-parity gate FAILED: pool audit: {e}")
        if mismatch or bad_steps:
            raise SystemExit(
                f"stream-parity gate FAILED: mismatches {mismatch}, "
                f"{bad_steps}/{logit_steps} non-bit-identical logit steps "
                f"between the streamed orchestrator and the synchronous "
                f"run() path")
        if not orch.prefill_overlaps_decode():
            raise SystemExit(
                "stream-parity gate FAILED: the metrics log shows no "
                "prefill overlapping a running request's decode — the "
                "orchestrator never actually interleaved admission with "
                "generation")
        print(f"stream-parity gate OK: {len(done)} requests, "
              f"{logit_steps} logit steps bit-identical between the "
              f"streamed orchestrator and the synchronous run() path; "
              f"prefill/decode overlap observed; both audits clean")
    if args.mesh:
        import jax
        print(f"mesh: {args.mesh} over {jax.device_count()} devices | "
              f"kv heads sharded {eng._nshard}-way | single fused launch "
              f"per tick per shard")
    if args.expect_mesh_parity:
        ref = ThinKVEngine(cfg, params=eng.params, backend=args.backend,
                           pool_blocks=pool_blocks,
                           prefix_cache=args.prefix_cache,
                           policy=args.policy, record_logits=True)
        ref.submit([p.copy() for p in prompts],
                   max_new_tokens=args.max_new, priorities=priorities)
        ref_done = ref.run()
        # compare the FULL request sets symmetrically: a request the
        # sharded run dropped (or never started) must fail the gate, not
        # silently fall out of a zip/keys iteration
        mismatch = []
        if len(done) != len(ref_done):
            mismatch.append(f"completed {len(done)} vs {len(ref_done)}")
        if set(eng.request_logits) != set(ref.request_logits):
            mismatch.append("recorded-request sets differ")
        mismatch += [
            r.uid for r, s in zip(done, ref_done)
            if r.uid != s.uid or r.output != s.output]
        logit_steps = 0
        bad_steps = 0
        for key in set(eng.request_logits) & set(ref.request_logits):
            seq, ref_seq = eng.request_logits[key], ref.request_logits[key]
            if len(seq) != len(ref_seq):
                mismatch.append(f"arrival{key}:steps")
                continue
            for a, b in zip(seq, ref_seq):
                logit_steps += 1
                if a.shape != b.shape or not (a == b).all():
                    bad_steps += 1
        try:
            audit_m = eng.audit_pool()
            audit_s = ref.audit_pool()
        except AssertionError as e:
            raise SystemExit(f"mesh-parity gate FAILED: pool audit: {e}")
        if mismatch or bad_steps or audit_m != audit_s:
            raise SystemExit(
                f"mesh-parity gate FAILED: output mismatches {mismatch}, "
                f"{bad_steps}/{logit_steps} non-bit-identical logit "
                f"steps, audits {audit_m} vs {audit_s}")
        print(f"mesh-parity gate OK: {len(done)} requests, {logit_steps} "
              f"logit steps bit-identical between --mesh {args.mesh} and "
              f"the unsharded engine; both audits clean")
    if args.expect_drift:
        drifts = [r.stats.get("drift") for r in done]
        missing = sum(1 for d in drifts if d is None)
        bad = [d for d in drifts if d is not None and
               not (np.isfinite(d["max_abs"]) and np.isfinite(d["mean_abs"])
                    and d["steps"] > 0)]
        drift_events = sum(1 for e in orch.events if e["kind"] == "drift")
        if missing or bad or eng.metrics["drift_probes"] != len(done) or \
                drift_events != len(done):
            raise SystemExit(
                f"drift gate FAILED: {missing} request(s) without drift "
                f"stats, {len(bad)} with non-finite/empty stats, "
                f"{eng.metrics['drift_probes']} probes and {drift_events} "
                f"drift events for {len(done)} requests")
        agree = np.mean([d["top1_agree"] for d in drifts])
        print(f"drift gate OK: {len(done)}/{len(done)} requests probed "
              f"against the uncompressed replay, all stats finite, "
              f"top-1 agreement {agree * 100:.1f}%")
    if args.expect_multi_tick:
        m = eng.metrics
        fails = []
        mean_tpd = m["ticks"] / max(m["dispatches"], 1)
        if mean_tpd <= 1.0:
            fails.append(f"mean ticks/dispatch {mean_tpd:.2f} <= 1")
        if m["dispatches"] / max(m["tokens"], 1) >= 1.0:
            fails.append("Python dispatches per decoded token >= 1")
        if m["early_exit_finish"] + m["early_exit_headroom"] < 1:
            fails.append("no early pack exit observed (finish or "
                         "headroom) — the trace never hit a scheduling "
                         "event mid-pack")
        if args.samples_per_slot > 1:
            if m["forks"] < 1:
                fails.append("no COW fork ever landed")
            if m["peak_refcount"] < 2:
                fails.append("shared-prefix refcounts never exceeded 1")
            if m["fork_cow_faults"] < 1:
                fails.append("no COW fault on a forked slot — divergence "
                             "never paid the copy (or never wrote near "
                             "shared blocks; lengthen --max-new past "
                             "--budget)")
            diverged = sum(
                1 for parent in streams for child in parent.forks
                if child.request.output != parent.request.output)
            if diverged:
                fails.append(f"{diverged} greedy fork(s) diverged from "
                             f"their parent's tokens")
        try:
            eng.audit_pool()
        except AssertionError as e:
            fails.append(f"pool audit: {e}")
        # bit-exact greedy parity vs the per-tick loop: a second engine
        # serves the identical workload one tick per dispatch
        ref = ThinKVEngine(cfg, params=eng.params, backend=args.backend,
                           pool_blocks=pool_blocks,
                           prefix_cache=args.prefix_cache,
                           policy=args.policy,
                           allow_forks=args.samples_per_slot > 1)
        if args.stream:
            _, _, _, ref_streams = _run_streamed(
                ref, args, [p.copy() for p in prompts], priorities)
            bad = sum(
                1 for a, b in zip(streams, ref_streams)
                for x, y in zip((a, *a.forks), (b, *b.forks))
                if x.request.output != y.request.output)
            if bad:
                fails.append(f"{bad} stream(s) not bit-identical to the "
                             f"per-tick replay")
        else:
            ref.submit([p.copy() for p in prompts],
                       max_new_tokens=args.max_new, priorities=priorities)
            ref_out = {r.uid: r.output for r in ref.run()}
            if {r.uid: r.output for r in done} != ref_out:
                fails.append("outputs differ from the per-tick replay")
        try:
            ref.audit_pool()
        except AssertionError as e:
            fails.append(f"per-tick replay pool audit: {e}")
        if fails:
            raise SystemExit("multi-tick gate FAILED: " + "; ".join(fails))
        forked = (f", {m['forks']} fork(s) sharing prefix blocks "
                  f"(peak refcount {m['peak_refcount']}, "
                  f"{m['fork_cow_faults']} fork COW faults, every fork "
                  f"token-identical to its parent)"
                  if args.samples_per_slot > 1 else "")
        print(f"multi-tick gate OK: {m['dispatches']} dispatches for "
              f"{m['ticks']} ticks ({mean_tpd:.2f} ticks/dispatch), "
              f"{m['early_exit_finish'] + m['early_exit_headroom']} early "
              f"exit(s), bit-identical to the per-tick loop, both audits "
              f"clean{forked}")


if __name__ == "__main__":
    main()
