"""Table 2/3 reproduction: memory-footprint model -> max batch -> throughput.

Three parts:
1. **Memory model** (exact, analytic — matches the paper's batch-size
   arithmetic): per-request KV footprint under FullKV / eviction-only
   (R-KV-style, bf16 at budget) / ThinKV (4-bit pool + scales + metadata),
   giving the max batch on A100-80GB / TPU v5e-16GB after weights.
2. **Measured CPU kernel-path comparison**: per-step cache maintenance cost
   of gather-based compaction (R-KV style: index + materialize the kept
   set every step) vs CT in-place slot reuse (scatter of one g-token group
   every g steps), on real jitted ops — the Obs. 4a/4b mechanism.
3. **Measured engine throughput**: the continuous-batching engine end to
   end under both decode backends (``reference`` = dense dequant XLA;
   ``kernel`` = the fused single-launch ``ct_paged_attention_fused`` —
   interpret mode off-TPU, so the kernel numbers on CPU measure dispatch
   structure, not HBM wins) plus chunked batched prefill tokens/s.  Every
   backend row reports the PER-TICK ``pallas_call`` LAUNCH COUNT (audited
   on the tick's jaxpr with scan trip-count multiplication): the fused
   decode tick is exactly 1 for the kernel backend at ANY layer count.
4. **Layer sweep** (``--layers``): per-tick decode throughput + launch
   counts at L in {4, 16, 32} — the launch-amortization win of folding
   the layer axis into the kernel grid grows linearly with L.
5. **Oversubscription sweep**: the engine with the shared block pool at
   100% / 50% / 25% of the dense worst case (``max_seqs * NB``) —
   throughput, preemption/resume counts, and mean queue wait under
   watermark admission + pause/spill/resume.  Every request must
   complete with zero dropped tokens at every pool size.
6. **Prefix-hit-rate sweep**: copy-on-write prefix caching at 0% / 50% /
   100% shared prompt prefix across requests — prefill tokens skipped,
   prefix hit rate, COW faults, and throughput.  Identical prompts
   (100%) must skip every covered chunk for every request after the
   first; outputs are gated bit-identical to the cache-off run.
7. **Device sweep** (tensor-parallel serving): the engine sharded over a
   ``model``-axis mesh of 1 / 4 / 8 devices (KV-head-sharded pool planes
   + per-shard fused attention launches; CPU host devices are FAKED via
   ``--xla_force_host_platform_device_count`` in a subprocess, so the
   numbers measure dispatch structure + collective overhead, not a real
   multi-chip win).  Outputs are gated IDENTICAL across every mesh size.
8. **Dispatch sweep** (multi-tick mega-dispatch): Python dispatches per
   decoded token and the host-gap share of wall time at
   ``ticks_per_dispatch`` x ``samples_per_slot`` (COW-forked best-of-n)
   — the fused ``while_loop`` pack must push dispatches/token below 1
   at 8 ticks per dispatch (gated).
9. **Policy sweep** (cache-size-vs-drift frontier): every registered
   retention policy (thinkv / rkv / uniform) x bit-mix and eviction-
   aggressiveness variants x pool fractions, served through the
   orchestrator with the logit-drift probe on — footprint fraction vs
   drift against the uncompressed dense replay (the serving-trace
   analogue of the paper's Fig. 8/10 curves).  Gated: all requests
   complete, finite drift on every request, clean pool + compiled-path
   contract audits per cell.

Results are also APPENDED to ``BENCH_table2.json`` at the repo root (one
record per run, tagged with the git SHA) so the perf trajectory is
tracked across PRs; every engine entry records its ``pool_blocks`` and
preemption counts so oversubscribed runs are distinguishable from
full-pool runs when comparing across PRs.  ``--smoke`` runs a tiny
interpret-mode configuration as a CI kernel-path regression gate.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import subprocess
import time

import jax
import jax.numpy as jnp
from jax.sharding import AxisType
import numpy as np

from repro.config import ThinKVConfig
from repro.configs import get_config
from repro.core import quantization as Q

GB = 1024 ** 3

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
BENCH_LOG = os.path.join(REPO_ROOT, "BENCH_table2.json")


def memory_model(arch="r1-llama-8b", gen_len=32768, budget=1024,
                 hbm_gb=80.0, weight_bytes_per_param=2.0):
    cfg = get_config(arch)
    tk = ThinKVConfig(token_budget=budget)
    weights = cfg.param_count() * weight_bytes_per_param
    free = hbm_gb * GB - weights

    full_per_req = gen_len * cfg.kv_bytes_per_token_fullkv()
    # eviction-only: budget tokens at bf16
    evict_per_req = budget * cfg.kv_bytes_per_token_fullkv()
    # ThinKV: pool (4-bit codes + 0.5B scales) with 2x slack + buffer + meta
    la = cfg.num_attention_layers()
    slot = 2 * cfg.kv_dim * (0.5 + 2 / Q.GROUP)      # K+V codes + scales
    pool = int(budget * 2.0) * slot * la
    buf = 2 * 2 * tk.group_size * cfg.kv_dim * la
    meta = int(budget * 2.0) * 10 * la
    thin_per_req = pool + buf + meta

    rows = []
    for name, per in [("FullKV", full_per_req),
                      ("evict-only@%d" % budget, evict_per_req),
                      ("ThinKV@%d" % budget, thin_per_req)]:
        rows.append({
            "method": name,
            "kv_bytes_per_req": per,
            "footprint_pct_of_full": 100.0 * per / full_per_req,
            "max_batch": int(max(free // per, 0)),
        })
    return rows


def measured_maintenance(budget=1024, layers=8, h=8, d=128, group=16,
                         steps=256, seed=0):
    """Wall-time of per-step gather compaction vs per-group CT scatter."""
    rng = np.random.default_rng(seed)
    n_slots = budget * 2
    k_pool = jnp.asarray(rng.standard_normal((layers, n_slots, h, d)),
                         jnp.bfloat16)

    @jax.jit
    def gather_compact(pool, keep_idx):
        return jnp.take(pool, keep_idx, axis=1)       # R-KV per-step gather

    @functools.partial(jax.jit, donate_argnums=(0,))
    def ct_scatter(pool, slot_idx, vals):
        # CT per-group scatter; donation makes it a true in-place update
        return pool.at[:, slot_idx].set(vals)

    keep_idx = jnp.asarray(rng.choice(n_slots, budget, replace=False))
    slot_idx = jnp.asarray(rng.choice(n_slots, group, replace=False))
    vals = jnp.asarray(rng.standard_normal((layers, group, h, d)),
                       jnp.bfloat16)

    gather_compact(k_pool, keep_idx).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        out = gather_compact(k_pool, keep_idx)
    out.block_until_ready()
    t_gather = (time.perf_counter() - t0) / steps

    pool = ct_scatter(k_pool, slot_idx, vals)
    pool.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps // group):
        pool = ct_scatter(pool, slot_idx, vals)
    pool.block_until_ready()
    t_scatter_per_group = (time.perf_counter() - t0) / max(steps // group, 1)

    # per-token maintenance cost: gather fires EVERY step (paper Table 5:
    # ~83% call rate); CT scatter fires once per g tokens
    per_tok_gather = t_gather
    per_tok_ct = t_scatter_per_group / group
    # bytes-moved model (the HBM-contention mechanism of Obs. 4a/4b; wall
    # clock on CPU underestimates it — XLA CPU ignores buffer donation, so
    # the scatter path pays a pool copy it never pays on TPU):
    row = h * d * 2                                       # bf16 K row
    bytes_gather_tok = budget * row * layers * 2          # K+V, every step
    bytes_ct_tok = row * layers * 2                       # one slot amortized
    return {
        "gather_us_per_token": per_tok_gather * 1e6,
        "ct_us_per_token": per_tok_ct * 1e6,
        "measured_speedup": per_tok_gather / max(per_tok_ct, 1e-12),
        "hbm_bytes_per_token_gather": bytes_gather_tok,
        "hbm_bytes_per_token_ct": bytes_ct_tok,
        "speedup": bytes_gather_tok / bytes_ct_tok,
    }


def _smoke_tk():
    from repro.config import ThinKVConfig as TKC
    return TKC(refresh_interval=16, group_size=8, block_size=8,
               token_budget=48, retention_schedule=(16, 8, 4),
               min_retention=4, max_segments=64, kmeans_iters=4)


def engine_throughput(arch="r1-llama-8b", requests=3, slots=2,
                      prompt_len=24, max_new=24, seed=0):
    """Measured decode tokens/s per backend + chunked-prefill tokens/s,
    each backend tagged with its per-tick pallas launch count.

    Off-TPU the kernel backend runs the Pallas kernel in INTERPRET mode —
    orders of magnitude slower than compiled; its number here validates the
    path end to end rather than demonstrating the HBM win (that is the
    TPU-compiled measurement in the ROADMAP's open items).
    """
    from repro.config import ServeConfig
    from repro.configs import get_smoke_config
    from repro.serving.engine import ThinKVEngine

    mcfg = get_smoke_config(arch)
    tk = _smoke_tk()
    scfg = ServeConfig(model=mcfg, thinkv=tk, max_seqs=slots,
                       temperature=0.0)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, mcfg.vocab_size, prompt_len)
               for _ in range(requests)]

    rows = {}
    params = None
    for backend in ("reference", "kernel"):
        eng = ThinKVEngine(scfg, params=params, backend=backend)
        params = eng.params
        # full compiled-path contract audit (repro.analysis): exact
        # launch counts, collective whitelist, no callbacks/fp64 on
        # EVERY entry point — not just the tick count this row records
        audit = eng.audit_compiled()
        if not audit.ok:
            raise SystemExit("compiled-path contract audit failed:\n"
                             + audit.summary())
        launches = audit.entries["_tick_fn"].census.launches_at(1)
        # warm the tick + prefill jits OUTSIDE the timed window (first call
        # pays trace/compile — dominant on CPU, huge for interpret mode)
        eng.submit([prompts[0].copy()], max_new_tokens=2)
        eng.run()
        base = dict(eng.metrics)
        # prefill-only pass: same prompts, 1 token (no decode ticks) —
        # isolates prefill wall time so the decode rate excludes it
        eng.submit([p.copy() for p in prompts], max_new_tokens=1)
        t0 = time.perf_counter()
        eng.run()
        prefill_wall = time.perf_counter() - t0
        mid = dict(eng.metrics)
        eng.submit([p.copy() for p in prompts], max_new_tokens=max_new)
        t0 = time.perf_counter()
        done = eng.run()
        wall = time.perf_counter() - t0
        decode_toks = eng.metrics["tokens"] - mid["tokens"]
        prefill_toks = mid["prefill_tokens"] - base["prefill_tokens"]
        # ~= wall minus the second run's (equal-prompt) prefill phase;
        # floored at 5% of wall so timer noise on tiny runs cannot produce
        # a near-zero denominator (and an absurd tok/s)
        decode_wall = max(wall - prefill_wall, 0.05 * wall)
        rows[backend] = {
            "decode_tokens": decode_toks,
            "prefill_tokens": prefill_toks,
            "wall_s": wall,
            "decode_tok_per_s": decode_toks / decode_wall,
            "prefill_chunks": (mid["prefill_chunks"]
                               - base["prefill_chunks"]),
            "requests": len(done),
            "pallas_launches_per_tick": launches,
            "pool_blocks": eng.num_pool_blocks,
            "preemptions": eng.metrics["preemptions"],
        }
    # prefill tokens/s measured separately: prompt-only requests on a
    # freshly warmed reference engine
    eng = ThinKVEngine(scfg, params=params, backend="reference")
    eng.submit([prompts[0].copy()], max_new_tokens=1)
    eng.run()
    warm_prefill = eng.metrics["prefill_tokens"]
    warm_chunks = eng.metrics["prefill_chunks"]
    eng.submit([p.copy() for p in prompts], max_new_tokens=1)
    t0 = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t0
    toks = eng.metrics["prefill_tokens"] - warm_prefill
    rows["prefill"] = {
        "tokens": toks,
        "wall_s": wall,
        "tok_per_s": toks / max(wall, 1e-9),
        "chunks": eng.metrics["prefill_chunks"] - warm_chunks,
    }
    return rows


def layer_sweep(layers=(4, 16, 32), arch="r1-llama-8b", ticks=6, slots=1,
                seed=0):
    """Per-tick decode wall time + pallas launch count at several layer
    counts: the launch-amortization win of the fused single-launch tick.

    Drives the jitted tick directly (fixed cache state, no scheduler) —
    the measurement isolates per-tick dispatch + attention cost, which is
    what the layer fold changes.
    """
    from repro.config import ServeConfig
    from repro.configs import get_smoke_config
    from repro.serving.engine import ThinKVEngine

    rows = []
    for L in layers:
        mcfg = dataclasses.replace(get_smoke_config(arch), num_layers=L)
        scfg = ServeConfig(model=mcfg, thinkv=_smoke_tk(), max_seqs=slots,
                           temperature=0.0)
        row = {"layers": int(L)}
        params = None
        for backend in ("reference", "kernel"):
            eng = ThinKVEngine(scfg, params=params, backend=backend)
            params = eng.params
            args = (eng.params, eng.pool, eng.tables, eng.caches,
                    jnp.zeros(slots, jnp.int32), jnp.ones(slots, bool),
                    eng._slot_rng)
            jax.block_until_ready(eng._tick(*args))      # warm the jit
            t0 = time.perf_counter()
            for _ in range(ticks):
                out = eng._tick(*args)
            jax.block_until_ready(out)
            wall = time.perf_counter() - t0
            row[backend] = {
                "tick_ms": 1e3 * wall / ticks,
                "decode_tok_per_s": slots * ticks / wall,
                "pallas_launches_per_tick": eng.tick_launch_count(),
            }
        rows.append(row)
        print(f"  L={L:3d}: reference {row['reference']['tick_ms']:8.1f}"
              f" ms/tick ({row['reference']['pallas_launches_per_tick']}"
              f" launches) | kernel {row['kernel']['tick_ms']:8.1f} ms/tick"
              f" ({row['kernel']['pallas_launches_per_tick']} launch)")
    return rows


def oversubscription_sweep(fracs=(1.0, 0.5, 0.25), arch="r1-llama-8b",
                           requests=6, slots=4, prompt_len=12, max_new=32,
                           seed=0):
    """Engine throughput vs pool size: the shared block pool at ``fracs``
    of the dense worst case (``slots * NB``), with mixed priorities.

    At every pool size ALL requests must complete with their full token
    count — under pressure the engine pauses victims (spill to host) and
    resumes them later, it never drops data.  Reports throughput,
    preemption/resume counts, and mean queue wait per pool size so the
    cross-PR log can track the cost of oversubscription."""
    from repro.config import ServeConfig
    from repro.configs import get_smoke_config
    from repro.core import ct_cache as CC
    from repro.serving.engine import ThinKVEngine

    mcfg = get_smoke_config(arch)
    tk = _smoke_tk()
    scfg = ServeConfig(model=mcfg, thinkv=tk, max_seqs=slots,
                       temperature=0.0)
    dims = CC.make_dims(tk, mcfg.num_layers, mcfg.num_kv_heads,
                        mcfg.head_dim)
    worst = slots * dims.NB
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, mcfg.vocab_size, prompt_len)
               for _ in range(requests)]
    priorities = [i % 2 for i in range(requests)]

    rows = []
    params = None
    for frac in fracs:
        pool_blocks = max(int(worst * frac), 1)
        eng = ThinKVEngine(scfg, params=params, backend="reference",
                           pool_blocks=pool_blocks)
        params = eng.params
        eng.submit([p.copy() for p in prompts], max_new_tokens=max_new,
                   priorities=priorities)
        t0 = time.perf_counter()
        done = eng.run()
        wall = time.perf_counter() - t0
        full = sum(len(r.output) == max_new for r in done)
        if len(done) != requests or full != requests:
            raise SystemExit(
                f"oversubscription regression at pool_frac={frac}: "
                f"{len(done)}/{requests} finished, {full} with full "
                f"outputs (dropped tokens)")
        row = {
            "pool_frac": frac,
            "pool_blocks": pool_blocks,
            "worst_case_blocks": worst,
            "requests": requests,
            "completed": len(done),
            "tokens": eng.metrics["tokens"],
            "decode_tok_per_s": eng.metrics["tokens"] / max(wall, 1e-9),
            "preemptions": eng.metrics["preemptions"],
            "resumes": eng.metrics["resumes"],
            "mean_queue_wait_ticks": (eng.metrics["queue_wait_ticks"]
                                      / max(eng.metrics["admissions"], 1)),
        }
        rows.append(row)
        print(f"  pool {100 * frac:5.0f}% ({pool_blocks:4d} blocks): "
              f"{row['decode_tok_per_s']:7.1f} tok/s | "
              f"{row['preemptions']:3d} preemptions | queue wait "
              f"{row['mean_queue_wait_ticks']:.1f} ticks")
    return rows


def prefix_sweep(shared_fracs=(0.0, 0.5, 1.0), arch="r1-llama-8b",
                 requests=6, slots=2, prompt_len=24, max_new=16, seed=0):
    """Engine throughput vs shared-prompt fraction under copy-on-write
    prefix caching: ``shared_fracs`` of every prompt's tokens are common
    across requests (1.0 = identical prompts — the shared-system-prompt
    fleet shape).  Reports prefill tokens skipped, hit rate, COW faults,
    and decode+prefill throughput per fraction; every run's outputs are
    gated IDENTICAL to the cache-off run (sharing must never change the
    math)."""
    from repro.config import ServeConfig
    from repro.configs import get_smoke_config
    from repro.serving.engine import ThinKVEngine

    mcfg = get_smoke_config(arch)
    tk = _smoke_tk()
    scfg = ServeConfig(model=mcfg, thinkv=tk, max_seqs=slots,
                       temperature=0.0)
    rng = np.random.default_rng(seed)

    rows = []
    params = None
    for frac in shared_fracs:
        shared_len = int(round(prompt_len * frac))
        # commit-aligned shared prefix so partial hits can attach
        shared_len -= shared_len % tk.group_size
        shared = rng.integers(0, mcfg.vocab_size, shared_len)
        prompts = [np.concatenate([
            shared, rng.integers(0, mcfg.vocab_size,
                                 prompt_len - shared_len)])
            for _ in range(requests)]

        outs = {}
        for cached in (False, True):
            eng = ThinKVEngine(scfg, params=params, backend="reference",
                               prefix_cache=cached)
            params = eng.params
            eng.submit([p.copy() for p in prompts], max_new_tokens=max_new)
            t0 = time.perf_counter()
            done = eng.run()
            wall = time.perf_counter() - t0
            outs[cached] = {r.uid: r.output for r in done}
            if cached:
                eng.audit_pool()
                pc = eng.prefix_cache.stats()
                row = {
                    "shared_frac": frac,
                    "shared_prefix_tokens": int(shared_len),
                    "requests": requests,
                    "completed": len(done),
                    "prefix_hits": eng.metrics["prefix_hits"],
                    "hit_rate": eng.metrics["prefix_hits"] / requests,
                    "prefill_tokens": eng.metrics["prefill_tokens"],
                    "prefill_tokens_skipped":
                        eng.metrics["prefix_tokens_skipped"],
                    "cow_faults": eng.metrics["cow_faults"],
                    "cache_entries": pc["entries"],
                    "cache_evictions": pc["evictions"],
                    "tok_per_s": (eng.metrics["tokens"]
                                  + eng.metrics["prefill_tokens"])
                        / max(wall, 1e-9),
                }
        if outs[True] != outs[False]:
            raise SystemExit(
                f"prefix-cache regression at shared_frac={frac}: cached "
                f"outputs differ from the cache-off run (sharing changed "
                f"the math)")
        if frac >= 1.0 and row["prefix_hits"] < requests - 1:
            raise SystemExit(
                f"prefix-cache regression: identical prompts scored "
                f"{row['prefix_hits']} hits (expected {requests - 1})")
        rows.append(row)
        print(f"  shared {100 * frac:5.0f}% ({shared_len:3d} tok): "
              f"hit rate {row['hit_rate']:4.2f} | "
              f"{row['prefill_tokens_skipped']:4d} prefill tok skipped | "
              f"{row['cow_faults']:3d} COW faults | "
              f"{row['tok_per_s']:7.1f} tok/s")
    return rows


def streaming_sweep(loads=(0.5, 1.5), pool_fracs=(1.0, 0.5),
                    arch="r1-llama-8b", requests=6, slots=2,
                    prompt_len=12, max_new=16, seed=0):
    """Open-loop streamed serving latency: the asyncio orchestrator under
    seeded Poisson arrivals in TICK space, swept over offered load (as a
    multiple of the saturated service rate ``slots / max_new`` requests
    per tick) x pool fraction.  Per cell: decode tok/s plus per-request
    TTFT / TPOT / queue-wait p50/p99 — the latency side of Table 2 that
    the closed-loop batch rows cannot show (at 1.5x offered load the
    queue-wait tail is the cost of oversubscription; TPOT should stay
    flat because the tick itself is unchanged).  Every cell must still
    complete every request — open-loop pressure may queue work, never
    drop it."""
    from repro.config import ServeConfig
    from repro.configs import get_smoke_config
    from repro.core import ct_cache as CC
    from repro.serving.engine import ThinKVEngine
    from repro.serving.orchestrator import Orchestrator

    mcfg = get_smoke_config(arch)
    tk = _smoke_tk()
    scfg = ServeConfig(model=mcfg, thinkv=tk, max_seqs=slots,
                       temperature=0.0)
    dims = CC.make_dims(tk, mcfg.num_layers, mcfg.num_kv_heads,
                        mcfg.head_dim)
    worst = slots * dims.NB
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, mcfg.vocab_size, prompt_len)
               for _ in range(requests)]

    rows = []
    params = None
    for frac in pool_fracs:
        for load in loads:
            rate = load * slots / max_new          # requests per tick
            gaps = np.random.default_rng(seed + 1).exponential(
                1.0 / rate, requests)
            at_tick = np.floor(np.cumsum(gaps)).astype(int)
            eng = ThinKVEngine(scfg, params=params, backend="reference",
                               pool_blocks=max(int(worst * frac), 1))
            params = eng.params
            # warm the jits outside the timed window
            eng.submit([prompts[0].copy()], max_new_tokens=2)
            eng.run()
            base_tokens = eng.metrics["tokens"]
            warmed = len(eng.scheduler.finished)
            orch = Orchestrator(eng)
            for i, p in enumerate(prompts):
                orch.schedule_arrival(after_tick=int(at_tick[i]),
                                      prompt=p.copy(),
                                      max_new_tokens=max_new, uid=i)
            t0 = time.perf_counter()
            # finished accumulates across episodes: drop the warm-up run
            done = orch.run_sync()[warmed:]
            wall = time.perf_counter() - t0
            full = sum(len(r.output) == max_new for r in done)
            if len(done) != requests or full != requests:
                raise SystemExit(
                    f"streaming regression at load={load} "
                    f"pool_frac={frac}: {len(done)}/{requests} finished, "
                    f"{full} with full outputs")
            pct = orch.percentiles(
                keys=("ttft_s", "ttft_ticks", "tpot_s",
                      "queue_wait_ticks"))
            row = {
                "offered_load": load,
                "arrival_rate_per_tick": rate,
                "pool_frac": frac,
                "pool_blocks": eng.num_pool_blocks,
                "requests": requests,
                "completed": len(done),
                "decode_tok_per_s": (eng.metrics["tokens"] - base_tokens)
                / max(wall, 1e-9),
                "preemptions": eng.metrics["preemptions"],
                "prefill_overlapped_decode":
                    orch.prefill_overlaps_decode(),
                "latency": pct,
            }
            rows.append(row)
            qw = pct.get("queue_wait_ticks", {"p50": 0.0, "p99": 0.0})
            tt = pct.get("ttft_ticks", {"p50": 0.0, "p99": 0.0})
            print(f"  load {load:4.2f}x pool {100 * frac:4.0f}%: "
                  f"{row['decode_tok_per_s']:7.1f} tok/s | TTFT p50/p99 "
                  f"{tt['p50']:5.1f}/{tt['p99']:5.1f} ticks | queue wait "
                  f"p50/p99 {qw['p50']:5.1f}/{qw['p99']:5.1f} ticks | "
                  f"{row['preemptions']:2d} preemptions")
    return rows


def policy_sweep(policies=("thinkv", "rkv", "uniform"),
                 variants=None, pool_fracs=(1.0, 0.5),
                 arch="r1-llama-8b", requests=4, slots=2, prompt_len=12,
                 max_new=24, budget=24, tau=8, seed=0, smoke=False):
    """Cache-size-vs-quality frontier across retention policies (the
    serving-trace analogue of the paper's Fig. 8/10 accuracy-vs-budget
    curves): every cell streams an OVERSUBSCRIBED workload through one
    registered policy x one (bit-mix, eviction-aggressiveness) config
    variant x one pool fraction with the logit-drift probe on, and
    records mean footprint fraction against drift vs the uncompressed
    dense replay.

    Frontier reading: footprint_frac is the x-axis (cache cost), drift
    mean |dlogit| / top-1 agreement the y-axis (quality proxy).  The
    probe's dense replay shares the attention-late tick dataflow delta
    across ALL policies, so cross-policy comparisons isolate retention
    quality (docs/policy.md).

    Gates (every cell): all requests complete with full outputs, every
    finished request carries finite drift stats, the pool refcount audit
    is clean, and the compiled-path contract audit passes with the
    policy's entry points (incl. the drift probe) registered."""
    from repro.config import ServeConfig
    from repro.configs import get_smoke_config
    from repro.core import ct_cache as CC
    from repro.serving.engine import ThinKVEngine
    from repro.serving.orchestrator import Orchestrator

    if variants is None:
        variants = [
            # (name, precision (T,E,R), retention_schedule, min_retention)
            ("paper", (2, 4, 4), (16, 8, 4), 4),
        ]
        if not smoke:
            variants += [
                ("high-bits", (4, 8, 8), (16, 8, 4), 4),
                ("aggressive", (2, 4, 4), (8, 4, 2), 2),
            ]
    mcfg = get_smoke_config(arch)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, mcfg.vocab_size, prompt_len)
               for _ in range(requests)]

    rows = []
    params = None
    for vname, precision, sched, min_ret in variants:
        # token_budget/tau tightened below the generated length so every
        # cell actually exercises eviction + annealing — with slack
        # budgets the policies never act and the frontier collapses to
        # one point
        tk = dataclasses.replace(_smoke_tk(), precision=precision,
                                 retention_schedule=sched,
                                 min_retention=min_ret,
                                 token_budget=budget,
                                 refresh_interval=tau)
        scfg = ServeConfig(model=mcfg, thinkv=tk, max_seqs=slots,
                           temperature=0.0)
        dims = CC.make_dims(tk, mcfg.num_layers, mcfg.num_kv_heads,
                            mcfg.head_dim)
        worst = slots * dims.NB
        for policy in policies:
            for frac in pool_fracs:
                cell = f"policy={policy} variant={vname} pool_frac={frac}"
                eng = ThinKVEngine(scfg, params=params,
                                   backend="reference",
                                   pool_blocks=max(int(worst * frac), 1),
                                   policy=policy, drift_probe=True)
                params = eng.params
                orch = Orchestrator(eng)
                for i, p in enumerate(prompts):
                    orch.schedule_arrival(after_tick=0, prompt=p.copy(),
                                          max_new_tokens=max_new, uid=i)
                t0 = time.perf_counter()
                done = orch.run_sync()
                wall = time.perf_counter() - t0
                full = sum(len(r.output) == max_new for r in done)
                if len(done) != requests or full != requests:
                    raise SystemExit(
                        f"policy-sweep regression at {cell}: "
                        f"{len(done)}/{requests} finished, {full} with "
                        f"full outputs")
                drifts = [r.stats.get("drift") for r in done]
                if any(d is None for d in drifts) or any(
                        not (np.isfinite(d["max_abs"])
                             and np.isfinite(d["mean_abs"])
                             and d["steps"] > 0) for d in drifts):
                    raise SystemExit(
                        f"policy-sweep regression at {cell}: missing or "
                        f"non-finite drift stats on a finished request")
                try:
                    eng.audit_pool()
                except AssertionError as exc:
                    raise SystemExit(
                        f"policy-sweep regression at {cell}: pool "
                        f"refcount audit: {exc}")
                audit = eng.audit_compiled()
                if not audit.ok:
                    raise SystemExit(
                        f"policy-sweep regression at {cell}: compiled-"
                        f"path contract audit failed:\n" + audit.summary())
                if "_drift_probe_fn" not in audit.entries:
                    raise SystemExit(
                        f"policy-sweep regression at {cell}: drift probe "
                        f"entry point never registered for audit")
                row = {
                    "policy": policy,
                    "variant": vname,
                    "precision": list(precision),
                    "retention_schedule": list(sched),
                    "min_retention": min_ret,
                    "pool_frac": frac,
                    "pool_blocks": eng.num_pool_blocks,
                    "requests": requests,
                    "completed": len(done),
                    "preemptions": eng.metrics["preemptions"],
                    "decode_tok_per_s":
                        eng.metrics["tokens"] / max(wall, 1e-9),
                    # frontier x-axis: cache cost
                    "footprint_frac": float(np.mean(
                        [r.stats["footprint_frac"] for r in done])),
                    "avg_bits": float(np.mean(
                        [r.stats["avg_bits"] for r in done])),
                    # frontier y-axis: quality proxy vs dense replay
                    "drift_max_abs": float(max(
                        d["max_abs"] for d in drifts)),
                    "drift_mean_abs": float(np.mean(
                        [d["mean_abs"] for d in drifts])),
                    "drift_top1_agree": float(np.mean(
                        [d["top1_agree"] for d in drifts])),
                }
                rows.append(row)
                print(f"  {policy:8s} {vname:11s} pool {100 * frac:4.0f}%:"
                      f" footprint {100 * row['footprint_frac']:6.2f}% | "
                      f"{row['avg_bits']:.2f} bits | drift mean "
                      f"{row['drift_mean_abs']:.4f} / max "
                      f"{row['drift_max_abs']:.4f} | top-1 "
                      f"{100 * row['drift_top1_agree']:5.1f}% | "
                      f"{row['preemptions']:2d} preemptions")
    if len({r["policy"] for r in rows}) < 2:
        raise SystemExit(
            "policy-sweep regression: fewer than 2 distinct policies "
            "swept — the frontier needs at least a comparison pair")
    return rows


def _device_dispatch_time(eng, reps=5):
    """Warmed wall time of ONE decode dispatch (single tick or mega pack)
    on a state snapshot with every slot active — the pure device +
    dispatch cost, no host scheduling between launches."""
    R = eng.cfg.max_seqs
    tokens = jnp.zeros(R, jnp.int32)
    active = jnp.ones(R, bool)
    if eng._megatick is not None:
        fn, args = eng._megatick, (
            eng.params, eng.pool, eng.tables, eng.caches, tokens, active,
            eng._slot_rng, jnp.full(R, 10 ** 6, jnp.int32),
            jnp.full(R, -1, jnp.int32),
            jnp.int32(eng.ticks_per_dispatch))
    else:
        fn, args = eng._tick, (
            eng.params, eng.pool, eng.tables, eng.caches, tokens, active,
            eng._slot_rng)
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def dispatch_sweep(tpds=(1, 4, 8), samples=(1, 2), arch="r1-llama-8b",
                   requests=4, slots=3, prompt_len=16, max_new=32, seed=0):
    """Mega-dispatch measurement: Python dispatches per decoded token and
    the host-gap share of wall time, swept over ``ticks_per_dispatch`` x
    ``samples_per_slot`` (COW-forked best-of-n).  ``device_s_est`` is the
    warmed per-dispatch device time times the dispatch count; the
    remainder of wall time (``host_gap_s_est``) is host scheduling +
    prefill — the cost the mega-dispatch amortises.  ``main`` gates
    ``dispatches_per_token < 1`` at ticks_per_dispatch >= 8."""
    from repro.config import ServeConfig
    from repro.configs import get_smoke_config
    from repro.serving.engine import ThinKVEngine
    from repro.serving.orchestrator import Orchestrator

    mcfg = get_smoke_config(arch)
    scfg = ServeConfig(model=mcfg, thinkv=_smoke_tk(), max_seqs=slots,
                       temperature=0.0)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, mcfg.vocab_size, prompt_len)
               for _ in range(requests)]
    rows, params = [], None
    for spr in samples:
        for tpd in tpds:
            eng = ThinKVEngine(scfg, params=params, backend="reference",
                               ticks_per_dispatch=tpd,
                               allow_forks=spr > 1)
            params = eng.params
            # warm the prefill/tick/megatick jits outside the timed window
            eng.submit([prompts[0].copy()], max_new_tokens=2)
            eng.run()
            warmed = len(eng.scheduler.finished)
            base = dict(eng.metrics)
            per_dispatch_dev = _device_dispatch_time(eng)
            t0 = time.perf_counter()
            if spr > 1:
                orch = Orchestrator(eng)
                for i, p in enumerate(prompts):
                    orch.submit(p.copy(), max_new_tokens=max_new,
                                samples_per_slot=spr)
                orch.close()
                done = orch.run_sync()[warmed:]
            else:
                eng.submit([p.copy() for p in prompts],
                           max_new_tokens=max_new)
                done = eng.run()
            wall = time.perf_counter() - t0
            m = eng.metrics
            dispatches = m["dispatches"] - base["dispatches"]
            ticks = m["ticks"] - base["ticks"]
            tokens = m["tokens"] - base["tokens"]
            device_s = per_dispatch_dev * dispatches
            row = {
                "ticks_per_dispatch": int(tpd),
                "samples_per_slot": int(spr),
                "requests": requests,
                "completed": len(done),
                "dispatches": int(dispatches),
                "ticks": int(ticks),
                "tokens": int(tokens),
                "dispatches_per_token": dispatches / max(tokens, 1),
                "mean_ticks_per_dispatch": ticks / max(dispatches, 1),
                "early_exit_finish": int(m["early_exit_finish"]
                                         - base["early_exit_finish"]),
                "early_exit_headroom": int(m["early_exit_headroom"]
                                           - base["early_exit_headroom"]),
                "forks": int(m["forks"] - base["forks"]),
                "fork_cow_faults": int(m["fork_cow_faults"]
                                       - base["fork_cow_faults"]),
                "peak_refcount": int(m["peak_refcount"]),
                "wall_s": wall,
                "device_s_est": device_s,
                "host_gap_s_est": max(wall - device_s, 0.0),
            }
            rows.append(row)
            print(f"  tpd={tpd} samples={spr}: "
                  f"{row['dispatches_per_token']:.3f} dispatches/token "
                  f"({row['mean_ticks_per_dispatch']:.2f} ticks/dispatch)"
                  f" | host gap {row['host_gap_s_est']:6.2f}s of "
                  f"{row['wall_s']:6.2f}s wall | {row['forks']} fork(s)")
    return rows


def mesh_sweep_inner(devices=(1, 4, 8), arch="r1-llama-8b", requests=3,
                     slots=2, prompt_len=16, max_new=16, seed=0):
    """Engine decode throughput at ``model``-axis mesh sizes (runs in a
    process whose host device count covers max(devices); the smoke
    config's head counts are overridden to 8 so every mesh divides the
    KV-head axis).  Outputs are gated identical across mesh sizes — the
    head-sharded engine must not change a single sampled token."""
    from repro.config import ServeConfig
    from repro.configs import get_smoke_config
    from repro.serving.engine import ThinKVEngine

    mcfg = dataclasses.replace(get_smoke_config(arch), num_heads=8,
                               num_kv_heads=8)
    scfg = ServeConfig(model=mcfg, thinkv=_smoke_tk(), max_seqs=slots,
                      temperature=0.0)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, mcfg.vocab_size, prompt_len)
               for _ in range(requests)]
    rows, params, outputs0 = [], None, None
    for d in devices:
        mesh = None
        if d > 1:
            mesh = jax.make_mesh((d,), ("model",),
                                 axis_types=(AxisType.Auto,))
        eng = ThinKVEngine(scfg, params=params, backend="reference",
                           mesh=mesh)
        params = eng.params
        # warm the jits outside the timed window
        eng.submit([prompts[0].copy()], max_new_tokens=2)
        eng.run()
        base_tokens = eng.metrics["tokens"]
        eng.submit([p.copy() for p in prompts], max_new_tokens=max_new)
        t0 = time.perf_counter()
        done = eng.run()
        wall = time.perf_counter() - t0
        outs = {r.uid: r.output for r in done}
        if outputs0 is None:
            outputs0 = outs
        elif outs != outputs0:
            raise SystemExit(
                f"mesh-sweep regression: outputs at model={d} differ "
                f"from the 1-device run (sharding changed the math)")
        rows.append({
            "devices": int(d),
            "decode_tokens": eng.metrics["tokens"] - base_tokens,
            "wall_s": wall,
            "decode_tok_per_s": (eng.metrics["tokens"] - base_tokens)
            / max(wall, 1e-9),
            "pallas_launches_per_tick_per_shard": eng.tick_launch_count(),
        })
        print(f"  model={d}: {rows[-1]['decode_tok_per_s']:7.1f} tok/s | "
              f"{rows[-1]['pallas_launches_per_tick_per_shard']} launch"
              f"/tick/shard", flush=True)
    return rows


def mesh_sweep(devices=(1, 4, 8), smoke=False):
    """Re-exec :func:`mesh_sweep_inner` in a subprocess with enough faked
    host devices (XLA_FLAGS must be set before the first jax import, so
    the parent process cannot run the sweep itself)."""
    import sys
    from repro.launch.mesh import refuse_on_tpu
    refuse_on_tpu("the table2 mesh sweep")
    env = dict(os.environ)
    flag = f"--xla_force_host_platform_device_count={max(devices)}"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flag).strip()
    env.setdefault("PYTHONPATH", "src")
    cmd = [sys.executable, os.path.abspath(__file__), "--mesh-sweep-inner",
           ",".join(str(d) for d in devices)]
    if smoke:
        cmd.append("--smoke")
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       cwd=REPO_ROOT, timeout=3000)
    for line in r.stdout.splitlines():
        if line.startswith("MESH_SWEEP_JSON:"):
            print("\n".join(l for l in r.stdout.splitlines()
                            if l.startswith("  model=")))
            return json.loads(line[len("MESH_SWEEP_JSON:"):])
    raise SystemExit(
        f"mesh sweep subprocess failed (rc={r.returncode}):\n"
        f"{r.stdout[-3000:]}\n{r.stderr[-2000:]}")


def _git_sha() -> str:
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"], cwd=REPO_ROOT,
            stderr=subprocess.DEVNULL).decode().strip()
    except Exception:
        return "unknown"


def append_bench_log(record, path=BENCH_LOG):
    """Append one run record to the cross-PR perf trajectory log."""
    data = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
            assert isinstance(data, list)
        except Exception:
            data = []
    data.append(record)
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
        f.write("\n")


def main(out_path="benchmarks/results/table2_throughput.json", *,
         smoke=False, layers=None):
    out = {}
    for dev, hbm in [("A100-80GB", 80.0), ("TPUv5e-16GB", 16.0)]:
        rows = memory_model(hbm_gb=hbm)
        out[dev] = rows
        print(f"  {dev}:")
        for r in rows:
            print(f"    {r['method']:16s} {r['footprint_pct_of_full']:6.2f}% "
                  f"of FullKV   max_batch={r['max_batch']}")
    out["maintenance"] = measured_maintenance(steps=64 if smoke else 256)
    m = out["maintenance"]
    print(f"  cache maintenance: gather {m['gather_us_per_token']:.1f}us/tok"
          f" vs CT {m['ct_us_per_token']:.2f}us/tok "
          f"({m['speedup']:.0f}x)")
    if smoke:
        out["engine"] = engine_throughput(requests=2, slots=2, prompt_len=8,
                                          max_new=8)
    else:
        out["engine"] = engine_throughput()
    e = out["engine"]
    kmode = "compiled" if jax.default_backend() == "tpu" else "interpret"
    print(f"  engine decode: reference "
          f"{e['reference']['decode_tok_per_s']:.1f} tok/s "
          f"({e['reference']['pallas_launches_per_tick']} launches/tick) vs "
          f"kernel[{kmode}] {e['kernel']['decode_tok_per_s']:.1f} tok/s "
          f"({e['kernel']['pallas_launches_per_tick']} launch/tick) | "
          f"batched prefill {e['prefill']['tok_per_s']:.1f} tok/s "
          f"({e['prefill']['chunks']} chunks)")
    if e["kernel"]["pallas_launches_per_tick"] != 1:
        raise SystemExit(
            "kernel-path regression: decode tick dispatches "
            f"{e['kernel']['pallas_launches_per_tick']} pallas launches "
            "(expected exactly 1 — the fused single-launch tick)")
    if layers is None:
        layers = (2, 4) if smoke else (4, 16, 32)
    out["layer_sweep"] = layer_sweep(layers=layers)
    print("  oversubscription sweep (watermark admission + preemption):")
    if smoke:
        out["oversubscription"] = oversubscription_sweep(
            requests=3, slots=4, prompt_len=8, max_new=16)
    else:
        out["oversubscription"] = oversubscription_sweep()
    print("  prefix-hit-rate sweep (copy-on-write prefix caching):")
    if smoke:
        out["prefix"] = prefix_sweep(requests=3, slots=2, prompt_len=16,
                                     max_new=8)
    else:
        out["prefix"] = prefix_sweep()
    print("  streaming sweep (open-loop Poisson arrivals, asyncio "
          "orchestrator):")
    if smoke:
        out["streaming"] = streaming_sweep(
            loads=(1.5,), pool_fracs=(0.5,), requests=4, slots=2,
            prompt_len=8, max_new=8)
    else:
        out["streaming"] = streaming_sweep()
    print("  dispatch sweep (multi-tick mega-dispatch x COW forks):")
    if smoke:
        out["dispatch"] = dispatch_sweep(tpds=(1, 8), samples=(1, 2),
                                         requests=3, slots=2,
                                         prompt_len=8, max_new=16)
    else:
        out["dispatch"] = dispatch_sweep()
    for r in out["dispatch"]:
        if r["ticks_per_dispatch"] >= 8 and \
                r["dispatches_per_token"] >= 1.0:
            raise SystemExit(
                f"mega-dispatch regression: {r['dispatches_per_token']:.2f}"
                f" Python dispatches per decoded token at "
                f"ticks_per_dispatch={r['ticks_per_dispatch']} "
                f"(expected < 1 — the fused while_loop pack)")
    print("  policy sweep (retention policies x bit mixes x eviction "
          "aggressiveness, drift-probed):")
    if smoke:
        out["policy_frontier"] = policy_sweep(
            pool_fracs=(0.5,), requests=3, slots=2, prompt_len=8,
            max_new=20, budget=16, smoke=True)
    else:
        out["policy_frontier"] = policy_sweep()
    print("  device sweep (tensor-parallel serving, model-axis mesh):")
    out["mesh_sweep"] = mesh_sweep(devices=(1, 4, 8), smoke=smoke)
    if os.path.dirname(out_path):
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    append_bench_log({
        "git_sha": _git_sha(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend_mode": kmode,
        "smoke": bool(smoke),
        # pool_blocks + preemptions also live in each engine backend row so
        # cross-PR comparisons can tell oversubscribed runs apart
        "pool_blocks": out["engine"]["reference"]["pool_blocks"],
        "preemptions": out["engine"]["reference"]["preemptions"]
        + out["engine"]["kernel"]["preemptions"],
        "engine": out["engine"],
        "layer_sweep": out["layer_sweep"],
        "oversubscription": out["oversubscription"],
        "prefix": out["prefix"],
        "streaming": out["streaming"],
        "dispatch": out["dispatch"],
        "policy_frontier": out["policy_frontier"],
        "mesh_sweep": out["mesh_sweep"],
    })
    print(f"  perf trajectory appended to {BENCH_LOG}")
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny interpret-mode run (CI kernel-path "
                         "regression gate)")
    ap.add_argument("--layers", type=str, default=None,
                    help="comma-separated layer counts for the sweep, "
                         "e.g. 4,16,32")
    ap.add_argument("--mesh-sweep-inner", type=str, default=None,
                    help=argparse.SUPPRESS)   # subprocess entry (needs the
    #                                           faked host device count)
    ap.add_argument("--out", default="benchmarks/results/"
                                     "table2_throughput.json")
    a = ap.parse_args()
    if a.mesh_sweep_inner:
        devs = tuple(int(x) for x in a.mesh_sweep_inner.split(","))
        kw = dict(requests=2, slots=2, prompt_len=8, max_new=8) \
            if a.smoke else {}
        rows = mesh_sweep_inner(devices=devs, **kw)
        print("MESH_SWEEP_JSON:" + json.dumps(rows))
        raise SystemExit(0)
    main(a.out, smoke=a.smoke,
         layers=tuple(int(x) for x in a.layers.split(","))
         if a.layers else None)
