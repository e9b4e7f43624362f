"""One checked pass over the serving engine's main path.

:func:`run_smoke` builds ``ThinKVEngine`` on the kernel backend, audits its
compiled entry points, serves seeded requests through the asyncio
``Orchestrator``, checks what comes out, and replays the same requests on
the reference backend to bound the kernels' logit error.
:func:`run_mesh_parity` serves the same requests once unsharded and once
head-sharded over ``--mesh model=N`` and compares the two.

``chip_smoke.py`` runs these on TPUs at qwen2-7b widths;
``tests/test_smoke.py`` runs them on the CPU at the smoke size, with the
kernels in interpret mode.  Every failed check raises
:class:`SmokeFailure`.  Wall times printed here are the host clock around
the served run on whatever device ran it — a smoke reading, not a
benchmark metric.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.config import ModelConfig, ServeConfig, ThinKVConfig
from repro.serving.engine import ThinKVEngine
from repro.serving.orchestrator import Orchestrator

#: Bound on max |kernel logit - reference logit|, relative to the largest
#: reference logit magnitude.  Both backends dequantize the same codes
#: exactly; what remains is matmul rounding (a TPU runs f32 XLA matmuls
#: in bf16 passes, ~4e-3 relative), while a wrong page, scale or mask
#: moves logits by a large share of their range.
LOGIT_BOUND_REL = 2e-2

#: Prompt lengths of the workload, cycled: 128 or more runs a big prefill
#: chunk (``flash_prefill`` + the batched paged kernel); a length that is
#: not a 128-multiple ends in g-sized tail chunks (the last one partial).
PROMPT_LENGTHS = (160, 137, 40, 23, 129, 64, 200, 48)


class SmokeFailure(AssertionError):
    """A smoke check failed."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@dataclasses.dataclass(frozen=True)
class Workload:
    prompts: Tuple[np.ndarray, ...]
    max_new: Tuple[int, ...]


def make_workload(vocab: int, *, requests: int, long_new: int,
                  short_new: int, seed: int = 0) -> Workload:
    """Seeded prompts over every prefill path; request 0 generates
    ``long_new`` tokens, the others ``short_new``."""
    rng = np.random.default_rng(seed)
    prompts = tuple(
        rng.integers(0, vocab, PROMPT_LENGTHS[i % len(PROMPT_LENGTHS)])
        .astype(np.int32) for i in range(requests))
    return Workload(prompts, (long_new,) + (short_new,) * (requests - 1))


@dataclasses.dataclass
class Served:
    """One engine's run of a workload, copied to the host."""
    outputs: List[List[int]]
    logits: List[np.ndarray]          # per request [steps, V]
    stats: List[Dict]
    wall_s: float
    metrics: Dict[str, float]


def serve(eng: ThinKVEngine, work: Workload) -> Served:
    """Serve ``work`` through the orchestrator (engine built with
    ``record_logits=True``); requests come back in submission order."""
    before = dict(eng.metrics)
    orch = Orchestrator(eng)
    streams = [orch.submit(p, max_new_tokens=n)
               for p, n in zip(work.prompts, work.max_new)]
    t0 = time.perf_counter()
    orch.run_sync()
    wall = time.perf_counter() - t0
    reqs = [s.request for s in streams]
    for r, n in zip(reqs, work.max_new):
        _check(len(r.output) == n,
               f"request {r.uid} emitted {len(r.output)} of {n} tokens")
    delta = {k: v - before.get(k, 0) for k, v in eng.metrics.items()
             if isinstance(v, (int, float))}
    return Served(
        outputs=[list(r.output) for r in reqs],
        logits=[np.stack(eng.request_logits[r.arrival]).astype(np.float32)
                for r in reqs],
        stats=[r.stats for r in reqs], wall_s=wall, metrics=delta)


def cache_counts(served: Served, group: int) -> Dict[str, int]:
    """Commits, refreshes and evicted token-slots (summed over layers) of
    the finished requests, from their final cache state."""
    commits = refreshes = evicted = 0
    for st in served.stats:
        committed = st["committed_tokens"]
        commits += committed // group
        refreshes += st["refreshes"]
        evicted += sum(committed - v for v in st["valid_tokens"])
    return {"commits": commits, "refreshes": refreshes,
            "evictions": evicted}


def compare_logits(a: Served, b: Served) -> Dict[str, float]:
    """Max |a - b| logit over each request's steps up to and including
    the first step whose greedy tokens differ (later steps see different
    contexts).  Step 0 is the prefill boundary, later steps decode."""
    prefill = decode = 0.0
    scale = 0.0
    steps = diverged = 0
    first = []
    for oa, ob, la, lb in zip(a.outputs, b.outputs, a.logits, b.logits):
        same = [x == y for x, y in zip(oa, ob)]
        n = same.index(False) + 1 if False in same else len(same)
        diverged += int(not all(same))
        first.append(n - 1 if not all(same) else None)
        d = np.abs(la[:n] - lb[:n]).max(axis=-1)
        prefill = max(prefill, float(d[0]))
        if n > 1:
            decode = max(decode, float(d[1:].max()))
            steps += n - 1
        scale = max(scale, float(np.abs(lb[:n]).max()))
    return {"prefill_max_abs": prefill, "decode_max_abs": decode,
            "decode_steps": steps, "ref_logit_max_abs": scale,
            "diverged_requests": diverged, "first_divergence": first}


def _wall(seconds: float) -> str:
    """A host-clock reading labelled with the device that ran the work."""
    return (f"{seconds:.3f}s {jax.devices()[0].platform} wall time "
            f"(smoke reading, not a benchmark metric)")


def _param_bytes(params) -> int:
    return int(sum(x.nbytes for x in jax.tree.leaves(params)))


def describe(mcfg: ModelConfig, full_layers: int, params, log) -> None:
    log(f"model {mcfg.name}: d_model {mcfg.d_model}, {mcfg.num_heads} "
        f"heads / {mcfg.num_kv_heads} kv heads x {mcfg.head_dim}, d_ff "
        f"{mcfg.d_ff}, vocab {mcfg.vocab_size}, qkv_bias {mcfg.qkv_bias}")
    log(f"depth cut: {mcfg.num_layers} of {full_layers} layers | params "
        f"{_param_bytes(params)} bytes "
        f"({jax.tree.leaves(params)[0].dtype})")


def _audit(eng: ThinKVEngine, log) -> None:
    """Census of every compiled entry point: contracts hold, the decode
    tick stages the fused paged kernel, and big-chunk prefill stages
    ``flash_prefill`` and the batched paged kernel."""
    report = eng.audit_compiled()
    log("compiled-path census:\n" + report.summary())
    _check(report.ok, "compiled-path contract audit failed")
    sites = {n: e.census.launch_sites for n, e in report.entries.items()}
    for name in ("_tick_fn", "_prefill_big_fn"):
        log(f"  {name} launch sites: {sorted(set(sites.get(name, ())))}")
    _check(any("ct_paged_attention_fused" in s for s in sites["_tick_fn"]),
           "decode tick stages no ct_paged_attention_fused launch")
    big = sites.get("_prefill_big_fn", [])
    _check(any("flash_prefill" in s for s in big)
           and any("ct_paged_attention_batched" in s for s in big),
           "big-chunk prefill does not stage flash_prefill + the paged "
           "kernel")


def run_smoke(mcfg: ModelConfig, tk: ThinKVConfig, *, full_layers: int,
              slots: int = 4, requests: int = 8, long_new: int,
              short_new: int, seed: int = 0, expect_compiled: bool = False,
              log: Callable[[str], None] = print) -> Dict:
    """The one-device smoke: kernel engine, census, served run, checks,
    then the reference replay.  Returns the readings it printed."""
    _check(long_new > tk.token_budget,
           "the long request must outgrow token_budget so eviction runs")
    scfg = ServeConfig(model=mcfg, thinkv=tk, max_seqs=slots,
                       temperature=0.0, seed=seed)
    work = make_workload(mcfg.vocab_size, requests=requests,
                         long_new=long_new, short_new=short_new, seed=seed)
    warm = Workload(work.prompts[1:2], (2,))

    t0 = time.perf_counter()
    eng = ThinKVEngine(scfg, backend="kernel", record_logits=True)
    jax.block_until_ready(eng.params)
    log(f"engine built in {_wall(time.perf_counter() - t0)}; "
        f"parameters initialised on the device from seed {seed}")
    describe(mcfg, full_layers, eng.params, log)
    compiled = eng._force is None
    log(f"backend={eng.backend} kernels "
        f"{'compiled' if compiled else 'in interpret mode'}")
    _check(eng.backend == "kernel", f"engine resolved to {eng.backend}")
    if expect_compiled:
        _check(compiled, "kernels run in interpret mode, not compiled")
    _audit(eng, log)

    t0 = time.perf_counter()
    serve(eng, warm)
    log(f"compile + warm-up request: {_wall(time.perf_counter() - t0)}")
    got = serve(eng, work)
    m = got.metrics
    log(f"served {requests} requests over {slots} slots: "
        f"{int(m['tokens'])} decode tokens + {int(m['prefill_tokens'])} "
        f"prompt tokens in {_wall(got.wall_s)} "
        f"({int(m['ticks'])} ticks, {int(m['prefill_big_chunks'])} big "
        f"prefill chunks, {int(m['prefill_chunks'])} g-chunks)")
    counts = cache_counts(got, tk.group_size)
    counts.update(preemptions=int(m["preemptions"]),
                  tokens=int(m["tokens"]))
    log("counters: " + ", ".join(f"{k} {v}" for k, v in counts.items()))
    for k in ("commits", "refreshes", "evictions"):
        _check(counts[k] > 0, f"no {k} happened")
    _check(m["prefill_big_chunks"] > 0, "no big prefill chunk ran")
    finite = all(bool(np.isfinite(lg).all()) for lg in got.logits)
    log(f"all {sum(len(lg) for lg in got.logits)} recorded logit rows "
        f"finite: {finite}")
    _check(finite, "non-finite logits")

    ref = ThinKVEngine(scfg, params=eng.params, backend="reference",
                       record_logits=True)
    serve(ref, warm)
    want = serve(ref, work)
    log(f"reference backend served the same requests in "
        f"{_wall(want.wall_s)}")
    cmp = compare_logits(got, want)
    bound = LOGIT_BOUND_REL * max(cmp["ref_logit_max_abs"], 1.0)
    log(f"kernel vs reference (temperature 0): max |dlogit| prefill "
        f"boundary {cmp['prefill_max_abs']:.6g}, decode "
        f"{cmp['decode_max_abs']:.6g} over {cmp['decode_steps']} steps "
        f"(bound {bound:.6g} = {LOGIT_BOUND_REL} x max |ref logit| "
        f"{cmp['ref_logit_max_abs']:.6g}); {cmp['diverged_requests']} of "
        f"{requests} requests' greedy tokens diverged")
    _check(cmp["decode_steps"] > 0, "no decode step was compared")
    _check(max(cmp["prefill_max_abs"], cmp["decode_max_abs"]) <= bound,
           "kernel logits outside the reference bound")
    return {"counts": counts, "compare": cmp, "bound": bound,
            "wall_s": got.wall_s}


def device_bytes() -> List[Optional[int]]:
    """Bytes in use per device, where the backend reports them."""
    out = []
    for d in jax.devices():
        st = d.memory_stats()
        out.append(int(st["bytes_in_use"]) if st else None)
    return out


def run_mesh_parity(mcfg: ModelConfig, tk: ThinKVConfig, *,
                    full_layers: int, shards: int, slots: int = 4,
                    requests: int = 8, long_new: int, short_new: int,
                    seed: int = 0,
                    log: Callable[[str], None] = print) -> Dict:
    """Tensor-parallel serving against the unsharded engine on the same
    parameters and requests.  The unsharded run goes first and is copied
    to the host and freed before the sharded engine places its replicas:
    two copies of the parameters never share a device."""
    from repro.launch.mesh import make_serve_mesh
    from repro.models import build_model

    scfg = ServeConfig(model=mcfg, thinkv=tk, max_seqs=slots,
                       temperature=0.0, seed=seed)
    work = make_workload(mcfg.vocab_size, requests=requests,
                         long_new=long_new, short_new=short_new, seed=seed)
    params = build_model(mcfg).init_params(seed)
    describe(mcfg, full_layers, params, log)
    one = ThinKVEngine(scfg, params=params, backend="kernel",
                       record_logits=True)
    base = serve(one, work)
    log(f"unsharded: {int(base.metrics['tokens'])} decode tokens in "
        f"{_wall(base.wall_s)}, compile included")
    host_params = jax.device_get(params)
    for x in jax.tree.leaves(params):
        x.delete()            # free device 0 even if a reference lingers
    del one, params
    gc.collect()

    mesh = make_serve_mesh(f"model={shards}")
    eng = ThinKVEngine(scfg, params=host_params, backend="kernel",
                       mesh=mesh, record_logits=True)
    del host_params
    got = serve(eng, work)
    log(f"model={shards}: {int(got.metrics['tokens'])} decode tokens in "
        f"{_wall(got.wall_s)}, compile included")
    plane = eng.pool.view.k_codes
    shard_shapes = sorted({tuple(s.data.shape)
                           for s in plane.addressable_shards})
    log(f"pool k_codes {tuple(plane.shape)} on "
        f"{len(plane.sharding.device_set)} devices, shards {shard_shapes}")
    _check(len(plane.sharding.device_set) == shards,
           "pool planes are not spread over every device")
    _check(shard_shapes == [plane.shape[:2] + (plane.shape[2] // shards,)
                            + plane.shape[3:]],
           "pool planes are not split on the kv-head axis")
    log(f"bytes in use per device: {device_bytes()}")

    same_tokens = got.outputs == base.outputs
    diff = max(float(np.abs(a - b).max())
               for a, b in zip(got.logits, base.logits))
    cmp = compare_logits(got, base)
    log(f"tokens match: {same_tokens} | logits bit-identical: "
        f"{diff == 0.0} (max |dlogit| {diff:.6g} over all steps)")
    log(f"first divergent step per request: {cmp['first_divergence']} | "
        f"max |dlogit| up to the first divergence: prefill boundary "
        f"{cmp['prefill_max_abs']:.6g}, decode {cmp['decode_max_abs']:.6g} "
        f"over {cmp['decode_steps']} steps")
    _check(same_tokens, "sharded tokens differ from the unsharded run")
    return {"tokens_match": same_tokens, "max_abs": diff, "compare": cmp}
