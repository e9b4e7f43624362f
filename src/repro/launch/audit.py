"""Compiled-path contract audit CLI:
``python -m repro.launch.audit [--fail-on-violation] [...]``.

The static counterpart of the trace suite's empirical parity cells: for
every cell of ``{backends} x {device counts} x {ticks-per-dispatch}``
this builds the serving engine, audits EVERY compiled entry point's
jaxpr against its declared ``CompiledContract``
(``repro.analysis.contracts``) — exact pallas launch counts, the
cross-shard collective whitelist, no callbacks / in-graph transfers /
fp64, no divergent cond branches — and additionally audits the
non-engine compiled paths (``flash_prefill``, the dryrun-seam
``prefill/decode/train`` steps) once per device count.

``--retrace`` also replays a small streamed pressure trace (prefix
sharing + oversubscribed pool through the asyncio orchestrator) under a
``RetraceGuard``: after the first warm batch, steady-state serving must
perform ZERO retraces and zero implicit device-to-host syncs.

Multi-device cells need ``XLA_FLAGS=--xla_force_host_platform_device_
count=N`` BEFORE the first jax import, so for each requested device
count that differs from the live process the CLI re-execs itself in a
subprocess with the flag set and merges the per-process JSON reports
into one ``analysis_report.json`` (the CI artifact).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path


def _build_engine(backend: str, devices: int, tpd: int, args):
    import numpy as np  # noqa: F401

    from repro.config import ServeConfig, ThinKVConfig
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_serve_mesh
    from repro.serving.engine import ThinKVEngine

    mcfg = get_smoke_config(args.arch)
    if devices > 1:
        mcfg = dataclasses.replace(mcfg, num_heads=args.heads,
                                   num_kv_heads=args.kv_heads)
    tk = ThinKVConfig(refresh_interval=16, group_size=8, block_size=8,
                      token_budget=args.budget,
                      retention_schedule=(16, 8, 4), min_retention=4,
                      max_segments=64, kmeans_iters=4)
    scfg = ServeConfig(model=mcfg, thinkv=tk, max_seqs=args.slots,
                       temperature=0.0)
    mesh = make_serve_mesh(f"model={devices}") if devices > 1 else None
    return ThinKVEngine(scfg, backend=backend, mesh=mesh,
                        ticks_per_dispatch=tpd,
                        prefix_cache=args.retrace)


def _stream(eng, prompts, max_new: int, stagger: int = 0):
    """Serve ``prompts`` through the asyncio orchestrator (one consumer
    task per request token stream), arrivals staggered ``stagger`` ticks
    apart."""
    import asyncio

    from repro.serving.orchestrator import Orchestrator

    orch = Orchestrator(eng)

    async def go():
        streams = [orch.schedule_arrival(after_tick=i * stagger, prompt=p,
                                         max_new_tokens=max_new)
                   for i, p in enumerate(prompts)]

        async def drain(s):
            async for _tok in s:
                pass

        consumers = [asyncio.ensure_future(drain(s)) for s in streams]
        orch.close()
        done = await orch.serve()
        for c in consumers:
            await c
        return done

    return asyncio.run(go()), orch


def _retrace_cell(backend: str, args) -> dict:
    """Streamed pressure-trace replay under the RetraceGuard: warmup
    batch (compiles every entry point), then a steady phase with
    different arrivals / pool pressure that must retrace NOTHING."""
    import numpy as np

    from repro.analysis import RetraceGuard

    eng = _build_engine(backend, 1, args.tpds[0], args)
    rng = np.random.default_rng(0)
    mk = lambda n, ln: [rng.integers(0, 256, ln) for _ in range(n)]
    with RetraceGuard(eng) as guard:
        # warmup: small + big-chunk prompts compile every prefill path
        _stream(eng, mk(2, args.slots * 4) +
                ([rng.integers(0, 256, eng.prefill_chunk + 8)]
                 if eng.prefill_chunk else []), max_new=8)
        guard.mark_steady()
        # steady phase: more requests, shared prefixes, staggered
        # arrivals — different batch/pool states over the SAME compiled
        # signatures
        shared = rng.integers(0, 256, 12)
        prompts = [np.concatenate([shared, p])
                   for p in mk(args.slots + 2, 6)] + mk(2, 3)
        _stream(eng, prompts, max_new=12, stagger=2)
        guard.assert_steady_state()
        rep = guard.report()
    rep["ok"] = rep["steady_retraces"] == 0
    return rep


def _run_cells(args) -> dict:
    """Audit every cell runnable in THIS process (single device count)."""
    import jax

    from repro.analysis import audit_engine, audit_flash_prefill
    from repro.analysis.contracts import _model_step_audits

    devices = jax.device_count()
    out = {"devices": devices, "cells": [], "steps": {}, "retrace": {}}
    for backend in args.backends:
        for tpd in args.tpds:
            eng = _build_engine(backend, devices, tpd, args)
            rep = audit_engine(eng)
            cell = {"backend": backend, "devices": devices,
                    "ticks_per_dispatch": tpd, **rep.to_dict()}
            out["cells"].append(cell)
            tag = f"{backend} x {devices}dev x tpd={tpd}"
            print(f"--- {tag} ---")
            print(rep.summary())
    fp = audit_flash_prefill()
    out["steps"]["flash_prefill"] = fp.to_dict()
    print(f"[{'OK ' if fp.ok else 'FAIL'}] flash_prefill: "
          f"launches={fp.census.launches}")
    if devices == 1:
        for name, a in _model_step_audits(args.arch).items():
            out["steps"][name] = a.to_dict()
            print(f"[{'OK ' if a.ok else 'FAIL'}] {name}: "
                  f"launches={a.census.launches} "
                  f"fp64={len(a.census.fp64)} "
                  f"callbacks={len(a.census.callbacks)}")
    if args.retrace and devices == 1:
        for backend in args.backends:
            rep = _retrace_cell(backend, args)
            out["retrace"][backend] = rep
            print(f"[{'OK ' if rep['ok'] else 'FAIL'}] retrace[{backend}]:"
                  f" calls={rep['calls']} steady_retraces="
                  f"{rep['steady_retraces']}")
    return out


def _report_ok(report: dict) -> bool:
    return (all(c["ok"] for c in report["cells"])
            and all(s["ok"] for s in report["steps"].values())
            and all(r["ok"] for r in report["retrace"].values()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="compiled-path contract audit over a config x mesh "
                    "matrix (docs/analysis.md)")
    ap.add_argument("--arch", default="r1-llama-8b")
    ap.add_argument("--backends", default="reference,kernel",
                    help="comma list of engine backends to audit")
    ap.add_argument("--devices", default="1",
                    help="comma list of device counts (counts other than "
                         "this process's are re-execed in subprocesses "
                         "with XLA_FLAGS set)")
    ap.add_argument("--ticks-per-dispatch", default="1,8", dest="tpds",
                    help="comma list of mega-dispatch trip counts")
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--budget", type=int, default=48)
    ap.add_argument("--heads", type=int, default=8,
                    help="head override for multi-device cells (must "
                         "divide by the device count)")
    ap.add_argument("--kv-heads", type=int, default=8, dest="kv_heads")
    ap.add_argument("--retrace", action="store_true",
                    help="also replay a streamed pressure trace under "
                         "the RetraceGuard (1-device cells)")
    ap.add_argument("--fail-on-violation", action="store_true",
                    help="CI gate: exit nonzero on any contract "
                         "violation or steady-state retrace")
    ap.add_argument("--out", default="analysis_report.json",
                    help="merged JSON report path ('' = don't write)")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    args.backends = [b for b in args.backends.split(",") if b]
    args.tpds = [int(t) for t in str(args.tpds).split(",") if t]
    device_counts = [int(d) for d in str(args.devices).split(",") if d]

    if args.child or len(device_counts) == 1:
        # leaf process: everything runs under the live device count
        want = device_counts[0]
        if not args.child and want > 1 and "--xla_force_host_platform" \
                not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={want}")
        import jax
        if jax.device_count() != want:
            print(f"warning: requested {want} devices, process has "
                  f"{jax.device_count()} (XLA_FLAGS must precede the "
                  f"first jax import)", file=sys.stderr)
        report = {"ok": True, "matrix": [], "reports": [_run_cells(args)]}
    else:
        # parent: one subprocess per device count, merged report
        from repro.launch.mesh import refuse_on_tpu
        refuse_on_tpu("python -m repro.launch.audit --devices a,b")
        report = {"ok": True, "matrix": device_counts, "reports": []}
        for want in device_counts:
            env = dict(os.environ)
            flags = env.get("XLA_FLAGS", "")
            flags = " ".join(f for f in flags.split()
                             if "host_platform_device_count" not in f)
            if want > 1:
                flags += f" --xla_force_host_platform_device_count={want}"
            env["XLA_FLAGS"] = flags.strip()
            tmp = Path(args.out or "analysis_report.json").with_suffix(
                f".d{want}.json")
            child = [sys.executable, "-m", "repro.launch.audit",
                     "--child", "--arch", args.arch,
                     "--backends", ",".join(args.backends),
                     "--devices", str(want),
                     "--ticks-per-dispatch",
                     ",".join(map(str, args.tpds)),
                     "--slots", str(args.slots),
                     "--budget", str(args.budget),
                     "--heads", str(args.heads),
                     "--kv-heads", str(args.kv_heads),
                     "--out", str(tmp)]
            if args.retrace:
                child.append("--retrace")
            rc = subprocess.call(child, env=env)
            if rc != 0 or not tmp.exists():
                report["ok"] = False
                report["reports"].append(
                    {"devices": want, "error": f"subprocess rc={rc}",
                     "cells": [], "steps": {}, "retrace": {}})
                continue
            # the child writes a full wrapper report; merge its LEAF
            # reports (one per device count it actually ran)
            child_rep = json.loads(tmp.read_text())
            report["ok"] = report["ok"] and child_rep["ok"]
            report["reports"].extend(child_rep["reports"])
            tmp.unlink()

    report["ok"] = report["ok"] and all(
        _report_ok(r) for r in report["reports"] if "error" not in r)
    n_cells = sum(len(r["cells"]) for r in report["reports"])
    print(f"\naudit: {n_cells} engine cell(s) across device counts "
          f"{[r['devices'] for r in report['reports']]} -> "
          f"{'OK' if report['ok'] else 'VIOLATIONS'}")
    if args.out:
        Path(args.out).write_text(json.dumps(report, indent=2))
        print(f"report written to {args.out}")
    if args.fail_on_violation and not report["ok"]:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
