"""Readings that the correctness limits are set from.

    python bench/readings.py --workload <cell> --seeds 11,12,13 --seconds 40

Runs the cell once per seed in one process (the engine and weights are
rebuilt for each seed) and prints, per seed, the widest gap of the served
tokens against the plain reference (the program's reading) with the run's
verdict, and the widest gap of the tokens the reference's lower-precision
control ranks first at the same positions (the control's reading) with
the verdict the harness gives the control in the program's place.  It
needs the chip the cell asks for; the benchmark's own runs never run the
control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import run as R
    from harness import spec
    found = R.open_chip(args.workload, "readings.py")
    if found is None:
        return 2
    cell, devices = found
    cfg, mix = spec.config(cell["config"]), spec.mix(cell["traffic"])
    peaks = spec.peaks(devices[0].device_kind)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = R.run_cell(cell, cfg, mix, peaks, seed=seed,
                         seconds=args.seconds, trace=False, t_start=t,
                         control=True, log=lambda s: print("  " + s))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "control_correct": out["control_correct"],
                          **out["compared"],
                          "metrics": {k: v["value"] for k, v in
                                      out["metrics"].items()}}), flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
