"""``BENCHMARK.json`` and the files each of its names resolves to.

Each configuration, traffic mix, metric, kernel count and device's peaks
is a file of its own, found by name:

* ``configs/<config>.json``, which names its ``system`` and ``reference``
  (``systems/<name>.py``, ``references/<name>.py``);
* ``traffic/<traffic>.json``;
* ``metrics/<metric>.py``;
* ``kernels/<kernel>.py``;
* ``peaks/<device_kind>.json``, the kind with every character outside
  letters, digits, ``_``, ``.`` and ``-`` replaced by ``_``.
"""
from __future__ import annotations

import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class SpecError(ValueError):
    """The benchmark's files do not resolve."""


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    path = BENCH / "configs" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no configuration file {path}")
    return json.loads(path.read_text())


def mix(name: str) -> dict:
    path = BENCH / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no traffic file {path}")
    return json.loads(path.read_text())


def metrics_for(bench: dict, cell_name: str, kind: str) -> list:
    """Entries of ``kind`` (``end_to_end`` or ``per_layer``) this cell
    reports: those whose ``workloads`` list it, or that have none."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def peaks_file(device_kind: str) -> Path:
    return BENCH / "peaks" / (re.sub(r"[^A-Za-z0-9_.-]", "_", device_kind)
                              + ".json")


def peaks(device_kind: str) -> dict:
    """The device's published peaks; a kind not in the table is an
    error, never a default."""
    path = peaks_file(device_kind)
    if not path.is_file():
        raise SpecError(f"no peak table for device kind {device_kind!r} "
                        f"({path.name})")
    p = json.loads(path.read_text())
    if p["device_kind"] != device_kind:
        raise SpecError(f"{path.name} is for {p['device_kind']!r}, not "
                        f"{device_kind!r}")
    return p
