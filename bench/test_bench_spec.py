"""``BENCHMARK.json`` resolves to its files and keeps the shape its
readers expect; an unknown device is refused."""
import json
import re
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import context, spec  # noqa: E402

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1] == "bench/run.py"
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((spec.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_resolve(c):
    assert NAME.match(c["name"])
    path = spec.ROOT / c["file"]
    assert path.is_file() and c["file"].startswith("bench/")
    cfg = json.loads(path.read_text())
    assert cfg["name"] == c["name"]
    assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    assert cfg["limits"]["max_gap"] is not None
    context.load_module("systems", cfg["system"])
    context.load_module("references", cfg["reference"])
    assert any(w["config"] == c["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_workloads_resolve(w):
    assert NAME.match(w["name"]) and w["chips"] in (1, 4)
    assert len(w["why"]) <= 200
    cfg = spec.config(w["config"])
    assert cfg["name"] == w["config"]
    mix = spec.mix(w["traffic"])
    assert {"arrival", "prompt_tokens", "output_tokens", "check"} <= set(mix)
    kinds = {m["name"] for m in spec.metrics_for(BENCH, w["name"],
                                                  "end_to_end")}
    assert "setup_s" in kinds and len(kinds) >= 2
    assert spec.metrics_for(BENCH, w["name"], "per_layer")


METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metrics_resolve(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert hasattr(context.load_module("metrics", m["name"]), "read")
    names = {w["name"] for w in BENCH["workloads"]}
    assert set(m.get("workloads", names)) <= names
    if "bound" in m:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", names))
    if "roofline" in m["name"] or "mfu" in m["name"]:
        assert m["unit"] == "%"


def test_unknown_device_kind_is_refused():
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v99 imaginary")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_run_refuses_without_a_tpu(tmp_path):
    import subprocess
    r = subprocess.run([sys.executable, str(spec.BENCH / "run.py"),
                        "--workload", BENCH["workloads"][0]["name"],
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, timeout=120,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                            "HOME": str(tmp_path)})
    assert r.returncode != 0
    assert r.stdout.strip() == "" or "correct" not in r.stdout.splitlines()[-1]
