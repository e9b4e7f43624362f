"""The chip smoke's phases on the CPU (kernels in interpret mode), the
script's refusal without a TPU, and the compile-cache placement."""
import os
import subprocess
import sys
from pathlib import Path

from repro.config import ThinKVConfig
from repro.configs import get_config, get_smoke_config
from repro.launch import smoke

ROOT = Path(__file__).resolve().parents[1]


def test_smoke_phases_pass_at_smoke_size():
    """Every phase of ``chip_smoke.py`` at the qwen2-7b smoke size: the
    census, the counters, finite logits, and the kernel backend within
    the stated bound of the reference (interpret mode is far tighter)."""
    lines = []
    out = smoke.run_smoke(get_smoke_config("qwen2-7b"),
                          ThinKVConfig(token_budget=64),
                          full_layers=get_config("qwen2-7b").num_layers,
                          requests=6, long_new=80, short_new=4,
                          log=lines.append)
    counts = out["counts"]
    assert min(counts["commits"], counts["refreshes"],
               counts["evictions"]) > 0, counts
    cmp = out["compare"]
    assert cmp["decode_steps"] > 0
    assert max(cmp["prefill_max_abs"], cmp["decode_max_abs"]) < 1e-3, cmp
    assert any("backend=kernel kernels in interpret mode" in s
               for s in lines)


def test_chip_smoke_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


_PROBE = ("import jax; from repro.launch.compile_cache import "
          "enable_compile_cache as e; d = e(); "
          "print(d, jax.config.jax_compilation_cache_dir)")


def test_compile_cache_placement(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache
    goes to the fixed ``<checkout>/.jax_cache``.  Run in subprocesses so
    this process never turns the cache on."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    got = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    assert got == [str(ROOT / ".jax_cache")] * 2
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    got = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    assert got == [str(tmp_path)] * 2
