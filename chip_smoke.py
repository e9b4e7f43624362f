"""Chip smoke test: the serving engine's main path, once, on TPUs.

    python chip_smoke.py            # one chip: qwen2-7b widths, 4 layers
    python chip_smoke.py --chips 4  # tensor-parallel parity on four chips

Serves seeded requests through ``ThinKVEngine`` and the asyncio
orchestrator with the compiled Pallas kernels, and checks them
(``repro.launch.smoke``): contract census, counters, finite logits, and the
kernel backend against the reference backend.  ``--chips 4`` runs only the
sharded-serving parity phase.  The last line of standard output is
``{"ok": true, "device": {...}}``; without a TPU, or when any check
fails, the script exits non-zero and does not print it.
"""
import argparse
import dataclasses
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

LAYERS = 4            # depth cut of qwen2-7b (28 layers); widths unchanged
TOKEN_BUDGET = 512    # low enough that the long request evicts
LONG_NEW, SHORT_NEW = 600, 24


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip sharded parity phase")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"JAX finds {len(devices)}", file=sys.stderr)
        return 2

    from repro.config import ThinKVConfig
    from repro.configs import get_config
    from repro.launch import smoke
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x {len(devices)}")
    print(f"compile cache: {enable_compile_cache()}")
    full = get_config("qwen2-7b")
    mcfg = dataclasses.replace(full, num_layers=LAYERS)
    tk = ThinKVConfig(token_budget=TOKEN_BUDGET)
    print(f"thinkv: g={tk.group_size} BS={tk.block_size} "
          f"tau={tk.refresh_interval} token_budget={tk.token_budget}")
    kw = dict(full_layers=full.num_layers, long_new=LONG_NEW,
              short_new=SHORT_NEW)
    if args.chips == 4:
        smoke.run_mesh_parity(mcfg, tk, shards=4, **kw)
    else:
        smoke.run_smoke(mcfg, tk, expect_compiled=True, **kw)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
