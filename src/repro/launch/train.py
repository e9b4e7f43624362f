"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

CPU-demo defaults run a reduced config; ``--full`` selects the assigned
full-size architecture (intended for real accelerator fleets; combine with
``--mesh-shape``).  Fault tolerance is on by default: checkpoints land in
--ckpt-dir and the launcher auto-resumes.
"""
from __future__ import annotations

import argparse

import jax
from jax.sharding import AxisType

from repro.config import MeshConfig, OptimizerConfig, TrainConfig
from repro.configs import get_config, get_smoke_config
from repro.data.synthetic import lm_batches
from repro.ft.failures import FailureInjector
from repro.training.trainer import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh-shape", default="")
    ap.add_argument("--fail-at", type=int, default=0,
                    help="inject a failure at this step (FT demo)")
    args = ap.parse_args()

    mcfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    mesh = None
    mesh_cfg = MeshConfig(shape=(1,), axis_names=("data",))
    if args.mesh_shape:
        shape = tuple(int(x) for x in args.mesh_shape.split(","))
        names = ("data", "model")[: len(shape)]
        mesh_cfg = MeshConfig(shape=shape, axis_names=names)
        mesh = jax.make_mesh(shape, names,
                             axis_types=(AxisType.Auto,) * len(shape))

    cfg = TrainConfig(
        model=mcfg, mesh=mesh_cfg,
        optimizer=OptimizerConfig(lr=args.lr, warmup_steps=10,
                                  decay_steps=args.steps),
        seq_len=args.seq, global_batch=args.batch, steps=args.steps,
        microbatches=args.microbatches, checkpoint_dir=args.ckpt_dir,
        checkpoint_every=args.ckpt_every)

    def data_fn(start_step):
        it = lm_batches(mcfg.vocab_size, args.batch, args.seq, seed=17)
        for _ in range(start_step):      # deterministic resume alignment
            next(it)
        return it

    injector = FailureInjector(fail_at_steps=(args.fail_at,)) \
        if args.fail_at else None
    trainer = Trainer(cfg, data_fn, mesh=mesh, failure_injector=injector)
    res = trainer.run()
    print(f"finished at step {res.final_step} "
          f"(resumed from {res.resumed_from}); "
          f"loss {res.losses[0]:.4f} -> {res.losses[-1]:.4f}; "
          f"stragglers: {res.straggler_summary}")


if __name__ == "__main__":
    main()
