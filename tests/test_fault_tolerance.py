"""Fault tolerance: atomic checkpoints, auto-resume equivalence, elastic
restore, rotation, straggler detection."""
import os

import jax
import jax.numpy as jnp
from jax.sharding import AxisType
import numpy as np
import pytest

from repro.checkpoint import checkpointer as CKPT
from repro.config import OptimizerConfig, TrainConfig
from repro.configs import get_smoke_config
from repro.data.synthetic import lm_batches
from repro.ft.failures import FailureInjector, InjectedFailure, \
    StragglerMonitor
from repro.training.trainer import Trainer


def _cfg(tmp_path, steps=8, ckpt_every=3):
    m = get_smoke_config("yi-6b")
    return TrainConfig(
        model=m, optimizer=OptimizerConfig(lr=1e-3, warmup_steps=2,
                                           decay_steps=steps),
        seq_len=16, global_batch=4, steps=steps,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_every=ckpt_every, keep_checkpoints=2)


def _data_fn_factory(cfg):
    def data_fn(start):
        it = lm_batches(cfg.model.vocab_size, cfg.global_batch, cfg.seq_len,
                        seed=7)
        for _ in range(start):
            next(it)
        return it
    return data_fn


def test_checkpoint_roundtrip(tmp_path, rng):
    tree = {"a": jnp.asarray(rng.standard_normal((4, 8)), jnp.float32),
            "b": {"c": jnp.arange(5)}}
    CKPT.save(tmp_path, 3, tree, extra={"note": "x"})
    assert CKPT.available_steps(tmp_path) == [3]
    out = CKPT.restore(tmp_path, 3, tree)
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert CKPT.manifest(tmp_path, 3)["extra"]["note"] == "x"


def test_checkpoint_atomicity_ignores_tmp(tmp_path):
    tree = {"a": jnp.zeros(3)}
    CKPT.save(tmp_path, 1, tree)
    # a crashed save leaves a .tmp dir: must be invisible to readers
    (tmp_path / "step_00000002.tmp").mkdir()
    assert CKPT.latest_step(tmp_path) == 1


def test_rotation_keeps_newest(tmp_path):
    mgr = CKPT.CheckpointManager(tmp_path, keep=2, save_every=1)
    tree = {"a": jnp.zeros(2)}
    for s in range(1, 6):
        mgr.maybe_save(s, tree, asynchronous=False)
    assert CKPT.available_steps(tmp_path) == [4, 5]


def test_failure_injection_and_resume_equivalence(tmp_path):
    """Train 8 steps uninterrupted vs fail-at-5 + restart: identical final
    loss trajectory after the shared prefix (auto-resume correctness)."""
    cfg = _cfg(tmp_path, steps=8, ckpt_every=2)
    data_fn = _data_fn_factory(cfg)

    # uninterrupted reference
    import dataclasses
    cfg_ref = dataclasses.replace(cfg, checkpoint_dir=str(tmp_path / "ref"))
    ref = Trainer(cfg_ref, data_fn).run()

    # interrupted run
    inj = FailureInjector(fail_at_steps=(5,))
    with pytest.raises(InjectedFailure):
        Trainer(cfg, data_fn, failure_injector=inj).run()
    # restart (fresh Trainer, same dirs) -> auto-resume
    res = Trainer(cfg, data_fn).run()
    assert res.resumed_from == 4          # ckpt_every=2 -> step 4 saved
    assert res.final_step == 8
    # last losses agree with the uninterrupted run
    np.testing.assert_allclose(res.losses[-1], ref.losses[-1], rtol=1e-4)


def test_loss_decreases(tmp_path):
    cfg = _cfg(tmp_path, steps=12, ckpt_every=100)

    def data_fn(start):
        # single repeated batch -> guaranteed overfit signal
        it = lm_batches(cfg.model.vocab_size, 4, 16, seed=3)
        batch = next(it)
        while True:
            yield batch
    res = Trainer(cfg, lambda s: data_fn(s)).run()
    assert res.losses[-1] < res.losses[0], res.losses


def test_elastic_restore_changes_sharding(tmp_path):
    """Save unsharded, restore with explicit shardings (mesh of 1) — the
    cross-topology protocol (value equality + requested sharding)."""
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    from jax.sharding import NamedSharding, PartitionSpec as P
    tree = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    CKPT.save(tmp_path, 7, tree)
    sh = {"w": NamedSharding(mesh, P("data", None))}
    out = CKPT.restore(tmp_path, 7, tree, sh)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(tree["w"]))
    assert out["w"].sharding == sh["w"]


def test_straggler_monitor_flags_outliers():
    mon = StragglerMonitor(window=16, threshold=2.0)
    for i in range(20):
        mon.end_step(i, elapsed=1.0)
    mon.end_step(20, elapsed=5.0)          # 5x median
    assert len(mon.events) == 1
    ev = mon.events[0]
    assert ev.ratio == pytest.approx(5.0)
    assert mon.summary()["stragglers"] == 1
