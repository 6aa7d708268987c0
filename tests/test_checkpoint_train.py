"""Checkpointing (atomic/async/torn-write), optimizer, fault-tolerant trainer,
straggler detector."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, load_checkpoint, save_checkpoint
from repro.cluster.straggler import StragglerDetector
from repro.configs import smoke_config
from repro.data import BlockDataset
from repro.models import transformer as T
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         clip_by_global_norm, linear_warmup_cosine)
from repro.train import TrainConfig, Trainer
from repro.train.loop import CALIBRATION_STEPS


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (4, 8)),
            "b": {"c": jnp.arange(6, dtype=jnp.int32),
                  "d": (jnp.ones(3), jnp.zeros(2))}}


def test_checkpoint_roundtrip(tmp_path):
    tree = _tree()
    path = str(tmp_path / "ck")
    save_checkpoint(path, tree, step=7)
    like = jax.tree.map(lambda x: jnp.zeros_like(x), tree)
    restored, step = load_checkpoint(path, like)
    assert step == 7
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_torn_write_skipped(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    tree = _tree()
    mgr.save(tree, 10)
    mgr.save(jax.tree.map(lambda x: x + 1, tree), 20)
    # corrupt the newest (simulate crash mid-write)
    meta = tmp_path / "step_0000000020" / "meta.json"
    meta.write_text(json.dumps({"complete": False}))
    restored, step = mgr.restore_latest(tree)
    assert step == 10  # fell back to the older valid one


def test_checkpoint_keep_k(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    for s in (1, 2, 3, 4):
        mgr.save(_tree(), s)
    assert mgr.steps() == [3, 4]


def test_adamw_decreases_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = adamw_init(params, cfg)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}          # d/dw ||w||^2
        params, state = adamw_update(params, grads, state, cfg)
    assert float(jnp.abs(params["w"]).max()) < 1e-2
    assert int(state["step"]) == 200


def test_clip_and_schedule():
    g = {"w": jnp.full((10,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) > 1.0
    from repro.optim import global_norm
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
    lr = linear_warmup_cosine(1e-3, 10, 100)
    assert float(lr(jnp.int32(0))) == 0.0
    assert float(lr(jnp.int32(10))) == pytest.approx(1e-3, rel=1e-2)
    assert float(lr(jnp.int32(100))) < 5e-4


def _mk_trainer(tmp_path, **kw):
    cfg = smoke_config("olmo-1b")
    defaults = dict(batch=2, seq_len=64, total_steps=12, ckpt_every=4,
                    warmup=2, ckpt_dir=str(tmp_path / "ck"), seed=3,
                    dvfs_enabled=kw.pop("dvfs_enabled", False))
    defaults.update(kw)
    tc = TrainConfig(**defaults)
    ds = BlockDataset(n_blocks=4, records_per_block=64, max_len=48,
                      vocab=cfg.vocab, seed=1)
    return Trainer(cfg, tc, dataset=ds)


def test_trainer_loss_decreases(tmp_path):
    res = _mk_trainer(tmp_path, total_steps=25).run(resume=False)
    assert np.isfinite(res["final_loss"])
    assert res["final_loss"] < res["first_loss"]


def test_trainer_failure_recovery_is_bitexact(tmp_path):
    """Crash at step 9, restore from ckpt at 8 -> same params as a clean run."""
    clean = _mk_trainer(tmp_path / "a").run(resume=False)
    faulty = _mk_trainer(tmp_path / "b").run(resume=False, inject_failure_at=9)
    for a, b in zip(jax.tree.leaves(clean["params"]),
                    jax.tree.leaves(faulty["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("error", [RuntimeError("device fault"),
                                   jax.errors.JaxRuntimeError(
                                       "RESOURCE_EXHAUSTED: device fault")],
                         ids=["runtime_error", "jax_runtime_error"])
def test_trainer_device_error_ends_run(tmp_path, error):
    """Only the injected failure is recovered from; a real error from the
    step (an HBM OOM, a device fault) propagates out of run()."""
    tr = _mk_trainer(tmp_path)

    class FailingStep:
        def lower(self, *args):
            return self

        def compile(self):
            return self

        def __call__(self, *args):
            raise error

    tr._step_fn = FailingStep()
    with pytest.raises(RuntimeError, match="device fault"):
        tr.run(resume=False)
    assert tr.history == []


def test_trainer_dvfs_saves_energy(tmp_path):
    res = _mk_trainer(tmp_path, dvfs_enabled=True, total_steps=16,
                      deadline_slack=1.3).run(resume=False)
    # the DVFS ledger uses simulated frequencies; busy energy must not exceed
    # the DVO (f_max) counterfactual
    assert res["energy"]["busy_j"] <= res["energy_dvo"]["busy_j"] * 1.001
    freqs = {h["rel_freq"] for h in res["history"]}
    assert any(f < 1.0 for f in freqs)  # it actually down-clocked something


def test_trainer_dvfs_calibrates_on_its_own_steps(tmp_path, monkeypatch):
    """A DVFS run takes its first 1 + CALIBRATION_STEPS steps unplanned at
    f_max, fits the cost model on all of them but the warm-up step 0, and
    runs every later step at the planned frequency of its block."""
    tr = _mk_trainer(tmp_path, dvfs_enabled=True, total_steps=10,
                     deadline_slack=1.3)
    fits = []
    plan = tr._plan

    def spy(calib):
        fits.append((len(tr.history), list(calib)))
        return plan(calib)

    monkeypatch.setattr(tr, "_plan", spy)
    hist = tr.run(resume=False)["history"]
    n = 1 + CALIBRATION_STEPS
    assert [h["rel_freq"] for h in hist[:n]] == [1.0] * n
    (planned_in_step, calib), = fits
    assert planned_in_step == n - 1
    assert [wall for _, wall in calib] == [h["wall_s"] for h in hist[1:n]]
    assert tr.controller is not None and tr.controller.plan is not None
    blocks = tr.dataset.n_blocks
    assert [h["rel_freq"] for h in hist[n:]] == [
        tr.controller.freq_for_block(h["step"] // tr.tc.steps_per_block
                                     % blocks) for h in hist[n:]]


def test_straggler_detector():
    det = StragglerDetector(warmup_steps=3)
    flags = [det.observe(i, 1.0 + 0.01 * (i % 3)) for i in range(10)]
    assert not any(flags)
    assert det.observe(10, 5.0)          # 5x outlier flagged
    assert det.events and det.events[0]["step"] == 10
    # late-vs-budget path
    det2 = StragglerDetector(warmup_steps=0, budget_factor=1.5)
    for i in range(3):
        det2.observe(i, 1.0, planned_slot_s=1.0)
    assert det2.observe(3, 1.6, planned_slot_s=1.0)
