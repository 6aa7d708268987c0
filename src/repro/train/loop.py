"""Fault-tolerant training runtime with first-class DV-DVFS integration.

The loop is the paper's pipeline at training granularity:
  data blocks -> (sample, estimate) -> frequency plan under an epoch deadline ->
  per-block actuation -> energy ledger,
wrapped with production concerns: gradient-accumulation microbatches, global-norm
clipping, LR schedule, atomic/async checkpoints with auto-restore, straggler
detection, and a failure-injection hook for the restart tests.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.base import ArchConfig
from repro.core import CostModel, RooflineTimeModel
from repro.data import BlockDataset, pack_tokens
from repro.models import transformer as T
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         clip_by_global_norm, linear_warmup_cosine)
from repro.train.dvfs_controller import (DVFSController, EnergyLedger,
                                         SimulatedActuator)
from repro.cluster.straggler import StragglerDetector

__all__ = ["TrainConfig", "make_train_step", "Trainer", "InjectedFailure",
           "CALIBRATION_STEPS"]

# timed f_max steps the cost model is fitted on (after one warm-up step)
CALIBRATION_STEPS = 3


class InjectedFailure(Exception):
    """The simulated node failure of ``Trainer.run(inject_failure_at=...)``."""


@dataclasses.dataclass
class TrainConfig:
    batch: int = 8
    seq_len: int = 256
    steps_per_block: int = 1
    num_microbatches: int = 1
    clip_norm: float = 1.0
    lr: float = 3e-4
    warmup: int = 20
    total_steps: int = 200
    ckpt_every: int = 20
    ckpt_keep: int = 3
    ckpt_dir: str = "/tmp/repro_ckpt"
    # DV-DVFS
    dvfs_enabled: bool = True
    planner: str = "paper"
    deadline_slack: float = 1.15     # epoch deadline = slack * est time at f_max
    error_margin: float = 0.05
    seed: int = 0


def make_train_step(cfg: ArchConfig, opt_cfg: AdamWConfig, *,
                    num_microbatches: int = 1, clip_norm: float = 1.0,
                    lr_fn: Callable | None = None):
    """Build the jit-able train step (used by the Trainer AND the dry-run)."""

    def loss_of(p, mb):
        return T.loss_fn(p, cfg, mb)

    def pin_grads(grads):
        """Shard the grad accumulator (ZeRO-style): per-microbatch gradient
        all-reduces fuse into reduce-scatters (perf_log.md iteration 5)."""
        if not cfg.grad_shard:
            return grads
        from jax.sharding import PartitionSpec as P
        axis, size = cfg.grad_shard

        def pin(g):
            for i, dim in enumerate(g.shape):
                if dim % size == 0 and dim >= size:
                    spec = [None] * g.ndim
                    spec[i] = axis
                    return jax.lax.with_sharding_constraint(g, P(*spec))
            return g

        return jax.tree.map(pin, grads)

    def step(params, opt_state, batch):
        if num_microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, batch)
            grads = pin_grads(grads)
        else:
            m = num_microbatches

            def split(x):
                return x.reshape((m, x.shape[0] // m) + x.shape[1:])

            mbs = jax.tree.map(split, batch)
            zero = pin_grads(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params))

            def body(carry, mb):
                gacc, lacc = carry
                (l, _), g = jax.value_and_grad(loss_of, has_aux=True)(params, mb)
                gacc = pin_grads(jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), gacc, g))
                return (gacc, lacc + l), None

            (gsum, lsum), _ = jax.lax.scan(body, (zero, jnp.float32(0.0)), mbs)
            grads = jax.tree.map(lambda g: g / m, gsum)
            loss = lsum / m
            metrics = {}
        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        lr = lr_fn(opt_state["step"]) if lr_fn is not None else None
        params, opt_state = adamw_update(params, grads, opt_state, opt_cfg, lr)
        out = {"loss": loss, "grad_norm": gnorm}
        if lr is not None:
            out["lr"] = lr
        return params, opt_state, out

    return step


class Trainer:
    """End-to-end: block dataset -> packed batches -> DV-DVFS-planned steps."""

    def __init__(self, cfg: ArchConfig, tc: TrainConfig,
                 dataset: BlockDataset | None = None,
                 roofline: RooflineTimeModel | None = None, chips: int = 1):
        self.cfg = cfg
        self.tc = tc
        self.dataset = dataset or BlockDataset(
            n_blocks=max(4, tc.total_steps // tc.steps_per_block),
            records_per_block=512, max_len=128, vocab=cfg.vocab,
            seed=tc.seed)
        self.opt_cfg = AdamWConfig(lr=tc.lr, moment_dtype=cfg.opt_dtype)
        lr_fn = linear_warmup_cosine(tc.lr, tc.warmup, tc.total_steps)
        self._step_fn = jax.jit(make_train_step(
            cfg, self.opt_cfg, num_microbatches=tc.num_microbatches,
            clip_norm=tc.clip_norm, lr_fn=lr_fn), donate_argnums=(0, 1))
        self.ckpt = CheckpointManager(tc.ckpt_dir, keep=tc.ckpt_keep)
        self.actuator = SimulatedActuator(roofline)
        self.ledger = EnergyLedger(chips=chips)
        self.dvo_ledger = EnergyLedger(chips=chips)  # counterfactual baseline
        self.straggler = StragglerDetector()
        self.controller: DVFSController | None = None
        self.history: list = []

    # ------------------------------------------------------------- data ----
    def _block_batch(self, block_idx: int):
        b = self.dataset.block(block_idx % self.dataset.n_blocks)
        packed = pack_tokens(b["tokens"], self.tc.batch, self.tc.seq_len)
        return ({"tokens": jnp.asarray(packed.tokens),
                 "labels": jnp.asarray(packed.labels)}, packed.nonpad_tokens)

    # ------------------------------------------------------------ dv-dvfs --
    def _plan(self, calib: list):
        """Fit the cost model on the timed f_max steps, then plan frequencies
        for the epoch (paper Fig. 3 pre-processing box)."""
        feats, meas = zip(*calib)
        cm = CostModel(("tokens", "const")).fit(list(feats), list(meas))
        block_feats = []
        for i in range(self.dataset.n_blocks):
            st = self.dataset.stats(i)
            # sampling sees record-level stats only (paper's <1% overhead)
            block_feats.append({"tokens": float(st.tokens) * self.tc.batch
                                * self.tc.seq_len / max(st.tokens_padded, 1),
                                "const": 1.0})
        self.controller = DVFSController(
            cost_model=cm, planner=self.tc.planner,
            error_margin=self.tc.error_margin,
            roofline=self.actuator.roofline, seed=self.tc.seed)
        blocks = self.controller.estimate_blocks(block_feats)
        est_total = sum(b.est_time_fmax for b in blocks)
        deadline = est_total * self.tc.deadline_slack
        self.controller.make_plan(blocks, deadline)
        return blocks

    # ------------------------------------------------------------- run -----
    def run(self, *, resume: bool = True,
            inject_failure_at: int | None = None) -> dict:
        """Train to ``total_steps``.

        The step donates its params and optimizer state, so the cost model
        is calibrated on the run's own first steps rather than on repeated
        calls over one state: the step is compiled before the loop, the
        first executed step is the warm-up, and the next
        ``CALIBRATION_STEPS`` (at f_max) are timed before the plan is made.
        Only an ``InjectedFailure`` is recovered from; any other error
        (a device fault, an out-of-memory) ends the run.
        """
        params = T.init_params(self.cfg, jax.random.PRNGKey(self.tc.seed))
        opt_state = adamw_init(params, self.opt_cfg)
        start_step = 0
        if resume:
            restored = self.ckpt.restore_latest(
                {"params": params, "opt": opt_state})
            if restored is not None:
                tree, start_step = restored
                params, opt_state = tree["params"], tree["opt"]

        step_fn = self._step_fn.lower(
            params, opt_state, self._block_batch(0)[0]).compile()
        calib: list = []  # (features, wall) of executed f_max steps

        step = start_step
        failed = False
        while step < self.tc.total_steps:
            block_idx = step // self.tc.steps_per_block
            batch, nonpad = self._block_batch(block_idx)
            rel_freq = (self.controller.freq_for_block(
                block_idx % self.dataset.n_blocks)
                if (self.tc.dvfs_enabled and self.controller) else 1.0)
            self.actuator.set(rel_freq)

            t0 = time.perf_counter()
            try:
                if inject_failure_at is not None and step == inject_failure_at \
                        and not failed:
                    failed = True
                    raise InjectedFailure(f"injected node failure at {step}")
                params, opt_state, metrics = step_fn(params, opt_state, batch)
                jax.block_until_ready(metrics["loss"])
            except InjectedFailure:
                # fault tolerance: restore newest valid checkpoint and continue
                restored = self.ckpt.restore_latest(
                    {"params": params, "opt": opt_state})
                if restored is None:
                    params = T.init_params(self.cfg,
                                           jax.random.PRNGKey(self.tc.seed))
                    opt_state = adamw_init(params, self.opt_cfg)
                    step = 0
                else:
                    tree, step = restored
                    params, opt_state = tree["params"], tree["opt"]
                continue
            wall = time.perf_counter() - t0

            if self.tc.dvfs_enabled and self.controller is None:
                calib.append(({"tokens": float(nonpad), "const": 1.0}, wall))
                if len(calib) == 1 + CALIBRATION_STEPS:
                    self._plan(calib[1:])  # calib[0] is the warm-up

            eff = self.actuator.effective_time(wall)
            self.ledger.record(eff, rel_freq)
            self.dvo_ledger.record(wall, 1.0)
            slot = (self.controller.plan.blocks[0].slot_s
                    if (self.controller and self.controller.plan
                        and self.controller.plan.blocks) else None)
            self.straggler.observe(step, wall, planned_slot_s=slot)

            self.history.append({"step": step, "loss": float(metrics["loss"]),
                                 "rel_freq": rel_freq, "wall_s": wall,
                                 "effective_s": eff})
            step += 1
            if step % self.tc.ckpt_every == 0 or step == self.tc.total_steps:
                self.ckpt.save({"params": params, "opt": opt_state}, step)
        self.ckpt.wait()
        losses = [h["loss"] for h in self.history]
        return {
            "params": params,
            "final_loss": losses[-1] if losses else float("nan"),
            "first_loss": losses[0] if losses else float("nan"),
            "energy": self.ledger.summary(),
            "energy_dvo": self.dvo_ledger.summary(),
            "straggler_events": list(self.straggler.events),
            "history": self.history,
        }
