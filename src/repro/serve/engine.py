"""Batched serving engine with DV-DVFS slot scheduling.

Serving maps onto the paper even more directly than training: each decode window
(a fixed number of tokens for the whole batch) is a "block", the per-request SLO
is the deadline, and decode is memory-bandwidth-bound on TPU — exactly the regime
where the planner, given the decode roofline, harvests FREE energy savings
(clock down to the zero-cost point without breaking the SLO).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster import NodeSpec, plan_cluster
from repro.configs.base import ArchConfig
from repro.core import (BlockInfo, RooflineTimeModel, plan_dvfs, plan_dvo)
from repro.models import transformer as T
from repro.train.dvfs_controller import EnergyLedger, SimulatedActuator

__all__ = ["ServeConfig", "ServingEngine"]


@dataclasses.dataclass
class ServeConfig:
    batch: int = 4
    max_len: int = 512
    window: int = 16            # decode tokens per scheduling block
    slo_tokens_per_s: float = 0.0   # 0 = derive from measured f_max rate
    slack: float = 1.2          # deadline = slack * f_max time when no SLO given
    planner: str = "global"
    greedy: bool = True
    # multi-replica decode: N replicas each decode their own batch under the
    # shared SLO; the cluster planner picks per-replica window frequencies
    # (slow hosts clock up, fast hosts harvest slack).  Replica 0 decodes
    # physically in this process; the others are accounted analytically.
    replicas: int = 1
    replica_speeds: tuple = ()  # relative host speeds, default all-1.0
    # full per-replica specs — ``NodeSpec`` / calibrated
    # ``CalibratedNodeSpec`` (repro.calibrate), one per replica: speeds AND
    # per-replica power models/ladders flow into the window plan.  Takes
    # precedence over ``replica_speeds``.
    replica_nodes: tuple = ()


class ServingEngine:
    def __init__(self, cfg: ArchConfig, params, sc: ServeConfig,
                 roofline: RooflineTimeModel | None = None, chips: int = 1):
        self.cfg = cfg
        self.params = params
        self.sc = sc
        self.actuator = SimulatedActuator(roofline)
        self.ledger = EnergyLedger(chips=chips)
        self.dvo_ledger = EnergyLedger(chips=chips)
        self._prefill = jax.jit(
            lambda p, b: T.prefill(p, cfg, b, sc.max_len))
        self._windows: dict = {}  # n_steps -> AOT-compiled window step

    def _sample_token(self, logits):
        if self.cfg.n_codebooks:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None, :]
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]

    def _window_fn(self, n_steps: int, tok, cache):
        """AOT-compiled multi-token decode window.

        One jitted ``lax.scan`` over ``n_steps`` decode steps (the whole
        scheduling window) replaces per-token python dispatch, so window wall
        times measure hardware, not interpreter overhead.  The cache is
        donated — decode rewrites it in place instead of copying the KV/state
        buffers every window.  Compiled ahead of time (``lower().compile()``)
        on first use per window length, keeping compilation out of the timed
        region; compiled executables are cached on the engine.
        """
        fn = self._windows.get(n_steps)
        if fn is None:
            cfg = self.cfg

            def run(params, tok, cache):
                def step(carry, _):
                    t, c = carry
                    logits, c = T.decode_step(params, cfg, t, c)
                    t = self._sample_token(logits)
                    return (t, c), t

                (tok_out, cache_out), toks = jax.lax.scan(
                    step, (tok, cache), None, length=n_steps)
                # (n, B, 1[,K]) -> (B, n[,K]) for axis-1 concatenation
                win = jnp.moveaxis(toks, 0, 1)[:, :, 0]
                return win, tok_out, cache_out

            fn = (jax.jit(run, donate_argnums=(2,))
                  .lower(self.params, tok, cache).compile())
            self._windows[n_steps] = fn
        return fn

    def _replica_speeds(self) -> tuple:
        """Host speeds normalized so replica 0 == 1.0.

        The cost estimate is MEASURED on replica 0, so the planner's
        reference node must be replica 0 — absolute speed units would give
        it phantom slack (or phantom load).  Normalizing makes any
        consistent unit choice valid.
        """
        sc = self.sc
        if sc.replica_nodes:
            source = "replica_nodes"
            speeds = tuple(float(nd.speed) for nd in sc.replica_nodes)
        elif sc.replica_speeds:
            source = "replica_speeds"
            speeds = tuple(float(s) for s in sc.replica_speeds)
        else:
            return (1.0,) * sc.replicas
        if len(speeds) != sc.replicas:
            raise ValueError(f"{source} has {len(speeds)} entries for "
                             f"{sc.replicas} replicas")
        return tuple(s / speeds[0] for s in speeds)

    def _plan_replicas(self, n_windows: int, window_fmax_s: float,
                       deadline: float):
        """Plan per-replica window frequencies under the shared SLO.

        Windows are pinned to their replica (a decode stream cannot migrate),
        so the cluster planner runs with an explicit assignment; heterogeneity
        enters through per-replica host speeds.  Returns replica 0's slice in
        the single-node plan shape the physical decode loop consumes.
        """
        from repro.core.scheduler import SchedulePlan
        sc = self.sc
        speeds = self._replica_speeds()
        blocks = [BlockInfo(r * n_windows + w, window_fmax_s,
                            roofline=self.actuator.roofline)
                  for r in range(sc.replicas) for w in range(n_windows)]
        assignment = [r for r in range(sc.replicas) for _ in range(n_windows)]
        if sc.replica_nodes:
            # calibrated path: keep each replica's own power model/ladder
            # (and fit provenance), re-normalized so replica 0 — where the
            # window cost was MEASURED — is the speed reference
            nodes = [dataclasses.replace(nd, speed=speeds[r])
                     for r, nd in enumerate(sc.replica_nodes)]
        else:
            nodes = [NodeSpec(f"replica{r}", speed=speeds[r])
                     for r in range(sc.replicas)]
        self.cluster_plan = plan_cluster(blocks, nodes, deadline,
                                         assignment=assignment)
        rep0 = self.cluster_plan.node_plans[0]
        return SchedulePlan("cluster", deadline, rep0.blocks,
                            self.cluster_plan.feasible)

    def _account_replica_tails(self, window_fmax_s: float) -> None:
        """Analytic energy accounting for replicas 1..N-1 (simulated hosts).

        Replica 0 decoded physically above; the remaining replicas' window
        times are the cluster plan's predictions, which are already in
        measured units (the plan was built from the measured f_max window).
        """
        speeds = self._replica_speeds()
        for r, node_plan in enumerate(self.cluster_plan.node_plans[1:], 1):
            for bp in node_plan.blocks:
                self.ledger.record(bp.pred_time_s, bp.rel_freq)
                self.dvo_ledger.record(window_fmax_s / speeds[r], 1.0)

    def generate(self, prompts: dict, n_tokens: int) -> dict:
        """Greedy-generate ``n_tokens`` for the batch with DV-DVFS windows.

        Every window is ONE jitted scan call (see ``_window_fn``); python
        only runs between windows, where the actuator switches frequency
        anyway.  Token streams are identical to the per-token loop: same
        decode steps in the same order, greedy sampling inside the scan.
        """
        sc = self.sc
        logits, cache = self._prefill(self.params, prompts)
        tok = self._sample_token(logits)
        jax.block_until_ready(tok)
        toks = [tok]
        done = 0

        def run_window(n, cache):
            nonlocal tok, done
            win, tok, cache = self._window_fn(n, tok, cache)(
                self.params, tok, cache)
            toks.append(win)
            done += n
            return cache

        # first decode step compiles the single-step window — untimed
        cache = run_window(1, cache)
        jax.block_until_ready(toks[-1])

        # measure one window at f_max to build the cost estimate
        n_cal = min(sc.window, max(n_tokens - 1, 0))
        if n_cal:
            self._window_fn(n_cal, tok, cache)  # compile outside the timer
            t0 = time.perf_counter()
            cache = run_window(n_cal, cache)
            jax.block_until_ready(toks[-1])
            window_fmax_s = time.perf_counter() - t0
        else:
            window_fmax_s = 0.0
        # the calibration window ran at f_max under both schemes
        self.ledger.record(window_fmax_s, 1.0)
        self.dvo_ledger.record(window_fmax_s, 1.0)

        remaining = max(n_tokens - done, 0)
        n_windows = int(np.ceil(remaining / sc.window))
        blocks = [BlockInfo(i, window_fmax_s, roofline=self.actuator.roofline)
                  for i in range(n_windows)]
        if sc.slo_tokens_per_s > 0:
            deadline = remaining * sc.batch / sc.slo_tokens_per_s
        else:
            deadline = window_fmax_s * n_windows * sc.slack
        self.cluster_plan = None
        if not n_windows:
            plan = None
        elif sc.replicas > 1:
            plan = self._plan_replicas(n_windows, window_fmax_s, deadline)
        else:
            plan = plan_dvfs(blocks, deadline, planner=sc.planner)
        self.plan = plan  # the plan actually driven (replica 0's slice if clustered)
        self.dvo_plan = plan_dvo(blocks, deadline) if n_windows else None

        for w in range(n_windows):
            n_w = min(sc.window, n_tokens - done)
            fn_ready = self._window_fn(n_w, tok, cache)  # compile untimed
            del fn_ready
            self.actuator.set(plan.blocks[w].rel_freq)
            t0 = time.perf_counter()
            cache = run_window(n_w, cache)
            jax.block_until_ready(toks[-1])
            wall = time.perf_counter() - t0
            eff = self.actuator.effective_time(wall)
            self.ledger.record(eff, plan.blocks[w].rel_freq)
            self.dvo_ledger.record(wall, 1.0)

        if self.cluster_plan is not None:
            self._account_replica_tails(window_fmax_s)

        out = jnp.concatenate(toks, axis=1)
        return {"tokens": out, "energy": self.ledger.summary(),
                "energy_dvo": self.dvo_ledger.summary(),
                "n_generated": done + 1}
