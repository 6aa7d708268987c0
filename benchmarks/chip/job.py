"""One DV-DVFS job, through the program's own functions, and the closed
loop of jobs that a measured window runs.

A job is one app over the whole dataset under a deadline:

  estimate  the dataset kind's estimate front (the program's sampler and,
            for text, the ``block_stats`` kernel), priced in seconds by the
            ``CostModel`` fitted in set-up;
  run       one map task a block, in block order: stage the host block to
            the device, run the jitted app, bring the output to the host;
  plan      ``repro.pipeline.plan_estimates`` with the default planner, the
            deadline being ``slack`` times the job's own measured f_max time
            (the chip cannot change its clock, so the plan is a what-if over
            the measured times);
  account   the benchmark's frozen power model prices the plan and DVO
            (every block at f_max) on the measured block times.

The harness holds this loop only because the program has no device
executor yet.
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys
import time
import traceback

import numpy as np

__all__ = ["Task", "Job", "Runner", "account"]


@dataclasses.dataclass
class Task:
    """One map task: host clock at its start, staged, app done, on host."""
    job: int
    block: int
    nbytes: int
    t0: float
    t_staged: float
    t_app: float
    t_done: float

    @property
    def wall_s(self) -> float:
        return self.t_done - self.t0

    @property
    def stage_s(self) -> float:
        return self.t_staged - self.t0

    @property
    def app_s(self) -> float:
        return self.t_app - self.t_staged


@dataclasses.dataclass
class Job:
    index: int
    t0: float
    estimate_s: float = float("nan")
    units: object = None              # EstimateArrays in cost units
    est_s: np.ndarray | None = None   # priced estimate, seconds at f_max
    app_s: np.ndarray | None = None   # measured app time a block
    plan: object = None               # PlanArrays
    plan_s: float = float("nan")
    acct: dict | None = None
    error: str | None = None

    @property
    def failed(self) -> bool:
        return self.error is not None or not self.acct["met"] \
            or self.acct["off_ladder"] > 0


def account(app_s, est_s, freqs, deadline_s: float, power: dict) -> dict:
    """Modelled busy energy (paper formula 7) of the plan and of DVO on the
    measured block times, and the plan's checks against the frozen ladder."""
    app_s, est_s, f = (np.asarray(x, np.float64) for x in (app_s, est_s, freqs))
    ladder = np.asarray(power["ladder"])
    on = np.isclose(f[:, None], ladder[None, :], rtol=0, atol=1e-9).any(axis=1)
    p_full, p_idle, alpha = power["p_full_w"], power["p_idle_w"], power["alpha"]
    watts = p_idle + (p_full - p_idle) * np.clip(f, 0.0, 1.0) ** alpha
    t_plan = app_s / np.maximum(f, 1e-6)
    tol = 1e-9 * max(deadline_s, 1.0)
    # the plan's own modelled time, on its estimates, must fit the deadline
    # whenever running every block at f_max would
    on_est = float((est_s / np.maximum(f, 1e-6)).sum())
    over = bool(est_s.sum() <= deadline_s and on_est > deadline_s + tol)
    return {"e_plan_j": float((t_plan * watts).sum()),
            "e_dvo_j": float(app_s.sum() * p_full),
            "t_plan_s": float(t_plan.sum()),
            "met": bool(t_plan.sum() <= deadline_s + tol),
            "off_ladder": int((~on).sum()),
            "over_deadline": over}


class Runner:
    """Everything a job needs, built in set-up; ``window`` runs the loop."""

    def __init__(self, *, kind, ds, config, mix, app, app_fn, cost_model,
                 pipeline_config, power, traced: bool):
        self.kind, self.ds, self.config, self.mix = kind, ds, config, mix
        self.app, self.app_fn, self.cost_model = app, app_fn, cost_model
        self.pipeline_config, self.power = pipeline_config, power
        self.traced = traced
        self.n_blocks = kind.n_blocks(ds)
        self.block_bytes = kind.block_bytes(ds)
        self.tasks: list = []
        self.jobs: list = []
        self.window_s = float("nan")
        self.outputs: dict = {}       # block -> host outputs of its map tasks
        self.estimates: list = []     # every estimate finished in the window

    def span(self, name: str):
        if not self.traced:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def price(self, units) -> tuple:
        """EstimateArrays in cost units -> (seconds a block, EstimateArrays
        in seconds with the same relative confidence interval)."""
        from repro.core.soa import EstimateArrays

        secs = np.array([max(self.cost_model.predict(
            {"units": float(u), "const": 1.0}), 1e-9) for u in units.total])
        rel = units.rel_halfwidth
        return secs, EstimateArrays(units.index, secs, secs * (1 - rel),
                                    secs * (1 + rel), units.n_sampled,
                                    units.n_records)

    def map_task(self, job: int, b: int) -> tuple:
        """(Task, host output) of block ``b``."""
        import jax

        t0 = time.perf_counter()
        with self.span("stage"):
            x = jax.block_until_ready(
                jax.device_put(self.kind.block(self.ds, b)))
        t1 = time.perf_counter()
        with self.span("app"):
            out = jax.block_until_ready(self.app_fn(x))
        t2 = time.perf_counter()
        with self.span("fetch"):
            host = jax.device_get(out)
        t3 = time.perf_counter()
        del x, out
        return Task(job, b, self.block_bytes, t0, t1, t2, t3), host

    def job(self, index: int) -> Job:
        """One whole job."""
        from repro.pipeline import plan_estimates

        j = Job(index=index, t0=time.perf_counter())
        with self.span("estimate"):
            j.units = self.kind.estimate(self.ds, self.config,
                                         self.pipeline_config, self.app)
            j.est_s, est = self.price(j.units)
        j.estimate_s = time.perf_counter() - j.t0
        self.estimates.append(j.units)
        app_s = np.zeros(self.n_blocks)
        for b in range(self.n_blocks):
            task, out = self.map_task(index, b)
            self.tasks.append(task)
            self.outputs.setdefault(b, []).append(out)
            app_s[b] = task.app_s
        j.app_s = app_s
        deadline = float(self.mix["slack"] * app_s.sum())
        t = time.perf_counter()
        with self.span("plan"):
            j.plan = plan_estimates(est, deadline, self.pipeline_config)
        j.plan_s = time.perf_counter() - t
        with self.span("account"):
            j.acct = account(app_s, j.est_s, j.plan.rel_freq, deadline,
                             self.power)
        return j

    def window(self, seconds: float) -> None:
        """Jobs back to back, each started within ``seconds`` and run to its
        end; ``window_s`` is the time from the first job's start to the last
        one's end.  A window that closed on the clock alone would cut the
        last job at a point that moves with every run, and count a share of
        its blocks that swings by whole blocks."""
        t_start = time.perf_counter()
        t_end = t_start + seconds
        with self.span("window"):
            while time.perf_counter() < t_end:
                index = len(self.jobs)
                try:
                    self.jobs.append(self.job(index))
                except Exception:  # a raising job fails and ends the window
                    traceback.print_exc(file=sys.stderr)
                    self.jobs.append(Job(index=index, t0=t_start,
                                         error=traceback.format_exc(limit=1)))
                    break
        self.window_s = time.perf_counter() - t_start
