"""Paper-faithful evaluation: Table 1 (motivation), Figs 6-10 (energy/time per
app vs DVO), Figs 11-12 (Zipf variety sensitivity), Fig 13 (deadline
sensitivity).

Methodology mirrors the paper:
  * equal-SIZE blocks whose per-block work varies (Zipf-ranked predicate
    density over aggregated heterogeneous sources),
  * per-block cost at f_max is MEASURED (jitted wall time, median of repeats),
  * sampling sees a fraction of each block; a linear cost model (calibrated on
    3 blocks) estimates PT_i; Algorithm 1 picks SFB_i,
  * the schedule is SIMULATED against the measured true costs; energy uses the
    analytic chip power model (EC = Σ PT_i·P_i, formula 7).
Deadlines: D = DVO_time × slack, slack_tight = 1.08, slack_firm = 1.20
(the paper's Table-3 tight/firm ratios are ~1.06-1.17).
"""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps import ALL_APPS, measure_block_seconds
from repro.core import (CPU_PAPER_POWER, TPU_V5E_POWER, BlockInfo, plan_dvfs,
                        plan_dvo, simulate, variety_stats)
from repro.data import BlockDataset

__all__ = ["motivation_table", "run_app_comparison", "compare_on_measured",
           "measure_blocks", "fig6_10", "fig11_12", "fig13"]

SLACK = {"tight": 1.08, "firm": 1.20}

_FEATURES = {
    "wordcount": ("tokens", "const"),
    "grep": ("tokens", "matches", "const"),
    "inverted_index": ("tokens_padded_logn", "const"),
    "avg": ("records", "selected", "const"),
    "sum": ("records", "selected", "const"),
}


# per-app block sizing: every app's per-block time lands >= ~100 ms so CPU
# wall-clock noise stays small relative to the quantity being scheduled
_APP_BLOCKS = {
    "wordcount": dict(records_per_block=16384, max_len=128, with_tokens=True),
    "grep": dict(records_per_block=32768, max_len=128, with_tokens=True),
    "inverted_index": dict(records_per_block=1024, max_len=128,
                           with_tokens=True),
    "avg": dict(records_per_block=1 << 21, max_len=8, with_tokens=False),
    "sum": dict(records_per_block=1 << 21, max_len=8, with_tokens=False),
}
_APP_KEYS = {
    "wordcount": ("tokens",), "grep": ("tokens",), "inverted_index": ("tokens",),
    "avg": ("values", "group", "select"), "sum": ("values", "group", "select"),
}


def _dataset(app_name: str, z: float = 1.0, n_blocks: int = 12,
             seed: int = 0) -> BlockDataset:
    kw = dict(_APP_BLOCKS[app_name])
    kw.pop("with_tokens")
    return BlockDataset(n_blocks=n_blocks, variety_z=z, seed=seed, **kw)


_MEASURE_CACHE: dict = {}


def _measure_app(app_name: str, ds: BlockDataset, repeats: int = 3,
                 sample_fraction: float = 0.05, seed: int = 0):
    """Measured per-block seconds (truth) + sampled measurements (what the
    planner sees): the paper's line-7 sampling = run the app on a ~5% row
    slice of each block.  Cached per (app, dataset, fraction) — figures 6-13
    reuse the same measurements like the paper reuses the same runs."""
    key = (app_name, ds.n_blocks, ds.records_per_block, ds.variety_z, ds.seed,
           sample_fraction, repeats, seed)
    if key in _MEASURE_CACHE:
        return _MEASURE_CACHE[key]
    out = _measure_app_uncached(app_name, ds, repeats, sample_fraction, seed)
    _MEASURE_CACHE[key] = out
    return out


def _measure_app_uncached(app_name: str, ds: BlockDataset, repeats: int = 3,
                          sample_fraction: float = 0.05, seed: int = 0):
    with_tokens = _APP_BLOCKS[app_name]["with_tokens"]
    return measure_blocks(app_name,
                          (ds.block(i, with_tokens=with_tokens)
                           for i in range(ds.n_blocks)),
                          repeats=repeats, sample_fraction=sample_fraction,
                          seed=seed)


def measure_blocks(app_name: str, blocks, repeats: int = 3,
                   sample_fraction: float = 0.05, seed: int = 0):
    """(times, t_subs): measured seconds of ``app_name`` over each host block
    dict, and over a ``sample_fraction`` row slice of it (at least 64 rows)."""
    app = ALL_APPS[app_name]()
    keys = _APP_KEYS[app_name]
    rng = np.random.default_rng(seed)
    times, t_subs = [], []
    for b in blocks:
        n = len(b[keys[0]])
        k = max(64, int(round(sample_fraction * n)))
        blk = {kk: jnp.asarray(b[kk]) for kk in keys}
        times.append(measure_block_seconds(app, blk, repeats=repeats))
        rows = np.sort(rng.choice(n, size=k, replace=False))
        sub = {kk: jnp.asarray(b[kk][rows]) for kk in keys}
        t_subs.append(measure_block_seconds(app, sub, repeats=repeats))
    return np.asarray(times), np.asarray(t_subs)


def motivation_table(z: float = 1.0, seed: int = 0) -> dict:
    """Table 1 analogue: mean/var/CoV of per-block time for 3 apps."""
    out = {}
    for app in ("wordcount", "grep", "inverted_index"):
        times, _ = _measure_app(app, _dataset(app, z=z, seed=seed))
        vs = variety_stats(times * 1e3)  # ms
        out[app] = {"mean_ms": vs.mean, "variance": vs.variance, "cov": vs.cov}
    return out


def run_app_comparison(app_name: str, *, z: float = 1.0, slack: float = 1.20,
                       planner: str = "paper", sample_fraction: float = 0.05,
                       seed: int = 0, power=CPU_PAPER_POWER) -> dict:
    """One app: DV-DVFS vs DVO with measured costs + sampled estimation."""
    ds = _dataset(app_name, z=z, seed=seed)
    times, t_sub = _measure_app(app_name, ds, sample_fraction=sample_fraction,
                                seed=seed)
    return {"app": app_name, "z": z,
            **compare_on_measured(times, t_sub, slack=slack, planner=planner,
                                  power=power)}


def compare_on_measured(times, t_sub, *, slack: float = 1.20,
                        planner: str = "paper", power=CPU_PAPER_POWER) -> dict:
    """Plan from the sampled times, simulate on the full ones, vs DVO."""
    times, t_sub = np.asarray(times), np.asarray(t_sub)
    n_blocks = len(times)
    # pre-processing/estimator box (paper Fig. 3): affine calibration
    # t_full ≈ a + b·t_sample on 3 fully-measured blocks corrects the fixed
    # overhead (vocab-sized outputs, dispatch) that does not scale with rows
    calib = [0, n_blocks // 2, n_blocks - 1]
    x = np.stack([np.ones(len(calib)), t_sub[calib]], axis=1)
    coef, *_ = np.linalg.lstsq(x, times[calib], rcond=None)
    est = np.maximum(coef[0] + coef[1] * t_sub, 1e-9)

    true_blocks = [BlockInfo(i, float(t)) for i, t in enumerate(times)]
    est_blocks = [BlockInfo(i, float(e)) for i, e in enumerate(est)]

    deadline = float(times.sum()) * slack
    plan = plan_dvfs(est_blocks, deadline, planner=planner, power=power)
    rep = simulate(plan, true_blocks, power=power)
    dvo = simulate(plan_dvo(true_blocks, deadline, power=power), true_blocks,
                   power=power)
    return {
        "slack": slack, "planner": planner,
        "deadline_s": deadline,
        "dvo_time_s": dvo.total_time_s, "dvo_energy_j": dvo.total_energy_j,
        "dvfs_time_s": rep.total_time_s, "dvfs_energy_j": rep.total_energy_j,
        "energy_improvement": rep.improvement_vs(dvo),
        "time_increase": rep.total_time_s / dvo.total_time_s - 1.0,
        "deadline_met": rep.deadline_met,
        "est_mape": float(np.mean(np.abs(np.asarray(est) - times) / times)),
    }


def fig6_10(planner: str = "paper", slack: float = 1.20,
            power=CPU_PAPER_POWER) -> list:
    return [run_app_comparison(a, planner=planner, slack=slack, power=power)
            for a in ("wordcount", "grep", "inverted_index", "avg", "sum")]


def fig11_12(planner: str = "paper") -> list:
    """Normalized energy/time vs DVO for z in {0, 1, 2} (uniform/moderate/high)."""
    rows = []
    for z in (0.0, 1.0, 2.0):
        for app in ("wordcount", "grep", "avg"):
            r = run_app_comparison(app, z=z, planner=planner)
            rows.append({"z": z, "app": app,
                         "norm_energy": 1.0 - r["energy_improvement"],
                         "norm_time": 1.0 + r["time_increase"],
                         "deadline_met": r["deadline_met"]})
    return rows


def fig13(planner: str = "paper") -> list:
    """Tight vs firm deadline (paper Table 3 / Fig 13)."""
    rows = []
    for name, slack in SLACK.items():
        for app in ("wordcount", "grep", "inverted_index", "avg", "sum"):
            r = run_app_comparison(app, slack=slack, planner=planner)
            rows.append({"deadline": name, "app": app,
                         "energy_improvement": r["energy_improvement"],
                         "time_increase": r["time_increase"],
                         "deadline_met": r["deadline_met"]})
    return rows
