"""DV-DVFS scheduler invariants — unit + hypothesis property tests."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (DEFAULT_LADDER, TPU_V5E_POWER, BlockInfo,
                        FrequencyLadder, PowerModel, RooflineTimeModel,
                        plan_dvfs, plan_dvo, simulate, zipf_block_sizes)


def _blocks(costs):
    return [BlockInfo(i, float(c)) for i, c in enumerate(costs)]


def test_dvo_is_identity_speed():
    blocks = _blocks([1.0, 2.0, 3.0])
    rep = simulate(plan_dvo(blocks, 10.0), blocks)
    assert rep.total_time_s == pytest.approx(6.0)
    assert rep.deadline_met


def test_paper_planner_meets_deadline_and_saves_energy():
    sizes = zipf_block_sizes(16, 10000, z=1.0, seed=0)
    costs = sizes / sizes.mean() * 5.0
    blocks = _blocks(costs)
    deadline = float(costs.sum() * 1.2)
    plan = plan_dvfs(blocks, deadline, planner="paper")
    rep = simulate(plan, blocks)
    dvo = simulate(plan_dvo(blocks, deadline), blocks)
    assert plan.feasible and rep.deadline_met
    assert rep.total_energy_j < dvo.total_energy_j
    assert rep.total_time_s >= dvo.total_time_s  # paper trades time for energy


def test_global_planner_dominates_paper():
    """The offline greedy must save at least as much energy as equal slots."""
    rng = np.random.default_rng(0)
    costs = rng.lognormal(1.0, 0.8, 24)
    blocks = _blocks(costs)
    deadline = float(costs.sum()) * 1.15
    rep_p = simulate(plan_dvfs(blocks, deadline, planner="paper"), blocks)
    rep_g = simulate(plan_dvfs(blocks, deadline, planner="global"), blocks)
    assert rep_g.deadline_met
    assert rep_g.total_energy_j <= rep_p.total_energy_j * 1.001


def test_roofline_free_downclock():
    """Memory-bound blocks save energy with zero time increase."""
    rt = RooflineTimeModel.from_counts(flops=1e12, hbm_bytes=20e9,
                                       coll_bytes=0, chips=1)
    assert rt.zero_cost_freq() < 0.5
    blocks = [BlockInfo(i, rt.time_at(1.0), roofline=rt) for i in range(8)]
    deadline = sum(b.est_time_fmax for b in blocks) * 1.0001  # NO slack
    plan = plan_dvfs(blocks, deadline, planner="global", error_margin=0.0)
    rep = simulate(plan, blocks)
    dvo = simulate(plan_dvo(blocks, deadline), blocks)
    assert rep.deadline_met
    assert rep.total_time_s == pytest.approx(dvo.total_time_s, rel=1e-6)
    assert rep.total_energy_j < dvo.total_energy_j * 0.8


@settings(max_examples=50, deadline=None)
@given(
    costs=st.lists(st.floats(0.1, 50.0), min_size=1, max_size=40),
    slack=st.floats(0.0, 1.0),
    planner=st.sampled_from(["paper", "global"]),
)
def test_property_deadline_and_ladder(costs, slack, planner):
    """For ANY block mix and any deadline >= DVO time: deadline met, frequencies
    from the ladder, energy never above DVO."""
    blocks = _blocks(costs)
    deadline = sum(costs) * (1.0 + slack) + 1e-6
    plan = plan_dvfs(blocks, deadline, planner=planner)
    rep = simulate(plan, blocks)
    assert plan.feasible
    assert rep.deadline_met
    for bp in plan.blocks:
        assert any(abs(bp.rel_freq - f) < 1e-9 for f in DEFAULT_LADDER.states)
    dvo = simulate(plan_dvo(blocks, deadline), blocks)
    assert rep.total_energy_j <= dvo.total_energy_j * (1 + 1e-9)


@settings(max_examples=30, deadline=None)
@given(st.floats(1.05, 2.0), st.floats(0.0, 0.4))
def test_property_firm_beats_tight(firm_slack, tighten):
    """Paper Fig. 13: a firmer deadline never saves LESS energy."""
    rng = np.random.default_rng(7)
    costs = rng.lognormal(1.0, 0.7, 16)
    blocks = _blocks(costs)
    total = float(costs.sum())
    tight = total * max(1.0 + 1e-9, firm_slack - tighten)
    firm = total * firm_slack
    e_tight = simulate(plan_dvfs(blocks, tight, planner="global"), blocks)
    e_firm = simulate(plan_dvfs(blocks, firm, planner="global"), blocks)
    assert e_firm.total_energy_j <= e_tight.total_energy_j * (1 + 1e-9)


def test_power_model_monotonic():
    pm = PowerModel()
    freqs = np.linspace(0.5, 1.0, 11)
    powers = [pm.power(1.0, f) for f in freqs]
    assert all(b > a for a, b in zip(powers, powers[1:]))
    assert pm.power(0.0, 1.0) == pytest.approx(pm.p_idle)
    # paper formula (3): full-util busy power == p_full
    assert pm.paper_block_power(1.0, 1.0) == pytest.approx(pm.p_full)


def test_ladder_validation():
    with pytest.raises(ValueError):
        FrequencyLadder(states=(0.5, 0.9))     # must end at 1.0
    with pytest.raises(ValueError):
        FrequencyLadder(states=(0.9, 0.5, 1.0))  # ascending
    lad = FrequencyLadder(states=(0.5, 0.75, 1.0))
    assert lad.lowest_feasible(0.6) == 0.75
    assert lad.lowest_feasible(0.2) == 0.5
    assert lad.floor_state(0.8) == 0.75
