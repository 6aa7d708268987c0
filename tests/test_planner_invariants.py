"""Property-based invariant contract for ALL planners (single-node + cluster).

Uses the hypothesis compat shim, so the sweep runs (fixed-seed) even where
hypothesis is not installed.  The contract (also documented in
``repro/cluster/__init__.py``):

  * a plan reported feasible predicts completion inside the deadline,
  * every planned frequency is a state of the governing ladder,
  * DV-DVFS busy energy never exceeds DVO (all-f_max) on the same blocks,
  * the global planner never pays time for roofline blocks' memory-bound
    down-clocks.
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (DEFAULT_LADDER, BlockInfo, FrequencyLadder,
                        RooflineTimeModel, block_time, plan_dvfs, plan_dvo,
                        simulate)
from repro.cluster import NodeSpec, plan_cluster, plan_independent

DEEP_LADDER = FrequencyLadder(
    states=tuple(round(f, 2) for f in np.arange(0.35, 1.001, 0.05)))
COARSE_LADDER = FrequencyLadder(states=(0.5, 0.75, 1.0))
LADDERS = {"default": DEFAULT_LADDER, "deep": DEEP_LADDER,
           "coarse": COARSE_LADDER}


def _blocks(costs):
    return [BlockInfo(i, float(c)) for i, c in enumerate(costs)]


def _in_ladder(freq, ladder):
    return any(abs(freq - f) < 1e-9 for f in ladder.states)


@settings(max_examples=40, deadline=None)
@given(
    costs=st.lists(st.floats(0.05, 30.0), min_size=1, max_size=32),
    slack=st.floats(0.0, 1.2),
    planner=st.sampled_from(["paper", "global"]),
    ladder_name=st.sampled_from(["default", "deep", "coarse"]),
)
def test_single_node_contract(costs, slack, planner, ladder_name):
    ladder = LADDERS[ladder_name]
    blocks = _blocks(costs)
    deadline = sum(costs) * (1.0 + slack) + 1e-6
    plan = plan_dvfs(blocks, deadline, planner=planner, ladder=ladder)
    # feasible => predicted completion inside the deadline
    if plan.feasible:
        assert plan.pred_total_time <= deadline + 1e-9
    # frequencies come from the governing ladder
    for bp in plan.blocks:
        assert _in_ladder(bp.rel_freq, ladder)
    # DVFS energy never above DVO on identical blocks
    dvo = plan_dvo(blocks, deadline)
    assert plan.pred_total_energy <= dvo.pred_total_energy * (1 + 1e-9)
    # and the simulated (truth == estimate) run agrees
    rep = simulate(plan, blocks)
    rep_dvo = simulate(dvo, blocks)
    assert rep.total_energy_j <= rep_dvo.total_energy_j * (1 + 1e-9)


@settings(max_examples=25, deadline=None)
@given(
    flops=st.floats(1e9, 1e13),
    hbm_bytes=st.floats(1e9, 50e9),
    n_blocks=st.integers(1, 12),
)
def test_roofline_never_pays_time_for_memory_bound_downclock(
        flops, hbm_bytes, n_blocks):
    """Any roofline down-clock to a state at or above the zero-cost frequency
    must leave the block's predicted time exactly at its f_max time."""
    rt = RooflineTimeModel.from_counts(flops=flops, hbm_bytes=hbm_bytes,
                                       coll_bytes=0, chips=1)
    blocks = [BlockInfo(i, rt.time_at(1.0), roofline=rt)
              for i in range(n_blocks)]
    t_fmax = sum(b.est_time_fmax for b in blocks)
    plan = plan_dvfs(blocks, t_fmax * 1.0001, planner="global",
                     error_margin=0.0)
    f_star = rt.zero_cost_freq()
    for b, bp in zip(blocks, plan.blocks):
        if bp.rel_freq >= f_star - 1e-9:
            assert bp.pred_time_s == pytest.approx(block_time(b, 1.0),
                                                   rel=1e-9)
    # with NO deadline slack the whole plan must be time-neutral
    assert plan.pred_total_time <= t_fmax * 1.0001 + 1e-9
    dvo = plan_dvo(blocks, t_fmax * 1.0001)
    assert plan.pred_total_energy <= dvo.pred_total_energy * (1 + 1e-9)


@settings(max_examples=25, deadline=None)
@given(
    costs=st.lists(st.floats(0.05, 30.0), min_size=1, max_size=24),
    slack=st.floats(0.05, 1.5),
    n_nodes=st.integers(1, 5),
    assignment=st.sampled_from(["lpt", "round_robin"]),
)
def test_cluster_contract(costs, slack, n_nodes, assignment):
    """Cluster plans: per-node deadline feasibility, per-node ladder
    membership, energy never above the all-f_max cluster baseline."""
    speeds = (1.0, 0.7, 1.3, 0.85, 1.2)
    ladders = (DEFAULT_LADDER, DEEP_LADDER, COARSE_LADDER)
    blocks = _blocks(costs)
    nodes = [NodeSpec(f"n{k}", speed=speeds[k % len(speeds)],
                      ladder=ladders[k % len(ladders)])
             for k in range(n_nodes)]
    # deadline: slowest-single-node time x slack always admits SOME plan
    worst = sum(costs) / min(n.speed for n in nodes)
    deadline = worst * (1.0 + slack)
    plan = plan_cluster(blocks, nodes, deadline, assignment=assignment)
    assert plan.feasible
    total_dvo = 0.0
    for np_ in plan.node_plans:
        assert np_.pred_finish_s <= deadline + 1e-9
        for bp in np_.blocks:
            assert _in_ladder(bp.rel_freq, np_.node.ladder)
        total_dvo += sum(
            np_.node.block_energy(b, np_.node.block_time(b, 1.0), 1.0)
            for b in blocks if plan.assignment()[b.index] == np_.node.name)
    assert plan.pred_total_energy <= total_dvo * (1 + 1e-9)
    # every block is planned exactly once
    assert sorted(plan.assignment().keys()) == [b.index for b in blocks]


@settings(max_examples=15, deadline=None)
@given(
    costs=st.lists(st.floats(0.5, 20.0), min_size=3, max_size=24),
    n_nodes=st.integers(2, 4),
)
def test_independent_baseline_contract(costs, n_nodes):
    """The round-robin + per-node Algorithm 1 baseline obeys the same ladder
    and energy contract (it is a planner too, just an oblivious one)."""
    blocks = _blocks(costs)
    nodes = [NodeSpec(f"n{k}", speed=(1.0, 0.8, 1.2, 0.9)[k % 4])
             for k in range(n_nodes)]
    worst = sum(costs) / min(n.speed for n in nodes)
    plan = plan_independent(blocks, nodes, worst * 1.5)
    for np_ in plan.node_plans:
        for bp in np_.blocks:
            assert _in_ladder(bp.rel_freq, np_.node.ladder)
    assert sorted(plan.assignment().keys()) == [b.index for b in blocks]
