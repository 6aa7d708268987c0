"""Vectorized hot path == loop reference, property-based.

The planner/sampler/kernel hot paths (``repro.core.scheduler``,
``repro.core.sampling``, ``repro.kernels.block_stats``) are array-level
rewrites of loop code that now lives in ``repro.core._reference`` (and
``plan_cluster_reference``).  This suite is the contract that lets the
references stay frozen: across random ladders, power models, rooflines,
deadlines, and assignments the vectorized implementations must produce
IDENTICAL plans (same frequencies, energies within 1e-9) and identical
sampling estimates.  Runs under the hypothesis compat shim, so the sweep
executes (fixed-seed) even where hypothesis is not installed.
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (BlockInfo, FrequencyLadder, PowerModel,
                        RooflineTimeModel, plan_dvfs, plan_dvo,
                        sample_block_cost, sample_blocks)
from repro.core import _reference as ref
from repro.cluster import NodeSpec, plan_cluster
from repro.cluster.planner import plan_cluster_reference


def _ladder(rnd_states):
    """Random strictly-ascending ladder ending at exactly 1.0."""
    states = tuple(sorted(set(round(s, 3) for s in rnd_states
                              if 0.05 <= s <= 0.99))) + (1.0,)
    return FrequencyLadder(states=states)


def _blocks(costs, rooflines):
    out = []
    for i, (c, rf) in enumerate(zip(costs, rooflines)):
        roof = None
        if rf is not None:
            flops, hbm = rf
            roof = RooflineTimeModel.from_counts(flops=flops, hbm_bytes=hbm,
                                                 coll_bytes=0.0)
        out.append(BlockInfo(i, float(c), est_rel_halfwidth=0.01 * (i % 7),
                             util=0.4 + 0.05 * (i % 12), roofline=roof))
    return out


def _assert_plans_identical(p, q):
    assert p.feasible == q.feasible
    assert p.planner == q.planner
    assert len(p.blocks) == len(q.blocks)
    for a, b in zip(p.blocks, q.blocks):
        assert a.index == b.index
        assert a.rel_freq == b.rel_freq          # exactly: same ladder state
        assert abs(a.pred_time_s - b.pred_time_s) <= 1e-9
        assert abs(a.pred_energy_j - b.pred_energy_j) <= 1e-9
    assert p.pred_total_energy == pytest.approx(q.pred_total_energy, abs=1e-6)


@settings(max_examples=60, deadline=None)
@given(
    costs=st.lists(st.floats(0.05, 40.0), min_size=1, max_size=48),
    slack=st.floats(0.0, 1.6),
    planner=st.sampled_from(["paper", "global"]),
    ladder_states=st.lists(st.floats(0.05, 0.99), min_size=1, max_size=14),
    p_full=st.floats(80.0, 400.0),
    p_idle=st.floats(1.0, 79.0),
    alpha=st.floats(0.8, 3.5),
    margin=st.floats(0.0, 0.25),
    adaptive=st.booleans(),
    roofline_every=st.integers(0, 4),
)
def test_plan_dvfs_matches_reference(costs, slack, planner, ladder_states,
                                     p_full, p_idle, alpha, margin, adaptive,
                                     roofline_every):
    ladder = _ladder(ladder_states)
    power = PowerModel(p_full=p_full, p_idle=p_idle, alpha=alpha)
    rooflines = [
        (1e9 * (1 + 37 * (i % 11)), 1e8 * (1 + 29 * (i % 13)))
        if roofline_every and i % roofline_every == 0 else None
        for i in range(len(costs))
    ]
    blocks = _blocks(costs, rooflines)
    deadline = sum(costs) * (1.0 + slack) + 1e-6
    kw = dict(planner=planner, ladder=ladder, power=power,
              error_margin=margin, adaptive_margin=adaptive)
    _assert_plans_identical(plan_dvfs(blocks, deadline, **kw),
                            ref.plan_dvfs_reference(blocks, deadline, **kw))


@settings(max_examples=30, deadline=None)
@given(
    costs=st.lists(st.floats(0.1, 25.0), min_size=1, max_size=32),
    slack=st.floats(0.0, 1.5),
    n_nodes=st.integers(1, 5),
    assignment=st.sampled_from(["auto", "lpt", "pack", "round_robin"]),
    margin=st.floats(0.0, 0.2),
)
def test_plan_cluster_matches_reference(costs, slack, n_nodes, assignment,
                                        margin):
    speeds = (1.0, 0.7, 1.3, 0.85, 1.2)
    ladders = (FrequencyLadder(),
               FrequencyLadder(states=(0.5, 0.75, 1.0)),
               FrequencyLadder(states=tuple(
                   round(f, 2) for f in np.arange(0.35, 1.001, 0.05))))
    powers = (PowerModel(), PowerModel(p_full=95.0, p_idle=15.0, alpha=3.0),
              PowerModel(p_full=300.0, p_idle=40.0, alpha=1.6))
    nodes = [NodeSpec(f"n{k}", speed=speeds[k % 5], ladder=ladders[k % 3],
                      power=powers[k % 3]) for k in range(n_nodes)]
    blocks = _blocks(costs, [None] * len(costs))
    worst = sum(costs) / min(nd.speed for nd in nodes)
    deadline = worst * (1.0 + slack) + 1e-6
    p = plan_cluster(blocks, nodes, deadline, assignment=assignment,
                     error_margin=margin)
    q = plan_cluster_reference(blocks, nodes, deadline,
                               assignment=assignment, error_margin=margin)
    assert p.feasible == q.feasible
    assert p.pred_total_energy == pytest.approx(q.pred_total_energy, abs=1e-6)
    for a_np, b_np in zip(p.node_plans, q.node_plans):
        assert a_np.node.name == b_np.node.name
        assert len(a_np.blocks) == len(b_np.blocks)
        for a, b in zip(a_np.blocks, b_np.blocks):
            assert a.index == b.index
            assert a.rel_freq == b.rel_freq
            assert abs(a.pred_energy_j - b.pred_energy_j) <= 1e-9


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 3000),
    fraction=st.floats(0.01, 0.3),
    n_boot=st.integers(10, 300),
    seed=st.integers(0, 10_000),
)
def test_sample_block_cost_matches_reference(n, fraction, n_boot, seed):
    """The (n_boot, k) gather bootstrap is bit-identical to the loop."""
    costs = np.random.default_rng(seed).lognormal(0.0, 0.7, n)
    a = sample_block_cost(costs, fraction=fraction, n_boot=n_boot, seed=seed)
    b = ref.sample_block_cost_reference(costs, fraction=fraction,
                                        n_boot=n_boot, seed=seed)
    assert a == b  # dataclass equality: every field identical


@settings(max_examples=10, deadline=None)
@given(
    n_blocks=st.integers(1, 30),
    seed=st.integers(0, 1000),
)
def test_sample_blocks_matches_reference(n_blocks, seed):
    rng = np.random.default_rng(seed)
    data = [rng.lognormal(0.0, 0.5, int(rng.integers(5, 500)))
            for _ in range(n_blocks)]
    assert sample_blocks(data, seed=seed) == \
        ref.sample_blocks_reference(data, seed=seed)


def test_sample_blocks_estimates_independent_of_set():
    """Block i's estimate must not depend on which other blocks are present
    (per-block seeding): dropping a block leaves the others unchanged."""
    rng = np.random.default_rng(0)
    data = [rng.lognormal(0.0, 0.5, 300) for _ in range(5)]
    full = sample_blocks(data, seed=9)
    assert sample_blocks(data[:3], seed=9) == full[:3]


def test_plan_dvo_matches_loop_semantics():
    """DVO: f_max everywhere, same totals as the scalar formulas."""
    from repro.core import TPU_V5E_POWER, block_time
    blocks = _blocks([1.0, 2.5, 0.3, 7.0], [None, (1e12, 2e10), None, None])
    plan = plan_dvo(blocks, 20.0)
    for b, bp in zip(blocks, plan.blocks):
        assert bp.rel_freq == 1.0
        assert bp.pred_time_s == pytest.approx(block_time(b, 1.0), abs=0)
        assert bp.pred_energy_j == pytest.approx(
            TPU_V5E_POWER.busy_energy(block_time(b, 1.0), 1.0, util=b.util),
            abs=0)


def test_schedule_plan_totals_cached():
    """pred_total_* are computed once (cached_property on the frozen plan)."""
    blocks = _blocks(np.linspace(1, 3, 64), [None] * 64)
    plan = plan_dvfs(blocks, 500.0, planner="global")
    first = plan.pred_total_energy
    assert "pred_total_energy" in plan.__dict__  # cached after first access
    assert plan.pred_total_energy is plan.__dict__["pred_total_energy"]
    assert first == sum(b.pred_energy_j for b in plan.blocks)
