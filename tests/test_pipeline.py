"""Streamed SoA pipeline == object-based path, property-based.

The chunked dataset→plan pipeline (``repro.pipeline``) never constructs
per-block Python objects; this suite is the contract that its plans are
nonetheless IDENTICAL to the object path (``BlockEstimate`` → ``BlockInfo``
→ ``plan_dvfs`` / ``plan_cluster``) run on the same estimates — across
random chunk sizes (including boundaries that split a node's block set),
planners, deadline regimes, and cluster assignments — and that the exact
sampler, chunk by chunk, is bit-identical to ``sample_blocks``.  The
pipeline plans one node; the cluster cases compose its estimates with
``plan_cluster_arrays`` as every caller does.  Runs under the hypothesis
compat shim.
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (BlockArrays, BlockInfo, EstimateArrays,
                        FrequencyLadder, PowerModel, plan_dvfs, plan_dvo,
                        plan_dvo_arrays, sample_blocks)
from repro.core import _reference as ref
from repro.core.sampling import sample_blocks_soa
from repro.cluster import NodeSpec, plan_cluster, plan_cluster_arrays
from repro.pipeline import (PipelineConfig, plan_estimates, stream_estimates,
                            stream_estimates_tokens, synthetic_cost_chunks)


def _assert_plan_arrays_match_schedule(pa, plan):
    """PlanArrays (streamed) == SchedulePlan (object path), exactly."""
    assert pa.feasible == plan.feasible
    assert len(pa) == len(plan.blocks)
    for i, b in enumerate(plan.blocks):
        assert int(pa.index[i]) == b.index
        assert pa.rel_freq[i] == b.rel_freq
        assert pa.pred_time_s[i] == b.pred_time_s
        assert pa.pred_energy_j[i] == b.pred_energy_j


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(1, 400),
    chunk=st.integers(1, 500),
    planner=st.sampled_from(["paper", "global"]),
    slack=st.floats(0.0, 1.0),
    z=st.floats(0.0, 2.0),
    seed=st.integers(0, 100),
)
def test_stream_plan_matches_object_path(n, chunk, planner, slack, z, seed):
    """Same estimates, object pipeline vs SoA pipeline: identical plans."""
    cfg = PipelineConfig(chunk_size=chunk, planner=planner)
    src = synthetic_cost_chunks(n, 24, z=z, seed=seed, chunk_size=chunk)
    est = stream_estimates(src, cfg)
    deadline = float(est.total.sum()) * (1.0 + slack) + 1e-6
    pa = plan_estimates(est, deadline, cfg)
    blocks = est.to_block_arrays().to_blocks()
    _assert_plan_arrays_match_schedule(pa, plan_dvfs(blocks, deadline,
                                                     planner=planner))
    # and the PlanArrays view reconstructs the same SchedulePlan (totals
    # agree up to summation order: python sum vs pairwise np.sum)
    sp = pa.to_schedule_plan()
    assert sp.pred_total_energy == pytest.approx(pa.pred_total_energy,
                                                 rel=1e-12, abs=1e-9)


@settings(max_examples=12, deadline=None)
@given(
    n=st.integers(1, 200),
    chunk_a=st.integers(1, 250),
    chunk_b=st.integers(1, 250),
    seed=st.integers(0, 50),
)
def test_estimates_and_plans_invariant_to_chunk_size(n, chunk_a, chunk_b,
                                                     seed):
    """Chunk boundaries must never leak into estimates or plans."""
    ea = stream_estimates(
        synthetic_cost_chunks(n, 16, seed=seed, chunk_size=chunk_a),
        PipelineConfig(chunk_size=chunk_a))
    eb = stream_estimates(
        synthetic_cost_chunks(n, 16, seed=seed, chunk_size=chunk_b),
        PipelineConfig(chunk_size=chunk_b))
    assert np.array_equal(ea.total, eb.total)
    assert np.array_equal(ea.ci_low, eb.ci_low)
    assert np.array_equal(ea.ci_high, eb.ci_high)
    deadline = float(ea.total.sum()) * 1.2
    pa = plan_estimates(ea, deadline, PipelineConfig())
    pb = plan_estimates(eb, deadline, PipelineConfig())
    assert np.array_equal(pa.rel_freq, pb.rel_freq)
    assert np.array_equal(pa.pred_energy_j, pb.pred_energy_j)


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(1, 120),
    chunk=st.integers(1, 40),
    n_nodes=st.integers(1, 4),
    assignment=st.sampled_from(["auto", "lpt", "pack", "round_robin"]),
    slack=st.floats(0.05, 1.0),
    seed=st.integers(0, 40),
)
def test_stream_cluster_matches_object_path(n, chunk, n_nodes, assignment,
                                            slack, seed):
    """Cluster SoA path == object path on the same streamed estimates —
    chunk sizes deliberately smaller than node counts' strides, so chunk
    boundaries split every node's block set."""
    speeds = (1.0, 0.7, 1.3, 0.85)
    ladders = (FrequencyLadder(), FrequencyLadder(states=(0.5, 0.75, 1.0)))
    powers = (PowerModel(), PowerModel(p_full=95.0, p_idle=15.0, alpha=3.0))
    nodes = [NodeSpec(f"n{k}", speed=speeds[k % 4], ladder=ladders[k % 2],
                      power=powers[k % 2]) for k in range(n_nodes)]
    cfg = PipelineConfig(chunk_size=chunk)
    est = stream_estimates(
        synthetic_cost_chunks(n, 16, seed=seed, chunk_size=chunk), cfg)
    worst = float(est.total.sum()) / min(nd.speed for nd in nodes)
    deadline = worst * (1.0 + slack) + 1e-6
    cpa = plan_cluster_arrays(est.to_block_arrays(), nodes, deadline,
                              assignment=assignment)
    blocks = est.to_block_arrays().to_blocks()
    obj = plan_cluster(blocks, nodes, deadline, assignment=assignment)
    got = cpa.to_cluster_plan()
    assert got.feasible == obj.feasible
    assert cpa.pred_total_energy == pytest.approx(obj.pred_total_energy,
                                                  abs=1e-9)
    for a_np, b_np in zip(got.node_plans, obj.node_plans):
        assert a_np.node.name == b_np.node.name
        assert len(a_np.blocks) == len(b_np.blocks)
        for a, b in zip(a_np.blocks, b_np.blocks):
            assert a.index == b.index
            assert a.rel_freq == b.rel_freq
            assert a.pred_time_s == b.pred_time_s
            assert a.pred_energy_j == b.pred_energy_j


@settings(max_examples=8, deadline=None)
@given(
    n=st.integers(1, 60),
    chunk=st.integers(1, 70),
    seed=st.integers(0, 30),
)
def test_exact_sampler_bit_identical_to_sample_blocks(n, chunk, seed):
    """method="exact", chunk by chunk: the SoA estimates ARE
    sample_blocks', bit for bit."""
    rng = np.random.default_rng(seed)
    costs = rng.lognormal(0.0, 0.6, (n, 120))
    est = EstimateArrays.concat([
        sample_blocks_soa(costs[start:start + chunk], seed=seed,
                          start_index=start, method="exact")
        for start in range(0, n, chunk)])
    want = sample_blocks(list(costs), seed=seed)
    assert est.to_block_estimates() == want


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(1, 250),
    slack=st.floats(0.0, 0.25),
    seed=st.integers(0, 60),
)
def test_tight_deadline_scan_matches_reference(n, slack, seed):
    """Budget-binding regime (kills dominate): the array-level scan must
    reproduce the loop reference exactly — this is the regime the old
    implementation handed to a per-step python tail."""
    rng = np.random.default_rng(seed)
    costs = rng.lognormal(0.0, 0.8, n) * 4.0
    blocks = [BlockInfo(i, float(c), util=float(rng.uniform(0.3, 1.0)))
              for i, c in enumerate(costs)]
    deadline = float(costs.sum()) * (1.0 + slack)
    p = plan_dvfs(blocks, deadline, planner="global")
    q = ref.plan_dvfs_reference(blocks, deadline, planner="global")
    assert p.feasible == q.feasible
    for a, b in zip(p.blocks, q.blocks):
        assert a.rel_freq == b.rel_freq
        assert a.pred_time_s == b.pred_time_s
        assert abs(a.pred_energy_j - b.pred_energy_j) <= 1e-9


def test_sampler_keys_decorrelated_from_generator_stream():
    """Source and sampler share one seed in the natural call; the sampler's
    selection keys must live in a different hash domain, or 'pick the k
    smallest keys' silently becomes 'pick the k cheapest records' and every
    estimate is biased low (caught in review: ratio was ~0.15)."""
    chunks = list(synthetic_cost_chunks(800, 200, z=1.0, seed=0,
                                        chunk_size=200))
    true_totals = np.concatenate([c["costs"].sum(axis=1) for c in chunks])
    est = stream_estimates(iter(chunks), PipelineConfig(chunk_size=200,
                                                        seed=0))
    ratio = float((est.total / true_totals).mean())
    assert 0.85 < ratio < 1.15


def test_cluster_node_plan_feasibility_is_per_node():
    """An infeasible node's PlanArrays must not claim feasible=True."""
    est = stream_estimates(synthetic_cost_chunks(30, 16, seed=6),
                           PipelineConfig())
    nodes = [NodeSpec("n0", speed=1.0), NodeSpec("n1", speed=1.0)]
    # deadline far below any node's share: nothing is feasible
    cpa = plan_cluster_arrays(est.to_block_arrays(), nodes,
                              float(est.total.sum()) * 1e-3,
                              assignment="round_robin")
    assert not cpa.feasible
    assert all(not np_.plan.feasible for np_ in cpa.node_plans)


def test_dvo_arrays_matches_object_dvo():
    est = stream_estimates(synthetic_cost_chunks(64, 16, seed=2),
                           PipelineConfig())
    ba = est.to_block_arrays()
    deadline = float(est.total.sum()) * 1.5
    pa = plan_dvo_arrays(ba, deadline)
    _assert_plan_arrays_match_schedule(pa, plan_dvo(ba.to_blocks(), deadline))


def test_token_pipeline_chunk_invariant_and_planable():
    """Tokens → batched stats kernel → estimates → plan, end to end."""
    from repro.data import BlockDataset
    ds = BlockDataset(n_blocks=10, records_per_block=48, max_len=32, seed=9)
    e1 = stream_estimates_tokens(ds.iter_token_chunks(3))
    e2 = stream_estimates_tokens(ds.iter_token_chunks(10))
    assert np.array_equal(e1.total, e2.total)
    assert np.isfinite(e1.total).all()
    assert np.all(e1.ci_high >= e1.total) and np.all(e1.ci_low <= e1.total)
    pa = plan_estimates(e1, float(e1.total.sum()) * 1.3, PipelineConfig())
    assert pa.feasible
    assert len(pa) == 10


def test_stats_soa_matches_object_stats():
    """BlockDataset.stats_soa (batched kernel, SoA) == stats(i) objects."""
    from repro.data import BlockDataset
    ds = BlockDataset(n_blocks=6, records_per_block=40, max_len=24, seed=4)
    soa = ds.stats_soa(chunk_size=4)
    for i in range(ds.n_blocks):
        s = ds.stats(i)
        assert soa["records"][i] == s.records
        assert soa["tokens"][i] == s.tokens
        assert soa["tokens_padded"][i] == s.tokens_padded
        assert soa["matches"][i] == s.matches
        assert soa["selected"][i] == s.selected


def test_block_arrays_roundtrip_preserves_blocks():
    """from_blocks -> to_blocks is the identity (incl. rooflines)."""
    from repro.core import BlockInfo, RooflineTimeModel
    roof = RooflineTimeModel.from_counts(flops=1e12, hbm_bytes=2e10,
                                         coll_bytes=1e8)
    blocks = [BlockInfo(3, 1.5, est_rel_halfwidth=0.02, util=0.7,
                        roofline=roof),
              BlockInfo(7, 0.5, util=0.4)]
    back = BlockArrays.from_blocks(blocks).to_blocks()
    assert back == blocks


def test_plan_arrays_is_soa_not_objects():
    """The streamed plan holds arrays; BlockPlan objects only on demand."""
    est = stream_estimates(synthetic_cost_chunks(128, 16, seed=0),
                           PipelineConfig())
    pa = plan_estimates(est, float(est.total.sum()) * 1.4, PipelineConfig())
    assert isinstance(pa.rel_freq, np.ndarray)
    assert isinstance(pa.pred_energy_j, np.ndarray)
    blocks = pa.to_blocks()
    assert len(blocks) == 128
    assert blocks[0].rel_freq == pa.rel_freq[0]


# --- calibrated rooflines in the stream path ---------------------------------

def _cluster_plans_equal(cpa, obj):
    got = cpa.to_cluster_plan()
    assert got.feasible == obj.feasible
    for a_np, b_np in zip(got.node_plans, obj.node_plans):
        assert a_np.node.name == b_np.node.name
        assert len(a_np.blocks) == len(b_np.blocks)
        for a, b in zip(a_np.blocks, b_np.blocks):
            assert a.index == b.index
            assert a.rel_freq == b.rel_freq
            assert a.pred_time_s == b.pred_time_s
            assert a.pred_energy_j == b.pred_energy_j


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(2, 80),
    chunk=st.integers(1, 40),
    beta=st.floats(0.0, 0.6),
    slack=st.floats(0.1, 1.0),
    seed=st.integers(0, 30),
)
def test_stream_calibrated_rooflines_match_object_path(n, chunk, beta,
                                                       slack, seed):
    """The fit's rooflines stamped on the streamed SoA
    (``CostFit.roofline_arrays``) == object path with ``CostFit.roofline()``
    stamped block by block — the fitted memory-bound fraction reaches
    streamed plans exactly as it reaches object plans."""
    import dataclasses as dc
    from repro.calibrate import fit_cost_model

    rng = np.random.default_rng(seed)
    # observations exercising the max-form kink at two frequencies, so the
    # fit recovers a nonzero memory-bound fraction when beta > 0
    rec = rng.uniform(100, 2000, 24)
    f = np.where(np.arange(24) % 2 == 0, 1.0, 0.6)
    wall = rec * 3e-4 * np.maximum((1.0 - beta) / f, 1.0)
    cf = fit_cost_model(rec, f, wall)

    nodes = [NodeSpec("a", speed=1.0), NodeSpec("b", speed=0.8)]
    cfg = PipelineConfig(chunk_size=chunk)
    est = stream_estimates(
        synthetic_cost_chunks(n, 16, seed=seed, chunk_size=chunk), cfg)
    deadline = float(est.total.sum()) / 0.8 * (1.0 + slack) + 1e-6
    ba = est.to_block_arrays(roofline=cf.roofline_arrays(est.n_records))
    cpa = plan_cluster_arrays(ba, nodes, deadline)

    # independent object path: scalar CostFit.roofline() per block
    blocks = [dc.replace(b, roofline=cf.roofline(b.records))
              for b in est.to_block_arrays().to_blocks()]
    obj = plan_cluster(blocks, nodes, deadline)
    _cluster_plans_equal(cpa, obj)


def test_stream_calibration_trace_calibrates_nodes():
    """``plan_cluster_arrays(calibration=CounterTrace)`` over streamed
    estimates == planning against ``calibrate_nodes(nodes, trace)`` — the
    streamed entry to the estimate->plan->measure loop."""
    from repro.calibrate import calibrate_nodes, synthetic_trace

    tr_parts = [synthetic_trace(nm, PowerModel(), speed=s, n_samples=60,
                                seed=i)
                for i, (nm, s) in enumerate([("a", 1.2), ("b", 0.9)])]
    from repro.calibrate import CounterTrace
    tr = CounterTrace.concat(tr_parts)
    nodes = [NodeSpec("a", speed=1.0), NodeSpec("b", speed=1.0)]

    est = stream_estimates(synthetic_cost_chunks(40, 16, seed=3),
                           PipelineConfig())
    deadline = float(est.total.sum()) * 1.2
    cpa = plan_cluster_arrays(est.to_block_arrays(), nodes, deadline,
                              calibration=tr)
    obj = plan_cluster(est.to_block_arrays().to_blocks(),
                       calibrate_nodes(nodes, tr), deadline)
    _cluster_plans_equal(cpa, obj)
