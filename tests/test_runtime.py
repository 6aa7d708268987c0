"""Invariant suite for the event-driven cluster runtime (``repro.runtime``).

Property-based via the hypothesis compat shim.  The contract:

  (a) with no faults, no cap, and actuation latency 0 the engine reproduces
      the block-boundary loop (``simulate_cluster_reference``) BIT-FOR-BIT
      — per-node busy seconds, energies, frequencies, report equality —
      from the same plan, static and online alike;
  (b) partial-block accounting is exact: a block split across k
      frequencies costs the sum of its segments' times/energies as priced
      by ``block_time_table``/``busy_energy_table``, verified from event
      timestamps alone;
  (c) migration never moves an in-flight block and never pushes a
      previously-feasible node past the deadline;
  (d) with ``power_cap_w`` set, the instantaneous cluster draw
      (reconstructed independently from the event log) never exceeds the
      cap at any event timestamp;
  (e) a fixed scenario is deterministic: two runs produce identical event
      logs and reports.
"""
import dataclasses

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import BlockInfo, FrequencyLadder
from repro.core.scheduler import block_time_table, busy_energy_table
from repro.cluster import (NodeSpec, SlowdownEvent, assign_blocks,
                           plan_cluster, plan_cluster_arrays,
                           simulate_cluster, simulate_cluster_reference)
from repro.cluster.planner import BlockPlan, ClusterPlan, NodePlan
from repro.runtime import (ActuationModel, FaultEvent, RuntimeConfig,
                           run_cluster)

DEEP = FrequencyLadder(
    states=tuple(round(f, 2) for f in np.arange(0.35, 1.001, 0.05)))
SPEED_SETS = {1: (1.0,), 2: (1.0, 0.7), 3: (1.0, 0.7, 1.3),
              4: (1.0, 0.7, 1.3, 0.9)}


def _blocks(costs):
    return [BlockInfo(i, float(c)) for i, c in enumerate(costs)]


def _nodes(n):
    return [NodeSpec(f"n{k}", speed=s, ladder=DEEP)
            for k, s in enumerate(SPEED_SETS[n])]


def _deadline(blocks, nodes, slack):
    rr = assign_blocks(blocks, nodes, strategy="round_robin")
    return max(sum(b.est_time_fmax for b in g) / n.speed
               for g, n in zip(rr, nodes)) * slack


# --- (a) bit-for-bit compatibility with the block-boundary loop -------------

@settings(max_examples=30, deadline=None)
@given(
    costs=st.lists(st.floats(0.5, 20.0), min_size=2, max_size=20),
    slack=st.floats(1.05, 2.0),
    n_nodes=st.integers(1, 4),
    online=st.booleans(),
    fault=st.booleans(),
)
def test_engine_reproduces_blockloop_bitforbit(costs, slack, n_nodes,
                                               online, fault):
    blocks = _blocks(costs)
    nodes = _nodes(n_nodes)
    plan = plan_cluster(blocks, nodes, _deadline(blocks, nodes, slack))
    events = [SlowdownEvent("n0", after_block=1, factor=1.7)] if fault else []
    kw = dict(online=online, events=events, ewma_alpha=0.5,
              replan_threshold=0.1)
    assert simulate_cluster(plan, blocks, **kw) \
        == simulate_cluster_reference(plan, blocks, **kw)


def test_engine_consumes_plan_arrays_directly():
    """ClusterPlanArrays in == ClusterPlan in (the SoA path needs no object
    materialization on the static run)."""
    blocks = _blocks([3.0, 7.0, 1.0, 5.0, 2.0, 4.0])
    nodes = _nodes(2)
    plan = plan_cluster(blocks, nodes, _deadline(blocks, nodes, 1.4))
    from repro.core.soa import BlockArrays
    r_obj = run_cluster(plan, blocks)
    r_soa = run_cluster(plan.to_arrays(), BlockArrays.from_blocks(blocks))
    assert r_obj == r_soa


# --- (b) partial-block accounting is exact ----------------------------------

def _single_node_plan(node, ests, freqs, deadline):
    bps = tuple(BlockPlan(i, deadline / len(ests), f,
                          node.block_time(BlockInfo(i, e), f),
                          node.block_energy(BlockInfo(i, e),
                                            node.block_time(BlockInfo(i, e), f),
                                            f))
                for i, (e, f) in enumerate(zip(ests, freqs)))
    return ClusterPlan("cluster", deadline, (NodePlan(node, bps),), True)


def test_midblock_switch_accounting_matches_tables():
    """Actuation latency forces block 1 to launch at block 0's frequency and
    switch mid-block; both segments must price off the planner's own
    time/energy tables, checked from event timestamps."""
    node = NodeSpec("n0", ladder=FrequencyLadder(states=(0.5, 1.0)))
    ests = (4.0, 6.0)
    plan = _single_node_plan(node, ests, (1.0, 0.5), 100.0)
    blocks = _blocks(ests)
    act = ActuationModel(latency_s=1.0, switch_energy_j=0.5)
    rep = run_cluster(plan, blocks, config=RuntimeConfig(actuation=act))

    tab_t = block_time_table(blocks, node.ladder.states)
    tab_e = busy_energy_table(tab_t, np.ones(2), node.ladder.states,
                              node.power)
    starts = {e[3]: e[0] for e in rep.event_log if e[1] == "block_start"}
    finishes = {e[3]: e for e in rep.event_log if e[1] == "block_finish"}
    switch = next(e for e in rep.event_log if e[1] == "freq_switch")
    assert switch[3] == 1 and switch[4] == 1.0 and switch[5] == 0.5
    # segment 1: 1.0 s at f=1.0 -> work fraction done
    seg1 = switch[0] - starts[1]
    assert seg1 == pytest.approx(act.latency_s, abs=1e-12)
    w1 = seg1 / tab_t[1, 1]          # T(f=1.0) is state column 1
    # segment 2 duration from event times == remaining work at T(f=0.5)
    seg2 = finishes[1][0] - switch[0]
    assert seg2 == pytest.approx((1.0 - w1) * tab_t[1, 0], rel=1e-12)
    # reported busy/energy == segment sums off the tables
    busy = finishes[1][4]
    energy = finishes[1][5]
    assert busy == pytest.approx(w1 * tab_t[1, 1] + (1 - w1) * tab_t[1, 0],
                                 rel=1e-12)
    assert energy == pytest.approx(w1 * tab_e[1, 1] + (1 - w1) * tab_e[1, 0],
                                   rel=1e-12)
    # the transition itself was charged
    assert rep.n_switches == 1 and rep.switch_energy_j == 0.5


def test_midblock_fault_repricing_exact():
    """A time-based fault lands mid-block: the remaining work fraction is
    re-priced at the faulted speed, exactly."""
    node = NodeSpec("n0")
    ests = (5.0,)
    plan = _single_node_plan(node, ests, (1.0,), 100.0)
    rep = run_cluster(plan, _blocks(ests),
                      events=[FaultEvent(2.0, "n0", 3.0)])
    t_full = 5.0
    w_done = 2.0 / t_full
    expect = 2.0 + (1.0 - w_done) * (t_full * 3.0)
    nr = rep.node_reports[0]
    assert nr.busy_s == pytest.approx(expect, rel=1e-12)
    assert rep.makespan_s == pytest.approx(expect, rel=1e-12)


@settings(max_examples=15, deadline=None)
@given(
    costs=st.lists(st.floats(1.0, 12.0), min_size=3, max_size=12),
    latency=st.floats(0.1, 2.0),
    fault_t=st.floats(0.5, 10.0),
    factor=st.floats(1.2, 3.0),
)
def test_segment_sums_close_under_switches_and_faults(costs, latency,
                                                      fault_t, factor):
    """Property: however switches and faults slice the blocks, every block's
    reported busy time equals the sum of its segment durations measured
    from event timestamps (work is neither lost nor double-counted)."""
    blocks = _blocks(costs)
    nodes = _nodes(2)
    plan = plan_cluster(blocks, nodes, _deadline(blocks, nodes, 1.3))
    rep = run_cluster(
        plan, blocks,
        config=RuntimeConfig(online=True, ewma_alpha=0.6,
                             replan_threshold=0.05,
                             actuation=ActuationModel(latency_s=latency)),
        events=[SlowdownEvent("n0", 1, factor),
                FaultEvent(fault_t, "n1", factor)],
        est_blocks=blocks)
    bounds: dict = {}
    for e in rep.event_log:
        if e[1] == "block_start" and isinstance(e[3], (int, np.integer)):
            bounds[e[3]] = e[0]
        elif e[1] == "block_finish":
            start = bounds[e[3]]
            assert e[4] == pytest.approx(e[0] - start, rel=1e-9, abs=1e-9)


# --- (c) migration safety ----------------------------------------------------

def _migration_scenario(factor=4.0, n_blocks=24, slack=2.2):
    blocks = [BlockInfo(i, 5.0) for i in range(n_blocks)]
    nodes = [NodeSpec("n0", speed=1.0, ladder=DEEP),
             NodeSpec("n1", speed=0.8, ladder=DEEP),
             NodeSpec("n2", speed=1.25, ladder=DEEP)]
    deadline = max(sum(b.est_time_fmax for b in g) / n.speed
                   for g, n in zip(assign_blocks(blocks, nodes), nodes)) \
        * slack
    plan = plan_cluster(blocks, nodes, deadline, assignment="lpt")
    n0_blocks = len(plan.node_plans[0].blocks)
    events = [SlowdownEvent("n0", after_block=n0_blocks // 2 - 1,
                            factor=factor)]
    return plan, blocks, events, deadline


def test_migration_recovers_what_fmax_cannot():
    """Acceptance scenario: the static plan and the clock-up-only online run
    both miss; migration meets the deadline."""
    plan, blocks, events, _ = _migration_scenario()
    kw = dict(ewma_alpha=0.7, replan_threshold=0.1)
    r_static = run_cluster(plan, blocks, events=events)
    r_online = run_cluster(plan, blocks, events=events, est_blocks=blocks,
                           config=RuntimeConfig(online=True, **kw))
    r_mig = run_cluster(plan, blocks, events=events, est_blocks=blocks,
                        config=RuntimeConfig(online=True, migrate=True, **kw))
    assert not r_static.deadline_met
    assert not r_online.deadline_met
    assert r_mig.deadline_met
    assert r_mig.n_migrations >= 1


@settings(max_examples=12, deadline=None)
@given(
    factor=st.floats(2.5, 6.0),
    slack=st.floats(1.8, 2.6),
    n_blocks=st.integers(12, 30),
)
def test_migration_never_moves_inflight_or_breaks_feasible_nodes(
        factor, slack, n_blocks):
    plan, blocks, events, deadline = _migration_scenario(factor, n_blocks,
                                                         slack)
    rep = run_cluster(plan, blocks, events=events, est_blocks=blocks,
                      config=RuntimeConfig(online=True, migrate=True,
                                           ewma_alpha=0.7,
                                           replan_threshold=0.1))
    start_times: dict = {}
    for e in rep.event_log:
        if e[1] == "block_start" and isinstance(e[3], (int, np.integer)):
            start_times.setdefault(e[3], e[0])
    for mv in rep.migrations:
        # queued only: the block must not have started anywhere before the
        # move, and must start on the destination at or after it
        assert start_times[mv.block_index] >= mv.time - 1e-12
        # the guard held at decision time
        assert mv.dst_pred_s <= deadline + 1e-9
    # nodes that were not slowed ran exactly as predicted -> the guard
    # means they still finish inside the deadline even with migrated work
    for nr in rep.node_reports:
        if nr.name != "n0":
            assert nr.finish_s <= deadline + 1e-6


# --- (d) cluster power cap ---------------------------------------------------

def _reconstruct_peak(rep, blocks, nodes):
    """Independent power timeline from the event log (not the ledger)."""
    util = {b.index: b.util for b in blocks}
    spec = {n.name: n for n in nodes}
    draw = {n.name: n.power.p_idle for n in nodes}
    cur_block: dict = {}
    peak = sum(draw.values())
    for e in rep.event_log:
        name = e[2]
        if e[1] == "block_start" and isinstance(e[3], (int, np.integer)):
            cur_block[name] = e[3]
            draw[name] = spec[name].power.power(util[e[3]], e[4])
        elif e[1] == "block_finish":
            draw[name] = spec[name].power.p_idle
        elif e[1] == "freq_switch" and len(e) == 6 and e[4] != "idle":
            draw[name] = spec[name].power.power(util[cur_block[name]], e[5])
        peak = max(peak, sum(draw.values()))
    return peak


@settings(max_examples=12, deadline=None)
@given(
    costs=st.lists(st.floats(1.0, 10.0), min_size=4, max_size=24),
    slack=st.floats(1.1, 1.8),
    cap_frac=st.floats(0.7, 0.98),
    migrate=st.booleans(),
)
def test_power_cap_never_exceeded(costs, slack, cap_frac, migrate):
    blocks = _blocks(costs)
    nodes = _nodes(3)
    deadline = _deadline(blocks, nodes, slack)
    free = run_cluster(plan_cluster(blocks, nodes, deadline), blocks)
    idle_floor = sum(n.power.p_idle for n in nodes)
    cap = max(free.peak_power_w * cap_frac, idle_floor * 1.3,
              idle_floor + 140.0)
    plan = plan_cluster(blocks, nodes, deadline, power_cap_w=cap)
    cfg = RuntimeConfig(power_cap_w=cap, online=migrate, migrate=migrate,
                        ewma_alpha=0.7, replan_threshold=0.1)
    rep = run_cluster(plan, blocks, config=cfg,
                      events=[SlowdownEvent("n0", 1, 2.0)] if migrate else (),
                      est_blocks=blocks if migrate else None)
    assert rep.peak_power_w <= cap + 1e-9
    assert _reconstruct_peak(rep, blocks, nodes) <= cap + 1e-9


def test_power_cap_screen_downclocks_plan():
    """Plan-time screen: with slack available, the capped plan stays
    deadline-feasible but chooses lower peak power than the free plan."""
    blocks = _blocks([5.0] * 18)
    nodes = _nodes(3)
    deadline = _deadline(blocks, nodes, 1.6)
    free = plan_cluster(blocks, nodes, deadline)
    r_free = run_cluster(free, blocks)
    cap = r_free.peak_power_w * 0.9
    capped = plan_cluster(blocks, nodes, deadline, power_cap_w=cap)
    assert capped.feasible and capped.power_cap_ok
    r_cap = run_cluster(capped, blocks,
                        config=RuntimeConfig(power_cap_w=cap))
    assert r_cap.deadline_met
    assert r_cap.peak_power_w <= cap + 1e-9
    assert r_cap.peak_power_w < r_free.peak_power_w - 1e-6


def test_late_migration_respects_wall_clock_slack():
    """A target that drained long ago has busy-time 'slack' that is wall-
    clock stale: migrated work cannot start before NOW.  A late trigger
    (straggler detected near the deadline) must therefore move nothing
    instead of pushing the previously-feasible target past the deadline."""
    blocks = [BlockInfo(i, 3.8) for i in range(5)] + [BlockInfo(5, 1.0)]
    nodes = [NodeSpec("n0", ladder=DEEP), NodeSpec("n1", ladder=DEEP)]
    deadline = 20.0
    plan = plan_cluster(blocks, nodes, deadline,
                        assignment=[0, 0, 0, 0, 0, 1])
    rep = run_cluster(plan, blocks,
                      events=[SlowdownEvent("n0", 1, 4.0)],
                      est_blocks=blocks,
                      config=RuntimeConfig(online=True, migrate=True,
                                           ewma_alpha=0.7,
                                           replan_threshold=0.1))
    # n1 finished its 1 s block at t=1; the straggler is detected at t=19,
    # when n1's wall-clock room is one block at most — no 3.8 s block fits
    assert rep.n_migrations == 0
    n1 = next(nr for nr in rep.node_reports if nr.name == "n1")
    assert n1.finish_s <= deadline + 1e-9


def test_all_launches_deferred_is_not_a_met_deadline():
    """A cap above the idle floor but below any launchable draw defers every
    block forever; the empty run must NOT report deadline_met."""
    blocks = _blocks([2.0, 3.0])
    nodes = _nodes(2)   # idle floor 140 W; cheapest launch needs ~150.4 W
    plan = plan_cluster(blocks, nodes, 100.0)
    rep = run_cluster(plan, blocks, config=RuntimeConfig(power_cap_w=150.0))
    assert not rep.deadline_met
    assert all(nr.n_blocks == 0 for nr in rep.node_reports)


def test_power_cap_below_idle_floor_rejected():
    blocks = _blocks([1.0, 2.0])
    nodes = _nodes(2)
    plan = plan_cluster(blocks, nodes, 100.0)
    with pytest.raises(ValueError):
        run_cluster(plan, blocks,
                    config=RuntimeConfig(power_cap_w=1.0))


# --- (e) determinism ---------------------------------------------------------

def test_full_feature_run_is_deterministic():
    """Everything on at once (faults, migration, latency, cap): two runs
    produce identical event logs and identical reports."""
    plan, blocks, events, deadline = _migration_scenario()
    free = run_cluster(plan, blocks)
    cap = free.peak_power_w * 1.05   # head-room so migration stays possible
    cfg = RuntimeConfig(online=True, migrate=True, ewma_alpha=0.7,
                        replan_threshold=0.1, power_cap_w=cap,
                        actuation=ActuationModel(latency_s=0.5,
                                                 switch_energy_j=1.0))
    events = events + [FaultEvent(deadline * 0.6, "n1", 1.5)]
    r1 = run_cluster(plan, blocks, config=cfg, events=events,
                     est_blocks=blocks)
    r2 = run_cluster(plan, blocks, config=cfg, events=events,
                     est_blocks=blocks)
    assert r1.event_log == r2.event_log
    assert r1 == r2
    assert len(r1.event_log) > 0


def test_pipeline_stream_run_handoff():
    """Dataset -> plan -> runtime, SoA end to end: the streamed estimates'
    cluster plan feeds the engine directly and executes drift-free against
    its own estimates (finish == prediction per node, deadline met on a
    feasible plan)."""
    from repro.pipeline import stream_estimates, synthetic_cost_chunks
    nodes = _nodes(3)
    est = stream_estimates(synthetic_cost_chunks(600, 32, seed=1))
    deadline = float(est.total.sum()) / (0.8 * len(nodes)) * 1.5
    ba = est.to_block_arrays()
    cpa = plan_cluster_arrays(ba, nodes, deadline, assignment="round_robin")
    rep = run_cluster(cpa, ba, config=RuntimeConfig(log_events=False))
    assert rep.deadline_met
    assert sum(nr.n_blocks for nr in rep.node_reports) == 600
    # truth == estimates: execution realizes the plan's own predictions
    for nr, npa in zip(rep.node_reports, cpa.node_plans):
        assert nr.busy_s == pytest.approx(npa.pred_finish_s, rel=1e-12)


def test_runtime_config_validation():
    with pytest.raises(ValueError):
        RuntimeConfig(migrate=True)            # migration needs online
    with pytest.raises(ValueError):
        RuntimeConfig(power_cap_w=0.0)
    with pytest.raises(ValueError):
        ActuationModel(latency_s=-1.0)


def test_cost_model_validation_rejects_negative_energy():
    """Transfer/switch energies are joules: negative values would let a
    planner 'gain' energy by moving or switching."""
    from repro.runtime import MigrationModel
    with pytest.raises(ValueError):
        MigrationModel(energy_j_per_record=-0.01)
    with pytest.raises(ValueError):
        MigrationModel(latency_s_per_block=-1.0)
    with pytest.raises(ValueError):
        ActuationModel(switch_energy_j=-0.5)


# --- (f) power-ledger end-of-run invariant ----------------------------------

def _drained_ledger_ok(engine):
    """Every node back at p_idle, every aux (wire) watt released."""
    led = engine.ledger
    for nid in range(len(engine.nodes)):
        assert led.draw_of(nid) == led._idle[nid]
        assert abs(led.aux_of(nid)) < 1e-9
    assert led.total_w == pytest.approx(sum(led._idle), abs=1e-9)


def test_power_ledger_drains_to_idle_after_run():
    """End-of-run ledger invariant: when the queue empties, no node still
    'draws' busy watts and no migration wire is still charged — across the
    full feature matrix (faults, migration wire, cap, latency), crashes
    included, on both engines."""
    from repro.runtime import NodeFailureEvent, RecoveryPolicy
    from repro.runtime.engine import ClusterRuntime
    from repro.runtime.vector import VectorClusterRuntime
    plan, blocks, events, deadline = _migration_scenario()
    free = run_cluster(plan, blocks)
    cfg_kw = dict(online=True, migrate=True, ewma_alpha=0.7,
                  replan_threshold=0.1, power_cap_w=free.peak_power_w * 1.05,
                  actuation=ActuationModel(latency_s=0.5,
                                           switch_energy_j=1.0))
    from repro.runtime import MigrationModel
    cfg_kw["migration"] = MigrationModel(latency_s_per_block=1.0,
                                         energy_j_per_record=0.01)
    ev = events + [FaultEvent(deadline * 0.6, "n1", 1.5)]
    ev_crash = ev + [NodeFailureEvent(time=deadline * 0.4, node="n2",
                                      flavor="transient",
                                      repair_s=deadline * 0.1)]
    for cls in (ClusterRuntime, VectorClusterRuntime):
        for events_i, rec in ((ev, None), (ev_crash, RecoveryPolicy())):
            eng = cls(plan, blocks,
                      config=RuntimeConfig(**cfg_kw, recovery=rec),
                      events=events_i, est_blocks=blocks)
            eng.run()
            _drained_ledger_ok(eng)
