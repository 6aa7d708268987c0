"""Counterfactual observability: replay ledgers, run-diff, watchdog.

The contract on top of the deterministic engines:

  (a) exact Δ-ledgers — every ``profile_mechanisms`` row's five channel
      deltas plus the rational-space residual ``math.fsum`` BITWISE to
      the difference of the two reports' own totals, on randomized
      scenarios; mechanisms that were already off replay to all-zero
      rows; the DVFS ablation on the everything-on scenario reproduces
      the paper headline (f_max pays strictly more busy energy);
  (b) run-diff — ``diff_runs(r, r)`` is empty for any report; ablations
      produce attributed non-empty diffs; added/dropped round-trip when
      the arguments swap (shedding exercises real add/drop sets);
  (c) watchdog — the alert stream is bitwise-identical scalar vs vector
      and across two runs; ``deadline_risk`` alerts reach the replanner
      hook and nothing else does;
  (d) flight-recorder guard — replay-grade tools (``build_spans``,
      ``explain_*``) refuse ring/off logs loudly, naming the mode and
      drop count, while ``diff_runs`` degrades to report-level rollups;
  (e) exporter validation — ``validate_prometheus`` passes real
      expositions and rejects malformed ones;
  (f) bench history — ``benchmarks.history`` appends schema-stamped
      entries and flags trend regressions against the median baseline.
"""
import dataclasses
import json
import math

import pytest
from _hypothesis_compat import given, settings, st
from test_runtime_vector import _everything_on_parts, _scenario

from repro.cluster.controller import OnlineReplanner
from repro.obs.counterfactual import (Scenario, ablate, neutralize,
                                      profile_mechanisms)
from repro.obs.diff import diff_runs
from repro.obs.explain import explain_energy, explain_miss
from repro.obs.export import to_prometheus, validate_prometheus
from repro.obs.metrics import StreamingMetrics
from repro.obs.spans import build_spans, require_full_log
from repro.obs.watchdog import Alert, Rule, Watchdog, standard_rules
from repro.serving import run_serving, serving_scenario

CHANNELS = ("busy_j", "idle_j", "switch_j", "wire_j", "failed_j")


def _cf_scenario(seed=None, parts=None):
    plan, truth, cfg, events, blocks = parts if parts else _scenario(seed)
    return Scenario(plan=plan, truth=truth, config=cfg,
                    events=tuple(events), est_blocks=blocks)


def _assert_reconciled(row):
    parts = [row["d_" + c] for c in CHANNELS] + [row["residual_j"]]
    assert math.fsum(parts) == row["d_total_j"], row["mechanism"]


# ------------------------------------------------------- (a) exact Δ-ledgers

@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=0, max_value=10**6))
def test_delta_ledger_reconciles_exactly(seed):
    sc = _cf_scenario(seed)
    for row in profile_mechanisms(sc, engines=("vector",)):
        _assert_reconciled(row)
        if not row["changed"]:   # identity replay: every delta exactly zero
            assert row["d_total_j"] == 0.0
            assert row["d_misses"] == 0
            assert row["d_slack_s"] == 0.0


def test_everything_on_both_engines_and_paper_headline():
    sc = _cf_scenario(parts=_everything_on_parts(seed=7))
    rows = profile_mechanisms(sc, engines=("vector", "scalar"))
    for row in rows:
        _assert_reconciled(row)
    dvfs = next(r for r in rows if r["mechanism"] == "dvfs")
    # the paper's claim as a counterfactual on this very run: pinning
    # every node at f_max pays strictly more busy energy
    assert dvfs["changed"]
    assert dvfs["d_busy_j"] > 0.0


def test_neutralize_dvfs_pins_every_ladder():
    sc = _cf_scenario(parts=_everything_on_parts(seed=7))
    neutral, changed = neutralize(sc, "dvfs")
    assert changed
    cpa = neutral.plan.to_arrays()
    assert all(npa.node.ladder.states == (1.0,) for npa in cpa.node_plans)
    # neutralizing the already-pinned scenario is a no-op
    again, changed2 = neutralize(neutral, "dvfs")
    assert not changed2 and again is neutral


def test_neutralize_rejects_unknown_mechanism():
    sc = _cf_scenario(seed=3)
    with pytest.raises(ValueError, match="unknown mechanism"):
        neutralize(sc, "gremlins")


def test_scenario_rejects_stateful_config():
    plan, truth, cfg, events, blocks = _scenario(3)
    bad = dataclasses.replace(cfg, metrics=StreamingMetrics())
    with pytest.raises(ValueError, match="metrics"):
        Scenario(plan=plan, truth=truth, config=bad)


# ------------------------------------------------------------- (b) run-diff

def test_diff_identity_is_empty():
    sc = _cf_scenario(parts=_everything_on_parts(seed=7))
    a = sc.run(engine="vector")
    b = sc.run(engine="vector")
    d = diff_runs(a, b)
    assert d.empty
    assert d.spans_aligned


def test_diff_attributes_migration_ablation():
    sc = _cf_scenario(parts=_everything_on_parts(seed=7))
    base = sc.run(engine="vector")
    abl = ablate(sc, "migration", engines=("vector",))
    d = diff_runs(base, abl)
    assert not d.empty
    assert d.blocks or d.moved
    assert any(m["mechanism"] == "migration" for m in d.mechanisms)
    # swapped arguments negate the totals and swap the move endpoints
    r = diff_runs(abl, base)
    assert r.totals["d_total_j"] == -d.totals["d_total_j"]
    assert sorted((i, b, a) for i, a, b in d.moved) == sorted(r.moved)


def _shedding_serving_scenario():
    """First seeded serving scenario whose guarded run actually sheds."""
    for seed in range(40):
        ss = serving_scenario(seed)
        if not (ss.serving.admission or ss.serving.shedding):
            continue
        rep = run_serving(ss.plan, ss.truth, ss.arrivals, config=ss.config(),
                          serving=ss.serving, arrival_truth=ss.arrival_truth,
                          events=ss.events, est_blocks=ss.blocks,
                          engine="vector")
        if rep.n_shed > 0:
            return ss
    pytest.skip("no shedding serving scenario in the seed sweep")


def test_diff_add_drop_round_trip_under_shedding():
    ss = _shedding_serving_scenario()
    sc = Scenario(plan=ss.plan, truth=ss.truth, config=ss.config(),
                  events=tuple(ss.events), est_blocks=ss.blocks,
                  arrivals=ss.arrivals, serving=ss.serving,
                  arrival_truth=ss.arrival_truth)
    assert sc.is_serving
    guarded = sc.run(engine="vector")
    opened = ablate(sc, "admission", engines=("vector",))
    d = diff_runs(guarded, opened)
    # accept-all executes block work the guarded run shed or rejected
    assert d.added
    assert not d.empty
    # jobs changed status (shed/rejected -> accepted) rather than appearing
    assert d.jobs and not (d.jobs_added or d.jobs_dropped)
    assert d.tenants
    # round-trip: swapping the arguments swaps added and dropped exactly
    r = diff_runs(opened, guarded)
    assert r.dropped == d.added
    assert r.added == d.dropped


def test_profile_mechanisms_serving_tenant_deltas():
    ss = _shedding_serving_scenario()
    sc = Scenario(plan=ss.plan, truth=ss.truth, config=ss.config(),
                  events=tuple(ss.events), est_blocks=ss.blocks,
                  arrivals=ss.arrivals, serving=ss.serving,
                  arrival_truth=ss.arrival_truth)
    rows = profile_mechanisms(sc, mechanisms=["admission"],
                              engines=("vector",))
    (row,) = rows
    _assert_reconciled(row)
    assert row["changed"]
    assert row["tenants"]    # accept-all shifts per-tenant SLO outcomes


# ------------------------------------------------------------- (c) watchdog

def _watch(parts, engine):
    plan, truth, cfg, events, blocks = parts
    mx = StreamingMetrics()
    wd = Watchdog(standard_rules(
        plan.deadline_s, energy_budget_j=30_000.0,
        shed_budget_hz=0.5)).attach(mx)
    from repro.runtime import run_cluster
    run_cluster(plan, truth, config=dataclasses.replace(cfg, metrics=mx),
                events=events, est_blocks=blocks, engine=engine)
    return wd.alerts


def test_watchdog_bitwise_identical_across_engines_and_runs():
    parts = _everything_on_parts(seed=7)
    a = _watch(parts, "vector")
    b = _watch(parts, "scalar")
    c = _watch(parts, "vector")
    assert a          # the tight seed-7 scenario does fire
    assert a == b     # scalar vs vector, bitwise (Alert is all-float)
    assert a == c     # two-run determinism


def test_watchdog_rule_validation():
    with pytest.raises(ValueError, match="unknown signal"):
        Rule("bad", "vibes", 1.0, 5.0)
    with pytest.raises(ValueError, match="fast_s"):
        Rule("bad", "deadline_risk", 5.0, 1.0)


def test_watchdog_dispatch_and_replanner_hook():
    fired, replanned = [], []

    class _Stub:
        def on_alert(self, alert):
            replanned.append(alert)
            return 0

    parts = _everything_on_parts(seed=7)
    plan, truth, cfg, events, blocks = parts
    mx = StreamingMetrics()
    wd = Watchdog(standard_rules(plan.deadline_s),
                  on_fire=fired.append, replanner=_Stub()).attach(mx)
    from repro.runtime import run_cluster
    run_cluster(plan, truth, config=dataclasses.replace(cfg, metrics=mx),
                events=events, est_blocks=blocks, engine="vector")
    assert list(wd.alerts) == fired
    # only deadline_risk alerts reach the replanner
    assert replanned == [a for a in fired if a.signal == "deadline_risk"]
    # a second poll re-evaluates but never re-fires the same alert
    n = len(fired)
    assert wd.poll() == wd.alerts
    assert len(fired) == n


def test_online_replanner_on_alert():
    plan, truth, cfg, events, blocks = _everything_on_parts(seed=7)
    ctl = OnlineReplanner(plan, est_blocks=blocks)
    risk = Alert(time=1.0, rule="deadline-risk", signal="deadline_risk",
                 window_s=1.0, severity="page", value=2.0,
                 slow_value=2.0)
    n = ctl.on_alert(risk)
    assert isinstance(n, int) and n >= 0
    # non-risk signals are ignored outright
    cap = dataclasses.replace(risk, rule="cap", signal="cap_pressure")
    assert ctl.on_alert(cap) == 0


# ------------------------------------------------- (d) flight-recorder guard

@pytest.mark.parametrize("mode", ["ring:64", "off"])
def test_replay_tools_refuse_truncated_logs(mode):
    plan, truth, cfg, events, blocks = _everything_on_parts(seed=7)
    cfg = dataclasses.replace(cfg, event_log=mode)
    from repro.runtime import run_cluster
    rep = run_cluster(plan, truth, config=cfg, events=events,
                      est_blocks=blocks, engine="vector")
    assert rep.event_log_mode == mode
    for tool in (build_spans,
                 lambda r: explain_miss(r, node="n0"),
                 explain_energy):
        with pytest.raises(ValueError) as err:
            tool(rep)
        assert mode in str(err.value)
        assert "events_dropped" in str(err.value)
    # diff_runs degrades to report-level rollups instead of raising
    d = diff_runs(rep, rep)
    assert d.empty
    assert not d.spans_aligned


def test_full_log_report_still_replays():
    plan, truth, cfg, events, blocks = _everything_on_parts(seed=7)
    from repro.runtime import run_cluster
    rep = run_cluster(plan, truth, config=cfg, events=events,
                      est_blocks=blocks, engine="vector")
    assert rep.event_log_mode == "full"
    require_full_log(rep)        # no raise
    assert build_spans(rep)      # report accepted directly


# ----------------------------------------------- (e) prometheus validation

def test_validate_prometheus_accepts_real_exposition():
    plan, truth, cfg, events, blocks = _everything_on_parts(seed=7)
    mx = StreamingMetrics()
    from repro.runtime import run_cluster
    run_cluster(plan, truth, config=dataclasses.replace(cfg, metrics=mx),
                events=events, est_blocks=blocks, engine="vector")
    text = to_prometheus(mx)
    assert validate_prometheus(text) == []


GOOD = ("# HELP repro_x Stuff.\n"
        "# TYPE repro_x counter\n"
        'repro_x{node="n0"} 1.0\n')


@pytest.mark.parametrize("text,needle", [
    (GOOD[:-1], "newline"),                               # no trailing \n
    ("# TYPE repro_x counter\nrepro_x 1\n", "HELP"),      # TYPE sans HELP
    (GOOD + "# TYPE repro_x gauge\n", "duplicate"),       # re-declared
    (GOOD.replace("counter", "accumulator"), "type"),     # bad kind
    (GOOD + "repro_y 1.0\n", "undeclared"),               # sample sans TYPE
    (GOOD.replace(' 1.0', ' -1.0'), "negative"),          # counter < 0
    (GOOD + 'repro_x{node="n0"} 2.0\n', "duplicate"),     # duplicate series
    (GOOD.replace('"n0"', '"n\\q0"'), "escape"),          # bad label escape
    (GOOD.replace(" 1.0", " banana"), "value"),           # unparsable value
])
def test_validate_prometheus_rejects(text, needle):
    problems = validate_prometheus(text)
    assert problems
    assert any(needle.lower() in p.lower() for p in problems), problems


# ------------------------------------------------------- (f) bench history

def _blob(bps, schema=6):
    return {"schema_version": schema, "git_sha": "deadbee",
            "obs_cf": [{"scenario": "watchdog", "n": 100,
                        "blocks_per_s": bps}]}


def test_history_append_and_trend_check(tmp_path):
    from benchmarks import history

    hist = str(tmp_path / "history.jsonl")
    bench = tmp_path / "bench.json"

    # empty history: nothing to check
    assert history.check(hist) == 0

    bench.write_text(json.dumps(_blob(1000.0)))
    entry = history.append(str(bench), hist)
    assert entry["schema_version"] == 6
    assert entry["metrics"] == {
        "obs_cf/n=100,scenario=watchdog": 1000.0}
    # single entry: no baseline yet, passes vacuously
    assert history.check(hist) == 0

    # steady runs pass against the median baseline
    history.append(str(bench), hist)
    history.append(str(bench), hist)
    assert history.check(hist) == 0

    # a big drop beyond the obs_cf threshold (0.3) fails the trend check
    bench.write_text(json.dumps(_blob(100.0)))
    history.append(str(bench), hist)
    assert history.check(hist) == 1

    # entries from another schema version are not compared at all
    bench.write_text(json.dumps(_blob(100.0, schema=7)))
    history.append(str(bench), hist)
    assert history.check(hist) == 0
