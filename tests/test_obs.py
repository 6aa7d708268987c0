"""Fleet observatory: spans, streaming metrics, exporters, attribution.

The contract of ``repro.obs`` over the deterministic event engine:

  (a) span identity — lifecycle span forests reconstructed from the
      scalar and vector engines' event logs are equal (the logs are
      bitwise-identical, the fold is deterministic), on randomized
      scenarios, the everything-on scenario, crash scenarios, and
      serving runs (job spans included);
  (b) exact attribution — ``explain_miss`` components ``math.fsum`` to
      the observed wall *bitwise* (per node and per job), and
      ``explain_energy`` channels sum to the observed joules, with
      per-node idles reproducing ``report.idle_energy_j`` in the
      engine's own summation order;
  (c) streaming metrics — the inline aggregator's totals match the
      sealed report (busy, energy, finishes, migrations, crashes, peak
      power) on both engines without materializing the event log, the
      binned power track integrates exactly to the ledger's recorded
      step samples, and the horizon-doubling rebin preserves integrals;
  (d) power/energy closure — the exported power track integrates
      (piecewise-constant-exact) to the report's energy channels on
      random fault/cap/migration scenarios, both engines;
  (e) event-log modes — ``ring:N`` retains exactly the last N rows of
      the full log (both engines, matching drop counts), ``off``
      records nothing, bad modes and ring-mode serving fail loudly;
  (f) exporters — Chrome-trace documents validate (and the validator
      rejects malformed ones), Prometheus text is well-formed, JSONL
      round-trips the log.
"""
import dataclasses
import json
import math

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from test_runtime_vector import _everything_on_parts, _scenario

from repro.obs.explain import explain_energy, explain_miss
from repro.obs.export import (to_chrome_trace, to_prometheus,
                              validate_chrome_trace, write_chrome_trace,
                              write_jsonl)
from repro.obs.metrics import StreamingMetrics, format_table, node_rows
from repro.obs.spans import build_job_spans, build_spans, flatten
from repro.runtime import (NodeFailureEvent, RecoveryPolicy, RuntimeConfig,
                           run_cluster)
from repro.runtime.events import EventLogSink
from repro.serving import run_serving, serving_scenario

MISS_KEYS = ("queueing_s", "cap_clamp_s", "crash_s", "migration_s",
             "slowdown_s", "actuation_s", "service_s")


def _crash_parts(seed=7):
    plan, truth, cfg, events, blocks = _everything_on_parts(seed=seed)
    events = list(events) + [
        NodeFailureEvent(time=8.0, node="n1", flavor="transient",
                         repair_s=5.0),
        NodeFailureEvent(time=15.0, node="n2", flavor="permanent")]
    cfg = dataclasses.replace(cfg, recovery=RecoveryPolicy())
    return plan, truth, cfg, events, blocks


def _run(parts, engine, **cfg_kw):
    plan, truth, cfg, events, blocks = parts
    if cfg_kw:
        cfg = dataclasses.replace(cfg, **cfg_kw)
    return run_cluster(plan, truth, config=cfg, events=events,
                       est_blocks=blocks, engine=engine)


# ---------------------------------------------------------------- (a) spans

@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=10**6))
def test_span_forests_identical_scalar_vector(seed):
    parts = _scenario(seed)
    a = build_spans(_run(parts, "scalar").event_log)
    b = build_spans(_run(parts, "vector").event_log)
    assert a == b


def test_everything_on_spans_cover_lifecycle():
    parts = _crash_parts()
    rep_a = _run(parts, "scalar")
    rep_b = _run(parts, "vector")
    sa = build_spans(rep_a.event_log)
    assert sa == build_spans(rep_b.event_log)
    cats = {s.cat for s in flatten(sa)}
    assert {"block", "freq", "telemetry", "wire", "migrate_in",
            "migrate_out", "crashed", "outage"} <= cats
    # block spans tile their busy time: children cover [start, end]
    for s in flatten(sa):
        if s.cat == "block":
            segs = [c for c in s.children if c.cat == "freq"]
            assert segs and segs[0].start == s.start \
                and segs[-1].end == s.end
            for c in s.children:
                assert s.start <= c.start <= c.end <= s.end
    # one outage per crash; the repaired one carries its down_s
    outages = [s for s in flatten(sa) if s.cat == "outage"]
    assert len(outages) == rep_a.n_crashes
    repaired = [s for s in outages if s.get("down_s") is not None]
    assert repaired and repaired[0].dur == pytest.approx(
        repaired[0].get("down_s"))


def test_job_spans_identical_and_well_formed():
    sc = serving_scenario(5)
    got = []
    for engine in ("scalar", "vector"):
        srep = run_serving(sc.plan, sc.truth, sc.arrivals,
                           config=sc.config(), serving=sc.serving,
                           events=sc.events, est_blocks=sc.blocks,
                           engine=engine)
        spans = build_spans(srep.event_log)
        jspans = build_job_spans(srep, spans)
        got.append((spans, jspans))
        assert len(jspans) == len(srep.jobs)
        for js, jr in zip(jspans, srep.jobs):
            assert js.get("status") == jr.status
            assert js.start == jr.time
            kinds = [c.cat for c in js.children]
            assert "decision" in kinds
            if jr.status == "accepted" and jr.t_finish >= 0.0:
                assert js.end == jr.t_finish
                assert "service" in kinds or "queue" in kinds
    assert got[0] == got[1]


def test_build_spans_rejects_ring_artifact():
    sink = EventLogSink(2)
    sink.extend([(0.0, "block_start", "n0", 0, 1.0),
                 (1.0, "block_finish", "n0", 0, 1.0, 50.0),
                 (2.0, "block_start", "n0", 1, 1.0)])
    with pytest.raises(ValueError, match="ring"):
        build_spans(sink)


# ----------------------------------------------------------- (b) attribution

def test_explain_miss_sums_exactly_per_node():
    parts = _crash_parts()
    rep = _run(parts, "vector")
    spans = build_spans(rep.event_log)
    crash_seen = 0.0
    for nr in rep.node_reports:
        ex = explain_miss(rep, node=nr.name, spans=spans)
        assert math.fsum([ex[k] for k in MISS_KEYS]) == ex["wall_s"]
        assert ex["wall_s"] == nr.finish_s
        assert all(ex[k] >= 0.0 for k in MISS_KEYS if k != "service_s")
        crash_seen += ex["crash_s"]
    assert crash_seen > 0.0  # the transient outage lands somewhere


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=10**6))
def test_explain_miss_exact_on_random_scenarios(seed):
    parts = _scenario(seed)
    rep = _run(parts, "vector")
    spans = build_spans(rep.event_log)
    for nr in rep.node_reports:
        ex = explain_miss(rep, node=nr.name, spans=spans)
        assert math.fsum([ex[k] for k in MISS_KEYS]) == ex["wall_s"]


def test_explain_miss_sums_exactly_per_job():
    for seed in (5, 11, 23):
        sc = serving_scenario(seed)
        srep = run_serving(sc.plan, sc.truth, sc.arrivals,
                           config=sc.config(), serving=sc.serving,
                           events=sc.events, est_blocks=sc.blocks,
                           engine="vector")
        spans = build_spans(srep.event_log)
        for jr in srep.jobs:
            ex = explain_miss(srep, job_id=jr.job_id, spans=spans)
            assert math.fsum([ex[k] for k in MISS_KEYS]) == ex["wall_s"]
            assert ex["missed"] == (not jr.slo_met)
            if jr.status == "rejected":
                assert ex["wall_s"] == 0.0


def test_explain_miss_argument_validation():
    rep = _run(_scenario(3), "vector")
    with pytest.raises(ValueError):
        explain_miss(rep)
    with pytest.raises(ValueError):
        explain_miss(rep, job_id=0, node="n0")
    with pytest.raises(KeyError):
        explain_miss(rep, node="nope")
    with pytest.raises(TypeError):
        explain_miss(rep, job_id=0)  # not a ServingReport


def test_explain_energy_channels_sum_exactly():
    parts = _crash_parts()
    plan = parts[0]
    rep = _run(parts, "vector")
    ee = explain_energy(rep)
    assert math.fsum([ee["busy_j"], ee["idle_j"], ee["switch_j"],
                      ee["wire_j"], ee["failed_j"]]) == ee["total_j"]
    assert ee["busy_j"] == rep.total_energy_j
    assert ee["wire_j"] == rep.migration_energy_j
    assert ee["failed_j"] == rep.failed_energy_j
    # per-node idles reproduce the engine's own formula and sum order
    specs = [npa.node for npa in plan.node_plans]
    per_node = [explain_energy(rep, node=s.name, specs=specs)
                for s in specs]
    assert sum(e["idle_j"] for e in per_node) == rep.idle_energy_j
    assert sum(e["busy_j"] for e in per_node) == rep.total_energy_j


# -------------------------------------------------------------- (c) metrics

def _metrics_run(parts, engine):
    mx = StreamingMetrics()
    rep = _run(parts, engine, metrics=mx)
    return mx, rep


@pytest.mark.parametrize("engine", ["scalar", "vector"])
def test_streaming_metrics_match_report(engine):
    mx, rep = _metrics_run(_crash_parts(), engine)
    snap = mx.snapshot()
    assert snap["counters"]["finishes"] == \
        sum(nr.n_blocks for nr in rep.node_reports)
    assert snap["counters"]["migrations"] == rep.n_migrations
    assert snap["counters"]["crashes"] == rep.n_crashes
    assert snap["counters"]["repairs"] == rep.n_repairs
    assert np.isclose(sum(g["busy_s"] for g in snap["nodes"].values()),
                      sum(nr.busy_s for nr in rep.node_reports))
    split = mx.energy_split()
    assert np.isclose(split["busy_j"], rep.total_energy_j)
    assert split["idle_j"] == rep.idle_energy_j
    assert split["switch_j"] == rep.switch_energy_j
    assert np.isclose(split["wire_j"], rep.migration_energy_j)
    assert np.isclose(split["failed_j"], rep.failed_energy_j)
    assert np.isclose(mx.peak_power_w, rep.peak_power_w)
    assert snap["backlog"] == 0.0


@pytest.mark.parametrize("engine", ["scalar", "vector"])
def test_metrics_power_track_integrates_to_ledger(engine):
    mx, rep = _metrics_run(_everything_on_parts(), engine)
    edges, watts = mx.power_timeline()
    binw = float(edges[1] - edges[0])
    ts = np.array([t for t, _ in rep.power_samples])
    ws = np.array([w for _, w in rep.power_samples])
    raw = float(np.sum(np.diff(ts) * ws[:-1]))
    assert np.isclose(float(np.sum(watts) * binw), raw, rtol=1e-9)
    _, util = mx.util_timeline()
    assert float(util.max()) <= 1.0 + 1e-9
    _, depth = mx.depth_timeline()
    assert depth[-1] == 0.0  # the batch drains
    _, fr = mx.rate_timeline("finish")
    assert np.isclose(float(np.sum(fr) * binw),
                      sum(nr.n_blocks for nr in rep.node_reports))


def test_metrics_horizon_growth_preserves_integrals():
    parts = _everything_on_parts()
    small = StreamingMetrics(bins=64, horizon_s=1e-3)  # forces rebins
    big = StreamingMetrics(bins=64)
    rep_s = _run(parts, "vector", metrics=small)
    rep_b = _run(parts, "vector", metrics=big)
    assert rep_s == rep_b
    for a, b in ((small, big),):
        ea, wa = a.power_timeline()
        eb, wb = b.power_timeline()
        assert np.isclose(float(np.sum(wa) * (ea[1] - ea[0])),
                          float(np.sum(wb) * (eb[1] - eb[0])), rtol=1e-9)


def test_metrics_work_without_event_log():
    parts = _everything_on_parts()
    mx = StreamingMetrics()
    rep = _run(parts, "vector", metrics=mx, event_log="off")
    assert rep.event_log == () and rep.power_samples == ()
    assert mx.snapshot()["counters"]["finishes"] == \
        sum(nr.n_blocks for nr in rep.node_reports)
    edges, watts = mx.power_timeline()
    assert float(watts.max()) > 0.0


def test_metrics_single_use_and_binding_guards():
    mx = StreamingMetrics()
    with pytest.raises(RuntimeError, match="not bound"):
        mx.snapshot()
    _run(_scenario(3), "vector", metrics=mx)
    with pytest.raises(RuntimeError, match="exactly one run"):
        _run(_scenario(3), "vector", metrics=mx)
    with pytest.raises(ValueError):
        StreamingMetrics(bins=7)


def test_serving_metrics_count_decisions():
    sc = serving_scenario(5)
    mx = StreamingMetrics()
    srep = run_serving(sc.plan, sc.truth, sc.arrivals,
                       config=dataclasses.replace(sc.config(), metrics=mx),
                       serving=sc.serving, events=sc.events,
                       est_blocks=sc.blocks, engine="vector")
    c = mx.snapshot()["counters"]
    # counters are admission *decisions* (a deferred-then-accepted job
    # counts one accept); rejected/shed are terminal, so they match 1:1
    assert c["jobs_rejected"] == srep.n_rejected
    assert c["sheds"] == srep.n_shed
    assert c["jobs_accepted"] >= srep.n_accepted
    assert c["jobs_deferred"] == srep.n_deferred


# -------------------------------------------------------- (d) power closure

@pytest.mark.parametrize("engine", ["scalar", "vector"])
@pytest.mark.parametrize("seed", [7, 19, 42])
def test_power_track_integrates_to_energy_channels(engine, seed):
    """∫(total_w − Σ p_idle) dt == busy + failed + wire energy above idle.

    The ledger's piecewise-constant power track, integrated exactly
    (rectangle sum — its own sampling), must close against the report's
    energy channels: every joule above the idle floor is a block's
    above-idle draw or a wire transfer.
    """
    parts = _everything_on_parts(seed=seed)
    plan = parts[0]
    rep = _run(parts, engine)
    ts = np.array([t for t, _ in rep.power_samples])
    ws = np.array([w for _, w in rep.power_samples])
    integral = float(np.sum(np.diff(ts) * ws[:-1]))
    idle_floor = sum(npa.node.power.p_idle for npa in plan.node_plans)
    above_idle = integral - idle_floor * float(ts[-1])
    expect = rep.total_energy_j + rep.failed_energy_j \
        + rep.migration_energy_j \
        - sum((nr.busy_s + nr.failed_busy_s)
              * npa.node.power.p_idle
              for nr, npa in zip(rep.node_reports, plan.node_plans))
    assert np.isclose(above_idle, expect, rtol=1e-9, atol=1e-6)


# ------------------------------------------------------- (e) event-log modes

def test_ring_mode_keeps_exact_tail_both_engines():
    parts = _everything_on_parts()
    full = _run(parts, "scalar")
    for n in (1, 10, 100):
        logs = []
        for engine in ("scalar", "vector"):
            rep = _run(parts, engine, event_log=f"ring:{n}")
            assert rep.events_dropped == max(len(full.event_log) - n, 0)
            assert rep.power_samples == ()  # bounded memory in ring mode
            logs.append(tuple(rep.event_log))
        assert logs[0] == logs[1] == full.event_log[-n:]


def test_off_mode_records_nothing():
    rep = _run(_everything_on_parts(), "vector", event_log="off")
    assert tuple(rep.event_log) == () and rep.events_dropped == 0
    assert rep.power_samples == ()


def test_event_log_mode_validation():
    with pytest.raises(ValueError, match="event_log"):
        RuntimeConfig(event_log="ring")
    with pytest.raises(ValueError, match="event_log"):
        RuntimeConfig(event_log="ring:0")
    with pytest.raises(ValueError, match="event_log"):
        RuntimeConfig(event_log="sometimes")
    assert RuntimeConfig(event_log="ring:64").ring_capacity() == 64
    assert RuntimeConfig().ring_capacity() is None


def test_serving_requires_full_event_log():
    sc = serving_scenario(5)
    cfg = dataclasses.replace(sc.config(), event_log="ring:16")
    with pytest.raises(ValueError, match="full"):
        run_serving(sc.plan, sc.truth, sc.arrivals, config=cfg,
                    serving=sc.serving, events=sc.events,
                    est_blocks=sc.blocks, engine="vector")


# ------------------------------------------------------------ (f) exporters

def test_chrome_trace_validates_and_has_tracks():
    parts = _crash_parts()
    rep = _run(parts, "vector")
    doc = to_chrome_trace(rep)
    assert validate_chrome_trace(doc) == []
    ev = doc["traceEvents"]
    names = {e["args"]["name"] for e in ev if e["ph"] == "M"}
    assert "cluster" in names and any(n.startswith("node:") for n in names)
    counters = {e["name"] for e in ev if e["ph"] == "C"}
    assert {"freq", "power_w"} <= counters
    assert any(e["ph"] == "X" and e["cat"] == "block" for e in ev)
    for e in ev:
        if e["ph"] == "X":
            assert e["dur"] >= 0.0 and e["ts"] >= 0.0


def test_chrome_trace_serving_jobs_track(tmp_path):
    sc = serving_scenario(5)
    srep = run_serving(sc.plan, sc.truth, sc.arrivals, config=sc.config(),
                       serving=sc.serving, events=sc.events,
                       est_blocks=sc.blocks, engine="vector")
    path = tmp_path / "trace.json"
    doc = write_chrome_trace(path, srep)
    assert validate_chrome_trace(doc) == []
    on_disk = json.loads(path.read_text())
    assert validate_chrome_trace(on_disk) == []
    names = {e["args"]["name"] for e in on_disk["traceEvents"]
             if e["ph"] == "M"}
    assert "jobs" in names


def test_chrome_trace_validator_rejects_malformed():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": {}}) != []
    cases = [
        {"ph": "Q", "name": "x", "pid": 0, "ts": 0.0},
        {"ph": "X", "name": "x", "pid": 0, "ts": -1.0, "dur": 1.0},
        {"ph": "X", "name": "x", "pid": 0, "ts": 0.0, "dur": "long"},
        {"ph": "C", "name": "x", "pid": 0, "ts": 0.0, "args": {"v": "hi"}},
        {"ph": "X", "name": "", "pid": 0, "ts": 0.0, "dur": 1.0},
        {"ph": "X", "name": "x", "pid": "zero", "ts": 0.0, "dur": 1.0},
    ]
    for ev in cases:
        assert validate_chrome_trace({"traceEvents": [ev]}) != [], ev


def test_prometheus_exposition_well_formed():
    parts = _everything_on_parts()
    mx = StreamingMetrics()
    rep = _run(parts, "vector", metrics=mx)
    for text in (to_prometheus(mx), to_prometheus(rep)):
        assert text.endswith("\n")
        for line in text.splitlines():
            if line.startswith("#"):
                assert line.startswith("# HELP") or line.startswith("# TYPE")
            else:
                name_part, value = line.rsplit(" ", 1)
                float(value)  # must parse
                assert name_part.startswith("repro_")
    assert 'node="n0"' in to_prometheus(mx)
    assert "repro_energy_joules" in to_prometheus(rep)


def test_jsonl_round_trips_event_log(tmp_path):
    rep = _run(_everything_on_parts(), "vector")
    path = tmp_path / "events.jsonl"
    n = write_jsonl(path, rep.event_log)
    assert n == len(rep.event_log)
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(rows) == n
    assert [r["t"] for r in rows] == [e[0] for e in rep.event_log]
    assert [r["kind"] for r in rows] == [e[1] for e in rep.event_log]


def test_node_rows_and_format_table():
    rep = _run(_crash_parts(), "vector")
    rows = node_rows(rep)
    assert [r["node"] for r in rows] == [nr.name for nr in rep.node_reports]
    assert any(r["state"] == "DOWN" for r in rows)  # the permanent crash
    text = format_table(rows, [("node", "node", "s"),
                               ("blocks", "blocks", "d"),
                               ("busy_s", "busy", "9.2f"),
                               ("state", "state", "s")])
    lines = text.splitlines()
    assert len(lines) == len(rows) + 1
    assert len({len(ln) for ln in lines}) == 1  # aligned
