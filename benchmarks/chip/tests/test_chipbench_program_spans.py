"""The per-layer metrics that read the program's own spans: a traced CPU
rehearsal of each cell prints them by name, and their readers find the
window's spans."""
import json

import pytest

from benchmarks.chip import run
from benchmarks.chip.cells import load_module, repo_root

ROOT = repo_root()
SPAN_METRICS = {"estimate_source_ms", "sample_keys_ms", "sample_select_ms",
                "sample_stats_ms", "window_compiles"}


@pytest.mark.parametrize("workload, kernel", [("text-wordcount", True),
                                              ("lineitem-avg", False)])
def test_traced_rehearsal_reads_program_spans(capsys, monkeypatch, workload,
                                              kernel):
    seen = []

    class Run(run.Run):
        def __init__(self, **kw):
            super().__init__(**kw)
            seen.append(self)

    monkeypatch.setattr(run, "Run", Run)
    rc = run.main(["--workload", workload, "--seed", str(2**31 + 29),
                   "--seconds", "1", "--trace", "1", "--cpu-rehearsal"])
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    names = set(res["metrics"])
    assert SPAN_METRICS <= names
    assert ("estimate_kernel_ms" in names) is kernel

    # the readers themselves: a value for each, the spans inside the window
    (r,) = seen
    for name in SPAN_METRICS | ({"estimate_kernel_ms"} if kernel else set()):
        value = load_module(ROOT, "metrics", name).read(r)
        assert value is not None and value >= 0, name
    assert load_module(ROOT, "metrics", "window_compiles").read(r) == 0
    spans = load_module(ROOT, "metrics", "_program_spans")
    tracer, lo, hi = spans._window(r)
    recs = tracer.records(lo, hi)
    roots = [x for x in recs if x.name == "pipeline.estimate"]
    plans = [x for x in recs if x.name == "pipeline.plan"]
    assert all(x.parent is None for x in roots + plans)
    assert len(roots) == len(plans) == len(r.jobs)
    # a job's estimate is the root's self time and its children's, and the
    # plan's record holds what a missed deadline is read from
    for root in roots:
        kids = [x for x in recs if x.parent == root.id]
        assert root.self_ns + sum(k.dur_ns for k in kids) == root.dur_ns
    for plan, job in zip(plans, r.jobs):
        assert plan.counts["deadline_s"] == pytest.approx(
            r.mix["slack"] * job.app_s.sum())
        assert plan.counts["planned_s"] == job.plan.pred_total_time
        assert plan.counts["blocks"] == r.runner.n_blocks


def test_a_program_without_the_recorder_reads_nothing(monkeypatch):
    """Laid over a checkout whose program has no ``repro.obs.tracer``, the
    readers return None and do not raise."""
    import sys

    monkeypatch.setitem(sys.modules, "repro.obs.tracer", None)
    monkeypatch.delattr(sys.modules["repro.obs"], "tracer", raising=False)

    class Bare:
        window_s = 1.0

        def module(self, group, name):
            return load_module(ROOT, group, name)

    for name in SPAN_METRICS | {"estimate_kernel_ms"}:
        assert load_module(ROOT, "metrics", name).read(Bare()) is None
