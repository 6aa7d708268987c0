"""The live span recorder (``repro.obs.tracer``): nesting and ids, self
time, the bounded ring, per-thread stacks, compilations counted into the
span that caused them, the Chrome-trace export, and the program's spans in
a real ``jax.profiler`` trace."""
import threading
import time
import weakref

import numpy as np
import pytest

from repro.obs import tracer
from repro.obs.export import to_chrome_trace, validate_chrome_trace
from repro.obs.tracer import Tracer


def _by_name(recs) -> dict:
    return {r.name: r for r in recs}


def test_nesting_ids_and_self_time():
    t = Tracer()
    with t.span("req", blocks=2):
        with t.span("a", keys=10):
            time.sleep(0.002)
            t.count(keys=5)
        with t.span("b"):
            with t.span("b.inner"):
                time.sleep(0.001)
        t.count(records=7)
    with t.span("other"):
        pass
    r = _by_name(t.records())
    req, a, b, inner = r["req"], r["a"], r["b"], r["b.inner"]
    assert req.parent is None and req.root == req.id
    assert a.parent == b.parent == req.id and inner.parent == b.id
    assert {a.root, b.root, inner.root} == {req.id}
    assert r["other"].root == r["other"].id != req.id
    assert a.counts == {"keys": 15} and req.counts == {"blocks": 2,
                                                       "records": 7}
    # self time: the duration less the children's, exactly
    assert req.self_ns == req.dur_ns - a.dur_ns - b.dur_ns
    assert b.self_ns == b.dur_ns - inner.dur_ns
    assert inner.self_ns == inner.dur_ns >= 1_000_000
    assert req.start_ns <= a.start_ns <= a.end_ns <= b.start_ns \
        <= inner.start_ns <= inner.end_ns <= b.end_ns <= req.end_ns
    # count() outside any span changes nothing
    t.count(keys=1)
    assert len(t.records()) == 5


def test_span_closes_on_error():
    t = Tracer()
    with pytest.raises(KeyError):
        with t.span("outer"):
            with t.span("inner"):
                raise KeyError("x")
    assert [r.name for r in t.records()] == ["inner", "outer"]
    assert t._stack() == []


def test_ring_is_bounded_and_a_lost_window_raises():
    t = Tracer()
    t0 = time.perf_counter_ns()
    n = tracer.CAPACITY + 12
    for i in range(n):
        with t.span("s", i=i):
            pass
    assert t.dropped == 12
    kept = t.records()
    assert len(kept) == tracer.CAPACITY
    assert kept[0].counts["i"] == 12 and kept[-1].counts["i"] == n - 1
    with pytest.raises(LookupError):
        t.records(t0)
    with pytest.raises(LookupError):
        t.records(kept[0].start_ns)
    # a window that starts after the oldest kept record ends is whole
    assert [r.counts["i"] for r in t.records(kept[-3].start_ns)] \
        == [n - 3, n - 2, n - 1]
    assert t.records(kept[-1].end_ns + 1) == []


def test_window_keeps_spans_that_start_and_end_inside_it():
    t = Tracer()
    with t.span("before"):
        pass
    lo = time.perf_counter_ns()
    with t.span("straddles"):
        with t.span("inside"):
            pass
        hi = time.perf_counter_ns()
    assert [r.name for r in t.records(lo, hi)] == ["inside"]
    assert {r.name for r in t.records(lo)} == {"inside", "straddles"}


def test_stack_is_per_thread():
    t = Tracer()
    ready, release = threading.Event(), threading.Event()

    def worker():
        with t.span("worker"):
            ready.set()
            release.wait(10)
            with t.span("worker.child"):
                pass

    th = threading.Thread(target=worker)
    with t.span("main"):
        th.start()
        assert ready.wait(10)
        with t.span("main.child"):
            release.set()
            th.join(10)
    assert not th.is_alive()
    r = _by_name(t.records())
    assert r["worker"].parent is None and r["main"].parent is None
    assert r["worker.child"].parent == r["worker"].id
    assert r["main.child"].parent == r["main"].id
    assert r["worker.child"].root == r["worker"].id != r["main"].id


def test_fresh_jit_counts_as_a_compile_of_its_span():
    import jax
    import jax.numpy as jnp

    t = Tracer()
    lo = time.perf_counter_ns()
    # a constant of this run's own: no compilation cache can hold the program
    c = float(lo % 1_000_003) + 0.5
    with t.span("outer"):
        with t.span("compiling"):
            jax.block_until_ready(
                jax.jit(lambda x: jnp.sin(x) * c + 1.5)(jnp.arange(7.0)))
    log = t.compiles(lo)
    r = _by_name(t.records(lo))
    assert r["compiling"].counts["compiles"] >= 1
    assert r["compiling"].counts["compile_ms"] > 0
    assert "compiles" not in r["outer"].counts
    assert log and {c.span for c in log} == {"compiling"}
    assert {c.span_id for c in log} == {r["compiling"].id}
    # outside every span: logged under "none", counted into no span
    jax.jit(lambda x: x * c - 2)(jnp.arange(3.0)).block_until_ready()
    assert t.compiles(lo)[-1].span == tracer.NO_SPAN



def test_one_compile_listener_feeds_only_live_recorders():
    import gc

    t = Tracer()
    with t.span("s"):
        pass
    assert t in tracer._live
    ref = weakref.ref(t)
    del t
    gc.collect()
    assert ref() is None


def test_forest_exports_as_a_valid_chrome_trace():
    t = Tracer()
    with t.span("pipeline.estimate", blocks=3):
        with t.span("sample.keys", keys=30):
            pass
        with t.span("sample.stats", rows=3):
            pass
    with t.span("pipeline.plan", deadline_s=1.5):
        pass
    forest = t.forest()
    assert [s.name for s in forest] == ["pipeline.estimate", "pipeline.plan"]
    est = forest[0]
    assert est.cat == "pipeline" and est.node == "host"
    assert [c.name for c in est.children] == ["sample.keys", "sample.stats"]
    assert est.children[0].cat == "sample"
    assert est.get("blocks") == 3 and est.get("parent") is None
    assert est.children[0].get("root") == est.get("id")
    doc = to_chrome_trace(spans={"host": forest})
    assert validate_chrome_trace(doc) == []
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
    assert sorted(names) == ["pipeline.estimate", "pipeline.plan",
                             "sample.keys", "sample.stats"]


PROGRAM_SPANS = ("sample.keys", "sample.select", "estimate.kernel",
                 "sample.stats", "estimate.source")


def test_token_front_spans_nest_in_estimate_in_a_profiler_trace(tmp_path):
    import jax

    from repro.pipeline import PipelineConfig, stream_estimates_tokens

    rng = np.random.default_rng(0)
    toks = rng.integers(0, 40, (3, 64, 16)).astype(np.int32)
    cfg = PipelineConfig(seed=11)
    stream_estimates_tokens([(0, toks)], cfg, pattern=(1, 2, 3))  # warm up
    lo = time.perf_counter_ns()
    jax.profiler.start_trace(str(tmp_path))
    try:
        stream_estimates_tokens([(0, toks)], cfg, pattern=(1, 2, 3))
    finally:
        jax.profiler.stop_trace()

    # the recorder: one request, every span under its root
    recs = tracer.records(lo)
    roots = [r for r in recs if r.name == "pipeline.estimate"]
    assert len(roots) == 1 and roots[0].parent is None
    assert roots[0].counts == {"blocks": 3, "records": 3 * 64}
    kids = {r.name: r for r in recs if r.parent == roots[0].id}
    assert set(kids) == set(PROGRAM_SPANS)
    assert kids["sample.keys"].counts == {"keys": 3 * 64}
    # min_samples: 16 rows a block of 16 int32 tokens, and the 3 row counts
    assert kids["estimate.kernel"].counts["bytes"] == 4 * (3 * 16 * 16 + 3)
    assert all(r.root == roots[0].id for r in recs)

    # the profiler's host plane: the same names, nested in time
    from jax.profiler import ProfileData

    files = sorted(tmp_path.rglob("*.xplane.pb"))
    assert files
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in ProfileData.from_file(str(files[-1])).planes
              if not plane.name.startswith("/device")
              for line in plane.lines for e in line.events]
    est = [e for e in events if e[0] == "pipeline.estimate"]
    assert len(est) == 1
    _, s0, e0 = est[0]
    for name in PROGRAM_SPANS:
        inner = [e for e in events if e[0] == name]
        assert inner, name
        assert all(s0 <= s <= e <= e0 for _, s, e in inner), name


@pytest.mark.parametrize("sigmas, refills", [(6.0, 0), (-6.0, 2)],
                         ids=["uniform chunk", "bound too low"])
def test_key_bound_counts_candidates_and_refills(sigmas, refills,
                                                 monkeypatch):
    """Blocks of at least a tile: ``sample.select`` counts the slots under
    the key bound (at least the rows kept, a few more) and the blocks
    hashed again; ``sample.keys`` every slot hashed, refills included."""
    from repro.core import sampling
    from repro.pipeline import PipelineConfig, stream_estimates

    monkeypatch.setattr(sampling, "_TAU_SIGMAS", sigmas)
    r = 1 << 17
    costs = np.random.default_rng(1).random((2, r))
    lo = time.perf_counter_ns()
    stream_estimates(costs, PipelineConfig(chunk_size=2, seed=2**31 + 5))
    recs = tracer.records(lo)
    keys = [rec.counts for rec in recs if rec.name == "sample.keys"]
    select = [rec.counts for rec in recs if rec.name == "sample.select"]
    assert keys == [{"keys": (2 + refills) * r}]
    assert len(select) == 1
    c = select[0]
    assert c["rows"] == 2 * 6554 and c["refills"] == refills
    assert c["rows"] <= c["candidates"]
    # six binomial sigmas above k: ~7 % more than the rows at this size
    assert c["candidates"] <= 1.1 * c["rows"] or refills
    assert c["candidates"] < 2 * r // 5


def test_importing_the_core_loads_neither_jax_nor_the_runtime():
    """The recorder imports JAX on its first span, not with ``repro.core``;
    ``repro.obs`` loads none of its simulated views."""
    import os
    import subprocess
    import sys

    code = ("import sys, repro.core, repro.pipeline, repro.obs; "
            "print(sorted(m for m in ('jax', 'repro.runtime') "
            "if m in sys.modules))")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, check=True,
                         env=dict(os.environ, PYTHONPATH=src))
    assert out.stdout.strip() == "[]"
