"""Fleet observatory: live spans, and views of the simulated runtime.

:mod:`repro.obs.tracer` records the real host work of the chip path as it
runs: spans at the layer boundaries of the estimate front
(``pipeline/stream.py``, ``core/sampling.py``) and of the planner, with
counts and the backend compilations each span caused.  Each span is also a
``jax.profiler.TraceAnnotation``, so it shows in a profiler trace of a real
job beside the device's ops, and ``write_chrome_trace(path,
spans={"host": tracer.forest()})`` dumps the recorder's ring.

Seven views of a simulated run, all derived from the same deterministic
event stream the runtime engines emit (scalar and vector logs are
bitwise-identical, so every artifact here is too):

* :mod:`repro.obs.spans` — per-block / per-job lifecycle span trees
  reconstructed from the full event log;
* :mod:`repro.obs.metrics` — ``StreamingMetrics``, the bounded-memory
  inline aggregator (``RuntimeConfig(metrics=...)``) plus the post-hoc
  table helpers the examples print;
* :mod:`repro.obs.export` — Chrome-trace/Perfetto JSON, Prometheus text
  exposition (both with structural validators), JSONL;
* :mod:`repro.obs.explain` — ``explain_miss`` / ``explain_energy``
  decompositions that sum *exactly* to the observed wall / joules;
* :mod:`repro.obs.counterfactual` — deterministic what-if replay:
  ``ablate`` / ``profile_mechanisms`` re-run a captured ``Scenario`` with
  one mechanism neutralized and ledger the exact delta;
* :mod:`repro.obs.diff` — ``diff_runs`` aligns two runs' span trees and
  rolls per-block deltas up to per-node/-tenant/-mechanism tables;
* :mod:`repro.obs.watchdog` — SRE-style multi-window SLO burn-rate
  alerting off the streaming metrics, deterministic alert streams.

Each name is imported from the module that defines it; this package
imports none of them, so ``repro.core`` can import the recorder without
loading the runtime that the simulated views read.
"""
