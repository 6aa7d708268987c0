"""What the per-layer metrics of the program's own spans read: the records
of ``repro.obs.tracer`` inside the measured window.

The window runs from the first job's start to that plus ``run.window_s``;
a span counts where it starts and ends inside it.  A program without the
recorder gives nothing, and each reader then returns None.
"""

ROOT = "pipeline.estimate"


def _window(run):
    """(tracer, first ns, last ns) of the window, or None."""
    try:
        from repro.obs import tracer
    except ImportError:
        return None
    if not run.runner.jobs:
        return None
    lo = min(j.t0 for j in run.runner.jobs)
    return tracer, int(lo * 1e9), int((lo + run.window_s) * 1e9)


def self_ms(run, name: str):
    """Self time of the spans named ``name``, in milliseconds per
    ``pipeline.estimate`` root span of the window."""
    window = _window(run)
    if window is None:
        return None
    tracer, lo, hi = window
    recs = tracer.records(lo, hi)
    roots = sum(r.name == ROOT and r.parent is None for r in recs)
    spans = [r.self_ns for r in recs if r.name == name]
    if not roots or not spans:
        return None
    return sum(spans) / roots / 1e6


def compiles(run):
    """Backend compilations that ended inside the window."""
    window = _window(run)
    if window is None:
        return None
    tracer, lo, hi = window
    return len(tracer.compiles(lo, hi))
