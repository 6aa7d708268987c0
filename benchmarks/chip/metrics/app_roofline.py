"""Share of the HBM roofline of the app's program: its least bytes a run
(``counts/<app>.py``) over peak HBM bandwidth, over its device time in the
trace.  Bound by HBM: the apps do a few integer operations a byte."""


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    runs, secs = run.trace.program(run.app_program)
    if runs == 0 or secs <= 0:
        return None
    least = run.module("counts", run.app).least_bytes(run.sample_block(),
                                                      run.config)
    return runs * least / run.peaks["hbm_bytes_per_s"] / secs * 100.0
