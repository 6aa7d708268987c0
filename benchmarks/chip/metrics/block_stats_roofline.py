"""Share of the HBM roofline of the ``block_stats`` kernel: its least bytes
a call (``counts/block_stats.py``) over peak HBM bandwidth, over its device
time in the trace.  Bound by HBM: a few vector operations a 4-byte token."""


def read(run):
    shape = run.kernel_shapes().get("block_stats")
    if run.trace is None or run.peaks is None or shape is None:
        return None
    calls, secs = run.trace.kernel("block_stats")
    if calls == 0 or secs <= 0:
        return None
    least = run.module("counts", "block_stats").least_bytes(shape)
    return calls * least / run.peaks["hbm_bytes_per_s"] / secs * 100.0
