"""Host milliseconds a job of pulling chunks from the estimate's source: self
time of the program's ``estimate.source`` spans per ``pipeline.estimate``
root in the window.  For lineitem the source is the harness's per-record
costs, computed as each chunk is pulled."""


def read(run):
    spans = run.module("metrics", "_program_spans")
    return spans.self_ms(run, "estimate.source")
