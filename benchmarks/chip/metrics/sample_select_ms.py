"""Host milliseconds a job of picking each block's sample, the k smallest
keys, and gathering its rows: self time of the program's ``sample.select``
spans per ``pipeline.estimate`` root in the window."""


def read(run):
    spans = run.module("metrics", "_program_spans")
    return spans.self_ms(run, "sample.select")
