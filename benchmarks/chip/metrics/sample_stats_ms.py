"""Host milliseconds a job of the sample's statistics (mean, variance and
confidence interval; for text also the per-row features): self time of the
program's ``sample.stats`` spans per ``pipeline.estimate`` root in the
window."""


def read(run):
    spans = run.module("metrics", "_program_spans")
    return spans.self_ms(run, "sample.stats")
