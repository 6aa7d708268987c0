"""Host milliseconds a job of the sampler's stateless hash over every slot
of every block: self time of the program's ``sample.keys`` spans per
``pipeline.estimate`` root in the window."""


def read(run):
    spans = run.module("metrics", "_program_spans")
    return spans.self_ms(run, "sample.keys")
