"""Host milliseconds a job of the block-stats kernel's round trip (the int32
copy of the sampled rows, their upload, the kernel, the fetch of its
features): self time of the program's ``estimate.kernel`` spans per
``pipeline.estimate`` root in the window."""


def read(run):
    spans = run.module("metrics", "_program_spans")
    return spans.self_ms(run, "estimate.kernel")
