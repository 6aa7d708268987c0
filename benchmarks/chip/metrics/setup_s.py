"""Seconds from process start to the first timed job: dataset generation
and host copy, calibration, warm-up, compilation or cache load."""


def read(run):
    return run.setup_s
