"""Host milliseconds of the estimate step a completed job (the pipeline's
estimate front and its pricing)."""

import numpy as np


def read(run):
    if not run.jobs:
        return None
    return float(np.mean([j.estimate_s for j in run.jobs])) * 1e3
