"""Host milliseconds of ``plan_estimates`` a completed job."""

import numpy as np


def read(run):
    if not run.jobs:
        return None
    return float(np.mean([j.plan_s for j in run.jobs])) * 1e3
