"""90th percentile over every map task completed in the window of its wall
on the host clock: stage, app and output on the host."""

import numpy as np


def read(run):
    if not run.tasks:
        return None
    return float(np.percentile([t.wall_s for t in run.tasks], 90)) * 1e3
