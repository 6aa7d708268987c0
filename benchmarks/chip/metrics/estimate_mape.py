"""Mean absolute error, in percent, of each block's priced estimate against
its measured app time at f_max, over the window's completed jobs."""

import numpy as np


def read(run):
    if not run.jobs:
        return None
    errs = [abs(j.est_s - j.app_s) / j.app_s for j in run.jobs]
    return float(np.mean(np.concatenate(errs))) * 100.0
