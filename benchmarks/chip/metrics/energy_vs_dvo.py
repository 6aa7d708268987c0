"""Modelled busy energy of the DV-DVFS plans over that of DVO (every block
at f_max) on the same measured block times, summed over the window's
completed jobs.  A model over measured f_max times, never a measurement."""


def read(run):
    if not run.jobs:
        return None
    return sum(j.acct["e_plan_j"] for j in run.jobs) \
        / sum(j.acct["e_dvo_j"] for j in run.jobs)
