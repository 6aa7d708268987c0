"""Input bytes of every map task of the window's jobs over the window's
seconds, from the first job's start to the last one's end (estimate, plan
and account time count)."""


def read(run):
    if not run.tasks:
        return None
    return sum(t.nbytes for t in run.tasks) / run.window_s / 1e9
