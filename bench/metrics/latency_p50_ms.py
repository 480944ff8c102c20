"""Median latency of every request due in the window, from the time it
was due to the time the client saw its answer; a request never answered
counts as waiting until the run stopped waiting."""

import numpy as np


def read(run):
    if run.loop != "open" or len(run.due) == 0:
        return None
    done = np.where(np.isfinite(run.t_done), run.t_done, run.drain_limit_s)
    return float(np.percentile(done - run.due, 50)) * 1e3
