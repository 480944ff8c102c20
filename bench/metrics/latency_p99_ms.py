"""99th percentile latency over every request due in the window (no
medians of chunks), timed as for ``latency_p50_ms``."""

import numpy as np


def read(run):
    if run.loop != "open" or len(run.due) == 0:
        return None
    done = np.where(np.isfinite(run.t_done), run.t_done, run.drain_limit_s)
    return float(np.percentile(done - run.due, 99)) * 1e3
