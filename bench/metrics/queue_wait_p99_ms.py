"""99th percentile over the window's requests of the wait from the time a
request was due to the dispatch of its batch (open-loop cells)."""

import numpy as np


def read(run):
    wait = run.t_dispatch - run.due
    wait = wait[np.isfinite(wait)]
    if run.loop != "open" or len(wait) == 0:
        return None
    return float(np.percentile(wait, 99)) * 1e3
