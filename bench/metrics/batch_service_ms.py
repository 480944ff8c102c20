"""Median over the window's batches of the engine's own time from a batch's
dispatch to its merged answers (pump, stages, segment merge)."""

import numpy as np


def read(run):
    if not run.batches:
        return None
    return float(np.median([b["t_done"] - b["t_pilot_dispatch"]
                            for b in run.batches])) * 1e3
