"""Arithmetic the per-layer readers share: device time per query of one
executable, and the bytes each search stage must move per query.

The bytes are a lower bound, counted from the cell's shapes and the
program's own per-query distance and expansion counters (``stats`` of
``PilotANNIndex.search`` on queries of the window, after it):

- stage ① with FES (``jit_pilot_fn``): every distance reads one pilot row
  of ``dp`` primary dimensions, and every expansion one adjacency row of
  ``R`` pilot ids;
- stages ② and ③ (``jit_cpu_fn``): a stage-② distance reads at least the
  ``d - dp`` residual dimensions of a row (its re-rank reads those alone;
  its bounded traversal reads whole rows, and the counter does not say
  which), a stage-③ distance a whole row of ``d``, and every stage-③
  expansion one adjacency row of ``R`` full ids.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def mean_batch_fill(run) -> Optional[float]:
    """Real queries per batch over the window's batches."""
    if not run.batches:
        return None
    return float(np.mean([b["n_real"] for b in run.batches]))


def us_per_query(run, module: str) -> Optional[float]:
    """Device microseconds of one executable per query it served."""
    t = run.trace
    fill = mean_batch_fill(run)
    if t is None or fill is None or not t.module_runs.get(module):
        return None
    return t.module_s[module] / (t.module_runs[module] * fill) * 1e6


def stage1_bytes(c: dict, s: dict) -> float:
    return ((c["fes_dist"] + c["pilot_dist"]) * s["dp"] * s["pilot_itemsize"]
            + c["pilot_expanded"] * s["R"] * s["pilot_id_itemsize"])


def stage23_bytes(c: dict, s: dict) -> float:
    row = s["vec_itemsize"]
    return (c["refine_dist"] * (s["d"] - s["dp"]) * row
            + c["final_dist"] * s["d"] * row
            + c["final_expanded"] * s["R"] * s["full_id_itemsize"])


def roofline_pct(run, module: str, bytes_fn) -> Optional[float]:
    """The least time the chip's memory bandwidth allows for the stage's
    bytes, over the device time it took, in percent.  These stages do a
    few flops per byte, so bandwidth, not compute, bounds them."""
    us = us_per_query(run, module)
    if us is None or run.counters is None or run.peaks is None:
        return None
    least_s = bytes_fn(run.counters, run.shapes) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (us * 1e-6)
