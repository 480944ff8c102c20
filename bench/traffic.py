"""The one traffic generator: a mix is a data file of parameters
(``bench/traffic/<name>.json``) that this module turns into a schedule.

Keys of a mix (all optional except ``loop``):

``loop``                  ``"closed"``: ``outstanding`` requests are kept in
                          flight, each completion sends the next query, and
                          no query is sent twice.
                          ``"open"``: queries arrive on a fixed schedule
                          whether or not earlier ones finished.
``max_qps``               closed loop: distinct queries are drawn for this
                          rate over the warm-up and the window; a run that
                          would need more fails rather than repeat one.
``rate_qps``              open loop: mean arrival rate.
``insert_rows_per_min``,  mutations (a segmented index only): rows per
``delete_rows_per_min``   minute, sent as tickets of ``mutation_ticket_rows``
                          rows at evenly spaced times.  Inserted rows are new
                          rows of the corpus's mixture; deleted rows are drawn
                          uniformly from the rows live by then.
``buckets``               the padded batch sizes the serving engine compiles
                          for: those this traffic fills.
``warmup_s``              seconds of this traffic served before the window.

Every seed gets the same number of queries and the same gaps between
arrivals, in another order: the gaps are the quantiles of the exponential
distribution at (i + 0.5) / N, shuffled by the seed, so that two seeds
differ in which queries come when and not in how much work a window holds.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List

import numpy as np

DEFAULT_BUCKETS = (8, 16, 32, 64, 128)


@dataclass
class Mutation:
    due_s: float
    kind: str                     # "insert" | "delete"
    rows: np.ndarray          # insert: rows of the extra pool; delete: gids


@dataclass
class Schedule:
    loop: str
    buckets: tuple
    warmup_s: float
    outstanding: int = 0
    arrivals_s: np.ndarray = field(default_factory=lambda: np.zeros(0))
    mutations: List[Mutation] = field(default_factory=list)


def load_mix(params: dict) -> dict:
    loop = params.get("loop")
    if loop not in ("closed", "open"):
        raise ValueError(f"traffic loop must be 'closed' or 'open', got "
                         f"{loop!r}")
    if loop == "closed" and int(params.get("outstanding", 0)) < 1:
        raise ValueError("a closed loop needs 'outstanding' >= 1")
    if loop == "closed" and float(params.get("max_qps", 0)) <= 0:
        raise ValueError("a closed loop needs 'max_qps' > 0")
    if loop == "open" and float(params.get("rate_qps", 0)) <= 0:
        raise ValueError("an open loop needs 'rate_qps' > 0")
    return params


def arrival_times(rate_qps: float, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """``round(rate * seconds)`` arrival offsets in [0, seconds), sorted."""
    n = int(round(rate_qps * seconds))
    if n == 0:
        return np.zeros(0)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = gaps[rng.permutation(n)]
    u = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) / gaps.sum()
    return u * seconds


def ticket_times(rows_per_min: float, ticket_rows: int, seconds: float,
                 phase: float) -> np.ndarray:
    """Send times of evenly spaced tickets of ``ticket_rows`` rows, the
    first ``phase`` of a spacing after the window opens."""
    if rows_per_min <= 0:
        return np.zeros(0)
    every = ticket_rows * 60.0 / rows_per_min
    return np.arange(phase * every, seconds, every)


def n_queries(params: dict, seconds: float) -> int:
    """Distinct queries the window (and a closed loop's warm-up) sends at
    most."""
    if params["loop"] == "open":
        return int(round(float(params["rate_qps"]) * seconds))
    return int(np.ceil(float(params["max_qps"])
                       * (float(params.get("warmup_s", 0.0)) + seconds)))


def build_schedule(params: dict, seed_rng: np.random.Generator,
                   seconds: float, *, live_gids: np.ndarray = None,
                   next_gid: int = 0, first_extra_row: int = 0) -> Schedule:
    """The window's schedule.  ``live_gids`` are the rows live when the
    window opens, ``next_gid`` the global id the next inserted row gets
    (inserts take consecutive ids in submission order), and
    ``first_extra_row`` the first row of the extra pool not yet used."""
    p = load_mix(params)
    sch = Schedule(loop=p["loop"],
                   buckets=tuple(p.get("buckets", DEFAULT_BUCKETS)),
                   warmup_s=float(p.get("warmup_s", 0.0)))
    if sch.loop == "closed":
        sch.outstanding = int(p["outstanding"])
    else:
        sch.arrivals_s = arrival_times(float(p["rate_qps"]), seconds,
                                       seed_rng)
    t_rows = int(p.get("mutation_ticket_rows", 16))
    ins = ticket_times(float(p.get("insert_rows_per_min", 0)), t_rows,
                       seconds, 0.25)
    dels = ticket_times(float(p.get("delete_rows_per_min", 0)), t_rows,
                        seconds, 0.75)
    events = sorted([(t, "insert") for t in ins] + [(t, "delete")
                                                     for t in dels])
    if not events:
        return sch
    # rows live by the time each ticket is sent, in ticket order: the
    # engine applies tickets in submission order, so a delete of a row
    # whose insert was sent earlier finds it applied
    live = np.array(live_gids, np.int64)
    n_live = len(live)
    next_row = first_extra_row
    for t, kind in events:
        if kind == "insert":
            rows = np.arange(next_row, next_row + t_rows)
            next_row += t_rows
            gids = np.arange(next_gid, next_gid + t_rows)
            next_gid += t_rows
            if n_live + t_rows > len(live):
                live = np.concatenate([live, np.zeros(len(live), np.int64)])
            live[n_live:n_live + t_rows] = gids
            n_live += t_rows
            sch.mutations.append(Mutation(float(t), "insert", rows))
        else:
            take = min(t_rows, n_live)
            picked = np.empty(take, np.int64)
            for j in range(take):           # uniform, without replacement
                i = int(seed_rng.integers(n_live))
                picked[j] = live[i]
                n_live -= 1
                live[i] = live[n_live]
            sch.mutations.append(Mutation(float(t), "delete", picked))
    return sch
