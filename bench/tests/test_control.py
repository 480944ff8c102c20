"""The check that decides ``correct`` fails where it must: under the
control (the exact reference at the next lower precision) and under the
faults a cell of this benchmark can have, each planted in the timed path
of a whole run on the CPU."""

from __future__ import annotations

import numpy as np
import pytest

from bench import control, harness
from bench.tests.conftest import quiet

SEEDS = (101, 202, 303)


@pytest.mark.parametrize("cell", ["tiny.closed", "tiny.churn"])
def test_the_control_is_not_correct(tiny_root, cell):
    for seed in SEEDS:
        r = control.control_readings(cell, seed, 3.0, root=tiny_root)
        checks = r["bf16x3"]
        assert checks["recall_at_10"]["value"] > 0.99
        assert not all(harness._within(c) for c in checks.values()), checks


def _alter_answers(eng):
    """An answer altered where it is produced: each query's nearest id
    is replaced by another row."""
    cpu = eng._cpu_call

    def altered(q, *po):
        ids, dists = cpu(q, *po)
        ids = np.asarray(ids).copy()
        ids[:, 0] = (ids[:, 0] + 1) % eng.index.n
        return ids, dists
    eng._cpu_call = altered


def _half_batch(eng):
    """Half of each batch left out: its rows get the answers of the other
    half."""
    cpu = eng._cpu_call

    def half(q, *po):
        ids, dists = (np.asarray(a).copy() for a in cpu(q, *po))
        h = len(ids) // 2
        ids[h:2 * h], dists[h:2 * h] = ids[:h], dists[:h]
        return ids, dists
    eng._cpu_call = half


def _deletes_unapplied(eng):
    """A step that returns its state unchanged: deletes are acknowledged
    and the index is left as it was."""
    eng.segments.delete = lambda gids: len(np.atleast_1d(gids))


def _inserts_unapplied(eng):
    """Inserts are acknowledged with fresh global ids and the index is
    left as it was."""
    seg = eng.segments

    def insert(vectors):
        b = len(np.atleast_2d(vectors))
        gids = np.arange(seg._next_gid, seg._next_gid + b)
        seg._next_gid += b
        seg._gid_dead = np.concatenate([seg._gid_dead, np.zeros(b, bool)])
        return gids
    seg.insert = insert


@pytest.mark.parametrize("cell,fault,fails", [
    ("tiny.closed", None, None),
    ("tiny.closed", _alter_answers, "dist_gap"),
    ("tiny.closed", _half_batch, "recall_at_10"),
    ("tiny.churn", None, None),
    ("tiny.churn", _deletes_unapplied, "stale_returned"),
    ("tiny.churn", _inserts_unapplied, "inserts_missing"),
])
def test_faults_make_the_run_incorrect(tiny_root, cell, fault, fails):
    out = harness.run_cell(cell, 7, 3.0, False, root=tiny_root,
                           require_accelerator=False, note=quiet,
                           fault=fault)
    bad = {k for k, c in out["checks"].items() if not harness._within(c)}
    if fault is None:
        assert out["correct"] and not bad, out["checks"]
    else:
        assert not out["correct"] and fails in bad, out["checks"]
