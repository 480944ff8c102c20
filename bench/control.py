#!/usr/bin/env python3
"""The control of the check that decides ``correct``.

The exact reference is put in the program's place, computed one
precision below the one the configuration states: the configuration's
distances are float32 with the contraction at ``Precision.HIGHEST``, so
the control contracts at ``Precision.HIGH`` (three bfloat16 passes).  Its
answers, for the queries a run of the cell compares and over the cell's
corpus, go through the same comparison as the program's; the control has
to come out not correct.  It needs no index: it reads a seed in seconds.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

prints one JSON line per seed with the readings, each beside the cell's
limit: on an accelerator of the native ``Precision.HIGH`` (the control),
of single-pass bfloat16 (``DEFAULT``) and of ``bf16x3``, an emulation of
three-pass bfloat16 by explicit products; on a CPU, which ignores the
precision of a float32 contraction, of ``bf16x3`` alone.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_readings(workload: str, seed: int, seconds: float, *,
                     root: Path = ROOT, precisions=("bf16x3",)) -> dict:
    import numpy as np

    from bench import harness
    from bench import reference as ref
    spec = harness.load_spec(root)
    cell, entry = harness.find_cell(spec, workload)
    cfg = harness.load_config(root, entry)
    mix = harness.load_traffic(root, cell["traffic"])
    corpus, n_q = harness.make_corpus(cfg, mix, seed, seconds)
    k = int(cfg["search_params"]["k"])
    qrows = np.arange(n_q)
    out = {}
    for prec in precisions:
        d32, ids = ref.shortlist_topk(corpus.base, corpus.queries[:n_q], k,
                                      precision=prec)
        out[str(prec)] = harness.compare_answers(
            corpus.base, corpus.queries, qrows, ids, d32, k, cfg["limits"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT)]
    import jax
    precs = ["bf16x3"]
    if jax.devices()[0].platform != "cpu":
        precs = [jax.lax.Precision.HIGH, jax.lax.Precision.DEFAULT] + precs
    for s in args.seeds.split(","):
        r = control_readings(args.workload, int(s), args.seconds,
                             precisions=precs)
        print(json.dumps({"seed": int(s), "control": r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
