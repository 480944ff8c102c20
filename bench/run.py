#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  The run
generates its corpus and traffic from ``--seed``, builds the index, warms
every batch shape the traffic uses, serves the traffic through
``ThroughputEngine`` for ``--seconds``, checks the window's answers
against the exact reference (``bench/reference.py``) and prints, as the
last line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window), ``device``, with ``--trace 1`` ``breakdown``, and last ``checks``:
each number compared, with its limit.  The same numbers are the last
lines of standard error.  Earlier lines of standard output are notes:
the device, set-up steps and their seconds, bytes in use, compiles and
the mutation backlog of the window.

Without an accelerator, with fewer chips than the cell needs, or without
the program beside it (``src/repro``), the run exits non-zero and prints
no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro.serving  # noqa: F401
    except ImportError as exc:
        print(f"bench: the program is not importable: {exc}",
              file=sys.stderr)
        return 2
    from bench import harness
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), root=ROOT, t_start=T_START)
    except harness.NoAccelerator as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        rel, lim = (">=", c["min"]) if "min" in c else ("<=", c["max"])
        print(f"check {name} {c['value']!r} limit {rel} {lim!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
