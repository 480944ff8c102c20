"""Benchmark entry point — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (value column unit depends on the
benchmark: distance-calcs, QPS, MB, or ratio; see each module docstring).

With ``--json [DIR]`` each module additionally writes machine-readable
``BENCH_<name>.json`` records (``{name, value, derived}`` per CSV line) so
the perf trajectory can be tracked across PRs (DESIGN.md §Perf hillclimb).

  PYTHONPATH=src python -m benchmarks.run [--only stage_breakdown ...]
  PYTHONPATH=src python -m benchmarks.run --only frontier_sweep --json .
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys
import time

ALL = ["density", "stage_breakdown", "accel_threshold", "recall_qps",
       "ablation", "memory_scaling", "fes_benefit", "graph_sensitivity",
       "pilot_kernel", "frontier_sweep", "serving_qps", "streaming_update",
       "pod_scaling", "slo_serving"]


class _Tee(io.TextIOBase):
    """stdout wrapper that records complete lines while passing them on."""

    def __init__(self, base):
        self.base = base
        self.lines = []
        self._buf = ""

    def write(self, s):
        self.base.write(s)
        self._buf += s
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            self.lines.append(line)
        return len(s)

    def flush(self):
        self.base.flush()


def _parse_records(lines):
    """CSV lines -> [{name, value, derived}]; comment/malformed lines skip."""
    records = []
    for line in lines:
        if line.startswith("#") or "," not in line:
            continue
        name, _, rest = line.partition(",")
        value, _, derived = rest.partition(",")
        try:
            value = float(value)
        except ValueError:
            pass  # keep as string (e.g. ERROR rows)
        records.append({"name": name.strip(), "value": value,
                        "derived": derived})
    return records


def _load_prior(path):
    """name -> numeric value from an existing BENCH_<name>.json (the
    previous PR's record, kept in the repo root), or {} when absent."""
    if not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return {}
    return {r["name"]: r["value"] for r in data.get("records", [])
            if isinstance(r.get("value"), (int, float))}


def _print_deltas(prior, records):
    """Per-record regression-visibility lines against the prior BENCH json
    (# delta <name>: old -> new (±pct%)); new/non-numeric rows are skipped."""
    for rec in records:
        old = prior.get(rec["name"])
        new = rec["value"]
        if old is None or not isinstance(new, (int, float)):
            continue
        pct = 100.0 * (new - old) / old if old else float("inf")
        print(f"# delta {rec['name']}: {old:.6g} -> {new:.6g} ({pct:+.1f}%)",
              flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None, choices=ALL)
    ap.add_argument("--json", nargs="?", const=".", default=None,
                    metavar="DIR",
                    help="also write BENCH_<name>.json per module into DIR "
                         "(default: cwd)")
    args = ap.parse_args(argv)
    names = args.only or ALL
    if args.json is not None:
        os.makedirs(args.json, exist_ok=True)

    import importlib

    from repro.runtime.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}", flush=True)
    failures = []
    for name in names:
        mod = importlib.import_module(f"benchmarks.{name}")
        print(f"# === {name} ({mod.__doc__.splitlines()[0].strip()}) ===",
              flush=True)
        t0 = time.time()
        tee = None
        if args.json is not None:
            tee = sys.stdout = _Tee(sys.stdout)
        try:
            mod.run()
        except Exception as e:  # noqa: BLE001
            print(f"{name},ERROR,{type(e).__name__}: {e}", flush=True)
            failures.append(name)
        finally:
            if tee is not None:
                sys.stdout = tee.base
                tee.lines.append(tee._buf)
                path = os.path.join(args.json, f"BENCH_{name}.json")
                prior = _load_prior(path)      # read before overwriting
                records = _parse_records(tee.lines)
                with open(path, "w") as f:
                    json.dump({"benchmark": name,
                               "records": records}, f, indent=1)
                print(f"# wrote {path}", flush=True)
                _print_deltas(prior, records)
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
