#!/usr/bin/env python3
"""Find the knee: the highest open-loop query rate the served path
sustains, by a sweep in one process on the chip.

    python3 bench/knee.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 100,200,300 [--mutations 0,5000]

builds the cell's configuration once, warms every batch shape of the
default bucket ladder, and then serves one open-loop window per pair of
query rate (queries/s) and mutation rate (rows per minute, inserted and
deleted alike, in the tickets of the cell's traffic file).  One JSON line
per window gives latency and queue-wait percentiles, whether the queue
wait grows through the window (the median wait of the last third of the
window against the first), and the mutation backlog at its close.  The
rates the cells' traffic files fix were read from this sweep once; the
benchmark's own runs never search for a rate.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def sweep(workload: str, seed: int, seconds: float, rates, mutations, *,
          root: Path = ROOT, note=lambda k, v: None):
    import numpy as np

    from bench import data as bdata
    from bench import harness, traffic
    spec = harness.load_spec(root)
    cell, entry = harness.find_cell(spec, workload)
    cfg = harness.load_config(root, entry)
    base_mix = harness.load_traffic(root, cell["traffic"])
    harness.configure_compile_cache(root)
    words = bdata.seed_words(seed, 4)
    rng = np.random.default_rng(words[2:])
    mix = {"loop": "open", "rate_qps": 1.0,
           "mutation_ticket_rows": base_mix.get("mutation_ticket_rows", 16),
           "insert_rows_per_min": max(mutations),
           "delete_rows_per_min": max(mutations)}
    n_ins = len(rates) * sum(harness.scheduled_inserts(
        dict(mix, insert_rows_per_min=m), seconds) for m in mutations)
    geo = cfg["geometry"]
    pool = 4096
    corpus = bdata.deep_like(seed, int(geo["rows"]), int(geo["dim"]),
                             n_extra=n_ins + harness.warm_rows(
                                 mix, cfg.get("serve_params", {})),
                             n_queries=pool,
                             decay=float(geo["spectral_decay"]))
    buckets = traffic.DEFAULT_BUCKETS
    index, built, eng, _ = harness.build_system(cfg, corpus.base, words[0],
                                                buckets, note)
    client = harness.Client(eng, corpus.queries)
    for b in buckets:
        for j in range(b):
            client.submit(j, client.clock())
        client.flush()
    n = len(corpus.base)
    live, next_gid, row = np.arange(n, dtype=np.int64), n, 0
    mutating = cfg["index"] == "segmented" and max(mutations) > 0
    if mutating:
        live, next_gid, row = harness.warm_mutations(
            client, corpus, mix, cfg.get("serve_params", {}), rng, live,
            next_gid)
    for m in mutations:
        for rate in rates:
            sch = traffic.build_schedule(
                dict(mix, rate_qps=rate, insert_rows_per_min=m,
                     delete_rows_per_min=m), rng, seconds, live_gids=live,
                next_gid=next_gid, first_extra_row=row)
            c = harness.Client(eng, corpus.queries)
            t0 = c.clock()
            c.open_loop(sch.arrivals_s, np.arange(len(sch.arrivals_s)) % pool,
                        seconds, sch.mutations if mutating else (),
                        corpus.extra)
            unanswered = len(c.reqs) - len(c.t_done)
            backlog = c.backlog_rows()
            c.finish(60.0)
            if mutating:
                c.flush_mutations()
                for tk, _ in c.acked:
                    if tk.kind == "insert":
                        live = np.concatenate([live, tk.gids])
                        next_gid += len(tk.gids)
                        row += len(tk.gids)
                    else:
                        live = np.setdiff1d(live, tk.payload)
            due = np.array(c.due) - t0
            done = np.array(c.t_done) - t0
            disp = np.array([c.t_disp[b] for b in c.batch_of]) - t0
            lat, wait = done - due[:len(done)], disp - due[:len(disp)]
            first = due[:len(wait)] < seconds / 3
            last = due[:len(wait)] > 2 * seconds / 3
            recs = eng.stats["batch_records"]
            fills = [recs[i]["n_real"] for i in sorted(set(c.batch_of))]
            yield {"rate_qps": rate, "mutation_rows_per_min": m,
                   "answered": len(done), "sent": len(c.reqs),
                   "unanswered_at_close": unanswered,
                   "latency_p50_ms": float(np.percentile(lat, 50)) * 1e3,
                   "latency_p99_ms": float(np.percentile(lat, 99)) * 1e3,
                   "wait_p99_ms": float(np.percentile(wait, 99)) * 1e3,
                   "wait_first_third_ms": float(np.median(wait[first])) * 1e3,
                   "wait_last_third_ms": float(np.median(wait[last])) * 1e3,
                   "mean_batch": float(np.mean(fills)),
                   "mutation_backlog_rows_at_close": backlog}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--mutations", default="0")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    rates = [float(r) for r in args.rates.split(",")]
    muts = [float(m) for m in args.mutations.split(",")]
    note = lambda k, v: print(f"{k} {v}", flush=True)
    for line in sweep(args.workload, args.seed, args.seconds, rates, muts,
                      note=note):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
