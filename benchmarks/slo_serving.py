"""SLO-aware resilient serving under overload and injected faults
(DESIGN.md §8).

Drives ``ThroughputEngine`` with an OPEN-LOOP arrival process (requests
arrive on a wall-clock schedule whether or not the engine keeps up — unlike
the closed-loop ``serving_qps``/``pod_scaling`` benchmarks) at offered loads
from 0.5x to 2x of measured saturation, with admission control
(``max_pending``), hard expiry (``slo_timeout_s``) and the p99-triggered
degradation ladder (``p99_budget_s``) enabled.  Every submitted request
reaches exactly one terminal state; the sweep reports, per load point:
goodput (completed / accepted), accept rate, p50/p99 latency of completed
requests, expiry and degraded-batch rates.

The final scenario is the resilience acceptance gate: a 2-shard
``ShardedSegmentedIndex`` with ONE SHARD STALLED via the fault injector,
still under 2x-saturation load.  The heartbeat monitor detects the stall,
fails over to survivors-only degraded serving (tombstone overlay), and the
run asserts the engine holds p99 <= 2x p50 for completed requests at >= 80%
goodput — overload plus a dead shard degrades quality/coverage, never
liveness.

On an accelerator the sweep runs in this process, which holds the chips;
the faulted scenario needs two devices and is skipped on one.  On the CPU
the sharded scenario needs forced host devices, which XLA reads when its
backend starts, so (pod_scaling idiom) the whole sweep runs in a child
process (``python -m benchmarks.slo_serving --child``) and this module
parses its JSON.

Env knobs (scripts/smoke.sh sets the small smoke shape):
  SLO_SERVING_N          corpus size          (default 4000)
  SLO_SERVING_REQUESTS   requests per load    (default 256)
  SLO_SERVING_RATES      x-saturation list    (default 0.5,1.0,1.5,2.0)
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from benchmarks.common import csv_line


def _sweep(n: int, n_req: int, rates) -> dict:
    """Saturation, the open-loop overload sweep and the faulted scenario;
    returns the per-scenario rows."""
    import jax
    import numpy as np

    from repro.core import IndexConfig, SearchParams
    from repro.core.distributed import ShardParams, ShardedSegmentedIndex
    from repro.core.segments import SegmentedIndex, UpdateParams
    from repro.data import synthetic_vectors
    from repro.runtime.chaos import FaultInjector
    from repro.serving import ServeParams, ThroughputEngine

    ds = synthetic_vectors(n, 48, n_queries=256, seed=0)
    queries = np.ascontiguousarray(ds.queries, np.float32)
    cfg = IndexConfig(R=16, sample_ratio=0.3, svd_ratio=0.5, n_entry=512,
                      build_method="exact")
    params = SearchParams(k=10, ef=32, ef_pilot=32)

    def slo_params(batch_svc):
        # admission bounds queueing to ~2 full batches; expiry is generous
        # (tail insurance, not the primary overload valve); the degradation
        # ladder arms when p99 drifts past a few service times
        return ServeParams(buckets=(8, 16, 32), depth=2, donate=True,
                           warmup=True, max_wait_s=0.002,
                           max_pending=64,
                           slo_timeout_s=max(0.1, 30.0 * batch_svc),
                           p99_budget_s=max(0.02, 4.0 * batch_svc),
                           degrade_ef_scale=0.5,
                           heartbeat_timeout_s=0.15)

    def offered_load(engine, rate, n_total):
        # open-loop: arrival i is due at t0 + i/rate regardless of progress
        reqs, done_at = [], {}

        def stamp():
            now = time.monotonic()
            for r in reqs:
                if r.terminal and r.rid not in done_at:
                    done_at[r.rid] = now

        t0 = time.monotonic()
        i = 0
        while i < n_total:
            due = min(n_total, int((time.monotonic() - t0) * rate) + 1)
            while i < due:
                reqs.append(engine.submit(queries[i % len(queries)]))
                i += 1
            engine.pump()
            stamp()
        engine.flush()
        stamp()
        wall = time.monotonic() - t0

        st = engine.stats
        states = [r.state for r in reqs]
        assert all(r.terminal for r in reqs), \
            "silent drop: non-terminal request"
        n_completed = states.count("completed")
        n_rejected = states.count("rejected")
        n_expired = states.count("expired")
        assert n_completed + n_rejected + n_expired == len(reqs)
        lats = sorted(done_at[r.rid] - r.enqueued_at
                      for r in reqs if r.state == "completed")
        accepted = len(reqs) - n_rejected
        pct = (lambda q: lats[int(q * (len(lats) - 1))] if lats
               else float("nan"))
        return {
            "p50_ms": 1e3 * pct(0.50), "p99_ms": 1e3 * pct(0.99),
            "goodput": n_completed / max(accepted, 1),
            "accept_rate": accepted / len(reqs),
            "expired_rate": n_expired / len(reqs),
            "degraded_frac": st["degraded_batches"] / max(st["batches"], 1),
            "qps_served": n_completed / wall,
            "failovers": st["shard_failovers"],
            "coverage_lost": st["degraded_coverage"],
        }

    # saturation: closed-loop QPS on the healthy single-device engine
    sat_idx = SegmentedIndex(cfg, ds.vectors, UpdateParams())
    sat_sp = ServeParams(buckets=(8, 16, 32), depth=2, donate=True,
                         warmup=True, max_wait_s=0.002)
    sat_eng = ThroughputEngine(sat_idx, params, sat_sp)
    _, _, sat_st = sat_eng.serve(
        queries[np.arange(n_req) % len(queries)])
    qps_max = n_req / max(sat_st["wall_s"], 1e-9)
    batch_svc = sat_st["wall_s"] / max(sat_st["batches"], 1)

    out = {"saturation": {"qps": qps_max, "batch_svc_ms": 1e3 * batch_svc}}

    # overload sweep: fresh engine per load point (isolated stats/windows;
    # executables come from the global jit cache, so re-warmup is cheap)
    for rate_x in rates:
        eng = ThroughputEngine(
            SegmentedIndex(cfg, ds.vectors, UpdateParams()), params,
            slo_params(batch_svc))
        out[f"load_{rate_x:g}x"] = offered_load(eng, rate_x * qps_max, n_req)

    # faulted scenario: one of two shards stalled, still at 2x saturation.
    # The injector runs on the real clock; the heartbeat monitor declares the
    # stalled shard dead ~150ms in and the engine fails over to survivors-only
    # degraded serving for the remainder of the run.
    if jax.device_count() >= 2:
        inj = FaultInjector()
        inj.inject("shard_stall", shard=1)
        sh = ShardedSegmentedIndex(cfg, ds.vectors, UpdateParams(),
                                   shard_params=ShardParams(n_shards=2))
        eng = ThroughputEngine(sh, params, slo_params(batch_svc),
                               fault_injector=inj)
        out["faulted_2x"] = offered_load(eng, 2.0 * qps_max, n_req)
    else:
        print(f"# faulted_2x skipped: {jax.device_count()} device")
    return out


def _env(name: str, default: str) -> str:
    return os.environ.get(name, default)


def _derived(row):
    return (f"p99_ms={row['p99_ms']:.1f};p50_ms={row['p50_ms']:.1f};"
            f"goodput={row['goodput']:.3f};accept={row['accept_rate']:.3f};"
            f"expired={row['expired_rate']:.3f};"
            f"degraded_batches={row['degraded_frac']:.2f};"
            f"qps_served={row['qps_served']:.0f}")


def _knobs():
    return (int(_env("SLO_SERVING_N", "4000")),
            int(_env("SLO_SERVING_REQUESTS", "256")),
            [float(r) for r in
             _env("SLO_SERVING_RATES", "0.5,1.0,1.5,2.0").split(",")])


def _sweep_in_child() -> dict:
    """The CPU sweep, in a child that forces two host devices."""
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the child picks its own device count
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(root, "src"), env.get("PYTHONPATH")) if p)
    proc = subprocess.run([sys.executable, "-m", "benchmarks.slo_serving",
                           "--child"], env=env, cwd=root,
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"slo_serving child failed:\n{proc.stderr[-3000:]}")
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("SLO_SERVING_JSON ")][-1]
    return json.loads(line.split(" ", 1)[1])


def run() -> None:
    import jax
    res = (_sweep_in_child() if jax.default_backend() == "cpu"
           else _sweep(*_knobs()))

    sat = res.pop("saturation")
    print(csv_line("slo_serving/saturation", sat["qps"],
                   f"QPS;closed-loop;batch_svc_ms={sat['batch_svc_ms']:.2f}"))
    for key, row in res.items():
        value = row["p99_ms"] * 1e3           # value column stays in us
        extra = ""
        if key.startswith("faulted"):
            # the resilience acceptance gate: a dead shard + 2x overload
            # must degrade coverage, not liveness or tail latency
            slo_ok = (row["goodput"] >= 0.80
                      and row["p99_ms"] <= 2.0 * row["p50_ms"])
            extra = (f";failovers={row['failovers']}"
                     f";coverage_lost={row['coverage_lost']:.2f}"
                     f";slo_ok={slo_ok}")
            assert row["failovers"] >= 1, \
                "faulted scenario never detected the stalled shard"
            assert slo_ok, (
                f"SLO violated under fault: goodput={row['goodput']:.3f} "
                f"p50={row['p50_ms']:.1f}ms p99={row['p99_ms']:.1f}ms")
        print(csv_line(f"slo_serving/{key}", value, _derived(row) + extra))


if __name__ == "__main__":
    if sys.argv[1:] == ["--child"]:
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        print("SLO_SERVING_JSON " + json.dumps(_sweep(*_knobs())))
    else:
        run()
