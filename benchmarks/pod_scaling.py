"""Pod-scale sharded serving: QPS vs shard count, with exact-parity check
(DESIGN.md §7).

Serves the same closed-loop query stream through ``ThroughputEngine`` over a
``ShardedSegmentedIndex`` at each shard count and over a single-device
``SegmentedIndex`` reference.  On an accelerator the sweep runs in this
process, which holds the chips, over the shard counts that fit the visible
devices.  On the CPU, shard counts come from forced host devices
(``--xla_force_host_platform_device_count``), which XLA reads when its
backend starts, so the sweep runs in a child process (``python -m
benchmarks.pod_scaling --child``) and this module parses its JSON.  On host
CPU "devices" every shard shares the same cores, so QPS is expected to DROP
with shard count — the curve measures cross-shard fan-out/psum overhead,
not pod speedup; on a real pod the per-shard cold tables shrink by 1/K
instead (the point of §7).

Each shards_K row's value is closed-loop QPS; ``derived`` carries retention
vs the single-device reference and the exact-parity bit (final ids AND
bitwise distances must match the reference — the run aborts otherwise).

Env knobs (scripts/smoke.sh sets the small smoke shape):
  POD_SCALING_N          corpus size            (default 4000)
  POD_SCALING_REQUESTS   request count          (default 192)
  POD_SCALING_SHARDS     comma list             (default 1,2,4)
  POD_SCALING_DEPTH      pipelining depth D     (default 2)
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import csv_line


def _sweep(shards, n: int, n_req: int, depth: int) -> dict:
    """Serve the stream over the single-device reference and each shard
    count; returns ``{"single_device": {...}, "shards_K": {...}}``."""
    import numpy as np

    from repro.core import IndexConfig, SearchParams
    from repro.core.distributed import ShardParams, ShardedSegmentedIndex
    from repro.core.segments import SegmentedIndex, UpdateParams
    from repro.data import synthetic_vectors
    from repro.serving import ServeParams, ThroughputEngine

    ds = synthetic_vectors(n, 48, n_queries=256, seed=0)
    rng = np.random.default_rng(1)
    queries = np.ascontiguousarray(
        ds.queries[rng.integers(0, len(ds.queries), size=n_req)], np.float32)
    cfg = IndexConfig(R=16, sample_ratio=0.3, svd_ratio=0.5, n_entry=512,
                      build_method="exact")
    params = SearchParams(k=10, ef=32, ef_pilot=32)
    sp = ServeParams(buckets=(8, 16, 32, 64), depth=depth, donate=True,
                     max_wait_s=0.002, warmup=True)

    def timed_serve(index):
        eng = ThroughputEngine(index, params, sp)
        ids, dists, st = eng.serve(queries)
        return ids, dists, n_req / max(st["wall_s"], 1e-9)

    rid, rdist, qps_ref = timed_serve(SegmentedIndex(cfg, ds.vectors,
                                                     UpdateParams()))
    out = {"single_device": {"qps": qps_ref}}
    for K in shards:
        sid, sdist, qps = timed_serve(ShardedSegmentedIndex(
            cfg, ds.vectors, UpdateParams(),
            shard_params=ShardParams(n_shards=K)))
        parity = bool(np.array_equal(rid, sid)
                      and np.array_equal(np.asarray(rdist).view(np.uint32),
                                         np.asarray(sdist).view(np.uint32)))
        out[f"shards_{K}"] = {"qps": qps, "parity": parity}
    return out


def _env(name: str, default: str) -> str:
    return os.environ.get(name, default)


def _knobs():
    return ([int(k) for k in _env("POD_SCALING_SHARDS", "1,2,4").split(",")],
            int(_env("POD_SCALING_N", "4000")),
            int(_env("POD_SCALING_REQUESTS", "192")),
            int(_env("POD_SCALING_DEPTH", "2")))


def _sweep_in_child() -> dict:
    """The CPU sweep, in a child that forces its own host device count."""
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the child picks its own device count
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(root, "src"), env.get("PYTHONPATH")) if p)
    proc = subprocess.run([sys.executable, "-m", "benchmarks.pod_scaling",
                           "--child"], env=env, cwd=root,
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"pod_scaling child failed:\n{proc.stderr[-3000:]}")
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("POD_SCALING_JSON ")][-1]
    return json.loads(line.split(" ", 1)[1])


def run() -> None:
    import jax
    if jax.default_backend() == "cpu":
        res = _sweep_in_child()
    else:
        shards, n, n_req, depth = _knobs()
        fit = [K for K in shards if K <= jax.device_count()]
        for K in sorted(set(shards) - set(fit)):
            print(f"# shards_{K} skipped: {jax.device_count()} devices")
        res = _sweep(fit, n, n_req, depth)

    qps_ref = res["single_device"]["qps"]
    print(csv_line("pod_scaling/single_device", qps_ref, "QPS;reference"))
    for key in sorted(k for k in res if k.startswith("shards_")):
        row = res[key]
        assert row["parity"], f"{key}: sharded results diverged from " \
                              f"the single-device reference"
        print(csv_line(f"pod_scaling/{key}", row["qps"],
                       f"QPS;retention_vs_single={row['qps'] / qps_ref:.2f}x;"
                       f"parity=exact"))


if __name__ == "__main__":
    if sys.argv[1:] == ["--child"]:
        shards, n, n_req, depth = _knobs()
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={max(shards)}")
        print("POD_SCALING_JSON " + json.dumps(_sweep(shards, n, n_req,
                                                      depth)))
    else:
        run()
