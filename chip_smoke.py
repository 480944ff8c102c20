#!/usr/bin/env python3
"""Bring-up check of the search-and-serve path on a TPU.

Drives ``ThroughputEngine`` over a ``SegmentedIndex`` once through the
library's own entry points, on a DEEP-like corpus (d=96, L2, the geometry
of big-ann-benchmarks' DEEP1B) generated from ``--seed``, and checks every
answer against an independent reference:

  data       corpus + queries from ``repro.data.synthetic_vectors``
  build      ``IndexConfig(R=32, sample_ratio=0.25, svd_ratio=0.5,
             build_method="nn_descent")`` on the device; wall time of each
             build step and device bytes in use
  search     ``SearchParams(k=10, ef=128)``; recall@10 against an exact
             brute force on the host in numpy (float32 shortlist, float64
             re-rank); below the bar, also recall with both beams doubled
  precision  stage-③ distances and one-hot-gathered ids against float64
             numpy, at the contraction precision the library uses and at
             the TPU's default precision
  serve      ``ThroughputEngine.serve`` ids equal ``index.search`` ids
  mutate     1,000 inserts are each their own top-1; after deleting them
             none is returned; no mutation failed
  kernels    on a small host-built index whose pilot fits the traversal
             kernels' VMEM bound, the compiled fused and persistent stage-①
             kernels return the ids of the unfused path

``--chips 4`` runs only the pod check instead: ``ShardedSegmentedIndex``
over four chips against a ``SegmentedIndex`` built from the same corpus
on the first chip (ids must match), with the bytes in use on every chip.

Every number goes on a line of its own.  The last line, on a TPU and only
when every phase passed, is the JSON object
``{"ok": true, "device": {...}}``.  Without a TPU the script exits non-zero
and prints no result; with an explicit ``--rows`` it first rehearses the
phases on the CPU (Pallas kernels interpreted).

  python3 chip_smoke.py                     # one chip, 1,000,000 rows
  python3 chip_smoke.py --chips 4           # the four-chip pod check
  JAX_PLATFORMS=cpu python3 chip_smoke.py --rows 20000   # CPU rehearsal
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

DIM = 96                 # DEEP1B: 96-d float vectors, L2
N_QUERIES = 1000
N_SERVE = 384
N_INSERT = 1000
KERNEL_ROWS = 20_000     # small index for the stage-① kernel check
KERNEL_QUERIES = 64
RECALL_MIN = 0.90


def emit(key: str, value) -> None:
    print(f"{key} {value}", flush=True)


def exact_topk(x: np.ndarray, q: np.ndarray, k: int, block: int = 65536,
               shortlist: int = 64):
    """Exact top-k squared-L2 neighbours: a float32 shortlist of
    ``shortlist`` rows per query over the corpus in blocks, then exact
    float64 distances over the shortlist.  A true top-k row leaves the
    shortlist only if float32 rounding moves it past ``shortlist - k``
    closer rows."""
    q32 = q.astype(np.float32)
    qn = (q32 * q32).sum(1)[:, None]
    cand_d = np.full((len(q), 0), np.inf, np.float32)
    cand_i = np.zeros((len(q), 0), np.int64)
    for s in range(0, len(x), block):
        xb = x[s:s + block]
        d = qn + (xb * xb).sum(1)[None, :] - 2.0 * (q32 @ xb.T)
        part = np.argpartition(d, min(shortlist, d.shape[1]) - 1,
                               axis=1)[:, :shortlist]
        cand_d = np.concatenate([cand_d, np.take_along_axis(d, part, 1)], 1)
        cand_i = np.concatenate([cand_i, part + s], 1)
        keep = np.argsort(cand_d, axis=1)[:, :shortlist]
        cand_d = np.take_along_axis(cand_d, keep, 1)
        cand_i = np.take_along_axis(cand_i, keep, 1)
    diff = x[cand_i].astype(np.float64) - q.astype(np.float64)[:, None, :]
    d64 = (diff * diff).sum(-1)
    order = np.argsort(d64, axis=1)[:, :k]
    return (np.take_along_axis(cand_i, order, 1),
            np.take_along_axis(d64, order, 1))


def device_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("bytes_in_use", -1))


class Smoke:
    def __init__(self, args):
        self.args = args
        self.failed = []

    def phase(self, name, fn, *a):
        """Run one phase; a raise or a failed check marks it failed.
        Returns False only when the phase raised (its results are
        missing), so a failed check still lets the later phases run."""
        t0 = time.perf_counter()
        ran = True
        try:
            ok = fn(*a)
        except Exception:                 # noqa: BLE001 — reported below
            traceback.print_exc()
            ok = ran = False
        emit(f"{name}.wall_s", time.perf_counter() - t0)
        emit(f"{name}.ok", bool(ok))
        if not ok:
            self.failed.append(name)
        return ran

    # -- phases ---------------------------------------------------------
    def data(self):
        ds = synthetic_vectors(self.rows, DIM, n_queries=N_QUERIES,
                               seed=self.args.seed,
                               spectral_decay=DATASET_PRESETS["deep"][1],
                               name="deep-like")
        self.x, self.q = ds.vectors, ds.queries
        emit("data.rows", len(self.x))
        emit("data.dim", self.x.shape[1])
        emit("data.queries", len(self.q))
        return True

    def build(self):
        cfg = IndexConfig(R=32, sample_ratio=0.25, svd_ratio=0.5,
                          build_method="nn_descent", seed=self.args.seed)
        self.index = SegmentedIndex(cfg, self.x)
        for k, v in self.index.base.build_seconds.items():
            emit(f"build.{k}_s", v)
        emit("build.pilot_rows", self.index.base.n_pilot)
        for k, v in sorted(self.index.base.arrays.items()):
            emit(f"build.array_bytes.{k}", v.nbytes)
        dev = jax.devices()[0]
        emit("build.device_bytes_in_use", device_bytes(dev))
        emit("build.device_peak_bytes",
             int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1)))
        return True

    def search(self):
        t0 = time.perf_counter()
        ids, dists, _ = self.index.search(self.q, PARAMS)
        emit("search.first_call_s", time.perf_counter() - t0)
        t0 = time.perf_counter()
        ids, dists, _ = self.index.search(self.q, PARAMS)
        emit("search.second_call_s", time.perf_counter() - t0)
        self.ids, self.dists = ids, dists
        t0 = time.perf_counter()
        self.gt, _ = exact_topk(self.x, self.q, 10)
        emit("search.host_bruteforce_s", time.perf_counter() - t0)
        hits = [len(set(a) & set(b)) for a, b in zip(ids[:, :10], self.gt)]
        recall = float(np.mean(hits)) / 10
        emit("search.recall_at_10", recall)
        emit("search.recall_min", RECALL_MIN)
        if recall < RECALL_MIN:
            # does a wider beam reach the bar? (says where to look next)
            wide = SearchParams(k=10, ef=2 * PARAMS.ef,
                                ef_pilot=2 * PARAMS.ef_pilot)
            ids2, _, _ = self.index.search(self.q, wide)
            hits = [len(set(a) & set(b))
                    for a, b in zip(ids2[:, :10], self.gt)]
            emit("search.recall_at_10_with_beams_doubled",
                 float(np.mean(hits)) / 10)
        return recall >= RECALL_MIN

    def precision(self):
        base = self.index.base
        m = 256
        ids = self.ids[:m]
        rq = np.asarray(base.rotate_queries(self.q[:m]))
        rv = np.asarray(base.arrays["rot_vecs"][jnp.asarray(ids)])
        d64 = ((rq[:, None, :].astype(np.float64) - rv) ** 2).sum(-1)
        scale = ((rq.astype(np.float64) ** 2).sum(-1)[:, None]
                 + (rv.astype(np.float64) ** 2).sum(-1))
        err = np.abs(self.dists[:m].astype(np.float64) - d64)
        emit("precision.stage3_dist_max_abs_err", float(err.max()))
        emit("precision.stage3_dist_max_err_over_norms",
             float((err / scale).max()))
        # the same identity with the contraction at the backend's default
        # precision (what every contraction ran at before it named one)
        dflt = jax.jit(lambda a, b: jnp.maximum(
            (a * a).sum(-1)[:, None] + (b * b).sum(-1)
            - 2.0 * jnp.einsum("bd,bkd->bk", a, b), 0.0))
        err_d = np.abs(np.asarray(dflt(rq, rv)).astype(np.float64) - d64)
        emit("precision.default_dist_max_abs_err", float(err_d.max()))
        emit("precision.default_dist_max_err_over_norms",
             float((err_d / scale).max()))
        # one-hot-matmul row gather of pilot adjacency ids (the traversal
        # kernels' gather), fp32 ids against the table itself
        tbl = np.asarray(base.arrays["sub_neighbors"]).astype(np.float32)
        rows = np.random.default_rng(self.args.seed).integers(
            0, len(tbl), 64)
        onehot = (np.arange(len(tbl))[None, :] == rows[:, None]
                  ).astype(np.float32)
        truth = tbl[rows].astype(np.float64)
        id_err = {}
        for name, prec in (("default", jax.lax.Precision.DEFAULT),
                           ("highest", jax.lax.Precision.HIGHEST)):
            got = jax.jit(lambda a, b, p=prec: jnp.dot(a, b, precision=p))(
                onehot, tbl)
            id_err[name] = float(np.abs(np.asarray(got) - truth).max())
            emit(f"precision.onehot_id_max_abs_err_{name}", id_err[name])
        emit("precision.max_table_id", int(tbl.max()))
        return id_err["highest"] == 0.0 and float((err / scale).max()) < 1e-5

    def serve(self):
        eng = ThroughputEngine(self.index, PARAMS,
                               ServeParams(buckets=(128,), depth=2))
        self.engine = eng
        q = self.q[:N_SERVE]
        t0 = time.perf_counter()
        ids, _, st = eng.serve(q)
        emit("serve.wall_s_in_call", st["wall_s"])
        emit("serve.first_serve_s", time.perf_counter() - t0)
        emit("serve.batches", st["batches"])
        ref, _, _ = self.index.search(q, PARAMS)
        same = bool(np.array_equal(ids, ref))
        emit("serve.ids_equal_search", same)
        return same

    def mutate(self):
        eng = self.engine
        rng = np.random.default_rng(self.args.seed + 1)
        src = self.x[rng.integers(0, len(self.x), N_INSERT)]
        new = (src + rng.normal(size=src.shape).astype(np.float32)
               * 0.05 * float(np.abs(self.x).mean())).astype(np.float32)
        t0 = time.perf_counter()
        ticket = eng.submit_upsert(new)
        eng.flush_mutations()
        emit("mutate.insert_s", time.perf_counter() - t0)
        gids = np.asarray(ticket.gids)
        ids, _, _ = eng.serve(new)
        top1 = int((ids[:, 0] == gids).sum())
        emit("mutate.inserted", len(gids))
        emit("mutate.top1_self", top1)
        t0 = time.perf_counter()
        dticket = eng.submit_delete(gids)
        eng.flush_mutations()
        emit("mutate.delete_s", time.perf_counter() - t0)
        ids, _, _ = eng.serve(new)
        leaked = int(np.isin(ids, gids).sum())
        emit("mutate.deleted_returned", leaked)
        failures = eng.stats["mutation_failures"]
        emit("mutation_failures", failures)
        return (top1 == N_INSERT and leaked == 0 and failures == 0
                and not ticket.failed and not dticket.failed)

    def kernels(self):
        rows = min(KERNEL_ROWS, self.rows)
        # the host build: this phase checks the stage-① kernels, and a
        # second device build would compile NN-descent at two more sizes
        # (20,000 rows: pilot 5,000, inside the kernels' VMEM bound)
        cfg = IndexConfig(R=32, sample_ratio=0.25, svd_ratio=0.5,
                          build_method="exact", seed=self.args.seed)
        t0 = time.perf_counter()
        idx = SegmentedIndex(cfg, self.x[:rows])
        emit("kernels.build_s", time.perf_counter() - t0)
        emit("kernels.rows", rows)
        emit("kernels.pilot_rows", idx.base.n_pilot)
        q = self.q[:KERNEL_QUERIES]
        ref, _, _ = idx.search(q, PARAMS)
        ok = True
        for name, flags in (("fused", dict(use_pallas_traversal=True)),
                            ("persistent",
                             dict(use_persistent_traversal=True))):
            p = SearchParams(k=PARAMS.k, ef=PARAMS.ef, **flags)
            t0 = time.perf_counter()
            ids, _, _ = idx.search(q, p)
            emit(f"kernels.{name}_first_call_s", time.perf_counter() - t0)
            same = bool(np.array_equal(ids, ref))
            emit(f"kernels.{name}_ids_equal_unfused", same)
            ok = ok and same
        emit("kernels.interpreted", jax.default_backend() == "cpu")
        return ok

    def pod(self):
        dev = jax.devices()
        if len(dev) < 4:
            raise RuntimeError(f"--chips 4 needs 4 devices, have {len(dev)}")
        cfg = IndexConfig(R=32, sample_ratio=0.25, svd_ratio=0.5,
                          build_method="nn_descent", seed=self.args.seed)
        t0 = time.perf_counter()
        single = SegmentedIndex(cfg, self.x)
        emit("pod.single_build_s", time.perf_counter() - t0)
        ids_1, d_1, _ = single.search(self.q, PARAMS)
        for i, d in enumerate(dev[:4]):
            emit(f"pod.single_device{i}_bytes_in_use", device_bytes(d))
        del single
        t0 = time.perf_counter()
        sharded = ShardedSegmentedIndex(cfg, self.x,
                                        shard_params=ShardParams(n_shards=4))
        emit("pod.sharded_build_s", time.perf_counter() - t0)
        for i, d in enumerate(dev[:4]):
            emit(f"pod.sharded_device{i}_bytes_in_use", device_bytes(d))
        ids_4, d_4, _ = sharded.search(self.q, PARAMS)
        same = bool(np.array_equal(ids_1, ids_4))
        emit("pod.ids_equal_single", same)
        emit("pod.dists_bitwise_equal",
             bool(np.array_equal(d_1.view(np.uint32), d_4.view(np.uint32))))
        gt, _ = exact_topk(self.x, self.q, 10)
        hits = [len(set(a) & set(b)) for a, b in zip(ids_4[:, :10], gt)]
        emit("pod.recall_at_10", float(np.mean(hits)) / 10)
        for i, d in enumerate(dev[:4]):
            emit(f"pod.device{i}_bytes_in_use_after_search",
                 device_bytes(d))
        return same

    # -- driver ---------------------------------------------------------
    def run(self) -> int:
        a = self.args
        self.rows = a.rows if a.rows is not None else 1_000_000
        dev = jax.devices()
        on_tpu = dev[0].platform == "tpu"
        if not on_tpu and a.rows is None:
            print("chip_smoke: no TPU found "
                  f"(backend {jax.default_backend()!r}); pass --rows N to "
                  "rehearse the phases on this backend", file=sys.stderr)
            return 1
        emit("device.platform", dev[0].platform)
        emit("device.kind", repr(dev[0].device_kind))
        emit("device.count", len(dev))
        emit("compile_cache", enable_compile_cache())
        steps = ([("data", self.data), ("pod", self.pod)] if a.chips == 4
                 else [("data", self.data), ("build", self.build),
                       ("search", self.search),
                       ("precision", self.precision),
                       ("serve", self.serve), ("mutate", self.mutate),
                       ("kernels", self.kernels)])
        needs = {"data", "build", "search", "serve"}   # later phases use
        for name, fn in steps:                          # what these make
            if not self.phase(name, fn) and name in needs:
                break
        if self.failed:
            print(f"chip_smoke: failed phases: {self.failed}",
                  file=sys.stderr)
            return 1
        if not on_tpu:
            print("chip_smoke: no TPU found; the phases passed on "
                  f"{dev[0].platform}, which is a rehearsal only",
                  file=sys.stderr)
            return 1
        print(json.dumps({"ok": True, "device": {
            "platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}}), flush=True)
        return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=None,
                    help="corpus rows (default 1,000,000; setting it on a "
                         "CPU backend runs the phases as a rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the ShardedSegmentedIndex pod check")
    args = ap.parse_args(argv)
    return Smoke(args).run()


try:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import IndexConfig, SearchParams
    from repro.core.distributed import ShardedSegmentedIndex, ShardParams
    from repro.core.segments import SegmentedIndex
    from repro.data import synthetic_vectors
    from repro.data.pipeline import DATASET_PRESETS
    from repro.runtime.compile_cache import enable_compile_cache
    from repro.serving import ServeParams, ThroughputEngine
except ImportError as exc:
    print(f"chip_smoke: the program is not importable here: {exc}",
          file=sys.stderr)
    sys.exit(2)

PARAMS = SearchParams(k=10, ef=128)

if __name__ == "__main__":
    sys.exit(main())
