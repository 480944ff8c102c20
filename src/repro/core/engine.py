"""Single-host PilotANN engine: index build + jit'd search entry points.

Build (offline, numpy): SVD rotation → full graph → sampled subgraph rebuilt
with the same construction algorithm (paper §4.1/§4.3) → FES clusters.
The stage-① ("pilot") payloads live in a *compact* id space — rows exist
only for sampled nodes, ids are stored at the narrowest sufficient integer
width, and the vector tables are optionally quantized to bf16/int8
(``IndexConfig.pilot_dtype``, core/quant.py) — so the accelerator-resident
bytes actually scale with ``sample_ratio``/``svd_ratio``/dtype, which is
what ``ResidencyPlanner`` solves over (DESIGN.md §4).

Search (online, JAX): multistage_search / baseline_search jit'd per
(batch, params) signature.  The distributed pod engine (core/distributed.py)
consumes the same index artifacts.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import csr, fes, graph_build, multistage, quant, svd
from repro.core.multistage import (BATCH_BUCKETS, SearchParams, StatsDict,
                                   pad_to_bucket)


@dataclass
class IndexConfig:
    """Build-time index knobs (full field reference: docs/api.md)."""
    R: int = 32                  # graph degree bound
    sample_ratio: float = 0.25   # subgraph node ratio (paper Table 3)
    svd_ratio: float = 0.5       # primary-dims ratio (paper Table 3)
    n_entry: int = 8192          # FES entry pool size
    fes_clusters: int = 32       # r (warp-width in paper; tile count here)
    coarse_ratio: float = 1.0 / 64  # entry-layer size (HNSW-hierarchy analogue)
    build_method: str = "auto"
    seed: int = 0
    # stage-① payload encoding (DESIGN.md §4): float32 | bfloat16 | int8.
    # int8 stores one fp32 per-dim scale row per table; stage ② then
    # re-scores the primary term exactly (multistage.py).
    pilot_dtype: str = "float32"
    # pilot-graph id width: auto (int16 when the compact id space fits,
    # else int32) | int16 | int32
    pilot_id_dtype: str = "auto"
    # optional hard budget for the stage-① resident bytes: the build raises
    # if memory_report()["pilot_bytes"] exceeds it (use ResidencyPlanner to
    # solve for knobs that fit)
    pilot_budget_bytes: Optional[int] = None
    # LRU bound on the jit'd-search cache, which is keyed
    # (bucket, params, baseline) and would otherwise grow without limit
    # across param changes (DESIGN.md §5); evictions are counted in
    # ``PilotANNIndex.jit_evictions`` / ``cache_stats()``
    jit_cache_capacity: int = 32


class PilotANNIndex:
    """Holds numpy artifacts + device arrays for the search stages."""

    def __init__(self, cfg: IndexConfig, vectors: np.ndarray):
        if cfg.pilot_dtype not in quant.PILOT_DTYPES:
            raise ValueError(f"pilot_dtype must be one of "
                             f"{quant.PILOT_DTYPES}, got {cfg.pilot_dtype!r}")
        self.cfg = cfg
        self.n, self.d = vectors.shape
        n, d = self.n, self.d
        # wall seconds of each build step (the graph builds add their
        # knn/prune/reverse/connect steps under full_graph./pilot_graph.)
        self.build_seconds: Dict[str, float] = {}
        clock = graph_build.PhaseTimer(self.build_seconds)

        # --- SVD rotation & split (§4.1) ---
        self.reducer = svd.svd_fit(vectors, cfg.svd_ratio, seed=cfg.seed)
        rot = self.reducer.rotate(vectors)                     # (n, d)
        dp = self.reducer.d_primary
        clock.lap("svd")

        # --- full graph ---
        self.full_graph = graph_build.build_graph(
            rot, cfg.R, method=cfg.build_method, seed=cfg.seed,
            clock=graph_build.PhaseTimer(self.build_seconds, "full_graph."))
        clock.lap("full_graph")

        # --- sampled subgraph, rebuilt with the same construction algo ---
        keep = csr.subgraph_sample(self.full_graph, cfg.sample_ratio,
                                   seed=cfg.seed)
        keep_ids = np.flatnonzero(keep)
        nk = len(keep_ids)
        if nk > 2:
            sub_compact = graph_build.build_graph(
                rot[keep_ids], cfg.R, method=cfg.build_method,
                seed=cfg.seed + 1, clock=graph_build.PhaseTimer(
                    self.build_seconds, "pilot_graph."))
            # remap compacted ids -> original ids; zero-out-degree CSR (§4.3)
            nb = sub_compact.neighbors
            remapped = np.where(nb < len(keep_ids),
                                keep_ids[np.clip(nb, 0, len(keep_ids) - 1)], n)
            sub_nb = np.full((n, cfg.R), n, np.int32)
            sub_nb[keep_ids] = remapped
            self.sub_graph = csr.Graph(sub_nb.astype(np.int32), n)
        else:
            self.sub_graph = csr.zero_outdegree_subgraph(self.full_graph, keep)
        self.keep = keep
        self.keep_ids = keep_ids
        self.n_pilot = nk
        clock.lap("pilot_graph")

        # --- compact pilot id space (DESIGN.md §4): full id -> pilot id
        # (dropped nodes and the full sentinel map to the pilot sentinel nk)
        full_to_pilot = np.full(n + 1, nk, np.int32)
        full_to_pilot[keep_ids] = np.arange(nk, dtype=np.int32)
        self._full_to_pilot = full_to_pilot
        id_dt = self._resolve_id_dtype(cfg.pilot_id_dtype, nk)
        pilot_nb = full_to_pilot[self.sub_graph.padded_table()[keep_ids]]
        pilot_nb = np.concatenate(
            [pilot_nb, np.full((1, cfg.R), nk, np.int32)], axis=0)

        # fp32 primary rows for the kept nodes (+ zero sentinel row); kept on
        # the host so set_pilot_dtype can requantize without a rebuild
        self._pilot_primary = np.concatenate(
            [rot[keep_ids][:, :dp], np.zeros((1, dp), np.float32)], axis=0)

        # --- FES (entries sampled from subgraph members; primary dims).
        # fes_index keeps *full*-corpus entry ids (build artifact); the
        # device table carries compact pilot ids for stage ①.  Capacity is
        # capped with the same formula ResidencyPlanner uses, so the
        # planner's FES byte estimate upper-bounds the realized table ---
        ne = min(cfg.n_entry, nk)
        self.fes_index = fes.build_fes(
            rot[:, :dp], keep_ids, r=cfg.fes_clusters, n_entry=cfg.n_entry,
            seed=cfg.seed,
            max_capacity=fes.fes_capacity_cap(ne, cfg.fes_clusters))
        clock.lap("fes")

        # --- coarse entry layer (HNSW-hierarchy analogue for the baseline
        #     and the "- FES" ablation: greedy descent over a small sampled
        #     layer provides entry points, costed like HNSW's upper layers) ---
        rng = np.random.default_rng(cfg.seed + 7)
        m = min(n, max(64, int(n * cfg.coarse_ratio)))
        coarse_ids = np.sort(rng.choice(n, size=m, replace=False))
        coarse_graph = graph_build.build_graph(rot[coarse_ids],
                                               min(cfg.R, 16), method="auto",
                                               seed=cfg.seed + 7)
        self.coarse_ids = coarse_ids
        self.coarse_graph = coarse_graph
        clock.lap("coarse")

        # --- device arrays ---
        zrow = lambda a: np.concatenate([a, np.zeros((1, a.shape[1]), a.dtype)], 0)
        self.arrays: Dict[str, jax.Array] = {
            "full_neighbors": jnp.asarray(self.full_graph.padded_table()),
            "sub_neighbors": jnp.asarray(pilot_nb.astype(id_dt)),
            "pilot_to_full": jnp.asarray(
                np.concatenate([keep_ids, [n]]).astype(np.int32)),
            "rot_vecs": jnp.asarray(zrow(rot)),
            "residual": jnp.asarray(zrow(rot[:, dp:])),
            "fes_centroids": jnp.asarray(self.fes_index.centroids),
            "fes_entry_ids": jnp.asarray(
                full_to_pilot[self.fes_index.entry_ids]),
            "fes_valid": jnp.asarray(self.fes_index.valid),
            "default_entries": jnp.asarray(
                np.array([graph_build.medoid(rot)], np.int32)),
            "pilot_default_entry": jnp.asarray(
                np.array([graph_build.medoid(rot[keep_ids])], np.int32)),
            "coarse_neighbors": jnp.asarray(coarse_graph.padded_table()),
            "coarse_vecs": jnp.asarray(zrow(rot[coarse_ids])),
            "coarse_ids": jnp.asarray(
                np.concatenate([coarse_ids, [n]]).astype(np.int32)),
            "coarse_pilot_ids": jnp.asarray(
                full_to_pilot[np.concatenate([coarse_ids, [n]])]),
            "coarse_entry": jnp.asarray(
                np.array([graph_build.medoid(rot[coarse_ids])], np.int32)),
        }
        self.arrays.update(self._quantized_pilot_arrays(cfg.pilot_dtype))
        jax.block_until_ready(self.arrays)
        clock.lap("device_put")
        # jit cache keyed on (bucket, params, baseline): client batches are
        # padded to a small fixed ladder of sizes (multistage.pad_to_bucket),
        # so ragged traffic compiles at most len(buckets) executables per
        # params key instead of one per distinct batch size (DESIGN.md §5)
        self.batch_buckets: Tuple[int, ...] = BATCH_BUCKETS
        # LRU-bounded (IndexConfig.jit_cache_capacity): param sweeps /
        # long-lived serving processes stop accumulating dead executables
        self._search_fns: "OrderedDict" = OrderedDict()
        self._jit_evictions = 0

        if cfg.pilot_budget_bytes is not None:
            got = self.memory_report()["pilot_bytes"]
            if got > cfg.pilot_budget_bytes:
                raise ValueError(
                    f"pilot payload is {got} B, over the "
                    f"pilot_budget_bytes={cfg.pilot_budget_bytes} budget; "
                    f"shrink it via ResidencyPlanner(n, d, R={cfg.R}, "
                    f"n_entry={cfg.n_entry}).plan(budget).to_config(), or "
                    f"reduce n_entry / raise fes_clusters (FES buckets), "
                    f"or lower sample_ratio/svd_ratio/pilot_dtype directly")

    # ------------------------------------------------------------------
    @staticmethod
    def _resolve_id_dtype(pilot_id_dtype: str, nk: int):
        i16_max = np.iinfo(np.int16).max
        if pilot_id_dtype == "int32":
            return np.int32
        if pilot_id_dtype == "int16":
            if nk + 1 > i16_max:
                raise ValueError(f"pilot id space {nk + 1} overflows int16")
            return np.int16
        if pilot_id_dtype == "auto":
            return np.int16 if nk + 1 <= i16_max else np.int32
        raise ValueError(f"pilot_id_dtype must be auto|int16|int32, "
                         f"got {pilot_id_dtype!r}")

    def _quantized_pilot_arrays(self, pilot_dtype: str) -> Dict[str, jax.Array]:
        """Encode the stage-① vector tables (primary rows + FES buckets).
        ``int8``/``int4`` side data is the per-dim scale row; ``pq`` side
        data is the block-diagonal codebook (core/quant.py)."""
        pdata, pside = quant.quantize(self._pilot_primary, pilot_dtype)
        fdata, fside = quant.quantize(self.fes_index.entries, pilot_dtype)
        out = {"primary": jnp.asarray(pdata),
               "fes_entries": jnp.asarray(fdata)}
        if pside is not None:
            if pilot_dtype == "pq":
                out["primary_codebook"] = jnp.asarray(pside)
                out["fes_entries_codebook"] = jnp.asarray(fside)
            else:
                out["primary_scale"] = jnp.asarray(pside)
                out["fes_entries_scale"] = jnp.asarray(fside)
        return out

    def set_pilot_dtype(self, pilot_dtype: str) -> "PilotANNIndex":
        """Re-encode the stage-① payloads in place (no graph/SVD rebuild) —
        the cheap dtype leg of a residency sweep.  Re-checks
        ``pilot_budget_bytes`` (the constructor's budget invariant must
        survive mutation): on violation the previous encoding is restored
        and ValueError raised.  Returns self."""
        if pilot_dtype not in quant.PILOT_DTYPES:
            raise ValueError(f"pilot_dtype must be one of "
                             f"{quant.PILOT_DTYPES}, got {pilot_dtype!r}")
        prev = self.cfg.pilot_dtype
        self._apply_pilot_dtype(pilot_dtype)
        budget = self.cfg.pilot_budget_bytes
        if budget is not None:
            got = self.memory_report()["pilot_bytes"]
            if got > budget:
                self._apply_pilot_dtype(prev)
                raise ValueError(
                    f"set_pilot_dtype({pilot_dtype!r}) would grow the pilot "
                    f"payload to {got} B, over pilot_budget_bytes={budget}; "
                    f"encoding left at {prev!r}")
        return self

    def _apply_pilot_dtype(self, pilot_dtype: str) -> None:
        self.cfg = dataclasses.replace(self.cfg, pilot_dtype=pilot_dtype)
        for k in ("primary_scale", "fes_entries_scale",
                  "primary_codebook", "fes_entries_codebook"):
            self.arrays.pop(k, None)
        self.arrays.update(self._quantized_pilot_arrays(pilot_dtype))

    # ------------------------------------------------------------------
    def rotate_queries(self, queries: np.ndarray) -> jax.Array:
        return jnp.asarray(self.reducer.rotate(queries))

    def _get_fn(self, params: SearchParams, baseline: bool, bucket: int):
        key = (bucket, dataclasses.astuple(params), baseline)
        if key in self._search_fns:
            self._search_fns.move_to_end(key)          # LRU touch
        else:
            fn = multistage.baseline_search if baseline else multistage.multistage_search
            self._search_fns[key] = jax.jit(partial(fn, params=params))
            while len(self._search_fns) > max(1, self.cfg.jit_cache_capacity):
                self._search_fns.popitem(last=False)   # evict least-recent
                self._jit_evictions += 1
        return self._search_fns[key]

    @property
    def jit_evictions(self) -> int:
        """Executables evicted from the LRU-bounded jit cache so far."""
        return self._jit_evictions

    def cache_stats(self) -> Dict[str, int]:
        """Jit-cache observables: live executables, LRU capacity, lifetime
        eviction count (the unbounded-growth fix, DESIGN.md §5)."""
        return {"cached_executables": len(self._search_fns),
                "capacity": self.cfg.jit_cache_capacity,
                "jit_evictions": self._jit_evictions}

    def compile_count(self, params: Optional[SearchParams] = None,
                      baseline: Optional[bool] = None) -> int:
        """Number of cached search executables, optionally filtered by
        params / baseline-ness — the bounded-retracing observable the
        bucket ladder exists to cap (DESIGN.md §5).  The cache is an LRU
        bounded by ``IndexConfig.jit_cache_capacity``; see
        ``cache_stats()`` for the eviction count."""
        pk = None if params is None else dataclasses.astuple(params)
        return sum(1 for (_, p, b) in self._search_fns
                   if (pk is None or p == pk)
                   and (baseline is None or b == baseline))

    def warmup(self, params: SearchParams, *, baseline: bool = False,
               buckets: Optional[Tuple[int, ...]] = None) -> int:
        """Precompile one executable per bucket (outside any latency-
        sensitive serving window); returns the number of buckets warmed."""
        buckets = buckets or self.batch_buckets
        for b in buckets:
            q = jnp.zeros((b, self.d), jnp.float32)
            fn = self._get_fn(params, baseline, b)
            jax.block_until_ready(fn(self.arrays, queries=q))
        return len(buckets)

    def _run_bucketed(self, q: jax.Array, params: SearchParams,
                      baseline: bool
                      ) -> Tuple[np.ndarray, np.ndarray, StatsDict]:
        # Pad ragged client batches to the shared bucket ladder — outside
        # jit, so the executable cache is keyed on a small fixed set of
        # shapes (bounded retracing, DESIGN.md §5).  Every rung is a
        # sublane multiple, so this also satisfies the Pallas alignment
        # contract (DESIGN.md §3; pad_for_pallas stays a no-op safety net
        # for caller-supplied non-aligned ladders).  Results slice back.
        q, B = pad_to_bucket(q, self.batch_buckets)
        q, _ = multistage.pad_for_pallas(q, params)
        fn = self._get_fn(params, baseline, q.shape[0])
        ids, dists, stats = fn(self.arrays, queries=q)
        return (np.asarray(ids[:B]), np.asarray(dists[:B]),
                jax.tree.map(lambda a: np.asarray(a)[:B], stats))

    def search(self, queries: np.ndarray, params: SearchParams,
               *, rotated: bool = False
               ) -> Tuple[np.ndarray, np.ndarray, StatsDict]:
        q = jnp.asarray(queries) if rotated else self.rotate_queries(queries)
        return self._run_bucketed(q, params, False)

    def search_baseline(self, queries: np.ndarray, params: SearchParams,
                        *, rotated: bool = False
                        ) -> Tuple[np.ndarray, np.ndarray, StatsDict]:
        q = jnp.asarray(queries) if rotated else self.rotate_queries(queries)
        return self._run_bucketed(q, params, True)

    # ------------------------------------------------------------------
    def memory_report(self) -> Dict:
        """Dtype-aware bytes by residence class (paper Table 3 accounting;
        field glossary in docs/api.md).  ``pilot_bytes`` is the stage-①
        accelerator-resident payload: compact subgraph ids + (possibly
        quantized) primary vectors + FES entry buckets, including the
        int8/int4 scale rows and the PQ codebooks."""
        A = self.arrays
        nbytes = lambda k: (int(A[k].size * A[k].dtype.itemsize)
                            if k in A else 0)
        pilot_graph = nbytes("sub_neighbors")
        pilot_vec = (nbytes("primary") + nbytes("primary_scale") +
                     nbytes("primary_codebook"))
        pilot_fes = (nbytes("fes_entries") + nbytes("fes_entries_scale") +
                     nbytes("fes_entries_codebook"))
        pilot = pilot_graph + pilot_vec + pilot_fes
        full = (nbytes("full_neighbors") + nbytes("rot_vecs") +
                nbytes("residual"))
        return {"pilot_bytes": pilot, "full_bytes": full,
                "ratio": float(full / max(pilot, 1)),
                "pilot_dtype": self.cfg.pilot_dtype,
                "pilot_id_dtype": str(A["sub_neighbors"].dtype),
                "pilot_graph_bytes": pilot_graph,
                "pilot_vec_bytes": pilot_vec,
                "pilot_fes_bytes": pilot_fes,
                "pilot_nodes": self.n_pilot,
                "d_primary": self.reducer.d_primary}


# ---------------------------------------------------------------------------
# Residency planning (DESIGN.md §4): solve the pilot knobs for a byte budget
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ResidencyPlan:
    """One solved operating point; ``to_config()`` turns it into an
    ``IndexConfig`` (geometry fields carried over from the planner)."""
    sample_ratio: float
    svd_ratio: float
    pilot_dtype: str
    est_pilot_bytes: int
    budget_bytes: int
    R: int
    n_entry: int
    fes_clusters: int
    pilot_id_dtype: str = "auto"

    @property
    def fits(self) -> bool:
        return self.est_pilot_bytes <= self.budget_bytes

    def to_config(self, base: Optional[IndexConfig] = None,
                  **overrides) -> IndexConfig:
        """``base`` supplies the fields the plan does not model (seed,
        build_method, coarse_ratio, ...); every byte-relevant field —
        geometry (R, n_entry, fes_clusters, id width) and the solved knobs
        — comes from the plan, so the build-time budget check matches the
        estimate.  ``overrides`` win last (overriding geometry voids the
        fits guarantee)."""
        cfg = base or IndexConfig()
        return dataclasses.replace(
            cfg, R=self.R, n_entry=self.n_entry,
            fes_clusters=self.fes_clusters,
            sample_ratio=self.sample_ratio, svd_ratio=self.svd_ratio,
            pilot_dtype=self.pilot_dtype,
            pilot_id_dtype=self.pilot_id_dtype,
            pilot_budget_bytes=self.budget_bytes, **overrides)


class ResidencyPlanner:
    """Solve ``(sample_ratio, svd_ratio, pilot_dtype)`` for a stage-①
    byte budget (DESIGN.md §4).

    The preference ladder sacrifices *encoding fidelity first* (fp32 → bf16
    → int8 → int4 → pq costs the least recall per byte saved — stage ②
    re-scores exactly either way), then SVD-primary dims, then coverage:
    among feasible grid points the planner picks the lexicographic max of
    ``(sample_ratio, svd_ratio, dtype fidelity)``.  If nothing fits, the
    smallest plan is returned with ``fits == False``.

    ``estimate()`` mirrors ``PilotANNIndex.memory_report()``: graph and
    vector bytes are exact, and the FES term is an *upper bound* — the
    build caps the padded bucket capacity with the same formula
    (``fes.fes_capacity_cap``), so a plan with ``fits=True`` cannot fail
    the build-time budget check on FES padding.
    """

    SAMPLE_GRID = (0.5, 0.4, 0.33, 0.25, 0.2, 0.15, 0.1)
    SVD_GRID = (0.75, 0.5, 0.33, 0.25)

    def __init__(self, n: int, d: int, *, R: int = 32, n_entry: int = 8192,
                 fes_clusters: int = 32, pilot_id_dtype: str = "auto"):
        self.n, self.d = n, d
        self.R, self.n_entry, self.fes_clusters = R, n_entry, fes_clusters
        self.pilot_id_dtype = pilot_id_dtype

    def estimate(self, sample_ratio: float, svd_ratio: float,
                 pilot_dtype: str) -> Dict[str, int]:
        """Estimated pilot bytes, broken down like ``memory_report()``."""
        nk = max(1, int(round(sample_ratio * self.n)))
        dp = max(1, min(self.d, int(round(svd_ratio * self.d))))
        id_dt = PilotANNIndex._resolve_id_dtype(self.pilot_id_dtype, nk)
        idb = np.dtype(id_dt).itemsize
        vb = quant.encoded_row_bytes(dp, pilot_dtype)
        side = quant.side_bytes(dp, pilot_dtype)
        graph = (nk + 1) * self.R * idb
        vec = (nk + 1) * vb + side
        ne = min(self.n_entry, nk)
        cap = fes.fes_capacity_cap(ne, self.fes_clusters)
        fes_b = self.fes_clusters * cap * vb + side
        return {"graph": graph, "vec": vec, "fes": fes_b,
                "total": graph + vec + fes_b}

    def plan(self, pilot_budget_bytes: int, *,
             sample_grid: Tuple[float, ...] = None,
             svd_grid: Tuple[float, ...] = None,
             dtypes: Tuple[str, ...] = quant.PILOT_DTYPES) -> ResidencyPlan:
        samples = sample_grid or self.SAMPLE_GRID
        svds = svd_grid or self.SVD_GRID
        best_key, best = None, None
        fallback_plan, fallback_est = None, None
        for sr in samples:
            for vr in svds:
                for dt in dtypes:
                    est = self.estimate(sr, vr, dt)["total"]
                    plan = ResidencyPlan(
                        sample_ratio=sr, svd_ratio=vr, pilot_dtype=dt,
                        est_pilot_bytes=est,
                        budget_bytes=pilot_budget_bytes,
                        R=self.R, n_entry=self.n_entry,
                        fes_clusters=self.fes_clusters,
                        pilot_id_dtype=self.pilot_id_dtype)
                    if est <= pilot_budget_bytes:
                        key = (sr, vr, quant.FIDELITY[dt])
                        if best_key is None or key > best_key:
                            best_key, best = key, plan
                    elif fallback_est is None or est < fallback_est:
                        fallback_plan, fallback_est = plan, est
        return best if best is not None else fallback_plan


def recall_at_k(ids: np.ndarray, gt: np.ndarray, k: int) -> float:
    """recall@k = |retrieved_k ∩ groundtruth_k| / k, averaged over queries."""
    hits = 0
    for row, g in zip(ids[:, :k], gt[:, :k]):
        hits += len(set(row.tolist()) & set(g.tolist()))
    return hits / (len(ids) * k)


def brute_force_topk(vectors: np.ndarray, queries: np.ndarray, k: int
                     ) -> np.ndarray:
    ids, _ = graph_build.brute_knn(vectors, k, queries=queries)
    return ids
