"""Multi-stage ANNS processing (PilotANN §4): the paper's core contribution.

  ① pilot traversal   — compact subgraph + SVD-primary vectors
                        (accelerator-resident; optionally quantized to
                        bf16/int8, DESIGN.md §4)
  ② residual refine   — exact full distances for the pilot beam, then a
                        bounded (2-round) traversal on the subgraph with
                        full vectors.  With an exact (fp32) pilot the
                        primary term is reused via the SVD identity
                        ‖x−q‖² = ‖xp−qp‖² + ‖xr−qr‖²; with a *quantized*
                        pilot the beam distances are approximate, so the
                        full distance is re-scored exactly from ``rot_vecs``
                        instead (adding an exact residual to an inexact
                        primary would bake the quantization error into the
                        "exact" stage).
  ③ final traversal   — full graph + full vectors, seeded with ②'s beam

"Staged data-ready processing": each stage only touches data that is already
resident for it; the inter-stage traffic is the candidate beam plus — for
①→② only — the visited filter (≈1 KB/query in the paper).  Stages ① and ②
share a *compact* pilot id space (rows exist only for sampled nodes — that
is what makes the pilot index scale with ``sample_ratio``), so stage ②
inherits ①'s visited filter directly; stage ③ lives in the full id space,
where the filter cannot follow the ``pilot_to_full`` mapping, so it rebuilds
its filter from the handed-over beam (DESIGN.md §4).  Graceful degradation:
with stages disabled this reduces to plain greedy search (the ablation of
Table 5).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import fes as F
from repro.core import quant
from repro.core import traversal as T

# Per-stage stats: every value is a (B,) int32 array of per-query
# distance-computation counts (docs/api.md glossary).  Both search entry
# points return exactly the same key set.
StatsDict = Dict[str, jax.Array]


@dataclass(frozen=True)
class SearchParams:
    """Per-call search knobs (hashable: the engine jit-caches per value).

    See docs/api.md for the full field reference and the glossary of the
    ``stats`` dict this search returns.
    """
    k: int = 10              # results returned per query
    ef: int = 128            # stage-③ beam width (recall/latency dial)
    ef_pilot: int = 128      # stage-① beam width
    fes_L: int = 32          # entries returned by FES (stage-0 fan-in)
    refine_iters: int = 2    # stage-② bounded traversal rounds (paper: 2)
    use_fes: bool = True     # stage 0: FES entry selection vs coarse layer
    use_pilot: bool = True   # stage ①: pilot subgraph traversal
    use_refine: bool = True  # stage ②: residual refinement
    visited_mode: str = "bloom"   # bloom | exact visited-set structure
    bloom_bits: int = 16384  # bloom filter width per query (bits)
    max_iters: int = 512     # safety bound on expansion rounds per stage
    # multi-frontier expansion: candidates expanded per round.  frontier_width
    # drives stages ②/③ (and the baseline); frontier_width_pilot drives
    # stage ①.  1 = the classic single-frontier round (bit-identical).
    frontier_width: int = 1
    frontier_width_pilot: int = 1
    # stage ① via the fused Pallas hop kernel (DESIGN.md §3); compiled on
    # an accelerator, interpreted on the CPU.
    use_pallas_traversal: bool = False
    # stage ① via the persistent whole-search kernel (one pallas_call for the
    # entire pilot search; implies the fused hop path).  DESIGN.md §3.
    use_persistent_traversal: bool = False


# Shape-bucketed batching (DESIGN.md §5): the default ladder of padded batch
# sizes the engine and the serving runtime compile for.  Every rung is a
# sublane (8) multiple so bucket-padded batches also satisfy the Pallas
# alignment contract of DESIGN.md §3; batches beyond the top rung round up to
# a multiple of it, so the executable count stays bounded for any bounded
# client batch size.
BATCH_BUCKETS: Tuple[int, ...] = (8, 16, 32, 64, 128)


def bucket_size(B: int, buckets: Tuple[int, ...] = BATCH_BUCKETS) -> int:
    """Padded size for a batch of ``B`` queries: the smallest ladder rung
    ``>= B``, or the next multiple of the top rung above the ladder."""
    for b in buckets:
        if B <= b:
            return b
    top = buckets[-1]
    return -(-B // top) * top


def pad_to_bucket(queries: jax.Array,
                  buckets: Tuple[int, ...] = BATCH_BUCKETS
                  ) -> Tuple[jax.Array, int]:
    """Pad a query batch to its ladder bucket (zero rows); returns
    ``(padded, original_B)``.  Callers slice results back to ``original_B``.
    Padded rows are independent under the batched traversal (every per-query
    op is row-local and a converged row is a fixed point), so real rows are
    unchanged — the same argument the Pallas alignment padding relies on
    (DESIGN.md §3).  Shared by ``engine.PilotANNIndex`` and the serving
    runtime (`serving/server.py`) so the jit cache is keyed on a small fixed
    set of shapes instead of every client batch size (DESIGN.md §5)."""
    B = queries.shape[0]
    nb = bucket_size(B, buckets)
    if nb == B:
        return queries, B
    return jnp.pad(queries, ((0, nb - B), (0, 0))), B


def pad_for_pallas(queries: jax.Array, params: SearchParams,
                   align: int = 8) -> Tuple[jax.Array, int]:
    """Shared ragged-batch padding for the Pallas stage-① paths (per-hop or
    persistent): pad the query batch to a sublane-aligned size so the fused
    kernels tile cleanly (DESIGN.md §3); callers slice results back to the
    returned original batch size.  Used by ``engine.PilotANNIndex`` (outside
    jit — also caps jit-signature churn for ragged client batches) and by
    ``pipeline.split_stages`` (inside jit — pad widths are static per
    trace).  A no-op for non-Pallas params or aligned batches."""
    B = queries.shape[0]
    use_pallas = params.use_pallas_traversal or params.use_persistent_traversal
    if not use_pallas or B % align == 0:
        return queries, B
    return jnp.pad(queries, ((0, align - B % align), (0, 0))), B


def hierarchical_entries(arrays: Dict[str, jax.Array], queries: jax.Array,
                         params: SearchParams, n_out: int = 4
                         ) -> Tuple[jax.Array, jax.Array]:
    """HNSW-hierarchy analogue: score the coarse sampled layer exactly and
    take the top entries (at least as strong as an HNSW upper-layer descent;
    every scored coarse node is charged to the baseline's budget).

    Returns (coarse slot indices (B, n_out), per-query cost).  Callers map
    slots through ``arrays["coarse_ids"]`` (full ids) or
    ``arrays["coarse_pilot_ids"]`` (compact pilot ids, sentinel for coarse
    nodes outside the subgraph)."""
    Bq = queries.shape[0]
    cv = arrays["coarse_vecs"][:-1]                # (m, d), drop sentinel row
    m = cv.shape[0]
    d2 = T.sq_dists(queries, cv)                   # (B, m)
    idx = jax.lax.top_k(-d2, n_out)[1]
    cost = jnp.full((Bq,), m, jnp.int32)
    return idx, cost


def refine_stage(arrays: Dict[str, jax.Array], params: SearchParams,
                 queries: jax.Array, cand_id: jax.Array, cand_dp: jax.Array,
                 visited: jax.Array = None, *,
                 dist_full_fn=None, dist_res_fn=None
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Stage ② (shared by ``multistage_search`` and
    ``pipeline.split_stages``): exact re-rank of the pilot beam, then a
    bounded traversal on the compact subgraph with FULL vectors.

    ``cand_id``/``cand_dp``: stage-①'s beam (compact pilot ids + stage-①
    distances); ``visited``: stage-①'s filter (same compact id space, so it
    carries over directly).  The re-rank is exact either way: for fp32
    pilots the SVD identity reuses the primary term; for quantized pilots
    (``primary`` stored bf16/int8) the beam distances carry quantization
    error, so the FULL distance is re-scored from ``rot_vecs`` instead
    (DESIGN.md §4).  Neighbours come from the compact table, distances from
    ``rot_vecs`` via ``pilot_to_full`` (no duplicated full-d subgraph
    table).

    Returns ``(seed_id, seed_d, refine_dist)``: the refined beam mapped
    back to FULL ids + its exact distances (stage ③'s seed), and the
    per-query distance-computation count.

    Deletes (DESIGN.md §6): when ``arrays`` carries a ``pilot_tombstone``
    bitmap, tombstoned pilot candidates are sentinel-masked out of the
    handed-over beam and the bounded traversal, so a deleted node can
    never ride the pilot beam into stage ③.

    Pod sharding (DESIGN.md §7): ``dist_full_fn(queries, full_ids)`` /
    ``dist_res_fn(q_residual, full_ids)`` override the direct ``rot_vecs``
    / ``residual`` table gathers with shard-side scoring (owned rows +
    psum), so this stage runs unchanged inside a ``shard_map`` over
    row-sharded cold tables.  The hooks must be exact: they replace a
    gather + ``sq_dists``, not an approximation of it."""
    nk = arrays["pilot_to_full"].shape[0] - 1
    dp = arrays["primary"].shape[1]
    ptf = arrays["pilot_to_full"]
    Bq = queries.shape[0]
    ptomb = arrays.get("pilot_tombstone")
    valid = cand_id < nk
    if ptomb is not None:
        cand_id = T.sentinel_mask(ptomb, cand_id, nk)
        valid = cand_id < nk
    cand_full = ptf[cand_id]
    if arrays["primary"].dtype != jnp.float32:    # quantized: exact re-score
        raw = (dist_full_fn(queries, cand_full) if dist_full_fn is not None
               else T.sq_dists(queries, arrays["rot_vecs"][cand_full]))
        d_full = jnp.where(valid, raw, jnp.inf)
    else:                                         # exact: SVD identity
        qr = queries[:, dp:]
        d_res = (dist_res_fn(qr, cand_full) if dist_res_fn is not None
                 else T.sq_dists(qr, arrays["residual"][cand_full]))
        d_full = jnp.where(valid, cand_dp + d_res, jnp.inf)
    n_rerank = jnp.sum(valid, axis=1).astype(jnp.int32)

    def dist2(qs, ids, fresh):
        if dist_full_fn is not None:
            return dist_full_fn(qs, ptf[ids])
        return T.sq_dists(qs, arrays["rot_vecs"][ptf[ids]])
    spec2 = T.TraversalSpec(ef=params.ef, visited_mode=params.visited_mode,
                            bloom_bits=params.bloom_bits,
                            frontier_width=params.frontier_width)
    st2 = T.greedy_search(spec2, queries, arrays["sub_neighbors"],
                          arrays["rot_vecs"], nk,
                          entry_ids=jnp.full((Bq, 1), nk, jnp.int32),
                          iters=params.refine_iters, visited=visited,
                          extra_id=cand_id, extra_d=d_full, dist_fn=dist2,
                          tombstone=ptomb)
    return ptf[st2.cand_id], st2.cand_d, n_rerank + st2.n_dist


def multistage_search(arrays: Dict[str, jax.Array], params: SearchParams,
                      queries: jax.Array
                      ) -> Tuple[jax.Array, jax.Array, StatsDict]:
    """arrays: device arrays built by engine.PilotANNIndex —
      full_neighbors (n+1, R), rot_vecs (n+1, d), residual (n+1, dr);
      compact pilot tables sub_neighbors (nk+1, R) int16/int32,
      primary (nk+1, dp) fp32/bf16/int8 [+ primary_scale (dp,)],
      pilot_to_full (nk+1,); fes_centroids (r, d), fes_entries (r, C, dp)
      [+ fes_entries_scale (dp,)], fes_entry_ids (r, C) *pilot* ids,
      fes_valid (r, C); coarse layer + pilot_default_entry.
    Mutable-index arrays additionally carry ``tombstone`` (n+1,) /
    ``pilot_tombstone`` (nk+1,) deletion bitmaps (DESIGN.md §6): tombstoned
    ids are sentinel-masked out of FES, every traversal stage and the
    stage handovers; absent keys (or all-false bitmaps) are bit-exact.
    Queries must already be SVD-rotated (engine handles it).
    Returns (ids (B, k), dists (B, k), stats).
    """
    n = arrays["rot_vecs"].shape[0] - 1
    nk = arrays["pilot_to_full"].shape[0] - 1      # compact pilot id space
    pilot_scale = arrays.get("primary_scale")
    pilot_codebook = arrays.get("primary_codebook")
    # true primary width: packed encodings (int4/pq) store fewer bytes per
    # row than dims, so the scale row / codebook carries the real dp
    dp = quant.primary_dim(arrays["primary"], pilot_scale,
                           codebook=pilot_codebook)
    Bq = queries.shape[0]
    stats: StatsDict = {}
    q_primary = queries[:, :dp]
    ptf = arrays["pilot_to_full"]
    tomb = arrays.get("tombstone")
    ptomb = arrays.get("pilot_tombstone")

    # ---- stage 0: entry selection --------------------------------------
    entry_full = None          # full-id entries (pilot disabled paths)
    if params.use_fes:
        entry_pilot, _ = F.fes_select_ref(
            q_primary, arrays["fes_centroids"], arrays["fes_entries"],
            arrays["fes_entry_ids"], arrays["fes_valid"], params.fes_L,
            entries_scale=arrays.get("fes_entries_scale"),
            entries_codebook=arrays.get("fes_entries_codebook"),
            tombstone=ptomb)
        if not params.use_pilot:
            entry_full = ptf[entry_pilot]
        # FES cost: one centroid pass + one cluster pass (counted per query)
        stats["fes_dist"] = jnp.full((Bq,), arrays["fes_centroids"].shape[0] +
                                     arrays["fes_entries"].shape[1], jnp.int32)
    else:
        # coarse layer holds full-d vectors; select entries with full queries
        slots, entry_cost = hierarchical_entries(arrays, queries, params)
        entry_full = arrays["coarse_ids"][slots]
        # pilot entries: coarse nodes mapped into the compact subgraph
        # (sentinel when sampled out) + the guaranteed pilot medoid so the
        # stage-① beam is never empty
        entry_pilot = jnp.concatenate(
            [arrays["coarse_pilot_ids"][slots],
             jnp.broadcast_to(arrays["pilot_default_entry"], (Bq, 1))],
            axis=1)
        stats["fes_dist"] = entry_cost

    # ---- stage ①: pilot traversal (compact subgraph, primary dims) -----
    if params.use_pilot:
        spec1 = T.TraversalSpec(ef=params.ef_pilot, visited_mode=params.visited_mode,
                                bloom_bits=params.bloom_bits,
                                max_iters=params.max_iters,
                                frontier_width=params.frontier_width_pilot,
                                use_pallas=(params.use_pallas_traversal or
                                            params.use_persistent_traversal),
                                use_persistent=params.use_persistent_traversal)
        st1 = T.greedy_search(spec1, q_primary, arrays["sub_neighbors"],
                              arrays["primary"], nk, entry_pilot,
                              vec_scale=pilot_scale,
                              vec_codebook=pilot_codebook, tombstone=ptomb)
        stats["pilot_dist"] = st1.n_dist
        stats["pilot_hops"] = st1.n_hops
        stats["pilot_expanded"] = st1.n_exp
        cand_id, cand_dp = st1.cand_id, st1.cand_d       # compact pilot ids
        cand_full = ptf[cand_id]                         # (B, ef1) full ids
        pilot_visited = st1.visited
    else:
        cand_id = cand_dp = cand_full = None
        stats["pilot_dist"] = jnp.zeros((Bq,), jnp.int32)
        stats["pilot_hops"] = jnp.zeros((Bq,), jnp.int32)
        stats["pilot_expanded"] = jnp.zeros((Bq,), jnp.int32)

    # ---- stage ②: residual refinement (shared helper; inherits ①'s
    # visited filter — same compact id space) ----------------------------
    if params.use_refine and params.use_pilot:
        seed_id, seed_d, stats["refine_dist"] = refine_stage(
            arrays, params, queries, cand_id, cand_dp, visited=pilot_visited)
    elif params.use_pilot:
        # degraded: hand pilot results (primary-only dists are NOT exact) to ③
        # by re-scoring them with full vectors there (extra entries)
        seed_id, seed_d = None, None
        stats["refine_dist"] = jnp.zeros((Bq,), jnp.int32)
    else:
        seed_id, seed_d = None, None
        stats["refine_dist"] = jnp.zeros((Bq,), jnp.int32)

    # ---- stage ③: final traversal (full graph + vectors) ---------------
    # the compact→full handover is the beam alone: stage ③ rebuilds its
    # visited filter from the seed beam (init_state inserts it), since the
    # stage-①/② filters live in the compact pilot id space (DESIGN.md §4)
    spec3 = T.TraversalSpec(ef=params.ef, visited_mode=params.visited_mode,
                            bloom_bits=params.bloom_bits,
                            max_iters=params.max_iters,
                            frontier_width=params.frontier_width)
    if seed_id is not None:
        st3 = T.greedy_search(spec3, queries, arrays["full_neighbors"],
                              arrays["rot_vecs"], n,
                              entry_ids=jnp.full((Bq, 1), n, jnp.int32),
                              extra_id=seed_id, extra_d=seed_d,
                              tombstone=tomb)
    elif params.use_pilot:  # pilot w/o refine: re-score pilot beam fully
        st3 = T.greedy_search(spec3, queries, arrays["full_neighbors"],
                              arrays["rot_vecs"], n, entry_ids=cand_full,
                              tombstone=tomb)
    else:
        st3 = T.greedy_search(spec3, queries, arrays["full_neighbors"],
                              arrays["rot_vecs"], n, entry_ids=entry_full,
                              tombstone=tomb)
    stats["final_dist"] = st3.n_dist
    stats["final_hops"] = st3.n_hops
    stats["final_expanded"] = st3.n_exp
    stats["total_cpu_dist"] = stats["refine_dist"] + stats["final_dist"]

    ids, dists = T.topk_from_state(st3, params.k)
    return ids, dists, stats


def baseline_search(arrays: Dict[str, jax.Array], params: SearchParams,
                    queries: jax.Array
                    ) -> Tuple[jax.Array, jax.Array, StatsDict]:
    """Single-stage greedy search on the full index (the HNSW-CPU baseline).

    Returns the same unified ``stats`` schema as ``multistage_search``
    (docs/api.md glossary): the skipped stages report zero, the coarse
    entry-layer scan is charged as ``fes_dist``, and ``total_cpu_dist``
    includes it (the baseline's entry scan is host-side work, unlike the
    accelerator-resident FES pass)."""
    n = arrays["rot_vecs"].shape[0] - 1
    Bq = queries.shape[0]
    spec = T.TraversalSpec(ef=params.ef, visited_mode=params.visited_mode,
                           bloom_bits=params.bloom_bits,
                           max_iters=params.max_iters,
                           frontier_width=params.frontier_width)
    slots, entry_cost = hierarchical_entries(arrays, queries, params)
    entries = arrays["coarse_ids"][slots]
    st = T.greedy_search(spec, queries, arrays["full_neighbors"],
                         arrays["rot_vecs"], n, entries,
                         tombstone=arrays.get("tombstone"))
    ids, dists = T.topk_from_state(st, params.k)
    zeros = jnp.zeros((Bq,), jnp.int32)
    return ids, dists, {"fes_dist": entry_cost,
                        "pilot_dist": zeros, "pilot_hops": zeros,
                        "pilot_expanded": zeros, "refine_dist": zeros,
                        "final_dist": st.n_dist, "final_hops": st.n_hops,
                        "final_expanded": st.n_exp,
                        "total_cpu_dist": st.n_dist + entry_cost}
