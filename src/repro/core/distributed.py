"""Pod-scale PilotANN: the distributed search step for the production mesh.

Mapping (DESIGN.md §2): every chip holds a replica of the *pilot index*
(subgraph CSR + SVD-primary vectors + FES clusters) sized to per-chip HBM;
the *full index* (graph + full-d vectors) is sharded row-wise across the
mesh.  Stage ① runs embarrassingly parallel — queries sharded over every
axis, zero collectives.  Stages ②③ traverse the sharded full index, where
each neighbour gather crosses the corpus sharding; the pilot stage exists to
bound exactly that traffic (the paper's PCIe argument, re-targeted at ICI).

Two gather schemes for the sharded stages:
  * ``naive``      — plain jnp.take on the row-sharded table; GSPMD lowers it
                     (typically local-masked-gather + all-reduce of the
                     gathered (B, R, d) block).  Paper-faithful baseline.
  * ``shardwise``  — beyond-paper: compute distances *shard-side* and
                     all-reduce only the (B, R) scalars (d× less traffic);
                     implemented by constraining the gathered block to stay
                     corpus-sharded so XLA reduces post-contraction.
The §Perf hillclimb measures both from the lowered HLO.

Pod-scale *serving* (DESIGN.md §7) lives here too: ``ShardedSegmentedIndex``
partitions the mutable ``core/segments.SegmentedIndex`` across a device mesh
— hot pilot payloads (subgraph, quantized pilot vectors + scales, FES,
tombstones) replicated per shard, cold tables (full adjacency, full-d
rotated vectors, residuals) row-sharded, delta segments owned round-robin by
shards — and serves it through a ``shard_map`` stage pair
(``core/pipeline.split_stages(shard_ctx=...)``) whose results are
bit-identical to the single-device index at every shard count.  The
exactness argument: every row is owned by exactly one shard, the owner
computes the identical ``traversal.sq_dists`` value, non-owners contribute
exact zeros, and a psum of one value plus zeros is the value; the cross-
shard beam merge is ``segments.merge_topk``'s canonical (distance, gid)
order, which is invariant to the row-to-shard assignment.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import fes as F
from repro.core import traversal as T
from repro.core.multistage import SearchParams
from repro.core.segments import DeltaSegment, SegmentedIndex


@dataclass(frozen=True)
class PodIndexSpec:
    """Production-scale index geometry (dry-run sizing)."""
    n: int = 100_000_000          # corpus size (DEEP/T2I/WIKI/LAION: 1e8)
    d: int = 96                   # vector dim (DEEP 96 ... LAION 768)
    d_primary: int = 48
    R: int = 32                   # graph degree
    n_pilot: int = 2_000_000      # replicated pilot subgraph nodes (zero-outdeg CSR rows are compacted here)
    fes_r: int = 32
    fes_capacity: int = 2048
    query_batch: int = 4096       # global in-flight query batch
    ef_pilot: int = 64
    ef: int = 64
    pilot_iters: int = 48         # fixed rounds (serving SLA style)
    refine_iters: int = 2
    final_iters: int = 24
    bloom_bits: int = 16384
    frontier_width: int = 1       # stage-②③ candidates expanded per round
    frontier_width_pilot: int = 1  # stage-① multi-frontier width
    vec_dtype: str = "float32"   # corpus vector storage (bf16 halves memory
                                 # and naive-gather wire bytes; fp32 accum)
    pilot_dtype: str = "float32"  # replicated pilot/FES vector encoding
                                  # (float32|bfloat16|int8|int4|pq;
                                  # DESIGN.md §4 — int8/int4 add one fp32
                                  # scale row per table, pq a codebook)

    # mutable pod serving (DESIGN.md §7): include tombstone bitmaps and
    # per-shard delta-segment tables in the specs/shardings.  Off by
    # default so immutable dry-run consumers see the historical key set.
    mutable: bool = False
    n_delta_segments: int = 8     # open delta segments (round-robin owned)
    delta_capacity: int = 65536   # rows per delta segment

    def pilot_bytes(self) -> int:
        """Per-chip replicated pilot payload, dtype-aware (the per-chip HBM
        budget the ResidencyPlanner solves against at pod scale)."""
        from repro.core import quant
        vb = quant.encoded_row_bytes(self.d_primary, self.pilot_dtype)
        side = 2 * quant.side_bytes(self.d_primary, self.pilot_dtype)
        return (self.n_pilot * vb
                + self.n_pilot * self.R * 4
                + self.fes_r * self.fes_capacity * vb
                + side)

    def full_bytes(self) -> int:
        return self.n * self.d * 4 + self.n * self.R * 4

    def delta_bytes(self) -> int:
        """Accelerator-resident delta-segment payload across the pod
        (adjacency + quantized pilot rows + scales + gids + liveness;
        the full-d rotated rows are cold-tier, like ``full_bytes``)."""
        if not self.mutable:
            return 0
        from repro.core import quant
        vb = quant.encoded_row_bytes(self.d_primary, self.pilot_dtype)
        side = quant.side_bytes(self.d_primary, self.pilot_dtype)
        per = (self.delta_capacity * self.R * 4
               + self.delta_capacity * vb
               + side
               + self.delta_capacity * 8      # global ids (int64)
               + self.delta_capacity)         # live bitmap
        return self.n_delta_segments * per


def _pilot_storage(dp: int, pilot_dtype: str):
    """Stored-table layout of one pilot encoding (core/quant.py):
    ``(row_width, element_dtype, side_shape)``.  The packed encodings store
    int8 lanes — two nibbles per byte (int4) or one PQ code per subspace —
    and the side array is the fp32 scale row (dense/int4) or the
    block-diagonal fp32 codebook (pq)."""
    from repro.core import quant
    if pilot_dtype == "int4":
        return quant.int4_packed_width(dp), jnp.int8, (dp,)
    if pilot_dtype == "pq":
        m, _, ksub = quant.pq_geometry(dp)
        return m, jnp.int8, (dp, m * ksub)
    return dp, getattr(jnp, pilot_dtype), (dp,)


def pod_array_specs(spec: PodIndexSpec, mesh) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every index array + queries."""
    n_dev = int(np.prod(mesh.devices.shape))
    Np = _round_to(spec.n + 1, n_dev)
    npl = _round_to(spec.n_pilot + 1, 1)
    pw, pdt, sshape = _pilot_storage(spec.d_primary, spec.pilot_dtype)
    return {
        # replicated pilot index (vector tables in spec.pilot_dtype; the
        # *_scale slots carry the encoding's side payload — all-ones scale
        # rows for the exact dtypes, real scales for int8/int4, and the
        # block-diagonal codebook for pq)
        "pilot_neighbors": jax.ShapeDtypeStruct((npl, spec.R), jnp.int32),
        "pilot_vecs": jax.ShapeDtypeStruct((npl, pw), pdt),
        "pilot_scale": jax.ShapeDtypeStruct(sshape, jnp.float32),
        "pilot_to_full": jax.ShapeDtypeStruct((npl,), jnp.int32),
        "fes_centroids": jax.ShapeDtypeStruct((spec.fes_r, spec.d_primary), jnp.float32),
        "fes_entries": jax.ShapeDtypeStruct((spec.fes_r, spec.fes_capacity,
                                             pw), pdt),
        "fes_scale": jax.ShapeDtypeStruct(sshape, jnp.float32),
        "fes_entry_ids": jax.ShapeDtypeStruct((spec.fes_r, spec.fes_capacity), jnp.int32),
        "fes_valid": jax.ShapeDtypeStruct((spec.fes_r, spec.fes_capacity), bool),
        # sharded full index
        "full_neighbors": jax.ShapeDtypeStruct((Np, spec.R), jnp.int32),
        "full_vecs": jax.ShapeDtypeStruct((Np, spec.d),
                                          getattr(jnp, spec.vec_dtype)),
        # queries (rotated, full-d)
        "queries": jax.ShapeDtypeStruct((spec.query_batch, spec.d), jnp.float32),
    } | ({} if not spec.mutable else {
        # mutable serving (DESIGN.md §7): deletion bitmaps + delta segments
        "tombstone": jax.ShapeDtypeStruct((Np,), bool),
        "pilot_tombstone": jax.ShapeDtypeStruct((npl,), bool),
        "delta_neighbors": jax.ShapeDtypeStruct(
            (spec.n_delta_segments, spec.delta_capacity, spec.R), jnp.int32),
        "delta_pilot": jax.ShapeDtypeStruct(
            (spec.n_delta_segments, spec.delta_capacity, pw), pdt),
        "delta_pilot_scale": jax.ShapeDtypeStruct(
            (spec.n_delta_segments,) + sshape, jnp.float32),
        "delta_gids": jax.ShapeDtypeStruct(
            (spec.n_delta_segments, spec.delta_capacity), jnp.int64),
        "delta_valid": jax.ShapeDtypeStruct(
            (spec.n_delta_segments, spec.delta_capacity), bool),
    })


def pod_shardings(spec: PodIndexSpec, mesh, *, corpus_axes=None,
                  query_axes=None) -> Dict[str, NamedSharding]:
    """Sharding assignment per DESIGN.md: pilot replicated, corpus row-sharded
    over ``corpus_axes`` (default: every mesh axis), stage-②③ queries sharded
    over the remaining axes."""
    axes = mesh.axis_names
    corpus_axes = corpus_axes or axes
    query_axes = query_axes or tuple(a for a in axes if a not in corpus_axes) \
        or axes  # if corpus uses all axes, queries shard over all too
    NS = lambda *s: NamedSharding(mesh, P(*s))
    rep = NS()
    return {
        "pilot_neighbors": rep,
        "pilot_vecs": rep,
        "pilot_scale": rep,
        "pilot_to_full": rep,
        "fes_centroids": rep,
        "fes_entries": rep,
        "fes_scale": rep,
        "fes_entry_ids": rep,
        "fes_valid": rep,
        "full_neighbors": NS(corpus_axes),
        "full_vecs": NS(corpus_axes),
        "queries": NS(query_axes),
    } | ({} if not spec.mutable else {
        # tombstones ride with the replicated pilot payload (argument
        # replacement on delete, no retrace); delta segments are owned
        # round-robin: sharded over segment slots, not rows
        "tombstone": rep,
        "pilot_tombstone": rep,
        "delta_neighbors": NS(corpus_axes),
        "delta_pilot": NS(corpus_axes),
        "delta_pilot_scale": NS(corpus_axes),
        "delta_gids": NS(corpus_axes),
        "delta_valid": NS(corpus_axes),
    })


def make_pod_search_step(spec: PodIndexSpec, params: Optional[SearchParams] = None,
                         *, gather_mode: str = "naive", unroll: bool = True,
                         mesh=None, corpus_axes=None, query_spec=None):
    """Returns search_step(arrays...) -> (ids, dists) suitable for
    jit(in_shardings=pod_shardings(...)).lower(**pod_array_specs(...)).

    gather_mode='shardwise' needs (mesh, corpus_axes, query_spec) and uses
    shard_map hooks: distances/neighbour-rows are produced corpus-shard-side
    and psum'd — (B, E) scalars on the wire instead of (B, E, d) vectors."""
    params = params or SearchParams(ef=spec.ef, ef_pilot=spec.ef_pilot,
                                    bloom_bits=spec.bloom_bits,
                                    frontier_width=spec.frontier_width,
                                    frontier_width_pilot=spec.frontier_width_pilot)

    def search_step(pilot_neighbors, pilot_vecs, pilot_scale, pilot_to_full,
                    fes_centroids, fes_entries, fes_scale, fes_entry_ids,
                    fes_valid, full_neighbors, full_vecs, queries):
        Bq = queries.shape[0]
        n_pilot = pilot_vecs.shape[0] - 1
        Np = full_vecs.shape[0]
        n = Np - 1
        dp = spec.d_primary     # true width (pilot rows may be packed)
        qp = queries[:, :dp]
        # side payloads only engage for the quantized encodings (the scale
        # rows are all-ones otherwise; skipping them statically keeps the
        # fp32 HLO unchanged).  For "pq" the *_scale slots carry the
        # block-diagonal codebooks (core/quant.py; pod_array_specs).
        if spec.pilot_dtype == "pq":
            vsc = esc = None
            vcb, ecb = pilot_scale, fes_scale
        elif spec.pilot_dtype in ("int8", "int4"):
            vsc, esc = pilot_scale, fes_scale
            vcb = ecb = None
        else:
            vsc = esc = vcb = ecb = None

        nbr_fn = dist_fn = None
        if gather_mode == "shardwise":
            nbr_for, dist_for = make_shardwise_fns(
                mesh, corpus_axes, query_spec, Np, spec.R)
            nbr_fn = nbr_for(full_neighbors)
            dist_fn = dist_for(full_vecs)
            # pilot stage is embarrassingly parallel: spread the query batch
            # over EVERY mesh axis there (it re-shards to query_spec at the
            # stage-②③ shard_map boundary automatically)
            from jax.sharding import PartitionSpec as P
            qp = jax.lax.with_sharding_constraint(
                qp, P(tuple(mesh.axis_names), None))

        # ---- stage 0: FES (replicated data; local) ----
        entry_local, _ = F.fes_select_ref(qp, fes_centroids, fes_entries,
                                          fes_entry_ids, fes_valid,
                                          params.fes_L, entries_scale=esc,
                                          entries_codebook=ecb)

        # ---- stage ①: pilot traversal (replicated data; local) ----
        spec1 = T.TraversalSpec(
            ef=params.ef_pilot, visited_mode="bloom",
            bloom_bits=params.bloom_bits,
            frontier_width=params.frontier_width_pilot,
            dense_visited_update=gather_mode == "shardwise",
            state_spec=(P(tuple(mesh.axis_names), None)
                        if gather_mode == "shardwise" else None))
        st1 = T.greedy_search(spec1, qp, pilot_neighbors, pilot_vecs, n_pilot,
                              entry_local, iters=spec.pilot_iters,
                              unroll=unroll, vec_scale=vsc, vec_codebook=vcb)
        # map pilot-compact ids to full-corpus ids
        cand_full = pilot_to_full[jnp.where(st1.cand_id < n_pilot,
                                            st1.cand_id, n_pilot)]
        cand_full = jnp.where(st1.cand_id < n_pilot, cand_full, n)

        # ---- stage ②: residual refinement (sharded scoring begins) ----
        if dist_fn is None:
            gathered = _gather_rows(full_vecs, cand_full, gather_mode)
            d_full = T.sq_dists(queries, gathered)
        else:
            d_full = dist_fn(queries, cand_full)
        d_full = jnp.where(cand_full < n, d_full, jnp.inf)

        # ---- stage ③: bounded traversal on the sharded full index.
        # W-wide rounds stay query-sharded under 'shardwise': nbr_fn runs
        # once per frontier ((B,) ids in, (B, R) rows psum'd back) and
        # dist_fn scores the whole (B, W·R) id block shard-side, so the only
        # W-dependent wire traffic is the (B, W·R) scalar psum ----
        spec3 = T.TraversalSpec(ef=params.ef, visited_mode="bloom",
                                bloom_bits=params.bloom_bits,
                                frontier_width=params.frontier_width,
                                dense_visited_update=gather_mode == "shardwise",
                                state_spec=(jax.sharding.PartitionSpec(
                                    query_spec[0], None)
                                    if gather_mode == "shardwise" and
                                    query_spec is not None else None))
        st3 = T.greedy_search(spec3, queries, full_neighbors, full_vecs, n,
                              entry_ids=jnp.full((Bq, 1), n, jnp.int32),
                              iters=spec.refine_iters + spec.final_iters,
                              unroll=unroll,
                              extra_id=cand_full, extra_d=d_full,
                              nbr_fn=nbr_fn, dist_fn=dist_fn)
        return T.topk_from_state(st3, params.k)

    return search_step


def _gather_rows(table: jax.Array, ids: jax.Array, mode: str) -> jax.Array:
    """Gather (B, E) rows from the row-sharded (N, d) table -> (B, E, d)."""
    return table[ids]


# ---------------------------------------------------------------------------
# Shardwise primitives (§Perf beyond-paper optimization)
#
# The naive sharded stages let GSPMD move gathered VECTORS (B, E, d) across
# the ICI.  Shard-side evaluation moves only what the traversal actually
# consumes: each corpus shard scores the ids it owns against the (replicated-
# over-corpus-axes) queries and contributes zeros elsewhere; one psum of
# (B, E) fp32 scalars replaces the (B, E, d) vector traffic — a d/1 wire-byte
# reduction (d=96: ~96x; d=768: ~768x) on every expansion round.  The same
# owned-rows + psum trick fetches neighbour rows ((B, R) int32).
# ---------------------------------------------------------------------------

def make_shardwise_fns(mesh, corpus_axes, query_spec, N: int, R: int):
    """Build (nbr_fn_factory, dist_fn_factory) for shard_map execution.

    Arrays are closed over per call:  the returned builders take the sharded
    tables and produce hooks with signature matching traversal.expansion_round.
    """
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    n_shards = int(np.prod([mesh.shape[a] for a in corpus_axes]))
    rows_per = N // n_shards
    caxes = corpus_axes if len(corpus_axes) > 1 else corpus_axes[0]

    def _shard_index():
        idx = 0
        for a in corpus_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    from jax.sharding import PartitionSpec
    qb = query_spec[0] if query_spec is not None and len(query_spec) else None
    spec1 = PartitionSpec(qb)          # (B,)
    spec2 = PartitionSpec(qb, None)    # (B, E) / (B, d)

    def nbr_fn_for(neighbor_table):
        def local(tbl, u):
            sid = _shard_index()
            lo = sid * rows_per
            loc = u.astype(jnp.int32) - lo
            owned = (loc >= 0) & (loc < tbl.shape[0])
            rows = tbl[jnp.clip(loc, 0, tbl.shape[0] - 1)]     # (B, R) local
            rows = jnp.where(owned[:, None], rows, 0)
            return jax.lax.psum(rows, caxes)

        sm = shard_map(local, mesh=mesh,
                       in_specs=(P(corpus_axes, None), spec1),
                       out_specs=spec2,
                       check_rep=False)
        return lambda u: sm(neighbor_table, u)

    def dist_fn_for(vec_table):
        def local(tbl, q, ids):
            sid = _shard_index()
            lo = sid * rows_per
            loc = ids.astype(jnp.int32) - lo
            owned = (loc >= 0) & (loc < tbl.shape[0])
            v = tbl[jnp.clip(loc, 0, tbl.shape[0] - 1)]        # (B, E, d)
            qf = q.astype(jnp.float32)
            vf = v.astype(jnp.float32)
            qn = jnp.sum(qf * qf, axis=-1)[:, None]
            vn = jnp.sum(vf * vf, axis=-1)
            dot = jnp.einsum("bd,bed->be", qf, vf,
                             precision=jax.lax.Precision.HIGHEST)
            d = jnp.maximum(qn + vn - 2.0 * dot, 0.0)
            d = jnp.where(owned, d, 0.0)
            return jax.lax.psum(d, caxes)                      # (B, E) scalars

        sm = shard_map(local, mesh=mesh,
                       in_specs=(P(corpus_axes, None), spec2, spec2),
                       out_specs=spec2,
                       check_rep=False)
        return lambda q, ids, fresh=None: sm(vec_table, q, ids)

    return nbr_fn_for, dist_fn_for


def _round_to(x: int, k: int) -> int:
    return -(-x // k) * k


# ---------------------------------------------------------------------------
# Pod-scale serving: the sharded mutable index (DESIGN.md §7)
#
# ``make_pod_search_step`` above is the *dry-run* sharded program (spec-sized
# stand-in arrays).  This section is the servable counterpart: a real
# ``SegmentedIndex`` partitioned across a device mesh and searched through
# the serving stage pair (``core/pipeline.split_stages(shard_ctx=...)``),
# with bit-exact parity against the single-device index at every shard
# count (tests/test_pod_serving.py runs it on forced host CPU devices).
# ---------------------------------------------------------------------------

#: base-index keys row-sharded under the "hot-replicated" placement; every
#: other array (pilot subgraph, quantized pilot rows + scales, FES tables,
#: coarse layer, tombstones) is replicated per shard
COLD_KEYS: Tuple[str, ...] = ("full_neighbors", "rot_vecs", "residual")


@dataclass(frozen=True)
class ShardParams:
    """Pod-serving shard layout (full field reference: docs/api.md).

    placement:
      * ``hot-replicated`` — the paper-faithful memory-bounded mode: hot
        pilot payload replicated on every shard, cold tables (``COLD_KEYS``)
        row-sharded; stages ②③ score cold rows shard-side (owned rows +
        psum of exact zeros elsewhere — bit-exact, module docstring).
      * ``replicated`` — every table replicated, the *query batch* sharded
        instead: pure throughput scaling for skewed/hot traffic that fits
        one device (batches must divide by ``n_shards``; the bucket ladder
        rungs are multiples of 8, so shard counts up to 8 always do).
    """
    n_shards: int = 1
    placement: str = "hot-replicated"   # hot-replicated | replicated

    def __post_init__(self):
        if self.placement not in ("hot-replicated", "replicated"):
            raise ValueError(f"unknown placement {self.placement!r}")
        if self.n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {self.n_shards}")


@dataclass(frozen=True)
class ShardContext:
    """Everything the sharded stage pair needs beyond the arrays: the mesh,
    the shard axis, the *true* corpus size (the sharded tables are padded to
    ``n_shards * rows_per`` rows, so ``arrays['rot_vecs'].shape[0] - 1`` is
    wrong on purpose) and the placement mode."""
    mesh: jax.sharding.Mesh
    axis: str
    n_shards: int
    rows_per: int
    n: int
    placement: str


def shard_local_nbr_fn(local_table: jax.Array, axis: str, rows_per: int):
    """Neighbour-row fetch hook for use INSIDE a shard_map body over a
    row-sharded adjacency table: each shard contributes the rows it owns
    (global row ``g`` lives on shard ``g // rows_per``) and exact zeros
    elsewhere; one psum of (B, R) int32 replaces a cross-shard gather.
    Values in the table are *global* ids, so only rows are partitioned."""
    def nbr_fn(u):
        sid = jax.lax.axis_index(axis)
        loc = u.astype(jnp.int32) - sid * rows_per
        owned = (loc >= 0) & (loc < rows_per)
        rows = local_table[jnp.clip(loc, 0, rows_per - 1)]
        rows = jnp.where(owned[..., None], rows, 0)
        return jax.lax.psum(rows, axis)
    return nbr_fn


def shard_local_dist_fn(local_table: jax.Array, axis: str, rows_per: int):
    """Distance hook for shard_map bodies over a row-sharded vector table,
    exactness contract of ``multistage.refine_stage``: the owning shard
    computes the identical ``traversal.sq_dists`` value (same row bytes,
    same formula), non-owners contribute exact 0.0, and the psum of one
    value plus zeros is bit-exact — so the sharded stages reproduce the
    single-device distances bit-for-bit (tests/test_pod_serving.py)."""
    def dist_fn(q, ids, fresh=None):
        sid = jax.lax.axis_index(axis)
        loc = ids.astype(jnp.int32) - sid * rows_per
        owned = (loc >= 0) & (loc < rows_per)
        v = local_table[jnp.clip(loc, 0, rows_per - 1)]
        d = T.sq_dists(q, v)
        d = jnp.where(owned, d, jnp.float32(0.0))
        return jax.lax.psum(d, axis)
    return dist_fn


class ShardedSegmentedIndex(SegmentedIndex):
    """A ``core/segments.SegmentedIndex`` partitioned across devices
    (DESIGN.md §7): the drop-in pod-scale backend for
    ``serving/server.ThroughputEngine``.

    Layout (``ShardParams.placement == "hot-replicated"``):
      * base *hot* payload — replicated on every shard;
      * base *cold* tables (``COLD_KEYS``) — row-sharded, rows padded to a
        multiple of the shard count (pad adjacency rows hold the sentinel);
      * delta segments — whole segments owned round-robin by shards
        (``DeltaSegment.device``), searched by the owner and merged exactly
        in the global id space (``segments.merge_topk``'s canonical
        (distance, gid) order makes the merge layout-invariant);
      * tombstones — replicated, refreshed by argument replacement.

    Searches run the sharded stage pair from
    ``core/pipeline.split_stages(shard_ctx=...)``; results are bit-identical
    to the single-device ``SegmentedIndex`` at every shard count because
    every scored row has exactly one owner (module docstring).

    Mutation plumbing (global ids, tombstones, repair, compaction) is
    inherited from ``SegmentedIndex``; only placement
    (``_ensure_delta``/``_install_shard_arrays``) and the base search path
    (``search``/``stage_pair``) are overridden.
    """


    def __init__(self, cfg, vectors, update_params=None, *,
                 shard_params: Optional[ShardParams] = None,
                 devices=None):
        sp = shard_params or ShardParams()
        devices = list(devices if devices is not None
                       else jax.devices()[:sp.n_shards])
        if len(devices) < sp.n_shards:
            raise ValueError(
                f"need {sp.n_shards} devices, have {len(devices)} "
                f"(hint: XLA_FLAGS=--xla_force_host_platform_device_"
                f"count=N before importing jax forces N CPU devices)")
        self.sp = sp
        self.devices = devices[:sp.n_shards]
        self.mesh = jax.sharding.Mesh(np.array(self.devices), ("shard",))
        self._shard_open: Dict[int, DeltaSegment] = {}
        self._target_shard: Optional[int] = None
        self._rr = 0
        self._stage_cache: "OrderedDict" = OrderedDict()
        # degraded mode (DESIGN.md §8): shards declared dead by the serving
        # layer's HeartbeatMonitor; their rows are masked out of the search
        # via a tombstone OVERLAY (set_dead_shards) — nothing is recompiled,
        # so clearing the set restores bit-parity instantly.
        self._dead_shards: frozenset = frozenset()
        self._tomb_deg = None
        self._ptomb_deg = None
        super().__init__(cfg, vectors, update_params)
        self._install_shard_arrays()

    # -- placement ----------------------------------------------------
    def _install_shard_arrays(self) -> None:
        """(Re)commit the base arrays to the mesh: hot keys replicated,
        cold keys (``COLD_KEYS``) row-sharded under "hot-replicated"
        placement — rows padded to ``n_shards * rows_per`` (adjacency
        pads hold the sentinel ``n``; vector pads are zeros and are
        never scored: every traversal id is ``<= n``)."""
        base = self.base
        n = base.n
        K = self.sp.n_shards
        Np = _round_to(n + 1, K)
        rep = NamedSharding(self.mesh, P())
        row = NamedSharding(self.mesh, P("shard"))
        hot_repl = self.sp.placement == "hot-replicated"
        arrs: Dict[str, jax.Array] = {}
        for k, v in base.arrays.items():
            if k in ("tombstone", "pilot_tombstone"):
                continue                     # ride as stage arguments
            if hot_repl and k in COLD_KEYS:
                h = np.asarray(v)
                pad = Np - h.shape[0]
                if pad:
                    fill = (np.full((pad, h.shape[1]), n, h.dtype)
                            if k == "full_neighbors"
                            else np.zeros((pad,) + h.shape[1:], h.dtype))
                    h = np.concatenate([h, fill], axis=0)
                arrs[k] = jax.device_put(h, row)
            else:
                arrs[k] = jax.device_put(v, rep)
        self._shard_arrays = arrs
        self._shard_ctx = ShardContext(
            mesh=self.mesh, axis="shard", n_shards=K,
            rows_per=Np // K, n=n, placement=self.sp.placement)
        self._stage_cache.clear()
        self._install_base_tombstones()

    def _install_base_tombstones(self) -> None:
        super()._install_base_tombstones()
        if not hasattr(self, "_shard_arrays"):
            return            # called from super().__init__; deferred
        rep = NamedSharding(self.mesh, P())
        self._tomb_rep = jax.device_put(
            np.asarray(self.base.arrays["tombstone"]), rep)
        self._ptomb_rep = jax.device_put(
            np.asarray(self.base.arrays["pilot_tombstone"]), rep)
        self._refresh_degraded_tombs()

    def shard_tombs(self) -> Tuple[jax.Array, jax.Array]:
        """(pilot_tombstone, tombstone) replicated on the mesh — the
        REQUIRED trailing arguments of the sharded stage pair.  In degraded
        mode (``set_dead_shards``) the returned bitmaps carry the dead-shard
        overlay, so already-compiled executables serve survivors-only
        results without a retrace."""
        if self._dead_shards:
            return self._ptomb_deg, self._tomb_deg
        return self._ptomb_rep, self._tomb_rep

    # -- degraded mode (DESIGN.md §8) ----------------------------------
    @property
    def dead_shards(self) -> frozenset:
        return self._dead_shards

    def set_dead_shards(self, dead) -> float:
        """Enter/leave degraded mode: mask every base row owned by a shard
        in ``dead`` (and skip its delta segments) via a tombstone overlay.

        The pilot stage keeps its full replicated payload compiled in; the
        overlay rides the existing tombstone ARGUMENTS, so the same
        executables serve stage-①-guided, exactly-rescored results from the
        surviving shards only — identical bits to a single-device index
        with the same rows deleted (the failover contract the multidevice
        harness proves).  Passing an empty set heals: the overlay is
        dropped and results return to bit-parity with the healthy index.

        Returns the fraction of live rows masked (the recall exposure the
        serving engine surfaces as ``stats["degraded_coverage"]``)."""
        dead = frozenset(int(s) for s in dead)
        for s in dead:
            if not 0 <= s < self.sp.n_shards:
                raise ValueError(f"shard {s} out of range "
                                 f"[0, {self.sp.n_shards})")
        self._dead_shards = dead
        self._refresh_degraded_tombs()
        return self.degraded_fraction()

    def _dead_base_rows(self) -> np.ndarray:
        """Boolean mask over base positional rows owned by dead shards
        (ownership is by padded row range: row j -> shard j // rows_per)."""
        n = self.base.n
        rp = self._shard_ctx.rows_per
        owner = np.minimum(np.arange(n) // rp, self.sp.n_shards - 1)
        return np.isin(owner, list(self._dead_shards))

    def _refresh_degraded_tombs(self) -> None:
        """(Re)build the overlay bitmaps = base tombstones OR dead-shard
        rows, derived exactly as ``_install_base_tombstones`` derives the
        base pair (pilot bitmap via ``keep_ids``) so degraded results match
        the deleted-rows oracle bit-for-bit.  Re-run whenever the base
        bitmaps refresh (deletes/compaction) while shards are dead."""
        if not self._dead_shards:
            self._tomb_deg = self._ptomb_deg = None
            return
        n, nk = self.base.n, self.base.n_pilot
        masked = self._base_tomb | self._dead_base_rows()
        tomb = np.zeros(n + 1, bool)
        tomb[:n] = masked
        ptomb = np.zeros(nk + 1, bool)
        ptomb[:nk] = masked[self.base.keep_ids]
        rep = NamedSharding(self.mesh, P())
        self._tomb_deg = jax.device_put(tomb, rep)
        self._ptomb_deg = jax.device_put(ptomb, rep)

    def degraded_fraction(self) -> float:
        """Fraction of live rows (base + delta) currently masked by the
        dead-shard overlay — 0.0 when healthy."""
        if not self._dead_shards:
            return 0.0
        live_base = ~self._base_tomb
        masked = int((live_base & self._dead_base_rows()).sum())
        total = int(live_base.sum())
        for seg in self.deltas:
            cnt = seg.live_count()
            total += cnt
            if getattr(seg, "shard", 0) in self._dead_shards:
                masked += cnt
        return masked / total if total else 0.0

    def _live_deltas(self):
        """Degraded mode also excludes delta segments owned by dead shards
        from the merge (their device is unreachable)."""
        if not self._dead_shards:
            return self.deltas
        return [seg for seg in self.deltas
                if getattr(seg, "shard", 0) not in self._dead_shards]

    # -- mutation routing ---------------------------------------------
    def insert(self, vectors: np.ndarray,
               shard: Optional[int] = None) -> np.ndarray:
        """Append vectors; the batch lands in the delta segment owned
        by ``shard`` (round-robin when None).  Global ids stay
        monotone across shards, so the cross-shard merge remains a
        pure top-k in the global id space."""
        if shard is not None and not 0 <= shard < self.sp.n_shards:
            raise ValueError(f"shard {shard} out of range "
                             f"[0, {self.sp.n_shards})")
        self._target_shard = shard
        try:
            return super().insert(vectors)
        finally:
            self._target_shard = None

    def _ensure_delta(self, need: int) -> DeltaSegment:
        s = self._target_shard
        if s is None:
            s = self._rr
            self._rr = (self._rr + 1) % self.sp.n_shards
        seg = self._shard_open.get(s)
        if seg is None:
            seg = DeltaSegment(self.d, self.base.reducer.d_primary,
                               self.base.cfg.R,
                               max(self.up.delta_capacity, 8))
            seg.device = self.devices[s]
            seg.shard = s
            self._shard_open[s] = seg
            self.deltas.append(seg)
        seg.grow(need)
        return seg

    def shard_of_gids(self, gids) -> np.ndarray:
        """Owning shard per global id (base rows by row range, delta
        rows by segment owner; dead/unknown ids report shard 0) —
        the engine's per-shard delete routing."""
        g = np.atleast_1d(np.asarray(gids, np.int64))
        out = np.zeros(len(g), np.int32)
        rp = self._shard_ctx.rows_per
        for i, gid in enumerate(g):
            j = int(np.searchsorted(self._base_gids, gid))
            if j < len(self._base_gids) and self._base_gids[j] == gid:
                out[i] = min(j // rp, self.sp.n_shards - 1)
                continue
            for seg in self.deltas:
                jj = int(np.searchsorted(seg.gids[:seg.m], gid))
                if jj < seg.m and seg.gids[jj] == gid:
                    out[i] = getattr(seg, "shard", 0)
                    break
        return out

    def compact(self, *, replan: bool = True):
        super().compact(replan=replan)
        self._shard_open = {}
        self._rr = 0
        self._install_shard_arrays()
        return self

    # -- search --------------------------------------------------------
    def stage_pair(self, params: SearchParams, *, donate: bool = True):
        """The cached sharded stage pair for ``params`` (compiled once
        per (params, donate, generation); the serving engine's
        ``_build_stages`` consumes this)."""
        key = (params, donate, self.generation)
        fns = self._stage_cache.get(key)
        if fns is None:
            from repro.core.pipeline import split_stages
            fns = split_stages(self._shard_arrays, params,
                               donate=donate, shard_ctx=self._shard_ctx)
            self._stage_cache[key] = fns
            while len(self._stage_cache) > 8:
                self._stage_cache.popitem(last=False)
        return fns

    def search(self, queries: np.ndarray, params: SearchParams,
               *, rotated: bool = False):
        """Sharded fan-out search, same contract as
        ``SegmentedIndex.search`` (global ids, exact merge); per-stage
        distance counters are not threaded through the shard_map
        stages, so the standard stats keys report zero here and only
        ``delta_dist`` is populated."""
        from repro.core.multistage import pad_to_bucket
        q = jnp.asarray(queries) if rotated else self.rotate_queries(
            np.asarray(queries, np.float32))
        qp, B = pad_to_bucket(q, self.base.batch_buckets)
        pilot, cpu = self.stage_pair(params, donate=False)
        ptomb, tomb = self.shard_tombs()
        po = pilot(qp, ptomb)
        ids, dists = cpu(qp, *po, ptomb, tomb)
        ids_b = np.asarray(ids)[:B]
        d_b = np.asarray(dists)[:B]
        gids, dd, scored = self.merge_with_deltas(q, ids_b, d_b,
                                                  params.k, params)
        zeros = np.zeros(B, np.int32)
        stats = {k: zeros for k in
                 ("fes_dist", "pilot_dist", "pilot_hops",
                  "pilot_expanded", "refine_dist", "final_dist",
                  "final_hops", "final_expanded", "total_cpu_dist")}
        stats["delta_dist"] = scored
        return gids, dd, stats

