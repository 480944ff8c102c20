"""Stage-level software pipelining of query batches (paper: "CPU–GPU
pipelining", Table 5 first ablation row; serving runtime in DESIGN.md §5).

On the GPU system, stage ① of batch i+1 overlaps stages ②③ of batch i across
the PCIe boundary.  The JAX analogue exploits async dispatch: the pilot
stages of up to ``depth`` batches are dispatched before the CPU-side stages
of the oldest batch are consumed, so the runtime overlaps them whenever the
backends can.  On a TPU pod the same structure overlaps the replicated-pilot
program with the sharded-traversal program (``depth`` executables in
flight).

The stage boundary carries the pilot beam (compact pilot ids + stage-①
distances) and the visited filter (stages ① and ② share the compact id
space); the shared ``multistage.refine_stage`` helper then re-scores
exactly (from ``rot_vecs`` when the pilot is quantized, via the SVD
residual identity when it is fp32 — DESIGN.md §4) and hands stage ③ the
beam alone, exactly as ``multistage.multistage_search`` does.

**Donation contract** (``donate=True``, DESIGN.md §5): the stage-boundary
buffers are use-once, so they are donated via ``jax.jit(...,
donate_argnums=...)`` and their storage is *recycled* instead of
reallocated per batch.  ``cpu_stages`` donates beam ids, beam distances
and the visited filter (consuming them invalidates the caller's arrays —
accidental reuse raises) and returns their storage aliased; the visited
filter — by far the largest boundary buffer, ``(B, bloom_bits)`` per batch
— cycles through a per-shape pool back into ``pilot_stage``, which takes
it as a donated scratch argument, clears it in-place and runs the
traversal in it.  Steady state allocates no new visited storage at all;
results are bit-identical to the undonated path.

Ragged batches: the Pallas stage-① paths need sublane-aligned batch sizes;
``pilot_stage`` pads with the shared ``multistage.pad_for_pallas`` helper
(inside jit — pad widths are static per trace) and slices its outputs back,
so ``cpu_stages`` and callers always see the caller's batch size.  The
*donated* path requires the caller's batches to be aligned already (XLA
aliases whole buffers only, so the scratch filter must equal the output
shape) — bucket-padded batches (``multistage.pad_to_bucket``) always are.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from functools import partial
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bloom as BL
from repro.core import quant
from repro.core import traversal as T
from repro.core import fes as F
from repro.core.multistage import SearchParams, pad_for_pallas, refine_stage


def visited_buffer(params: SearchParams, batch: int, nk: int) -> jax.Array:
    """A cleared stage-① visited filter of the shape ``pilot_stage``
    produces: ``(batch, bloom_bits)`` bool for bloom mode, ``(batch, nk+1)``
    for the exact bitmap.  The donated path's scratch/pool buffers come from
    here (DESIGN.md §5)."""
    if params.visited_mode == "bloom":
        return BL.bloom_init(batch, params.bloom_bits)
    return BL.exact_init(batch, nk)


def _pilot_spec(params: SearchParams) -> T.TraversalSpec:
    return T.TraversalSpec(ef=params.ef_pilot, visited_mode=params.visited_mode,
                           bloom_bits=params.bloom_bits,
                           max_iters=params.max_iters,
                           frontier_width=params.frontier_width_pilot,
                           use_pallas=(params.use_pallas_traversal or
                                       params.use_persistent_traversal),
                           use_persistent=params.use_persistent_traversal)


class _DonatedStages:
    """The donated variant of the stage pair, presenting the same
    ``pilot(queries)`` / ``cpu(queries, cand_id, cand_d, visited)``
    interface as the plain jitted functions while cycling the visited
    filter's storage through a per-shape pool (module docstring).

    Mutable-index serving (a ``core/segments.SegmentedIndex`` base) passes
    the deletion bitmaps as optional trailing *arguments* — ``pilot(queries,
    pilot_tomb)`` / ``cpu(queries, cand_id, cand_d, visited, pilot_tomb,
    tomb)`` — because closure-captured arrays are burned into the trace as
    constants, while same-shape argument replacement (a delete) never
    retraces (DESIGN.md §6).  Omitting them keeps the immutable fast path
    (a separate trace without the masking ops)."""

    def __init__(self, arrays: Dict[str, jax.Array], params: SearchParams):
        self.params = params
        self.arrays = arrays
        self.nk = arrays["pilot_to_full"].shape[0] - 1
        n = arrays["rot_vecs"].shape[0] - 1
        pilot_scale = arrays.get("primary_scale")
        pilot_codebook = arrays.get("primary_codebook")
        dp = quant.primary_dim(arrays["primary"], pilot_scale,
                               codebook=pilot_codebook)
        self._pool: Dict[int, List[jax.Array]] = {}
        self._pallas = (params.use_pallas_traversal or
                        params.use_persistent_traversal)

        @partial(jax.jit, donate_argnums=(2,))
        def pilot_fn(arrays, queries, visited_scratch, pilot_tomb=None):
            # clear the recycled filter in place (donated: output aliases it)
            cleared = visited_scratch ^ visited_scratch
            qp = queries[:, :dp]
            entry_ids, _ = F.fes_select_ref(
                qp, arrays["fes_centroids"], arrays["fes_entries"],
                arrays["fes_entry_ids"], arrays["fes_valid"], params.fes_L,
                entries_scale=arrays.get("fes_entries_scale"),
                entries_codebook=arrays.get("fes_entries_codebook"),
                tombstone=pilot_tomb)
            st1 = T.greedy_search(_pilot_spec(params), qp,
                                  arrays["sub_neighbors"], arrays["primary"],
                                  self.nk, entry_ids, visited=cleared,
                                  vec_scale=pilot_scale,
                                  vec_codebook=pilot_codebook,
                                  tombstone=pilot_tomb)
            return st1.cand_id, st1.cand_d, st1.visited

        @partial(jax.jit, donate_argnums=(2, 3, 4))
        def cpu_fn(arrays, queries, cand_id, cand_dp, visited,
                   pilot_tomb=None, tomb=None):
            Bq = queries.shape[0]
            arr = arrays if pilot_tomb is None else dict(
                arrays, pilot_tombstone=pilot_tomb, tombstone=tomb)
            seed_id, seed_d, _ = refine_stage(arr, params, queries,
                                              cand_id, cand_dp,
                                              visited=visited)
            spec3 = T.TraversalSpec(ef=params.ef,
                                    visited_mode=params.visited_mode,
                                    bloom_bits=params.bloom_bits,
                                    max_iters=params.max_iters,
                                    frontier_width=params.frontier_width)
            st3 = T.greedy_search(spec3, queries, arrays["full_neighbors"],
                                  arrays["rot_vecs"], n,
                                  entry_ids=jnp.full((Bq, 1), n, jnp.int32),
                                  extra_id=seed_id, extra_d=seed_d,
                                  tombstone=tomb)
            ids, dists = T.topk_from_state(st3, params.k)
            # hand the boundary buffers back so their (donated) storage is
            # aliased into outputs instead of freed-and-reallocated; the
            # wrapper pools the visited filter and drops the beams
            return ids, dists, cand_id, cand_dp, visited

        self._pilot_fn, self._cpu_fn = pilot_fn, cpu_fn

    def pilot(self, queries: jax.Array, *tombs):
        Bq = queries.shape[0]
        if self._pallas and Bq % 8 != 0:
            raise ValueError(
                f"donated split_stages needs sublane-aligned batches with "
                f"the Pallas stage-① paths (got B={Bq}); pad with "
                f"multistage.pad_to_bucket first")
        pool = self._pool.get(Bq)
        scratch = pool.pop() if pool else visited_buffer(self.params, Bq,
                                                         self.nk)
        return self._pilot_fn(self.arrays, queries, scratch, *tombs)

    def cpu(self, queries: jax.Array, cand_id, cand_dp, visited, *tombs):
        ids, dists, _cid, _cd, vis_r = self._cpu_fn(
            self.arrays, queries, cand_id, cand_dp, visited, *tombs)
        self._pool.setdefault(queries.shape[0], []).append(vis_r)
        return ids, dists


class _ShardedStages:
    """The pod-sharded stage pair (DESIGN.md §7): the same
    ``pilot(queries, pilot_tomb)`` / ``cpu(queries, cand_id, cand_d,
    visited, pilot_tomb, tomb)`` interface as the other variants, executed
    as ``shard_map`` programs over ``shard_ctx.mesh``.  The deletion
    bitmaps are REQUIRED trailing arguments here (a sharded serving index
    is mutable by construction).

    Placement (``shard_ctx.placement``):
      * ``hot-replicated`` — hot arrays replicated, ``distributed.COLD_KEYS``
        row-sharded; stage ① is replicated compute, stages ②③ score cold
        rows shard-side via ``distributed.shard_local_dist_fn`` /
        ``shard_local_nbr_fn`` (owned rows + psum — bit-exact, see
        ``multistage.refine_stage``'s hook contract).
      * ``replicated`` — all arrays replicated, the query batch sharded
        over the mesh instead (batch must divide by the shard count; the
        bucket ladder's multiples-of-8 rungs always do for <= 8 shards).

    The true corpus size comes from ``shard_ctx.n`` — the sharded cold
    tables are row-padded to a multiple of the shard count, so the usual
    ``rot_vecs.shape[0] - 1`` would over-count.  Donation: same contract
    as ``_DonatedStages`` (boundary buffers donated, visited filter pooled
    through the pilot's scratch argument); jit donation composes with
    shard_map, aliasing each shard's local buffer."""

    COLD = ("full_neighbors", "rot_vecs", "residual")

    def __init__(self, arrays: Dict[str, jax.Array], params: SearchParams,
                 ctx, *, donate: bool = False):
        if params.use_pallas_traversal or params.use_persistent_traversal:
            raise ValueError("sharded split_stages supports the jnp stage "
                             "paths only (Pallas stage ① is per-device)")
        from jax.experimental.shard_map import shard_map
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import distributed as DI

        self.params = params
        self.ctx = ctx
        self.donate = donate
        self.nk = arrays["pilot_to_full"].shape[0] - 1
        self._pool: Dict[int, List[jax.Array]] = {}
        mesh, axis, n = ctx.mesh, ctx.axis, ctx.n
        rows_per = ctx.rows_per
        dp = quant.primary_dim(arrays["primary"],
                               arrays.get("primary_scale"),
                               codebook=arrays.get("primary_codebook"))
        hot_repl = ctx.placement == "hot-replicated"
        keys = tuple(sorted(arrays.keys()))
        self._ops = tuple(arrays[k] for k in keys)
        arr_specs = tuple(P(axis) if hot_repl and k in self.COLD else P()
                          for k in keys)
        qspec = P() if hot_repl else P(axis)
        self._qsharding = NamedSharding(mesh, qspec)
        self._rsharding = NamedSharding(mesh, P())

        def pilot_core(ops, queries, visited_scratch, pilot_tomb):
            a = dict(zip(keys, ops))
            cleared = visited_scratch ^ visited_scratch
            qp = queries[:, :dp]
            entry_ids, _ = F.fes_select_ref(
                qp, a["fes_centroids"], a["fes_entries"],
                a["fes_entry_ids"], a["fes_valid"], params.fes_L,
                entries_scale=a.get("fes_entries_scale"),
                entries_codebook=a.get("fes_entries_codebook"),
                tombstone=pilot_tomb)
            st1 = T.greedy_search(_pilot_spec(params), qp,
                                  a["sub_neighbors"], a["primary"],
                                  self.nk, entry_ids, visited=cleared,
                                  vec_scale=a.get("primary_scale"),
                                  vec_codebook=a.get("primary_codebook"),
                                  tombstone=pilot_tomb)
            return st1.cand_id, st1.cand_d, st1.visited

        def cpu_core(ops, queries, cand_id, cand_dp, visited,
                     pilot_tomb, tomb):
            a = dict(zip(keys, ops))
            Bq = queries.shape[0]
            if hot_repl:
                dfull = DI.shard_local_dist_fn(a["rot_vecs"], axis, rows_per)
                dres = DI.shard_local_dist_fn(a["residual"], axis, rows_per)
            else:
                dfull = dres = None
            arr = dict(a, pilot_tombstone=pilot_tomb, tombstone=tomb)
            seed_id, seed_d, _ = refine_stage(
                arr, params, queries, cand_id, cand_dp, visited=visited,
                dist_full_fn=dfull, dist_res_fn=dres)
            spec3 = T.TraversalSpec(ef=params.ef,
                                    visited_mode=params.visited_mode,
                                    bloom_bits=params.bloom_bits,
                                    max_iters=params.max_iters,
                                    frontier_width=params.frontier_width)
            if hot_repl:
                # tombstone-mask the *local* table here: with an nbr_fn,
                # greedy_search's own masking applies to the (unused)
                # positional table only.  Masking is value-wise (global
                # ids), so it composes with row sharding.
                masked = T.sentinel_mask(tomb, a["full_neighbors"], n)
                nbr3 = DI.shard_local_nbr_fn(masked, axis, rows_per)
                dist3 = dfull
            else:
                masked = a["full_neighbors"]
                nbr3 = dist3 = None
            st3 = T.greedy_search(spec3, queries, masked, a["rot_vecs"], n,
                                  entry_ids=jnp.full((Bq, 1), n, jnp.int32),
                                  extra_id=seed_id, extra_d=seed_d,
                                  nbr_fn=nbr3, dist_fn=dist3,
                                  tombstone=tomb)
            ids, dists = T.topk_from_state(st3, params.k)
            return ids, dists, cand_id, cand_dp, visited

        sm_pilot = shard_map(pilot_core, mesh=mesh,
                             in_specs=(arr_specs, qspec, qspec, P()),
                             out_specs=(qspec, qspec, qspec),
                             check_rep=False)
        sm_cpu = shard_map(cpu_core, mesh=mesh,
                           in_specs=(arr_specs, qspec, qspec, qspec, qspec,
                                     P(), P()),
                           out_specs=(qspec,) * 5,
                           check_rep=False)
        if donate:
            self._pilot_fn = jax.jit(sm_pilot, donate_argnums=(2,))
            self._cpu_fn = jax.jit(sm_cpu, donate_argnums=(2, 3, 4))
        else:
            self._pilot_fn = jax.jit(sm_pilot)
            self._cpu_fn = jax.jit(sm_cpu)

    def _check_batch(self, Bq: int) -> None:
        if self.ctx.placement != "hot-replicated" and \
                Bq % self.ctx.n_shards != 0:
            raise ValueError(
                f"'replicated' placement shards the query batch: B={Bq} "
                f"must divide by n_shards={self.ctx.n_shards} (bucket-pad "
                f"with multistage.pad_to_bucket first)")

    def pilot(self, queries: jax.Array, *tombs):
        if len(tombs) != 1:
            raise TypeError("sharded pilot stage requires the pilot "
                            "tombstone argument: pilot(queries, pilot_tomb)")
        Bq = queries.shape[0]
        self._check_batch(Bq)
        q = jax.device_put(queries, self._qsharding)
        pt = jax.device_put(tombs[0], self._rsharding)
        pool = self._pool.get(Bq)
        scratch = pool.pop() if pool and self.donate else jax.device_put(
            visited_buffer(self.params, Bq, self.nk), self._qsharding)
        return self._pilot_fn(self._ops, q, scratch, pt)

    def cpu(self, queries: jax.Array, cand_id, cand_dp, visited, *tombs):
        if len(tombs) != 2:
            raise TypeError("sharded cpu stage requires both tombstone "
                            "arguments: cpu(..., pilot_tomb, tomb)")
        q = jax.device_put(queries, self._qsharding)
        pt = jax.device_put(tombs[0], self._rsharding)
        tb = jax.device_put(tombs[1], self._rsharding)
        ids, dists, _cid, _cd, vis_r = self._cpu_fn(
            self._ops, q, cand_id, cand_dp, visited, pt, tb)
        if self.donate:
            self._pool.setdefault(queries.shape[0], []).append(vis_r)
        return ids, dists


def split_stages(arrays: Dict[str, jax.Array], params: SearchParams,
                 *, donate: bool = False, shard_ctx=None):
    """jit the pilot stage (①+FES) and the CPU stages (②③) separately so
    they can be dispatched independently (the pipelining boundary).
    Returns ``(pilot_stage, cpu_stages)`` with
    ``pilot_stage(queries) -> (cand_id, cand_d, visited)`` and
    ``cpu_stages(queries, cand_id, cand_d, visited) -> (ids, dists)``.

    donate=True swaps in the donated variant (module docstring): the
    boundary buffers are donated via ``donate_argnums`` — consuming them in
    ``cpu_stages`` invalidates the caller's arrays — and the visited
    filter's storage is recycled through ``pilot_stage``'s donated scratch
    argument, so the steady-state serving loop stops allocating it.  The
    interface and the results are identical either way.

    Serving a mutable ``core/segments.SegmentedIndex`` (DESIGN.md §6)
    passes the deletion bitmaps as optional trailing arguments —
    ``pilot_stage(queries, pilot_tomb)`` / ``cpu_stages(..., pilot_tomb,
    tomb)`` — so deletes flow into already-compiled executables without a
    retrace (closure-captured arrays would be baked in as constants);
    omitted, the immutable traces carry no masking ops.  The index arrays
    themselves are arguments of both executables too: baked in as
    constants, a 1M-row index made a 2.9 GB TPU executable that compiled for
    minutes and exceeded the persistent compile cache's entry limit.

    shard_ctx (a ``distributed.ShardContext``) selects the pod-sharded
    variant (DESIGN.md §7): the stages become ``shard_map`` programs over
    the context's mesh — bit-identical results at every shard count — and
    the deletion bitmaps become REQUIRED trailing arguments."""
    if shard_ctx is not None:
        stages = _ShardedStages(arrays, params, shard_ctx, donate=donate)
        return stages.pilot, stages.cpu
    if donate:
        stages = _DonatedStages(arrays, params)
        return stages.pilot, stages.cpu

    n = arrays["rot_vecs"].shape[0] - 1
    nk = arrays["pilot_to_full"].shape[0] - 1
    pilot_scale = arrays.get("primary_scale")
    pilot_codebook = arrays.get("primary_codebook")
    dp = quant.primary_dim(arrays["primary"], pilot_scale,
                           codebook=pilot_codebook)

    @jax.jit
    def pilot_stage(arrays, queries, pilot_tomb=None):
        B0 = queries.shape[0]
        qpad, _ = pad_for_pallas(queries, params)
        qp = qpad[:, :dp]
        entry_ids, _ = F.fes_select_ref(
            qp, arrays["fes_centroids"], arrays["fes_entries"],
            arrays["fes_entry_ids"], arrays["fes_valid"], params.fes_L,
            entries_scale=arrays.get("fes_entries_scale"),
            entries_codebook=arrays.get("fes_entries_codebook"),
            tombstone=pilot_tomb)
        st1 = T.greedy_search(_pilot_spec(params), qp,
                              arrays["sub_neighbors"], arrays["primary"], nk,
                              entry_ids, vec_scale=pilot_scale,
                              vec_codebook=pilot_codebook,
                              tombstone=pilot_tomb)
        return st1.cand_id[:B0], st1.cand_d[:B0], st1.visited[:B0]

    @jax.jit
    def cpu_stages(arrays, queries, cand_id, cand_dp, visited,
                   pilot_tomb=None, tomb=None):
        Bq = queries.shape[0]
        arr = arrays if pilot_tomb is None else dict(
            arrays, pilot_tombstone=pilot_tomb, tombstone=tomb)
        seed_id, seed_d, _ = refine_stage(arr, params, queries,
                                          cand_id, cand_dp, visited=visited)
        spec3 = T.TraversalSpec(ef=params.ef, visited_mode=params.visited_mode,
                                bloom_bits=params.bloom_bits,
                                max_iters=params.max_iters,
                                frontier_width=params.frontier_width)
        st3 = T.greedy_search(spec3, queries, arrays["full_neighbors"],
                              arrays["rot_vecs"], n,
                              entry_ids=jnp.full((Bq, 1), n, jnp.int32),
                              extra_id=seed_id, extra_d=seed_d,
                              tombstone=tomb)
        return T.topk_from_state(st3, params.k)

    return partial(pilot_stage, arrays), partial(cpu_stages, arrays)


def degrade_params(params: SearchParams, scale: float = 0.5) -> SearchParams:
    """The low-cost rung of the serving degradation ladder (DESIGN.md §8):
    the same pipeline at ``scale``-reduced beam/frontier budget.

    Shrinks the recall/latency dials — ``ef``, ``ef_pilot``, ``fes_L`` —
    while keeping everything that defines the *result contract* (``k``,
    visited structure, kernel selection) identical, so the degraded stage
    pair is just another entry in the bucketed executable ladder: same
    shapes, same trailing tombstone arguments, precompiled by ``warmup``.
    ``ThroughputEngine`` switches to this rung per-batch when the rolling
    p99 budget is at risk instead of blowing the SLO."""
    if not 0.0 < scale <= 1.0:
        raise ValueError(f"scale must be in (0, 1], got {scale}")
    import dataclasses
    return dataclasses.replace(
        params,
        ef=max(params.k, int(params.ef * scale)),
        ef_pilot=max(params.k, int(params.ef_pilot * scale)),
        fes_L=max(4, int(params.fes_L * scale)))


def pipelined_search(arrays: Dict[str, jax.Array], params: SearchParams,
                     query_batches: List[jax.Array],
                     *, pipelined: bool = True, depth: int = 2,
                     donate: bool = False,
                     record_into: Optional[List[Dict]] = None
                     ) -> Tuple[List[Tuple[np.ndarray, np.ndarray]], float]:
    """Run a stream of query batches; returns (results, wall_seconds).

    depth: maximum batches in flight — the pilot stages of up to ``depth``
    batches are dispatched while the oldest batch's CPU stages drain
    (depth=2 reproduces the classic two-deep overlap).  With
    pipelined=False the stages of each batch run strictly in sequence
    (jax.block_until_ready between stages) — the "- pipelining" ablation.
    donate: recycle the stage-boundary buffers through ``donate_argnums``
    (see ``split_stages``; requires sublane-aligned batches on the Pallas
    paths).  record_into: optional list; one dict per batch with per-stage
    wall-clock timestamps (``t_pilot_dispatch`` / ``t_cpu_start`` /
    ``t_done``, seconds relative to the timed region's start) is appended —
    the serving runtime's per-stage accounting (DESIGN.md §5)."""
    if depth < 1:
        raise ValueError(f"depth must be >= 1, got {depth}")
    pilot_stage, cpu_stages = split_stages(arrays, params, donate=donate)

    # warmup/compile outside the timed region
    w = pilot_stage(query_batches[0])
    jax.block_until_ready(cpu_stages(query_batches[0], *w))

    results: List = [None] * len(query_batches)
    t0 = time.perf_counter()
    now = lambda: time.perf_counter() - t0

    def drain(entry):
        j, qj, poj, t_disp = entry
        t_cpu = now()
        results[j] = jax.block_until_ready(cpu_stages(qj, *poj))
        if record_into is not None:
            record_into.append({"batch": j, "t_pilot_dispatch": t_disp,
                                "t_cpu_start": t_cpu, "t_done": now()})

    if pipelined:
        inflight: deque = deque()  # (idx, queries, pilot outputs, t_dispatch)
        for i, q in enumerate(query_batches):
            po = pilot_stage(q)           # dispatched async
            inflight.append((i, q, po, now()))
            if len(inflight) >= depth:
                drain(inflight.popleft())
        while inflight:
            drain(inflight.popleft())
    else:
        for i, q in enumerate(query_batches):
            t_disp = now()
            po = jax.block_until_ready(pilot_stage(q))
            drain((i, q, po, t_disp))
    dt = time.perf_counter() - t0
    return [(np.asarray(a), np.asarray(b)) for a, b in results], dt
