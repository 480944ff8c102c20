"""Batched greedy graph traversal (Algorithm 1 of the paper) in pure JAX.

TPU adaptation (DESIGN.md §2): instead of the GPU's thread-per-candidate
dynamic traversal, a *query batch* advances one neighbour-expansion round per
step — every op is dense and fixed-shape, so the same code runs under jit on
CPU (reference engine), vectorises on TPU, and lowers on the production mesh
(distributed engine).  The candidate list is a sorted (B, ef) beam; visited
tracking is a bloom filter (paper §4.3) or an exact bitmap.  Rounds are
W-wide (spec.frontier_width): the top-W unchecked beam entries expand
together, scoring up to W·R neighbours in one (B, W·R, d) MXU-dense block —
the CAGRA-style lever that trades a few extra distance computations for a
~W× cut in rounds-to-convergence (serial depth).

The traversal returns per-query distance-computation counts — the unit in
which the paper reports all of its complexity results (Tables 1–2, Fig. 3–4).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import bloom as B

INF = jnp.float32(jnp.inf)


class SearchState(NamedTuple):
    cand_id: jax.Array   # (B, ef) int32, sorted by distance; sentinel = n
    cand_d: jax.Array    # (B, ef) float32
    checked: jax.Array   # (B, ef) bool
    visited: jax.Array   # (B, n_bits/n) bool filter
    n_dist: jax.Array    # (B,) int32 distance-computation counter
    n_hops: jax.Array    # (B,) int32 expansion *rounds* with work
    n_exp: jax.Array     # (B,) int32 candidates actually expanded
                         # (== n_hops at frontier_width=1)


@dataclass(frozen=True)
class TraversalSpec:
    ef: int
    visited_mode: str = "bloom"      # bloom | exact
    bloom_bits: int = 16384
    max_iters: int = 512
    # multi-frontier expansion: expand the top-W unchecked beam entries per
    # round, scoring up to W·R neighbours in one (B, W·R, dp) distance block.
    # W=1 is bit-identical to the classic single-frontier round.
    frontier_width: int = 1
    # distributed engines pin the per-query state (beam, visited bitset) to
    # the query sharding and use the scatter-free bloom update: the scatter
    # form partitions as replicated-operand + all-reduce(OR) — gigabytes per
    # expansion round
    state_spec: Optional[object] = None
    dense_visited_update: bool = False
    # fused Pallas hop (kernels/traversal_kernel.py, DESIGN.md §3): one
    # kernel per expansion round instead of the op-by-op body below.  The
    # kernel is compiled on an accelerator and interpreted on the CPU
    # (kernels/backend.resolve_interpret).
    use_pallas: bool = False
    # persistent stage-① kernel (kernels/traversal_kernel.fused_pilot_search):
    # the whole search — frontier selection, gather, visited filter,
    # distances, merge, convergence — runs inside ONE pallas_call with a
    # while_loop over hops, so beam/visited/counters stay in VMEM for the
    # whole search.  Requires use_pallas; falls back to per-hop kernels when
    # custom nbr_fn/dist_fn hooks are injected or unroll is requested.
    use_persistent: bool = False


def sentinel_mask(tombstone: jax.Array, ids: jax.Array, n: int) -> jax.Array:
    """Sentinel-mask tombstoned ids (DESIGN.md §6): every id whose bit is
    set in the ``(n+1,)`` tombstone bitmap becomes the sentinel ``n``
    (dtype-preserving, so int16 pilot tables stay int16).  Applied to the
    adjacency table this prunes every edge INTO a deleted node — deleted
    nodes keep their out-edges but stop being scored, entering beams, or
    surfacing in results.  With an all-false bitmap ``where`` is the
    identity, which is what keeps the zero-tombstone paths bit-exact."""
    t = tombstone[jnp.clip(ids, 0, tombstone.shape[0] - 1)]
    return jnp.where(t, jnp.asarray(n, ids.dtype), ids)


def sq_dists(q: jax.Array, vecs: jax.Array) -> jax.Array:
    """q: (B, d); vecs: (B, R, d) — or (m, d) shared across the batch —
    -> (B, R) / (B, m) squared euclidean, fp32.

    Formulated as norms - 2·dot so the contraction is a matmul (MXU-dense on
    TPU; the FES kernel uses the same identity with cluster tiling).  This is
    the single source of truth for the norms-minus-2dot identity; callers
    (stage ② re-rank, coarse entry layer) reuse it instead of open-coding.
    The contraction names ``Precision.HIGHEST``.  A shared-table matmul
    runs on the MXU, which at the default precision rounds f32 inputs to
    bf16 (a one-hot id gather that way was off by up to 511 on a v5e).
    The batched form measured no such error on a v5e (stage-③ distances
    within 2.92e-7 of the norms at the default precision, 3.25e-7 at
    HIGHEST), so there the setting states what stages ②/③ need rather
    than repairs a measured error."""
    q = q.astype(jnp.float32)
    vecs = vecs.astype(jnp.float32)
    qn = jnp.sum(q * q, axis=-1)[:, None]
    vn = jnp.sum(vecs * vecs, axis=-1)
    hi = jax.lax.Precision.HIGHEST
    if vecs.ndim == 2:                     # one shared (m, d) table
        dot = jnp.matmul(q, vecs.T, precision=hi)
        return jnp.maximum(qn + vn[None, :] - 2.0 * dot, 0.0)
    dot = jnp.einsum("bd,brd->br", q, vecs, precision=hi)
    return jnp.maximum(qn + vn - 2.0 * dot, 0.0)


def _visited_init(spec: TraversalSpec, batch: int, n: int) -> jax.Array:
    if spec.visited_mode == "bloom":
        return B.bloom_init(batch, spec.bloom_bits)
    return B.exact_init(batch, n)


def _visited_test(spec: TraversalSpec, filt, ids):
    return (B.bloom_test if spec.visited_mode == "bloom" else B.exact_test)(filt, ids)


def _visited_insert(spec: TraversalSpec, filt, ids, mask):
    if spec.visited_mode != "bloom":
        return B.exact_insert(filt, ids, mask)
    fn = B.bloom_insert_dense if spec.dense_visited_update else B.bloom_insert
    return fn(filt, ids, mask)


def init_state(spec: TraversalSpec, queries: jax.Array, entry_ids: jax.Array,
               vectors: jax.Array, n: int,
               visited: Optional[jax.Array] = None,
               extra_id: Optional[jax.Array] = None,
               extra_d: Optional[jax.Array] = None,
               vec_scale: Optional[jax.Array] = None,
               vec_codebook: Optional[jax.Array] = None) -> SearchState:
    """Build the initial beam from entry points (+ optionally pre-scored
    candidates handed over from an earlier stage).  ``vec_scale``: per-dim
    dequantization scale for int8/int4 vector tables; ``vec_codebook``:
    PQ codebook (core/quant.py).  ``decode_rows`` is the identity for exact
    tables, so the fp32/bf16 paths stay bit-exact."""
    from repro.core import quant

    Bq, E = entry_ids.shape
    valid = entry_ids < n
    table = jnp.concatenate([vectors, jnp.zeros((1, vectors.shape[1]),
                                                vectors.dtype)], axis=0)
    evecs = quant.decode_rows(table[entry_ids], vec_scale,   # (B, E, d)
                              codebook=vec_codebook)
    d = jnp.where(valid, sq_dists(queries, evecs), INF)
    n_dist = jnp.sum(valid, axis=1).astype(jnp.int32)
    if extra_id is not None:
        entry_ids = jnp.concatenate([extra_id, entry_ids], axis=1)
        d = jnp.concatenate([extra_d, d], axis=1)
        valid = jnp.concatenate([extra_id < n, valid], axis=1)

    # dedupe identical ids (keep best distance): sort by (id, d), mask repeats
    order = jnp.lexsort((d, entry_ids))
    sid = jnp.take_along_axis(entry_ids, order, axis=1)
    sd = jnp.take_along_axis(d, order, axis=1)
    dup = jnp.concatenate([jnp.zeros((Bq, 1), bool), sid[:, 1:] == sid[:, :-1]],
                          axis=1)
    sd = jnp.where(dup, INF, sd)
    sid = jnp.where(dup, n, sid)

    # sort by distance, pad/trim to ef
    k = spec.ef
    order = jnp.argsort(sd, axis=1)
    sid = jnp.take_along_axis(sid, order, axis=1)
    sd = jnp.take_along_axis(sd, order, axis=1)
    if sid.shape[1] >= k:
        cand_id, cand_d = sid[:, :k], sd[:, :k]
    else:
        pad = k - sid.shape[1]
        cand_id = jnp.pad(sid, ((0, 0), (0, pad)), constant_values=n)
        cand_d = jnp.pad(sd, ((0, 0), (0, pad)), constant_values=jnp.inf)

    filt = visited if visited is not None else _visited_init(spec, Bq, n)
    filt = _visited_insert(spec, filt, jnp.where(cand_id < n, cand_id, 0),
                           cand_id < n)
    return SearchState(cand_id=cand_id.astype(jnp.int32), cand_d=cand_d,
                       checked=cand_id >= n, visited=filt,
                       n_dist=n_dist, n_hops=jnp.zeros((Bq,), jnp.int32),
                       n_exp=jnp.zeros((Bq,), jnp.int32))


def expansion_round(spec: TraversalSpec, state: SearchState, queries: jax.Array,
                    neighbor_table: jax.Array, vector_table: jax.Array,
                    n: int, nbr_fn=None, dist_fn=None,
                    vec_scale: Optional[jax.Array] = None,
                    vec_codebook: Optional[jax.Array] = None) -> SearchState:
    """One synchronous W-wide neighbour-expansion round for the whole batch.

    The top ``W = spec.frontier_width`` unchecked beam entries are expanded
    together: their up-to W·R neighbours are scored in a single
    ``(B, W·R, d)`` distance block (one MXU-dense matmul) and merged into the
    beam in one ``ef + W·R``-wide stable sort.  Visited filtering is
    *sequential per frontier* — frontier ``w`` is tested against the filter
    including frontiers ``< w``'s inserts — so a node reachable from two
    frontiers in the same round is scored once, exactly as if the frontiers
    had been expanded in consecutive single-frontier rounds.  W=1 therefore
    reduces bit-identically to the classic one-candidate round.

    ``nbr_fn(u) -> (B, R)`` (called once per frontier) and
    ``dist_fn(queries, ids, fresh) -> ids.shape`` override the table lookups —
    the distributed engine injects shard_map versions that fetch/score corpus
    rows shard-side (perf: 'shardwise').  ``vec_scale``: per-dim int8
    dequantization scale for quantized vector tables (core/quant.py);
    bfloat16 tables need no scale (sq_dists widens exactly)."""
    Bq, ef = state.cand_id.shape
    R = neighbor_table.shape[1]
    W = spec.frontier_width

    if spec.use_pallas and nbr_fn is None and dist_fn is None:
        return _pallas_round(spec, state, queries, neighbor_table,
                             vector_table, n, vec_scale=vec_scale,
                             vec_codebook=vec_codebook)

    # top-W unchecked candidates per query: the beam is distance-sorted, so
    # the first W unchecked slots are the W best (rows with none stay idle)
    unchecked = ~state.checked & (state.cand_id < n)
    has_work = jnp.any(unchecked, axis=1)
    cum = jnp.cumsum(unchecked.astype(jnp.int32), axis=1)
    sel = unchecked & (cum <= W)
    checked = state.checked | sel
    n_exp = state.n_exp + jnp.sum(sel, axis=1).astype(jnp.int32)

    visited = state.visited
    nbrs_w, fresh_w = [], []
    for w in range(W):
        mask_w = sel & (cum == w + 1)                     # w-th frontier slot
        u_w = jnp.where(jnp.any(mask_w, axis=1),
                        jnp.sum(jnp.where(mask_w, state.cand_id, 0), axis=1),
                        n)
        nw = (neighbor_table[u_w] if nbr_fn is None else nbr_fn(u_w))  # (B, R)
        vw = nw < n
        seen = _visited_test(spec, visited, jnp.where(vw, nw, 0))
        fw = vw & ~seen
        visited = _visited_insert(spec, visited, jnp.where(vw, nw, 0), fw)
        nbrs_w.append(nw)
        fresh_w.append(fw)
    nbrs = nbrs_w[0] if W == 1 else jnp.concatenate(nbrs_w, axis=1)  # (B, W·R)
    fresh = fresh_w[0] if W == 1 else jnp.concatenate(fresh_w, axis=1)

    if dist_fn is None:
        from repro.core import quant
        nvecs = quant.decode_rows(vector_table[nbrs], vec_scale,
                                  codebook=vec_codebook)       # (B, W·R, d)
        d = jnp.where(fresh, sq_dists(queries, nvecs), INF)
    else:
        d = jnp.where(fresh, dist_fn(queries, nbrs, fresh), INF)
    n_dist = state.n_dist + jnp.sum(fresh, axis=1).astype(jnp.int32)
    if spec.state_spec is not None:
        visited = lax.with_sharding_constraint(visited, spec.state_spec)

    # merge beam with fresh neighbours (stable: ties keep beam-first order)
    all_id = jnp.concatenate([state.cand_id, jnp.where(fresh, nbrs, n)], axis=1)
    all_d = jnp.concatenate([state.cand_d, d], axis=1)
    all_ck = jnp.concatenate([checked, ~fresh], axis=1)
    order = jnp.argsort(all_d, axis=1)[:, :ef]
    new_id = jnp.take_along_axis(all_id, order, axis=1)
    new_d = jnp.take_along_axis(all_d, order, axis=1)
    new_ck = jnp.take_along_axis(all_ck, order, axis=1)
    if spec.state_spec is not None:
        new_id = lax.with_sharding_constraint(new_id, spec.state_spec)
        new_d = lax.with_sharding_constraint(new_d, spec.state_spec)
    return SearchState(
        cand_id=new_id,
        cand_d=new_d,
        checked=new_ck,
        visited=visited,
        n_dist=n_dist,
        n_hops=state.n_hops + has_work.astype(jnp.int32),
        n_exp=n_exp,
    )


def _pallas_round(spec: TraversalSpec, state: SearchState, queries: jax.Array,
                  neighbor_table: jax.Array, vector_table: jax.Array,
                  n: int, vec_scale: Optional[jax.Array] = None,
                  vec_codebook: Optional[jax.Array] = None) -> SearchState:
    """Fused expansion round: the whole W-wide hop body runs as one Pallas
    kernel (frontier selection + gather + visited filter + MXU distances +
    bitonic beam merge); only the counters are maintained here (cheap
    (B, ef)/(B, W·R) reductions)."""
    from repro.kernels.traversal_kernel import fused_traversal_hop

    unchecked = ~state.checked & (state.cand_id < n)
    has_work = jnp.any(unchecked, axis=1)
    cum = jnp.cumsum(unchecked.astype(jnp.int32), axis=1)
    n_sel = jnp.sum(unchecked & (cum <= spec.frontier_width),
                    axis=1).astype(jnp.int32)
    new_id, new_d, new_ck, visited, fresh = fused_traversal_hop(
        queries, neighbor_table, vector_table, state.cand_id, state.cand_d,
        state.checked, state.visited, n, width=spec.frontier_width,
        visited_mode=spec.visited_mode, vec_scale=vec_scale,
        vec_codebook=vec_codebook)
    return SearchState(
        cand_id=new_id,
        cand_d=new_d,
        checked=new_ck,
        visited=visited,
        n_dist=state.n_dist + jnp.sum(fresh, axis=1).astype(jnp.int32),
        n_hops=state.n_hops + has_work.astype(jnp.int32),
        n_exp=state.n_exp + n_sel,
    )


def greedy_search(spec: TraversalSpec, queries: jax.Array,
                  neighbor_table: jax.Array, vector_table: jax.Array, n: int,
                  entry_ids: jax.Array, *,
                  iters: Optional[int] = None,
                  unroll: bool = False,
                  visited: Optional[jax.Array] = None,
                  extra_id: Optional[jax.Array] = None,
                  extra_d: Optional[jax.Array] = None,
                  nbr_fn=None, dist_fn=None,
                  vec_scale: Optional[jax.Array] = None,
                  vec_codebook: Optional[jax.Array] = None,
                  tombstone: Optional[jax.Array] = None) -> SearchState:
    """Greedy best-first search (Algorithm 1), batched, W-wide per round
    (spec.frontier_width).

    neighbor_table: (n+1, R) padded adjacency (row n = sentinel row).
    vector_table:   (n+1, d) vectors with zero row at n.  May be stored
    bfloat16, int8, nibble-packed int4 or PQ codes (core/quant.py); pass the
    per-dim ``vec_scale`` for int8/int4 and ``vec_codebook`` for pq so
    distances dequantize (the fused kernels dequantize / ADC-score in VMEM).
    tombstone: optional (n+1,) bool deletion bitmap (DESIGN.md §6) —
    tombstoned ids are sentinel-masked out of the adjacency, the entry set
    and the handed-over beam before the search starts, so they are never
    scored and never surface; the hop bodies (jnp and Pallas alike) run
    unchanged, and an all-false bitmap is bit-exact with ``None``.
    iters: if given, runs a fixed number of rounds (stage-② refinement and
    the distributed serving step use this); otherwise runs to convergence
    (no unchecked candidate anywhere) with spec.max_iters as a safety bound.
    unroll: emit the fixed rounds as straight-line HLO instead of a while
    loop — the dry-run uses this so cost_analysis()/collective parsing see
    every round (XLA does not scale loop-body costs by trip count).
    With spec.use_persistent (and no hooks/unroll) the entire hop loop runs
    inside one persistent Pallas kernel instead (DESIGN.md §3) — results
    are identical either way.
    """
    if tombstone is not None:
        neighbor_table = sentinel_mask(tombstone, neighbor_table, n)
        entry_ids = sentinel_mask(tombstone, entry_ids, n)
        if extra_id is not None:
            dead = tombstone[jnp.clip(extra_id, 0, n)]
            extra_id = jnp.where(dead, n, extra_id)
            extra_d = jnp.where(dead, INF, extra_d)
    state = init_state(spec, queries, entry_ids, vector_table[:-1], n,
                       visited=visited, extra_id=extra_id, extra_d=extra_d,
                       vec_scale=vec_scale, vec_codebook=vec_codebook)

    if spec.use_pallas and nbr_fn is None and dist_fn is None:
        # hoist the kernel's row-alignment padding out of the hop loop: with
        # pre-aligned tables the per-round fused_traversal_hop pad is a no-op
        # instead of an O(n·d) copy per expansion round
        from repro.kernels.traversal_kernel import align_tables
        neighbor_table, vector_table = align_tables(neighbor_table,
                                                    vector_table, n)

        if spec.use_persistent and not unroll:
            # persistent stage-① kernel: the whole search (hop loop included)
            # is ONE pallas_call — beam/visited/counters never leave VMEM.
            # Convergence is handled inside the kernel; a converged round is
            # a fixed point, so a fixed `iters` budget and run-to-convergence
            # agree with the per-hop path exactly.
            from repro.kernels.traversal_kernel import fused_pilot_search
            rounds = iters if iters is not None else spec.max_iters
            nid, nd, nck, nvis, d_dist, d_hops, d_exp = fused_pilot_search(
                queries, neighbor_table, vector_table, state.cand_id,
                state.cand_d, state.checked, state.visited, n,
                rounds=rounds, width=spec.frontier_width,
                visited_mode=spec.visited_mode, vec_scale=vec_scale,
                vec_codebook=vec_codebook)
            return SearchState(cand_id=nid, cand_d=nd, checked=nck,
                               visited=nvis, n_dist=state.n_dist + d_dist,
                               n_hops=state.n_hops + d_hops,
                               n_exp=state.n_exp + d_exp)

    round_fn = partial(expansion_round, spec, queries=queries,
                       neighbor_table=neighbor_table,
                       vector_table=vector_table, n=n,
                       nbr_fn=nbr_fn, dist_fn=dist_fn, vec_scale=vec_scale,
                       vec_codebook=vec_codebook)

    if iters is not None and unroll:
        for _ in range(iters):
            state = round_fn(state)
        return state
    if iters is not None:
        return lax.fori_loop(0, iters, lambda i, s: round_fn(s), state)

    def cond(carry):
        i, s = carry
        work = jnp.any(~s.checked & (s.cand_id < n))
        return work & (i < spec.max_iters)

    def body(carry):
        i, s = carry
        return i + 1, round_fn(s)

    _, state = lax.while_loop(cond, body, (jnp.int32(0), state))
    return state


def topk_from_state(state: SearchState, k: int) -> Tuple[jax.Array, jax.Array]:
    return state.cand_id[:, :k], state.cand_d[:, :k]
