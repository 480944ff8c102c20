"""Device-resident graph build & repair (DESIGN.md §9).

CAGRA-style NN-descent on the accelerator: instead of the host-side
``brute_knn`` / bucketed ``clustered_knn``, candidate k-NN lists are
seeded by one blocked brute-force matmul sweep with approximate top-K
selection (``_seed_lists``) and refined by *sample-and-merge rounds* over
fixed-width per-node lists — every round proposes neighbours-of-neighbours
plus reverse neighbours,
scores them in blocked batched matmuls (the same norms-minus-2·dot
single source of truth as ``core/traversal.sq_dists``) and merges them
into the list with a dedupe + (distance, id) top-K.  All shapes are
static, so the whole round jits once per (n, K, S) signature; the merge
step optionally routes through the fused Pallas kernel
(``kernels/build_kernel.fused_candidate_merge``), whose jnp oracle is
``kernels/ref.nn_descent_round_ref``.

The same module hosts the *device repair* primitives that
``core/segments.SegmentedIndex.insert`` uses when
``UpdateParams.repair_method`` resolves to "device":

* ``occlusion_prune_device`` — the bulk build prune: a jit'd, row-blocked
  mirror of ``graph_build.occlusion_prune`` (same candidate scan order,
  same ``occludes`` predicate, same keep-pruned backfill), used by
  ``build_graph_device`` to turn NN-descent lists into a degree-R graph.
* ``prune_batch`` — a batched ``graph_build.prune_one``: B nodes pruned
  in one fused call (stable distance sort, occluder-only candidates via
  ``edge_ok``, keep-pruned backfill), returning per-node kept-edge
  indices in the exact append order of the host primitive.  For a single
  node this is *bit-parity* with ``prune_one`` up to float-associativity
  of the pairwise distances (tests/test_graph_build_device.py pins it).

Parity contract: the integer outputs (adjacency) match the host path
whenever no occlusion comparison lands within float-rounding distance of
the ``d_kc == d_qc / alpha^2`` threshold — exact ties are measure-zero
for real data and the seeded suites never cross one.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.csr import Graph
from repro.core.graph_build import PhaseTimer, _finish_graph

BIG = 3.0e38  # +inf stand-in that survives sorts (kernels/topk_kernel.BIG)


# ---------------------------------------------------------------------------
# NN-descent (CAGRA-style sample-and-merge rounds)
# ---------------------------------------------------------------------------

def _merge_candidates(cand_ids: jax.Array, cand_d: jax.Array,
                      prop_ids: jax.Array, prop_d: jax.Array, n: int
                      ) -> Tuple[jax.Array, jax.Array]:
    """Dedupe-by-id then (distance, id) top-K merge of scored proposals
    into the incumbent lists — the jnp path is the kernel's own oracle
    (``kernels/ref.candidate_merge_ref``) so parity is by construction."""
    from repro.kernels.ref import candidate_merge_ref
    return candidate_merge_ref(cand_ids, cand_d, prop_ids, prop_d, n)


def _reverse_lists(nbr: jax.Array, n: int, S: int) -> jax.Array:
    """Fixed-width reverse-neighbour lists: for every forward edge
    i -> nbr[i, s] (< n), node nbr[i, s] receives i as a reverse
    candidate; each node keeps up to S of them (sort-by-destination +
    searchsorted slice — the device analogue of ``add_reverse_edges``'s
    rank trick).  Returns (n, S) int32 with sentinel n."""
    src = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None],
                           nbr.shape).reshape(-1)
    dst = nbr.reshape(-1)
    order = jnp.argsort(dst)                          # sentinels sort last
    dst_s = dst[order]
    src_s = src[order]
    starts = jnp.searchsorted(dst_s, jnp.arange(n, dtype=jnp.int32))
    idx = starts[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    idxc = jnp.minimum(idx, dst.shape[0] - 1)
    hit = (idx < dst.shape[0]) & \
        (dst_s[idxc] == jnp.arange(n, dtype=jnp.int32)[:, None])
    return jnp.where(hit, src_s[idxc], n)


def _score_and_merge(x_pad: jax.Array, xsq_pad: jax.Array, ids: jax.Array,
                     dd: jax.Array, used: jax.Array, props: jax.Array, *,
                     n: int, block: int, use_pallas: bool,
                     interpret: Optional[bool]
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Score the (n, P) proposals and merge them into the (n, K) lists,
    ``block`` rows at a time: the gather + batched matmul and the merge's
    sort buffers stay a few MB of live values at any corpus size.  Every
    step is row-local, so blocking does not change the result.  ``used``
    flags the incumbent entries a round has already sampled; the merged
    lists keep the flag on those entries and clear it on every other."""
    K, P = ids.shape[1], props.shape[1]
    n_pad = x_pad.shape[0] - 1
    nb = -(-n // block)
    pad = nb * block - n
    rows = jnp.concatenate([jnp.arange(n, dtype=jnp.int32),
                            jnp.zeros(pad, jnp.int32)])
    fill = lambda a, v: jnp.concatenate(
        [a, jnp.full((pad, a.shape[1]), v, a.dtype)], axis=0)

    def chunk(args):
        qi, pr, ci, cd, cu = args                 # (blk,), (blk, P), (blk, K)
        qv = x_pad[qi]
        pv = x_pad[jnp.minimum(pr, n_pad)]
        dot = jax.lax.dot_general(pv, qv[:, :, None],
                                  (((2,), (1,)), ((0,), (0,))),
                                  precision=jax.lax.Precision.HIGHEST,
                                  preferred_element_type=jnp.float32)[..., 0]
        d = xsq_pad[qi][:, None] + xsq_pad[jnp.minimum(pr, n_pad)] - 2.0 * dot
        d = jnp.where(pr >= n, BIG, jnp.maximum(d, 0.0))
        if use_pallas:
            from repro.kernels.build_kernel import fused_candidate_merge
            oi, od = fused_candidate_merge(ci, cd, pr, d, n,
                                           interpret=interpret)
        else:
            oi, od = _merge_candidates(ci, cd, pr, d, n)
        sampled = jnp.where(cu, ci, -1)
        ou = jnp.any(oi[:, :, None] == sampled[:, None, :], axis=2)
        return oi, od, ou

    oi, od, ou = jax.lax.map(chunk, (rows.reshape(nb, block),
                                     fill(props, n).reshape(nb, block, P),
                                     fill(ids, n).reshape(nb, block, K),
                                     fill(dd, BIG).reshape(nb, block, K),
                                     fill(used, False).reshape(nb, block, K)))
    flat = lambda a: a.reshape(nb * block, K)[:n]
    return flat(oi), flat(od), flat(ou)


SEED_CHUNK = 65536   # corpus rows scored per step of the seeding pass


@functools.partial(jax.jit, static_argnames=("n", "K", "block", "chunk"))
def _seed_lists(x_pad: jax.Array, xsq_pad: jax.Array, *, n: int, K: int,
                block: int, chunk: int) -> Tuple[jax.Array, jax.Array]:
    """The seeding round: each row's K nearest rows by a blocked brute
    force on the MXU — ``block`` rows against ``chunk`` corpus rows per
    step, ``lax.approx_min_k`` per step (exact on the CPU) and an exact
    merge into the running lists.  Random seeds leave NN-descent short of
    a navigable graph at scale (DEEP-like data, ef=128: recall@10 0.78 at
    1M rows from random seeds), and this pass is one matmul sweep.

    Selection runs at the default precision (bf16 inputs on the TPU's
    MXU): on DEEP-like data at 100k rows, a top-64 chosen from distances
    with bf16-rounded inputs holds 99.8% of the true 32 nearest.  The K
    kept rows are then re-scored at ``Precision.HIGHEST`` and re-sorted,
    so the lists carry exact distances into the rounds.
    Returns (ids (n, K) sentinel ``n``, d2 (n, K) with BIG on sentinels)."""
    d = x_pad.shape[1]
    nc = -(-n // chunk)
    cols = nc * chunk
    xc = jnp.concatenate([x_pad[:n], jnp.zeros((cols - n, d), x_pad.dtype)])
    xcn = jnp.concatenate([xsq_pad[:n], jnp.zeros((cols - n,), jnp.float32)])
    nb = -(-n // block)
    rows = jnp.concatenate([jnp.arange(n, dtype=jnp.int32),
                            jnp.full((nb * block - n,), n, jnp.int32)])
    lane = jnp.arange(chunk, dtype=jnp.int32)

    def row_block(qi):
        q = x_pad[qi]
        qn = xsq_pad[qi][:, None]

        def step(c, carry):
            bd, bi = carry
            xb = jax.lax.dynamic_slice_in_dim(xc, c * chunk, chunk)
            xbn = jax.lax.dynamic_slice_in_dim(xcn, c * chunk, chunk)
            dot = jax.lax.dot_general(q, xb, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            ids = c * chunk + lane
            dist = jnp.maximum(qn + xbn[None, :] - 2.0 * dot, 0.0)
            dist = jnp.where((ids[None, :] == qi[:, None])
                             | (ids[None, :] >= n), BIG, dist)
            cd, cj = jax.lax.approx_min_k(dist, K)
            md = jnp.concatenate([bd, cd], axis=1)
            mi = jnp.concatenate([bi, c * chunk + cj], axis=1)
            neg, sel = jax.lax.top_k(-md, K)
            return -neg, jnp.take_along_axis(mi, sel, axis=1)

        bd, bi = jax.lax.fori_loop(
            0, nc, step, (jnp.full((block, K), BIG, jnp.float32),
                          jnp.full((block, K), n, jnp.int32)))
        bi = jnp.where(bd >= BIG, n, bi)
        xv = x_pad[jnp.minimum(bi, n)]
        dot = jnp.einsum("bd,bkd->bk", q, xv,
                         precision=jax.lax.Precision.HIGHEST)
        dist = jnp.where(bi >= n, BIG,
                         jnp.maximum(qn + xsq_pad[bi] - 2.0 * dot, 0.0))
        neg, sel = jax.lax.top_k(-dist, K)
        return jnp.take_along_axis(bi, sel, axis=1), -neg

    ids, dd = jax.lax.map(row_block, rows.reshape(nb, block))
    return ids.reshape(nb * block, K)[:n], dd.reshape(nb * block, K)[:n]


@functools.partial(jax.jit, static_argnames=("n", "S", "block",
                                             "use_pallas", "interpret"))
def _nn_descent_round(x_pad: jax.Array, xsq_pad: jax.Array, ids: jax.Array,
                      dd: jax.Array, used: jax.Array, *, n: int, S: int,
                      block: int, use_pallas: bool, interpret: Optional[bool]
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One sample-and-merge round over (n, K) candidate lists.

    Each node samples its S nearest entries that no earlier round sampled
    (NN-descent's "new" flags, ``used``), falling back to sampled ones
    when fewer are left: sampling the S nearest every round re-joins the
    same neighbourhoods and stalls (on DEEP-like data at 100k rows, 32-NN
    recall 0.78 after 8 rounds against 0.84 with the flags).  Proposals per
    node: S*S neighbours-of-neighbours + S reverse neighbours, scored and
    merged by ``_score_and_merge`` (the jnp merge or the Pallas kernel).
    Monotone: the merged multiset contains every incumbent entry, so
    per-rank distances never increase round over round (pinned by
    test_graph_build_props.py)."""
    K = ids.shape[1]
    # unsampled entries first, each group in list (distance) order
    rank = (jnp.where(used | (ids >= n), K, 0)
            + jnp.arange(K, dtype=jnp.int32)[None, :])
    pos = jnp.argsort(rank, axis=1)[:, :S]
    nbr = jnp.take_along_axis(ids, pos, axis=1)               # (n, S)
    used = used | (rank <= jnp.take_along_axis(rank, pos[:, -1:], axis=1))
    nbr_tbl = jnp.concatenate(
        [nbr, jnp.full((1, S), n, ids.dtype)], axis=0)
    nn = nbr_tbl[jnp.minimum(nbr, n)].reshape(n, S * S)
    rev = _reverse_lists(nbr, n, S)
    props = jnp.concatenate([nn, rev], axis=1)                # (n, P)
    self_id = jnp.arange(n, dtype=props.dtype)[:, None]
    props = jnp.where(props == self_id, n, props)
    return _score_and_merge(x_pad, xsq_pad, ids, dd, used, props, n=n,
                            block=block, use_pallas=use_pallas,
                            interpret=interpret)


def nn_descent(x: np.ndarray, K: int, *, rounds: int = 8,
               S: Optional[int] = None, block: int = 1024,
               use_pallas: bool = False, interpret: Optional[bool] = None,
               clock: Optional[PhaseTimer] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Device NN-descent: approximate K-NN lists for every row of ``x``.

    Returns host (ids (n, K) int32 sentinel ``n``, d2 (n, K) float32 with
    +inf on sentinels) — drop-in for ``brute_knn``/``clustered_knn``
    output feeding ``occlusion_prune``.  The seeding round
    (``_seed_lists``) is an O(n^2 * d) matmul sweep with approximate top-K
    selection; each later round is O(n * (S^2 + S) * d) and repairs what
    the approximate selection missed.  ``clock`` laps ``knn_seed`` and
    ``knn_rounds``."""
    x = np.ascontiguousarray(x, np.float32)
    n, d = x.shape
    K = min(K, max(1, n - 1))
    S = S if S is not None else min(K, 16)
    block = max(8, min(block, n))

    x_pad = jnp.asarray(np.concatenate([x, np.zeros((1, d), np.float32)]))
    xsq_pad = jnp.sum(x_pad * x_pad, axis=-1)
    clock = clock or PhaseTimer(None)
    ids, dd = jax.block_until_ready(_seed_lists(
        x_pad, xsq_pad, n=n, K=K, block=block,
        chunk=min(SEED_CHUNK, -(-n // 128) * 128)))
    clock.lap("knn_seed")
    used = jnp.zeros(ids.shape, bool)
    for _ in range(max(0, rounds)):
        ids, dd, used = _nn_descent_round(
            x_pad, xsq_pad, ids, dd, used, n=n, S=S, block=block,
            use_pallas=use_pallas, interpret=interpret)
    ids_h = np.asarray(ids)
    dd_h = np.asarray(dd).astype(np.float32)
    dd_h = np.where(ids_h >= n, np.inf, dd_h)
    clock.lap("knn_rounds")
    return ids_h.astype(np.int32), dd_h


# ---------------------------------------------------------------------------
# Bulk occlusion prune (build-time; mirrors graph_build.occlusion_prune)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("R", "keep_pruned"))
def _occlusion_prune_block(x: jax.Array, cand_ids: jax.Array,
                           cand_d: jax.Array, n: jax.Array, alpha: jax.Array,
                           *, R: int, keep_pruned: bool) -> jax.Array:
    """One row block of ``occlusion_prune_device``: same column scan,
    same predicate, same backfill as the host version — vectorised over
    the block with a kept-vector carry instead of per-row lists."""
    B, K = cand_ids.shape
    dim = x.shape[1]
    iota_r = jnp.arange(R, dtype=jnp.int32)[None, :]

    def body(j, carry):
        kept, kept_vecs, cnt, taken = carry
        c = cand_ids[:, j]
        dj = cand_d[:, j]
        valid = (c < n) & jnp.isfinite(dj) & (cnt < R)
        cv = x[jnp.clip(c, 0, x.shape[0] - 1)]
        diff = kept_vecs - cv[:, None, :]
        d_kc = jnp.sum(diff * diff, axis=-1)                  # (B, R)
        mask_k = iota_r < cnt[:, None]
        occluded = jnp.any(
            mask_k & (d_kc < dj[:, None] / (alpha * alpha)), axis=1)
        take = valid & ~occluded
        slot = iota_r == cnt[:, None]
        put = take[:, None] & slot
        kept = jnp.where(put, c[:, None], kept)
        kept_vecs = jnp.where(put[:, :, None], cv[:, None, :], kept_vecs)
        cnt = cnt + take.astype(jnp.int32)
        taken = taken.at[:, j].set(take)
        return kept, kept_vecs, cnt, taken

    init = (jnp.full((B, R), n, jnp.int32),
            jnp.zeros((B, R, dim), jnp.float32),
            jnp.zeros((B,), jnp.int32),
            jnp.zeros((B, K), bool))
    kept, _, cnt, taken = jax.lax.fori_loop(0, K, body, init)

    if keep_pruned:
        def fill_body(j, carry):
            kept, cnt = carry
            c = cand_ids[:, j]
            fill = (~taken[:, j]) & (c < n) & jnp.isfinite(cand_d[:, j]) & \
                (cnt < R)
            put = fill[:, None] & (iota_r == cnt[:, None])
            kept = jnp.where(put, c[:, None], kept)
            return kept, cnt + fill.astype(jnp.int32)
        kept, cnt = jax.lax.fori_loop(0, K, fill_body, (kept, cnt))
    return kept


def occlusion_prune_device(x: np.ndarray, cand_ids: np.ndarray,
                           cand_d: np.ndarray, R: int, *, alpha: float = 1.2,
                           keep_pruned: bool = True,
                           block: int = 4096) -> np.ndarray:
    """Device mirror of ``graph_build.occlusion_prune`` (same scan order,
    predicate and backfill — integer-output parity pinned by
    tests/test_graph_build_props.py).  Row-blocked so one executable
    serves any corpus size at a fixed (block, K) signature."""
    n, K = cand_ids.shape
    block = max(8, min(block, n))
    xj = jnp.asarray(np.ascontiguousarray(x, np.float32))
    out = np.full((n, R), n, np.int32)
    ids_h = np.asarray(cand_ids, np.int64)
    d_h = np.asarray(cand_d, np.float32)
    for s in range(0, n, block):
        e = min(s + block, n)
        bi = np.full((block, K), n, np.int64)
        bd = np.full((block, K), np.inf, np.float32)
        bi[:e - s] = ids_h[s:e]
        bd[:e - s] = d_h[s:e]
        kept = _occlusion_prune_block(
            xj, jnp.asarray(bi.astype(np.int32)), jnp.asarray(bd),
            jnp.int32(n), jnp.float32(alpha), R=R, keep_pruned=keep_pruned)
        out[s:e] = np.asarray(kept)[:e - s]
    return out


# ---------------------------------------------------------------------------
# Batched repair prune (insert-time; mirrors graph_build.prune_one)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("R", "keep_pruned"))
def _prune_batch_jit(cand_vecs: jax.Array, cand_d: jax.Array,
                     edge_ok: jax.Array, alpha: jax.Array, *, R: int,
                     keep_pruned: bool) -> jax.Array:
    B, C, _ = cand_vecs.shape
    finite = jnp.isfinite(cand_d)
    order = jnp.argsort(cand_d, axis=1, stable=True)
    sd = jnp.take_along_axis(cand_d, order, axis=1)
    sv = jnp.take_along_axis(cand_vecs, order[:, :, None], axis=1)
    sok = jnp.take_along_axis(edge_ok, order, axis=1)
    sfin = jnp.take_along_axis(finite, order, axis=1)
    iota_c = jnp.arange(C, dtype=jnp.int32)[None, :]

    def body(t, carry):
        taken, ecnt, etaken = carry
        cv = jax.lax.dynamic_slice_in_dim(sv, t, 1, axis=1)[:, 0]
        dq = jax.lax.dynamic_index_in_dim(sd, t, axis=1, keepdims=False)
        diff = sv - cv[:, None, :]
        d_kc = jnp.sum(diff * diff, axis=-1)                  # (B, C)
        occ = jnp.any(taken & (d_kc < dq[:, None] / (alpha * alpha)), axis=1)
        fin_t = jax.lax.dynamic_index_in_dim(sfin, t, 1, keepdims=False)
        ok_t = jax.lax.dynamic_index_in_dim(sok, t, 1, keepdims=False)
        take = fin_t & (ecnt < R) & ~occ
        slot = iota_c == t
        taken = taken | (take[:, None] & slot)
        e_take = take & ok_t
        etaken = etaken | (e_take[:, None] & slot)
        return taken, ecnt + e_take.astype(jnp.int32), etaken

    init = (jnp.zeros((B, C), bool), jnp.zeros((B,), jnp.int32),
            jnp.zeros((B, C), bool))
    taken, ecnt, etaken = jax.lax.fori_loop(0, C, body, init)

    take_fill = jnp.zeros((B, C), bool)
    if keep_pruned:
        fill = (~taken) & sok & sfin
        rank = jnp.cumsum(fill.astype(jnp.int32), axis=1) - fill
        take_fill = fill & (rank < (R - ecnt)[:, None])

    # host append order: main-loop edges in scan order, then backfill
    key = jnp.where(etaken, iota_c,
                    jnp.where(take_fill, C + iota_c, 2 * C))
    sel = jnp.argsort(key, axis=1)[:, :R]
    got = jnp.take_along_axis(key, sel, axis=1) < 2 * C
    orig = jnp.take_along_axis(order, sel, axis=1)
    return jnp.where(got, orig, -1).astype(jnp.int32)


def prune_batch(cand_vecs: np.ndarray, cand_d: np.ndarray, R: int, *,
                alpha: float = 1.2, edge_ok: Optional[np.ndarray] = None,
                keep_pruned: bool = True) -> np.ndarray:
    """Batched ``graph_build.prune_one``: prune B candidate lists in one
    fused device call.  ``cand_vecs`` (B, C, d), ``cand_d`` (B, C) with
    +inf marking padded/invalid slots, ``edge_ok`` (B, C) — False rows
    join the kept set as occluders but never take an edge slot.

    Returns (B, R) int32 indices into the candidate axis in the host
    primitive's append order (scan-order keepers, then keep-pruned
    backfill), padded with -1."""
    cand_vecs = np.ascontiguousarray(cand_vecs, np.float32)
    B, C, _ = cand_vecs.shape
    ok = np.ones((B, C), bool) if edge_ok is None \
        else np.ascontiguousarray(edge_ok, bool)
    out = _prune_batch_jit(jnp.asarray(cand_vecs),
                           jnp.asarray(np.ascontiguousarray(cand_d,
                                                            np.float32)),
                           jnp.asarray(ok), jnp.float32(alpha),
                           R=R, keep_pruned=keep_pruned)
    return np.asarray(out)


def warm_prune_batch(shapes, R: int, *, keep_pruned: bool = True) -> None:
    """Precompile ``prune_batch`` executables for (B, C, d) signatures —
    called by ``SegmentedIndex.warmup`` so insert-time repair never
    compiles inside a serving window."""
    for (B, C, d) in shapes:
        prune_batch(np.zeros((B, C, d), np.float32),
                    np.full((B, C), np.inf, np.float32), R,
                    keep_pruned=keep_pruned)


def patch_reverse_edges_batched(neighbors: np.ndarray, x: np.ndarray,
                                src_ids: np.ndarray, n: int, R: int, *,
                                alpha: float = 1.2) -> np.ndarray:
    """Batched ``graph_build.patch_reverse_edges``: reverse edges for a
    whole insert batch are collected per target row first (arrival order,
    deduplicated against the row and the queue), free slots are appended
    in bulk, and every *overflowing* row is re-pruned in ONE
    ``prune_batch`` call instead of a python loop of ``prune_one``.

    For a single inserted node this is step-for-step identical to the
    host primitive.  For a batch it differs only when two or more new
    nodes overflow the *same* target row: the host path re-prunes that
    row once per arrival while this path re-prunes it once over the whole
    incoming set — the same candidate pool, so the kept rows rarely
    differ and the degree bound always holds (DESIGN.md §9)."""
    nbr_w = neighbors.shape[1]
    incoming: dict = {}
    for u in np.asarray(src_ids, np.int64):
        for v in neighbors[u]:
            v = int(v)
            if v >= n or v == u:
                continue
            row = neighbors[v]
            deg = int((row < n).sum())
            if (row[:deg] == u).any():
                continue
            q = incoming.setdefault(v, [])
            if u not in q:
                q.append(int(u))
    full = []
    for v, us in incoming.items():
        deg = int((neighbors[v] < n).sum())
        if deg + len(us) <= R:
            neighbors[v, deg:deg + len(us)] = np.asarray(us, neighbors.dtype)
        else:
            full.append((v, us, deg))
    if not full:
        return neighbors
    # one fused re-prune over every overflowing row; pad (B, C) up to
    # small rungs so the jit signature stays bounded across batches
    B = len(full)
    C = max(deg + len(us) for _, us, deg in full)
    C = -(-C // 8) * 8
    Bp = 1 << max(0, (B - 1).bit_length())
    cand = np.full((Bp, C), -1, np.int64)
    cd = np.full((Bp, C), np.inf, np.float32)
    cv = np.zeros((Bp, C, x.shape[1]), np.float32)
    for i, (v, us, deg) in enumerate(full):
        c = np.concatenate([neighbors[v][:deg], us]).astype(np.int64)
        diff = x[c] - x[v][None, :]
        cand[i, :len(c)] = c
        cd[i, :len(c)] = (diff * diff).sum(-1).astype(np.float32)
        cv[i, :len(c)] = x[c]
    kept = prune_batch(cv, cd, R, alpha=alpha)
    for i, (v, us, deg) in enumerate(full):
        sel = kept[i][kept[i] >= 0]
        new_row = np.full(nbr_w, n, neighbors.dtype)
        new_row[:len(sel)] = cand[i, sel]
        neighbors[v] = new_row
    return neighbors


# ---------------------------------------------------------------------------
# Full device build
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n", "block"))
def _merge_reverse(x_pad: jax.Array, xsq_pad: jax.Array, ids: jax.Array,
                   dd: jax.Array, kept: jax.Array, *, n: int, block: int
                   ) -> Tuple[jax.Array, jax.Array]:
    R = kept.shape[1]
    rev = _reverse_lists(kept, n, R)
    wide = lambda a, v: jnp.concatenate(
        [a, jnp.full((n, R), v, a.dtype)], axis=1)
    oi, od, _ = _score_and_merge(
        x_pad, xsq_pad, wide(ids, n), wide(dd, BIG),
        jnp.zeros((n, ids.shape[1] + R), bool), rev, n=n, block=block,
        use_pallas=False, interpret=None)
    return oi, od


def reverse_candidates(x: np.ndarray, ids: np.ndarray, dd: np.ndarray,
                       kept: np.ndarray, block: int = 1024
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Vamana's reverse-edge pass on the device: every row's (n, K) k-NN
    candidates plus up to R rows whose pruned lists ``kept`` (n, R) point
    at it, scored, deduplicated and sorted into (n, K + R) lists for the
    final prune.  Without it a pruned k-NN graph gives few in-edges to
    rows that sit in no one's k-NN list, and with full rows the host's
    ``add_reverse_edges`` finds no slot for them (DEEP-like data, exact
    lists, ef=128: recall@10 0.9748 without, 0.9957 with at 100k rows)."""
    n, d = x.shape
    x_pad = jnp.asarray(np.concatenate([x, np.zeros((1, d), np.float32)]))
    xsq_pad = jnp.sum(x_pad * x_pad, axis=-1)
    dd = np.where(ids >= n, BIG, dd).astype(np.float32)
    oi, od = _merge_reverse(x_pad, xsq_pad, jnp.asarray(ids, jnp.int32),
                            jnp.asarray(dd), jnp.asarray(kept, jnp.int32),
                            n=n, block=max(8, min(block, n)))
    oi, od = np.asarray(oi), np.asarray(od).astype(np.float32)
    return oi, np.where(oi >= n, np.inf, od)


def build_graph_device(x: np.ndarray, R: int = 32, *, alpha: float = 1.2,
                       knn_k: Optional[int] = None,
                       rounds: int = 8, reverse: bool = True,
                       repair: bool = True, use_pallas: bool = False,
                       clock: Optional[PhaseTimer] = None) -> Graph:
    """``graph_build.build_graph`` with the O(n^2) host kNN replaced by
    device NN-descent and the prune run on device: a first prune, whose
    lists give every row its reverse candidates (``reverse_candidates``),
    and the final prune over k-NN plus reverse candidates.  The host
    reverse-edge fill and the NSG-style connectivity repair follow.
    Dispatched by ``build_graph(..., method="nn_descent")``, whose step
    ``clock`` it shares."""
    x = np.ascontiguousarray(x, np.float32)
    n = x.shape[0]
    knn_k = knn_k or min(n - 1, 2 * R)
    clock = clock or PhaseTimer(None)
    ids, dd = nn_descent(x, knn_k, rounds=rounds, use_pallas=use_pallas,
                         clock=clock)
    kept = occlusion_prune_device(x, ids, dd, R, alpha=alpha,
                                  keep_pruned=False)
    ids, dd = reverse_candidates(x, ids, dd, kept)
    nb = occlusion_prune_device(x, ids, dd, R, alpha=alpha)
    clock.lap("prune")
    return _finish_graph(nb, x, R, reverse=reverse, repair=repair,
                         clock=clock)
