"""Pure-jnp oracles for the Pallas kernels (sweep-tested in tests/)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

BIG = 3.0e38  # python float, as kernels/topk_kernel.BIG


def fes_distances_ref(q_grouped: jax.Array, entries: jax.Array,
                      scale: jax.Array = None,
                      codebook: jax.Array = None) -> jax.Array:
    """(r, QC, d) x (r, C, d) -> (r, QC, C) squared euclidean, fp32.
    ``scale`` (d,): per-dim dequantization for int8 entry tables; with
    ``scale`` wider than the entry rows the entries are nibble-packed int4;
    ``codebook`` (d, m·ksub) marks PQ code entries scored by ADC LUT
    (identical formulation to the Pallas kernel: per-group LUT matmul then
    a multi-hot code matmul, so kernel/oracle parity is bit-exact)."""
    from repro.core import quant

    q = q_grouped.astype(jnp.float32)
    if codebook is not None:                       # pq: ADC via LUT matmul
        cb = codebook.astype(jnp.float32)
        cn = jnp.sum(cb * cb, axis=0)              # (m·ksub,)
        dot = jax.lax.dot_general(q, cb, (((2,), (0,)), ((), ())),
                                  precision=jax.lax.Precision.HIGHEST,
                                  preferred_element_type=jnp.float32)
        lut = cn[None, None, :] - 2.0 * dot        # (r, QC, m·ksub)
        codes = entries.astype(jnp.int32)          # (r, C, m)
        m = codes.shape[-1]
        ksub = cb.shape[1] // m
        flat = codes + ksub * jnp.arange(m, dtype=jnp.int32)
        mk_iota = jnp.arange(cb.shape[1], dtype=jnp.int32)
        hot = jnp.any(flat[..., None] == mk_iota, axis=-2)  # (r, C, m·ksub)
        qn = jnp.sum(q * q, axis=-1)[..., :, None]
        adc = jax.lax.dot_general(
            lut, hot.astype(jnp.float32),
            (((2,), (2,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)    # (r, QC, C)
        return qn + adc
    if scale is not None and entries.shape[-1] < scale.shape[0]:   # int4
        hp = entries.shape[-1]
        entries = quant.int4_unpack(entries)
        scale = jnp.pad(scale.astype(jnp.float32),
                        (0, 2 * hp - scale.shape[0]), constant_values=1.0)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 2 * hp - q.shape[-1])))
    e = entries.astype(jnp.float32)
    if scale is not None:
        e = e * scale.astype(jnp.float32)
    qn = jnp.sum(q * q, axis=-1)[..., :, None]
    en = jnp.sum(e * e, axis=-1)[..., None, :]
    dot = jnp.einsum("rqd,rcd->rqc", q, e,
                     precision=jax.lax.Precision.HIGHEST)
    return qn + en - 2.0 * dot


def _pilot_oracle_operands(q, vec_table, vec_scale, vec_codebook):
    """Mirror of traversal_kernel._encoding_operands for the jnp oracles:
    returns ``(q, vec_table, vec_scale, lut)`` with q/scale padded to the
    same widths the kernel pads to (so the fp32 reduction trees match and
    kernel/oracle parity stays bit-exact).  ``lut`` is the per-query PQ ADC
    table (None for the dense/int4 encodings)."""
    from repro.core import quant

    qf = q.astype(jnp.float32)
    if vec_codebook is not None:                   # pq
        dp8 = -(-qf.shape[1] // 8) * 8
        if dp8 != qf.shape[1]:
            qf = jnp.pad(qf, ((0, 0), (0, dp8 - qf.shape[1])))
        cb = vec_codebook.astype(jnp.float32)
        if cb.shape[0] != dp8:
            cb = jnp.pad(cb, ((0, dp8 - cb.shape[0]), (0, 0)))
        cn = jnp.sum(cb * cb, axis=0)
        lut = cn[None, :] - 2.0 * jax.lax.dot_general(
            qf, cb, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        return qf, vec_table, None, lut
    if vec_scale is not None and vec_table.shape[1] < vec_scale.shape[0]:
        hp = vec_table.shape[1]                    # int4: unpack to 2·hp
        qf = jnp.pad(qf, ((0, 0), (0, 2 * hp - qf.shape[1])))
        vec_table = quant.int4_unpack(vec_table)
        vec_scale = jnp.pad(vec_scale.astype(jnp.float32),
                            (0, 2 * hp - vec_scale.shape[0]),
                            constant_values=1.0)
    return qf, vec_table, vec_scale, None


def traversal_hop_ref(q, nbr_table, vec_table, beam_id, beam_d, beam_ck,
                      visited, n: int, *, width: int = 1,
                      visited_mode: str = "bloom", vec_scale=None,
                      vec_codebook=None):
    """Oracle for fused_traversal_hop: one full W-wide expansion round in
    pure jnp (top-W frontier select, gather, sequential-per-frontier visited
    filter, distances, stable beam merge).  ``vec_scale`` (d,): per-dim
    dequantization for int8 vector tables (bf16 needs none — the fp32 cast
    below widens it exactly); int4 tables are detected by their packed width
    and unpacked here; ``vec_codebook`` marks a PQ code table scored by ADC
    LUT lookups in the kernel's exact accumulation order.
    Returns (new_id, new_d, new_ck, new_visited, fresh) with fresh (B, W·R)."""
    from repro.core import bloom as B

    q, vec_table, vec_scale, lut = _pilot_oracle_operands(
        q, vec_table, vec_scale, vec_codebook)
    Bq, ef = beam_id.shape
    unchecked = ~beam_ck & (beam_id < n)
    cum = jnp.cumsum(unchecked.astype(jnp.int32), axis=1)
    sel = unchecked & (cum <= width)
    checked = beam_ck | sel

    test = B.bloom_test if visited_mode == "bloom" else B.exact_test
    ins = B.bloom_insert if visited_mode == "bloom" else B.exact_insert
    nbrs_w, fresh_w = [], []
    for w in range(width):
        mask_w = sel & (cum == w + 1)
        u_w = jnp.where(jnp.any(mask_w, axis=1),
                        jnp.sum(jnp.where(mask_w, beam_id, 0), axis=1), n)
        nw = nbr_table[u_w]                               # (B, R)
        vw = nw < n
        seen = test(visited, jnp.where(vw, nw, 0))
        fw = vw & ~seen
        visited = ins(visited, jnp.where(vw, nw, 0), fw)
        nbrs_w.append(nw)
        fresh_w.append(fw)
    nbrs = jnp.concatenate(nbrs_w, axis=1)                # (B, W·R)
    fresh = jnp.concatenate(fresh_w, axis=1)

    qf = q.astype(jnp.float32)
    qn = jnp.sum(qf * qf, axis=-1)[:, None]
    if lut is not None:                                   # pq: ADC lookups
        codes = vec_table[nbrs].astype(jnp.int32)         # (B, W·R, m)
        m = codes.shape[-1]
        ksub = lut.shape[1] // m
        acc = jnp.broadcast_to(qn, fresh.shape)
        for sub in range(m):                              # kernel's fixed
            idx = ksub * sub + codes[..., sub]            # subspace order
            acc = acc + jnp.take_along_axis(lut, idx, axis=1)
        d = jnp.maximum(acc, 0.0)
    else:
        nv = vec_table[nbrs].astype(jnp.float32)          # (B, W·R, d)
        if vec_scale is not None:
            nv = nv * vec_scale.astype(jnp.float32)
        vn = jnp.sum(nv * nv, axis=-1)
        dot = jnp.einsum("bd,brd->br", qf, nv,
                     precision=jax.lax.Precision.HIGHEST)
        d = jnp.maximum(qn + vn - 2.0 * dot, 0.0)
    d = jnp.where(fresh, d, jnp.inf)

    all_id = jnp.concatenate([beam_id, jnp.where(fresh, nbrs, n)], axis=1)
    all_d = jnp.concatenate([beam_d, d], axis=1)
    all_ck = jnp.concatenate([checked, ~fresh], axis=1)
    order = jnp.argsort(all_d, axis=1)[:, :ef]
    return (jnp.take_along_axis(all_id, order, axis=1),
            jnp.take_along_axis(all_d, order, axis=1),
            jnp.take_along_axis(all_ck, order, axis=1),
            visited, fresh)


def pilot_search_ref(q, nbr_table, vec_table, beam_id, beam_d, beam_ck,
                     visited, n: int, *, rounds: int, width: int = 1,
                     visited_mode: str = "bloom", vec_scale=None,
                     vec_codebook=None):
    """Oracle for fused_pilot_search: run up to ``rounds`` W-wide expansion
    rounds (stopping at convergence) by iterating traversal_hop_ref.
    Returns (beam_id, beam_d, beam_ck, visited, n_dist, n_hops, n_exp) with
    the counters as (B,) int32 deltas, like the persistent kernel."""
    Bq = beam_id.shape[0]
    nd = nh = ne = jnp.zeros((Bq,), jnp.int32)
    for _ in range(rounds):
        unchecked = ~beam_ck & (beam_id < n)
        if not bool(jnp.any(unchecked)):
            break
        has_work = jnp.any(unchecked, axis=1)
        cum = jnp.cumsum(unchecked.astype(jnp.int32), axis=1)
        n_sel = jnp.sum((unchecked & (cum <= width)).astype(jnp.int32), axis=1)
        beam_id, beam_d, beam_ck, visited, fresh = traversal_hop_ref(
            q, nbr_table, vec_table, beam_id, beam_d, beam_ck, visited, n,
            width=width, visited_mode=visited_mode, vec_scale=vec_scale,
            vec_codebook=vec_codebook)
        nd = nd + jnp.sum(fresh.astype(jnp.int32), axis=1)
        nh = nh + has_work.astype(jnp.int32)
        ne = ne + n_sel
    return beam_id, beam_d, beam_ck, visited, nd, nh, ne


def candidate_merge_ref(cand_ids, cand_d, prop_ids, prop_d, n: int):
    """Oracle for build_kernel.fused_candidate_merge — one NN-descent
    sample-and-merge step (DESIGN.md §9): concatenate the incumbent
    (B, K) candidate lists with (B, P) scored proposals, drop ids >= n,
    dedupe by id (keeping the smallest-distance copy), and return the
    (distance, id) top-K.  Sentinel slots come back as id ``n`` with
    distance BIG.  Also the production jnp merge used by
    ``core/device_build.nn_descent`` when the Pallas path is off."""
    K = cand_ids.shape[1]
    all_ids = jnp.concatenate([cand_ids, prop_ids], axis=1)
    all_d = jnp.concatenate([cand_d, prop_d], axis=1)
    bad = all_ids >= n
    all_d = jnp.where(bad, BIG, all_d)
    all_ids = jnp.where(bad, n, all_ids)
    perm = jnp.lexsort((all_d, all_ids))              # primary id, then d
    sid = jnp.take_along_axis(all_ids, perm, axis=1)
    sd = jnp.take_along_axis(all_d, perm, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((sid.shape[0], 1), bool), sid[:, 1:] == sid[:, :-1]],
        axis=1)
    bad = dup | (sid >= n)
    sd = jnp.where(bad, BIG, sd)
    sid = jnp.where(bad, n, sid)
    perm2 = jnp.lexsort((sid, sd))[:, :K]             # primary d, tie by id
    return (jnp.take_along_axis(sid, perm2, axis=1),
            jnp.take_along_axis(sd, perm2, axis=1))


def expand_merge_ref(q, nvecs, nids, fresh, beam_id, beam_d, beam_ck, n: int):
    """Oracle for fused_expand_merge: score fresh neighbours, merge into the
    sorted beam, return (ids, dists, checked) (B, ef)."""
    ef = beam_id.shape[1]
    qf = q.astype(jnp.float32)
    nv = nvecs.astype(jnp.float32)
    qn = jnp.sum(qf * qf, axis=-1)[:, None]
    vn = jnp.sum(nv * nv, axis=-1)
    dot = jnp.einsum("bd,brd->br", qf, nv,
                     precision=jax.lax.Precision.HIGHEST)
    d = jnp.maximum(qn + vn - 2.0 * dot, 0.0)
    d = jnp.where(fresh, d, BIG)

    all_d = jnp.concatenate([beam_d, d], axis=1)
    all_id = jnp.concatenate([beam_id, jnp.where(fresh, nids, n)], axis=1)
    all_ck = jnp.concatenate([beam_ck, ~fresh], axis=1)
    # sort by (d, id) to match the kernel's deterministic tie-break
    order = jnp.lexsort((all_id, all_d))
    take = order[:, :ef]
    return (jnp.take_along_axis(all_id, take, axis=1),
            jnp.take_along_axis(all_d, take, axis=1),
            jnp.take_along_axis(all_ck, take, axis=1))
