"""Pallas TPU kernels for stage-① pilot traversal: fused W-wide expansion
hops and the persistent whole-search kernel.

The unfused hop body (``core.traversal.expansion_round``) round-trips four
intermediates through HBM per expansion round: the gathered neighbour ids,
the gathered neighbour vectors, the (B, W·R) distance block, and the
(B, ef+W·R) merge buffer.  Two fusion levels fix that (DESIGN.md §3):

* ``fused_traversal_hop`` — one ``pallas_call`` per expansion round: frontier
  selection (top-W unchecked beam entries), neighbour gather, visited
  filtering, MXU distances and the sorted-beam merge all run in VMEM; only
  the beam/visited state crosses HBM between rounds.
* ``fused_pilot_search`` — the *persistent* kernel: the entire search runs
  inside ONE ``pallas_call`` with a ``lax.while_loop`` over hops, so the
  beam, visited filter and counters stay VMEM-resident for the whole search
  and the convergence check happens on-chip.  A converged round is a fixed
  point (sentinel frontier → sentinel gathers → no fresh → stable re-sort of
  a sorted beam), which is what makes the in-kernel early exit agree exactly
  with the per-hop path under both fixed budgets and run-to-convergence.

TPU adaptation notes (DESIGN.md §3 spells out the full contract):
  * gathers are *one-hot matmuls*: ``onehot(u) @ table`` runs on the MXU
    where a dynamic row gather from VMEM does not lower.  The matmul runs at
    ``Precision.HIGHEST``: at the TPU's default precision its operands are
    rounded to bf16, which holds integers exactly only up to 256.  It needs
    node ids that are fp32-exact (n < 2**24), and both tables whole in VMEM
    next to (bt, Npad) one-hot transients, so ``VMEM_LIMIT_BYTES`` — not
    HBM — bounds the pilot these kernels serve (DESIGN.md §3 gives the
    largest pilot that compiles for a v5e; above it the compiler's refusal
    propagates).
  * Mosaic lowers no ``rev`` or ``cumsum`` and no boolean vector crossing
    memory: the bitonic lane exchange is two ``pltpu.roll`` calls and a
    select, frontier selection takes the lowest unchecked lane W times, and
    flags cross the kernel boundary as int32 0/1.
  * the visited structure (bloom filter or exact bitmap) is updated with the
    scatter-free one-hot form of ``core.bloom.bloom_insert_dense``, looped
    over the neighbour slots so the transient stays (bt, n_bits).  Frontiers
    are filtered *sequentially* (frontier w tests against frontiers < w's
    inserts), matching the unfused multi-frontier round exactly.
  * the beam merge uses a *stable* bitonic compare-exchange network (same
    static schedule as ``topk_kernel``'s, plus a position payload for
    tie-breaks) so the fused merge matches the unfused path's stable
    argsort exactly, ties included — at any frontier width.
  * masked distances use BIG (3e38), not +inf, inside the sort; the wrapper
    maps +inf <-> BIG at the boundary so callers keep the +inf convention.

Both host wrappers are jit-safe: they pad the query batch to the tile size,
table rows to the sublane multiple (sentinel rows, id = n), and the visited
lanes to 128, then slice everything back.

Quantized pilot payloads (DESIGN.md §4): the vector table may be stored
bfloat16, int8, nibble-packed int4 or PQ codes (``core/quant.py``).  The
*dense* encodings share one path: a per-dimension fp32 scale operand
dequantizes the table in VMEM once per invocation
(``vec = vec.astype(f32) * scale``; all-ones for exact tables, which is
bit-exact).  ``int4`` adds an in-VMEM nibble unpack before the same
multiply (two dims per int8 lane, plane-packed so the unpack is a lane
concatenation).  ``pq`` replaces the MXU dot-product distances entirely:
the kernel builds a per-query ADC lookup table
(``lut = ‖c‖² − 2·q @ codebook``) once per invocation, one-hot-gathers each
neighbour's *code row* (m bytes instead of d floats) and accumulates
``qn + Σ_s lut[s·ksub + code_s]`` with one-hot LUT gathers.  The static
``vec_encoding`` parameter selects the path at trace time.  Neighbour
tables may be int16 (compact pilot id space) — the one-hot gather converts
ids to fp32 either way.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret
from repro.kernels.topk_kernel import BIG, _next_pow2, bitonic_sort


def _bloom_hashes(ids: jax.Array, n_bits: int):
    """core.bloom.hashes with literal constants — Pallas kernels cannot
    capture the module-level jnp.uint32 arrays bloom.py uses.  Must stay
    bit-identical to bloom.hashes (parity with the unfused path)."""
    x = ids.astype(jnp.uint32)
    h1 = (x * np.uint32(0x9E3779B1)) ^ ((x * np.uint32(0x85EBCA77)) >> 15)
    h2 = (x * np.uint32(0xC2B2AE3D)) ^ (x >> 13) ^ (x * np.uint32(0x27D4EB2F))
    return ((h1 % np.uint32(n_bits)).astype(jnp.int32),
            (h2 % np.uint32(n_bits)).astype(jnp.int32))


def _col(x: jax.Array, lane: jax.Array, j: int) -> jax.Array:
    """Column ``j`` of a (bt, w) array as (bt, 1): a lane select and a
    lane reduction, which Mosaic lowers at any lane offset."""
    return jnp.sum(jnp.where(lane == j, x, 0), axis=1, keepdims=True)


def _gather_rows(onehot: jax.Array, table: jax.Array) -> jax.Array:
    """``onehot @ table`` at full fp32 precision: the one-hot matmul is a
    row gather only if no operand is rounded to bf16 (ids above 256 and
    fp32 vector entries are not bf16-exact)."""
    return jax.lax.dot_general(onehot, table, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _round_body(q, qn, nbr_f, vec, row_iota, bit_iota, bid, bd, bck, vis, *,
                n: int, R: int, W: int, ef: int, Wsort: int, hash_bits: int,
                visited_mode: str, lut=None, ksub: int = 16):
    """One W-wide expansion round on VMEM-resident values.  Shared by the
    per-hop kernel and the persistent kernel's loop body (which is what
    guarantees their bit-exact parity).

    ``vec`` is the dequantized fp32 vector table for the dense encodings;
    with ``lut`` set (PQ payloads, DESIGN.md §4) it is the fp32 *code* table
    (bt-invariant, values 0..ksub-1) and distances come from per-query LUT
    gathers instead of MXU dot-products.

    Every array is 2-D; flags (``bck``, ``vis``, fresh) are int32 0/1, and
    per-query scalars are (bt, 1) columns.  Distances stay in the BIG
    domain.  Returns ``(new_id, new_d, new_ck, vis, fresh, n_sel,
    has_work)`` where fresh is (bt, W·R), n_sel is the per-row count of
    expanded candidates and has_work is 1 on rows that had any unchecked
    candidate."""
    bt = bid.shape[0]
    WR = W * R
    lane_ef = jax.lax.broadcasted_iota(jnp.int32, (bt, ef), 1)
    lane_r = jax.lax.broadcasted_iota(jnp.int32, (bt, R), 1)
    lane_s = jax.lax.broadcasted_iota(jnp.int32, (bt, WR), 1)

    # ---- frontier selection: the W best unchecked candidates per query
    # (the beam is distance-sorted, so they are the first W unchecked
    # slots), one lowest-lane pick per frontier ----
    avail = (bck == 0) & (bid < n)
    has_work = jnp.max(avail.astype(jnp.int32), axis=1, keepdims=True)
    checked = bck
    n_sel = jnp.zeros((bt, 1), jnp.int32)

    # ---- per frontier: one-hot gather + sequential visited filter.  The
    # slot loops are rolled (fori_loop): Mosaic emits every vector op once
    # per vreg, so an unrolled loop over (bt, Npad) one-hots grows the
    # kernel, and its compile time, with R·Npad ----
    nbrs = jnp.full((bt, WR), n, jnp.int32)
    fresh = jnp.zeros((bt, WR), jnp.int32)
    for w in range(W):
        first = jnp.min(jnp.where(avail, lane_ef, ef), axis=1, keepdims=True)
        pick = lane_ef == first                           # empty if first=ef
        u_w = jnp.where(first < ef,
                        jnp.sum(jnp.where(pick, bid, 0), axis=1,
                                keepdims=True),
                        n)                                # sentinel row
        checked = jnp.where(pick, 1, checked)
        avail = avail & ~pick
        n_sel = n_sel + (first < ef).astype(jnp.int32)
        onehot_u = (row_iota == u_w).astype(jnp.float32)
        nbrs_w = (_gather_rows(onehot_u, nbr_f) + 0.5).astype(jnp.int32)

        if visited_mode == "bloom":
            h1, h2 = _bloom_hashes(nbrs_w, hash_bits)
        else:
            h1 = h2 = jnp.clip(nbrs_w, 0, vis.shape[1] - 1)

        # test all R slots against the filter as of this frontier (matches
        # the unfused round: within a frontier duplicates are each scored;
        # across frontiers, frontier w sees frontiers < w's inserts), then
        # union this frontier's inserts
        def slot(r, carry, vis=vis, nbrs_w=nbrs_w, h1=h1, h2=h2, w=w):
            ins, nbrs, fresh = carry
            id_r = _col(nbrs_w, lane_r, r)
            m1 = bit_iota == _col(h1, lane_r, r)
            m2 = bit_iota == _col(h2, lane_r, r)
            seen = (jnp.max(jnp.where(m1, vis, 0), axis=1, keepdims=True)
                    * jnp.max(jnp.where(m2, vis, 0), axis=1, keepdims=True))
            fr = (id_r < n) & (seen == 0)                 # (bt, 1)
            at = lane_s == w * R + r
            return (jnp.where((m1 | m2) & fr, 1, ins),
                    jnp.where(at, id_r, nbrs),
                    jnp.where(at & fr, 1, fresh))

        ins, nbrs, fresh = lax.fori_loop(
            0, R, slot, (jnp.zeros_like(vis), nbrs, fresh))
        vis = jnp.maximum(vis, ins)

    # ---- distances, one gather-matmul per slot: the MXU norms identity
    # for dense tables; for PQ payloads the gather fetches the m-byte code
    # row and the distance is qn + Σ_s lut[s·ksub + code_s] — one-hot LUT
    # gathers over the per-query ADC table, no d-wide dot-product ----
    if lut is not None:
        lut_iota = jax.lax.broadcasted_iota(jnp.int32, lut.shape, 1)
        m = vec.shape[1]
        lane_m = jax.lax.broadcasted_iota(jnp.int32, (bt, m), 1)

    def score(s, d):
        onehot_r = (row_iota == _col(nbrs, lane_s, s)).astype(jnp.float32)
        nv = _gather_rows(onehot_r, vec)
        if lut is None:
            vn = jnp.sum(nv * nv, axis=1, keepdims=True)
            dot = jnp.sum(nv * q, axis=1, keepdims=True)
            d_s = jnp.maximum(qn + vn - 2.0 * dot, 0.0)
        else:
            crow = (nv + 0.5).astype(jnp.int32)           # codes fp32-exact
            acc = qn
            for sub in range(m):                          # fixed subspace
                idx = ksub * sub + _col(crow, lane_m, sub)  # accumulation
                oh = lut_iota == idx                        # order
                acc = acc + jnp.sum(jnp.where(oh, lut, 0.0), axis=1,
                                    keepdims=True)
            d_s = jnp.maximum(acc, 0.0)
        return jnp.where(lane_s == s, d_s, d)

    d = lax.fori_loop(0, WR, score, jnp.full((bt, WR), BIG, jnp.float32))
    d = jnp.where(fresh != 0, d, BIG)                     # (bt, W·R)

    # ---- stable merge into the sorted beam: ties break on the original
    # lane, which is what the unfused path's stable argsort does ----
    pad = Wsort - (ef + WR)
    keys = jnp.concatenate(
        [bd, d] + ([jnp.full((bt, pad), BIG, jnp.float32)] if pad else []),
        axis=1)
    vals = jnp.concatenate(
        [bid, jnp.where(fresh != 0, nbrs, n)] +
        ([jnp.full((bt, pad), n, jnp.int32)] if pad else []), axis=1)
    flags = jnp.concatenate(
        [checked, 1 - fresh] +
        ([jnp.ones((bt, pad), jnp.int32)] if pad else []), axis=1)
    pos = jax.lax.broadcasted_iota(jnp.int32, (bt, Wsort), 1)
    keys, _, vals, flags = bitonic_sort(keys, pos, vals, flags)
    return (vals[:, :ef], keys[:, :ef], flags[:, :ef], vis, fresh,
            n_sel, has_work)


def _decode_operands(q, vec_ref, scl_ref, cb_ref, encoding: str):
    """Hoisted in-VMEM decode, once per kernel invocation (DESIGN.md §4):

    * ``dense`` — int8/bf16/fp32 tables widen to fp32 and multiply the
      per-dim scale row (all-ones for exact tables: bit-exact).
    * ``int4``  — unpack the plane-packed nibbles (low plane = dims
      0..hp-1, high plane = dims hp..2hp-1: a lane concatenation, no
      shuffle) then the same scale multiply.
    * ``pq``    — no table decode at all: build the per-query ADC LUT
      ``lut = ‖c‖² − 2·q @ codebook`` from the block-diagonal codebook and
      return the raw fp32 code table for one-hot code-row gathers.

    Returns ``(vec, lut)`` with ``lut`` None except for ``pq``."""
    if encoding == "pq":
        cb = cb_ref[...].astype(jnp.float32)              # (dp8, m·ksub)
        cn = jnp.sum(cb * cb, axis=0, keepdims=True)
        dot = jax.lax.dot_general(q, cb, (((1,), (0,)), ((), ())),
                                  precision=jax.lax.Precision.HIGHEST,
                                  preferred_element_type=jnp.float32)
        return vec_ref[...].astype(jnp.float32), cn - 2.0 * dot
    if encoding == "int4":
        v = vec_ref[...].astype(jnp.int32)
        lo = v & 0xF
        lo = jnp.where(lo >= 8, lo - 16, lo)
        hi = (v >> 4) & 0xF
        hi = jnp.where(hi >= 8, hi - 16, hi)
        unpacked = jnp.concatenate([lo, hi], axis=1).astype(jnp.float32)
        return unpacked * scl_ref[0:1, :], None
    return vec_ref[...].astype(jnp.float32) * scl_ref[0:1, :], None


def _hop_kernel(q_ref, nbr_ref, vec_ref, scl_ref, cb_ref, bid_ref, bd_ref,
                bck_ref, vis_ref, oid_ref, od_ref, ock_ref, ovis_ref,
                ofresh_ref, *,
                n: int, R: int, W: int, ef: int, Wsort: int, hash_bits: int,
                visited_mode: str, encoding: str = "dense"):
    q = q_ref[...].astype(jnp.float32)                    # (bt, dp)
    bt = bid_ref.shape[0]
    Npad = nbr_ref.shape[0]
    vpad = vis_ref.shape[1]
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (bt, Npad), 1)
    bit_iota = jax.lax.broadcasted_iota(jnp.int32, (bt, vpad), 1)
    vec, lut = _decode_operands(q, vec_ref, scl_ref, cb_ref, encoding)
    nid, nd, nck, vis, fresh, _, _ = _round_body(
        q, qn, nbr_ref[...].astype(jnp.float32),
        vec, row_iota, bit_iota,
        bid_ref[...], bd_ref[...], bck_ref[...], vis_ref[...],
        n=n, R=R, W=W, ef=ef, Wsort=Wsort, hash_bits=hash_bits,
        visited_mode=visited_mode, lut=lut)
    oid_ref[...] = nid
    od_ref[...] = nd
    ock_ref[...] = nck
    ovis_ref[...] = vis
    ofresh_ref[...] = fresh


def _persistent_kernel(q_ref, nbr_ref, vec_ref, scl_ref, cb_ref, bid_ref,
                       bd_ref, bck_ref, vis_ref, oid_ref, od_ref, ock_ref,
                       ovis_ref, ocnt_ref,
                       *, n: int, R: int, W: int, ef: int, Wsort: int,
                       hash_bits: int, visited_mode: str, rounds: int,
                       encoding: str = "dense"):
    """Whole stage-① search in one kernel: hop loop, state and convergence
    check all live in VMEM.  The loop exits as soon as the tile has no
    unchecked candidate (or the round budget runs out); a converged round is
    a fixed point, so per-tile early exit cannot change the result."""
    q = q_ref[...].astype(jnp.float32)                    # (bt, dp)
    bt = bid_ref.shape[0]
    Npad = nbr_ref.shape[0]
    vpad = vis_ref.shape[1]
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (bt, Npad), 1)
    bit_iota = jax.lax.broadcasted_iota(jnp.int32, (bt, vpad), 1)
    nbr_f = nbr_ref[...].astype(jnp.float32)              # hoisted operands
    vec, lut = _decode_operands(q, vec_ref, scl_ref, cb_ref, encoding)

    def cond(carry):
        i, bid, _bd, bck, _vis, _nd, _nh, _ne = carry
        work = jnp.max(jnp.where((bck == 0) & (bid < n), 1, 0))
        return (i < rounds) & (work > 0)

    def body(carry):
        i, bid, bd, bck, vis, nd, nh, ne = carry
        nid, nbd, nck, nvis, fresh, n_sel, has_work = _round_body(
            q, qn, nbr_f, vec, row_iota, bit_iota, bid, bd, bck, vis,
            n=n, R=R, W=W, ef=ef, Wsort=Wsort, hash_bits=hash_bits,
            visited_mode=visited_mode, lut=lut)
        return (i + 1, nid, nbd, nck, nvis,
                nd + jnp.sum(fresh, axis=1, keepdims=True),
                nh + has_work, ne + n_sel)

    z = jnp.zeros((bt, 1), jnp.int32)
    carry = (jnp.int32(0), bid_ref[...], bd_ref[...], bck_ref[...],
             vis_ref[...], z, z, z)
    _, bid, bd, bck, vis, nd, nh, ne = lax.while_loop(cond, body, carry)
    oid_ref[...] = bid
    od_ref[...] = bd
    ock_ref[...] = bck
    ovis_ref[...] = vis
    lane = jax.lax.broadcasted_iota(jnp.int32, (bt, _CNT_LANES), 1)
    ocnt_ref[...] = jnp.where(lane == 0, nd, jnp.where(
        lane == 1, nh, jnp.where(lane == 2, ne, 0)))


_CNT_LANES = 8  # counters output: lanes 0..2 = (n_dist, n_hops, n_exp)
# scoped VMEM the traversal kernels may use: they hold the whole pilot
# table (both copies of the double buffer) plus (bt, Npad) one-hot
# transients, so this, not HBM, bounds the pilot size they serve
# (DESIGN.md §3).  A v5e core has 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 100 * 1024 * 1024


def align_tables(nbr_table: jax.Array, vec_table: jax.Array, n: int,
                 sublane: int = 8) -> Tuple[jax.Array, jax.Array]:
    """Pad table rows to the kernel's sublane multiple (sentinel id-n rows /
    zero vector rows).  Single source of truth for the alignment contract:
    greedy_search hoists this out of the hop loop, and the kernel wrappers
    apply it as a no-op fallback for direct callers."""
    N1 = nbr_table.shape[0]
    Npad = -(-N1 // sublane) * sublane
    if Npad == N1:
        return nbr_table, vec_table
    return (jnp.pad(nbr_table, ((0, Npad - N1), (0, 0)), constant_values=n),
            jnp.pad(vec_table, ((0, Npad - N1), (0, 0))))


def _pad_state(q, nbr_table, vec_table, beam_id, beam_d, beam_ck, visited,
               n: int, b_tile: int):
    """Shared wrapper-side padding: align table rows, pad visited lanes to a
    128 multiple and the batch to a b_tile multiple (idle all-checked
    sentinel beams, which also keeps padded rows out of the persistent
    kernel's convergence check).  Boolean state leaves as int32 0/1: the
    kernels keep booleans out of their operands (Mosaic cannot narrow a
    stored byte to a vector mask)."""
    Bq = q.shape[0]
    vbits = visited.shape[1]
    nbr_t, vec_t = align_tables(nbr_table, vec_table, n)
    vpad = -(-vbits // 128) * 128
    vis = jnp.pad(visited, ((0, 0), (0, vpad - vbits))) \
        if vpad != vbits else visited

    bt = min(b_tile, Bq)
    Bpad = -(-Bq // bt) * bt
    if Bpad != Bq:
        pb = Bpad - Bq
        q = jnp.pad(q, ((0, pb), (0, 0)))
        beam_id = jnp.pad(beam_id, ((0, pb), (0, 0)), constant_values=n)
        beam_d = jnp.pad(beam_d, ((0, pb), (0, 0)), constant_values=jnp.inf)
        beam_ck = jnp.pad(beam_ck, ((0, pb), (0, 0)), constant_values=True)
        vis = jnp.pad(vis, ((0, pb), (0, 0)))
    bd = jnp.where(jnp.isfinite(beam_d), beam_d, BIG)
    return (q, nbr_t, vec_t, beam_id, bd, beam_ck.astype(jnp.int32),
            vis.astype(jnp.int32), Bpad, bt, vpad, vbits)


def _apply_tombstone(tombstone, nbr_table, beam_id, beam_d, n: int):
    """Sentinel-mask a deletion bitmap into the kernel operands (DESIGN.md
    §6): tombstoned targets in the adjacency and tombstoned beam entries
    become sentinel-id/``+inf`` rows *before* the pallas_call, so the kernel
    bodies never see them and stay byte-identical to the tombstone-free
    build.  With ``tombstone=None`` (or an all-false bitmap) every ``where``
    is the identity — the bit-exactness contract the parity tests pin."""
    if tombstone is None:
        return nbr_table, beam_id, beam_d
    nbr_table = jnp.where(tombstone[nbr_table],
                          jnp.asarray(n, nbr_table.dtype), nbr_table)
    dead = tombstone[jnp.clip(beam_id, 0, n)]
    return (nbr_table, jnp.where(dead, n, beam_id),
            jnp.where(dead, jnp.inf, beam_d))


def _scale_operand(vec_scale, dp: int) -> jax.Array:
    """(8, dp) fp32 dequant-scale block (sublane-tiled); all-ones when the
    table is exact — multiplying by 1.0f is bit-exact, so passing the
    operand unconditionally keeps the kernel signature static without
    perturbing fp32/bf16 parity."""
    s = (jnp.ones((dp,), jnp.float32) if vec_scale is None
         else vec_scale.astype(jnp.float32))
    return jnp.broadcast_to(s[None, :], (8, dp))


def _encoding_operands(q, vec_table, vec_scale, vec_codebook):
    """Classify the stored table and build the kernel operand set
    ``(q, scale, codebook, encoding)`` — generalizing the ``_scale_operand``
    contract to the packed encodings (core/quant.py, DESIGN.md §4):

    * dense (fp32/bf16/int8): q untouched, scale row (all-ones when exact),
      dummy codebook block.
    * int4: the stored rows are ceil(d/2) packed bytes — q and the scale
      row pad to the unpacked width 2·hp (zero query cols / unit scales;
      the packed pad nibbles decode to exact 0, so padding is inert).
    * pq: the stored rows are m code bytes — the codebook rows (true dims)
      pad to the sublane multiple along with q; scale is unit (unused).
    """
    if vec_codebook is not None:
        dp8 = -(-q.shape[1] // 8) * 8
        if dp8 != q.shape[1]:
            q = jnp.pad(q, ((0, 0), (0, dp8 - q.shape[1])))
        cb = vec_codebook.astype(jnp.float32)
        if cb.shape[0] != dp8:
            cb = jnp.pad(cb, ((0, dp8 - cb.shape[0]), (0, 0)))
        return q, jnp.ones((8, dp8), jnp.float32), cb, "pq"
    dummy_cb = jnp.zeros((8, 128), jnp.float32)
    if vec_scale is not None and vec_table.shape[1] < vec_scale.shape[0]:
        hp = vec_table.shape[1]
        d2 = 2 * hp
        q = jnp.pad(q, ((0, 0), (0, d2 - q.shape[1])))
        s = jnp.pad(vec_scale.astype(jnp.float32),
                    (0, d2 - vec_scale.shape[0]), constant_values=1.0)
        return q, _scale_operand(s, d2), dummy_cb, "int4"
    return q, _scale_operand(vec_scale, q.shape[1]), dummy_cb, "dense"


def _traversal_call(kernel, q, nbr_table, vec_table, beam_id, beam_d,
                    beam_ck, visited, n: int, *, width: int, b_tile: int,
                    interpret, vec_scale, vec_codebook, tombstone,
                    extra_out: jax.ShapeDtypeStruct, **static):
    """Shared wrapper of the per-hop and the persistent kernel: tombstone
    masking, padding, encoding operands, the ``pallas_call`` itself and
    the slicing back.  ``extra_out`` is the kernel's fifth output per row
    (fresh mask or counters).  Returns the five outputs at the caller's
    batch size with booleans restored and +inf distances."""
    Bq = q.shape[0]
    R = nbr_table.shape[1]
    ef = beam_id.shape[1]
    assert n < (1 << 24), "one-hot gather needs fp32-exact node ids"
    assert vec_table.shape[0] == nbr_table.shape[0]
    assert width >= 1

    nbr_table, beam_id, beam_d = _apply_tombstone(tombstone, nbr_table,
                                                  beam_id, beam_d, n)
    (q, nbr_t, vec_t, beam_id, bd, beam_ck, vis, Bpad, bt, vpad,
     vbits) = _pad_state(q, nbr_table, vec_table, beam_id, beam_d, beam_ck,
                         visited, n, b_tile)
    Npad = nbr_t.shape[0]
    q, scl, cb, encoding = _encoding_operands(q, vec_t, vec_scale,
                                              vec_codebook)
    dq, wv = q.shape[1], vec_t.shape[1]
    xw = extra_out.shape[1]

    kern = functools.partial(
        kernel, n=n, R=R, W=width, ef=ef,
        Wsort=_next_pow2(ef + width * R), hash_bits=vbits,
        encoding=encoding, **static)
    row = lambda w: pl.BlockSpec((bt, w), lambda i: (i, 0))
    whole = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    oid, od, ock, ovis, oxtra = pl.pallas_call(
        kern,
        grid=(Bpad // bt,),
        in_specs=[row(dq), whole((Npad, R)), whole((Npad, wv)),
                  whole(scl.shape), whole(cb.shape),
                  row(ef), row(ef), row(ef), row(vpad)],
        out_specs=(row(ef), row(ef), row(ef), row(vpad), row(xw)),
        out_shape=(
            jax.ShapeDtypeStruct((Bpad, ef), jnp.int32),
            jax.ShapeDtypeStruct((Bpad, ef), jnp.float32),
            jax.ShapeDtypeStruct((Bpad, ef), jnp.int32),
            jax.ShapeDtypeStruct((Bpad, vpad), jnp.int32),
            jax.ShapeDtypeStruct((Bpad, xw), extra_out.dtype),
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=resolve_interpret(interpret),
    )(q, nbr_t, vec_t, scl, cb, beam_id, bd, beam_ck, vis)

    od = jnp.where(od >= BIG, jnp.inf, od)
    return (oid[:Bq], od[:Bq], ock[:Bq] != 0, ovis[:Bq, :vbits] != 0,
            oxtra[:Bq])


def fused_traversal_hop(q: jax.Array, nbr_table: jax.Array,
                        vec_table: jax.Array, beam_id: jax.Array,
                        beam_d: jax.Array, beam_ck: jax.Array,
                        visited: jax.Array, n: int, *, width: int = 1,
                        visited_mode: str = "bloom", b_tile: int = 8,
                        interpret: Optional[bool] = None,
                        vec_scale: jax.Array = None,
                        vec_codebook: jax.Array = None,
                        tombstone: jax.Array = None
                        ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                   jax.Array, jax.Array]:
    """One fused W-wide expansion round.

    q (B, dp); nbr_table (n+1, R) integer table with sentinel row n;
    vec_table (n+1, dp) with zero row at n — stored fp32, bf16 or int8
    (pass ``vec_scale`` (dp,) for int8), nibble-packed int4 (``vec_scale``
    (dp,) with dp > table width), or PQ codes (pass ``vec_codebook``
    (dp, m·ksub); DESIGN.md §4); beam_* (B, ef) sorted
    beam (+inf sentinel distances); visited (B, n_bits) bloom filter or
    (B, n+1) exact bitmap; tombstone: optional (n+1,) deletion bitmap,
    sentinel-masked into the operands before the kernel (DESIGN.md §6;
    bit-exact no-op when ``None``/all-false).

    Returns ``(new_id, new_d, new_ck, new_visited, fresh)`` with the same
    semantics as ``core.traversal.expansion_round`` minus the counters —
    ``fresh`` (B, W·R) lets the caller account n_dist.
    """
    R = nbr_table.shape[1]
    oid, od, ock, ovis, ofresh = _traversal_call(
        _hop_kernel, q, nbr_table, vec_table, beam_id, beam_d, beam_ck,
        visited, n, width=width, b_tile=b_tile, interpret=interpret,
        vec_scale=vec_scale, vec_codebook=vec_codebook, tombstone=tombstone,
        extra_out=jax.ShapeDtypeStruct((0, width * R), jnp.int32),
        visited_mode=visited_mode)
    return oid, od, ock, ovis, ofresh != 0


def fused_pilot_search(q: jax.Array, nbr_table: jax.Array,
                       vec_table: jax.Array, beam_id: jax.Array,
                       beam_d: jax.Array, beam_ck: jax.Array,
                       visited: jax.Array, n: int, *, rounds: int,
                       width: int = 1, visited_mode: str = "bloom",
                       b_tile: int = 8, interpret: Optional[bool] = None,
                       vec_scale: jax.Array = None,
                       vec_codebook: jax.Array = None,
                       tombstone: jax.Array = None
                       ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                                  jax.Array, jax.Array, jax.Array]:
    """Persistent stage-① search: run up to ``rounds`` W-wide expansion
    rounds — with in-kernel convergence exit — inside one ``pallas_call``.

    Inputs as ``fused_traversal_hop`` (the initial beam/visited state comes
    from ``core.traversal.init_state``; quantized tables pass ``vec_scale``
    and/or ``vec_codebook``; ``tombstone`` deletion bitmaps are
    sentinel-masked into the operands, DESIGN.md §6).
    Returns ``(beam_id, beam_d, beam_ck, visited, n_dist, n_hops, n_exp)``
    where the three counters are (B,) int32 *deltas* accumulated over the
    executed rounds (the caller adds them to the init-state counters).
    """
    assert rounds >= 0
    oid, od, ock, ovis, ocnt = _traversal_call(
        _persistent_kernel, q, nbr_table, vec_table, beam_id, beam_d,
        beam_ck, visited, n, width=width, b_tile=b_tile,
        interpret=interpret, vec_scale=vec_scale, vec_codebook=vec_codebook,
        tombstone=tombstone,
        extra_out=jax.ShapeDtypeStruct((0, _CNT_LANES), jnp.int32),
        visited_mode=visited_mode, rounds=rounds)
    return oid, od, ock, ovis, ocnt[:, 0], ocnt[:, 1], ocnt[:, 2]
