"""Pallas TPU kernel: fused NN-descent candidate merge (DESIGN.md §9).

The hot step of the CAGRA-style device builder (``core/device_build``)
is, per node and per round: take the (K,) incumbent candidate list and
the (P,) freshly scored proposals, drop invalid ids, dedupe by id
keeping the best-distance copy, and keep the (distance, id) top-K.
This kernel fuses both sorts in VMEM with the traversal kernels'
bitonic machinery (``topk_kernel.bitonic_sort`` — a static
compare-exchange network, identical control flow across batch lanes):

  1. sort by (id, distance)  — ids as exact fp32 keys (requires
     n < 2^24, the same id-width contract as the traversal kernel's
     one-hot gathers), payload = distance + int id;
  2. mask adjacent duplicates (a one-lane roll and compare, no gather);
  3. sort by (distance, id) and emit the first K lanes.

The jnp oracle is ``kernels/ref.candidate_merge_ref``; both produce
bit-identical ids/distances (the sorts order the same total key), which
tests/test_graph_build_device.py pins over random sweeps.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret
from repro.kernels.topk_kernel import BIG, _next_pow2, bitonic_sort

MAX_ID_EXACT = 1 << 24  # fp32 integer-exactness bound for id sort keys


def _candidate_merge_kernel(cid_ref, cd_ref, pid_ref, pd_ref,
                            oid_ref, od_ref, *, K: int, W: int, n: int):
    cid = cid_ref[...]                                  # (Bt, K) int32
    cd = cd_ref[...]                                    # (Bt, K) f32
    pid = pid_ref[...]                                  # (Bt, P) int32
    pd = pd_ref[...]                                    # (Bt, P) f32
    Bt, P = pid.shape
    pad = W - (K + P)
    ids = jnp.concatenate(
        [cid, pid] + ([jnp.full((Bt, pad), n, jnp.int32)] if pad else []),
        axis=1)
    d = jnp.concatenate(
        [cd, pd] + ([jnp.full((Bt, pad), BIG, jnp.float32)] if pad else []),
        axis=1)
    bad = ids >= n
    d = jnp.where(bad, BIG, d)
    ids = jnp.where(bad, n, ids)

    # pass 1: group by id (distance-ascending within a group)
    idf = ids.astype(jnp.float32)
    k1, v1, f1 = bitonic_sort(idf, d, ids)
    lane = jax.lax.broadcasted_iota(jnp.int32, f1.shape, 1)
    prev = jnp.where(lane == 0, -1, pltpu.roll(f1, 1, 1))
    drop = (f1 == prev) | (f1 >= n)
    sd = jnp.where(drop, BIG, v1)
    sidf = jnp.where(drop, jnp.float32(n), k1)
    sid = jnp.where(drop, n, f1)

    # pass 2: (distance, id) ascending; first K lanes are the new list
    k2, _, f2 = bitonic_sort(sd, sidf, sid)
    oid_ref[...] = f2[:, :K]
    od_ref[...] = k2[:, :K]


def fused_candidate_merge(cand_ids: jax.Array, cand_d: jax.Array,
                          prop_ids: jax.Array, prop_d: jax.Array, n: int,
                          *, b_tile: int = 8,
                          interpret: Optional[bool] = None
                          ) -> Tuple[jax.Array, jax.Array]:
    """cand_ids/cand_d (B, K) incumbent lists (sentinel >= n, BIG);
    prop_ids/prop_d (B, P) scored proposals.  Returns the merged
    (ids, d) (B, K) — see module docstring for the contract.  ``b_tile``
    rows per grid step: Mosaic unrolls the sorting network once per vreg,
    so its compile time grows faster than linearly with the tile (for a
    v5e at K=64, P=272: about 2.5 s at 8 rows, 9 s at 32, 166 s at 128)."""
    if n >= MAX_ID_EXACT:
        raise ValueError(f"n={n} exceeds fp32-exact id keys "
                         f"({MAX_ID_EXACT}); use the jnp merge path")
    B, K = cand_ids.shape
    P = prop_ids.shape[1]
    W = _next_pow2(K + P)
    bt = min(b_tile, _next_pow2(max(B, 1)))
    Bp = -(-B // bt) * bt
    if Bp != B:
        cand_ids = jnp.concatenate(
            [cand_ids, jnp.full((Bp - B, K), n, cand_ids.dtype)])
        cand_d = jnp.concatenate(
            [cand_d, jnp.full((Bp - B, K), BIG, cand_d.dtype)])
        prop_ids = jnp.concatenate(
            [prop_ids, jnp.full((Bp - B, P), n, prop_ids.dtype)])
        prop_d = jnp.concatenate(
            [prop_d, jnp.full((Bp - B, P), BIG, prop_d.dtype)])

    kern = functools.partial(_candidate_merge_kernel, K=K, W=W, n=n)
    oid, od = pl.pallas_call(
        kern,
        grid=(Bp // bt,),
        in_specs=[
            pl.BlockSpec((bt, K), lambda i: (i, 0)),
            pl.BlockSpec((bt, K), lambda i: (i, 0)),
            pl.BlockSpec((bt, P), lambda i: (i, 0)),
            pl.BlockSpec((bt, P), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((bt, K), lambda i: (i, 0)),
            pl.BlockSpec((bt, K), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((Bp, K), jnp.int32),
            jax.ShapeDtypeStruct((Bp, K), jnp.float32),
        ),
        interpret=resolve_interpret(interpret),
    )(cand_ids, cand_d, prop_ids, prop_d)
    return oid[:B], od[:B]
