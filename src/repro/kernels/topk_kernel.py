"""Pallas TPU kernel: fused neighbour-distance + beam-merge (expansion step).

The hot inner loop of graph traversal (Algorithm 1, lines 6-10) is
  (a) score R gathered neighbour vectors against the query, and
  (b) merge them into the sorted ef-beam.
On GPU PilotANN does (a)+(b) per warp; the TPU analogue fuses them in VMEM so
the (B, R) distances and the (B, ef+R) merge buffer never round-trip to HBM.
Sorting uses a bitonic network (static compare-exchange schedule — identical
control flow across batch lanes, which is exactly what the VPU wants).

Inputs are pre-gathered neighbour vectors (the gather itself is an XLA op —
on TPU a DMA engine job — so the kernel stays dense).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

BIG = 3.0e38  # python float: +inf stand-in that survives bitonic compares


def _next_pow2(x: int) -> int:
    return 1 << (x - 1).bit_length()


def bitonic_sort(keys: jax.Array, tie: jax.Array, *payloads: jax.Array):
    """Ascending bitonic sort of (B, W) rows by the pair (keys, tie),
    carrying ``payloads`` along.  W must be a power of two.

    Written for Mosaic's TPU lowering: the partner exchange is two lane
    rolls and a select (``_swap_lanes``), and every compare-exchange is
    boolean logic feeding a select on 32-bit values — no lane reversal,
    no boolean-valued select.  Returns ``(keys, tie, *payloads)``."""
    B, W = keys.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (B, W), 1)
    stages = int(math.log2(W))
    for s in range(stages):
        for t in range(s, -1, -1):
            stride = 1 << t
            k_p = _swap_lanes(keys, stride, lane)
            t_p = _swap_lanes(tie, stride, lane)
            # (keys, tie) <= partner's, lexicographically
            less = (keys < k_p) | ((keys == k_p) & (tie <= t_p))
            # lanes whose pair sorts ascending and that hold its low end
            # keep the smaller element; the others keep the larger
            up = (((lane >> t) ^ (lane >> (s + 1))) & 1) == 0
            keep = ~(less ^ up)
            keys = jnp.where(keep, keys, k_p)
            tie = jnp.where(keep, tie, t_p)
            payloads = tuple(jnp.where(keep, p, _swap_lanes(p, stride, lane))
                             for p in payloads)
    return (keys, tie) + payloads


def _swap_lanes(x: jax.Array, stride: int, lane: jax.Array) -> jax.Array:
    """Exchange every lane with its partner ``lane ^ stride``: two lane
    rotations (``pltpu.roll`` follows ``jnp.roll``) and a select."""
    W = x.shape[1]
    nxt = pltpu.roll(x, W - stride, 1)        # nxt[i] = x[i + stride]
    prv = pltpu.roll(x, stride, 1)            # prv[i] = x[i - stride]
    return jnp.where((lane & stride) == 0, nxt, prv)


def _expand_merge_kernel(q_ref, nvec_ref, nid_ref, fresh_ref,
                         bid_ref, bd_ref, bck_ref,
                         oid_ref, od_ref, ock_ref, *, ef: int, W: int, n: int):
    q = q_ref[...].astype(jnp.float32)                     # (Bt, d)
    nv = nvec_ref[...].astype(jnp.float32)                 # (Bt, R, d)
    nid = nid_ref[...]                                     # (Bt, R)
    fresh = fresh_ref[...] != 0                            # (Bt, R) int32

    qn = jnp.sum(q * q, axis=-1)[:, None]
    vn = jnp.sum(nv * nv, axis=-1)
    dot = jnp.sum(nv * q[:, None, :], axis=-1)
    d = jnp.maximum(qn + vn - 2.0 * dot, 0.0)              # (Bt, R)
    d = jnp.where(fresh, d, BIG)

    Bt, R = nid.shape
    pad = W - (ef + R)
    keys = jnp.concatenate(
        [bd_ref[...], d] +
        ([jnp.full((Bt, pad), BIG, jnp.float32)] if pad else []), axis=1)
    vals = jnp.concatenate(
        [bid_ref[...], jnp.where(fresh, nid, n)] +
        ([jnp.full((Bt, pad), n, jnp.int32)] if pad else []), axis=1)
    flags = jnp.concatenate(
        [bck_ref[...], 1 - fresh_ref[...]] +
        ([jnp.ones((Bt, pad), jnp.int32)] if pad else []), axis=1)

    keys, vals, flags = bitonic_sort(keys, vals, flags)
    od_ref[...] = keys[:, :ef]
    oid_ref[...] = vals[:, :ef]
    ock_ref[...] = flags[:, :ef]


def fused_expand_merge(q: jax.Array, nvecs: jax.Array, nids: jax.Array,
                       fresh: jax.Array, beam_id: jax.Array, beam_d: jax.Array,
                       beam_ck: jax.Array, n: int, *, b_tile: int = 128,
                       interpret: Optional[bool] = None):
    """q (B, d); nvecs (B, R, d); nids/fresh (B, R);
    beam_* (B, ef) sorted beam.  Returns merged (ids, dists, checked) (B, ef).
    Non-fresh rows enter with +INF distance (dropped unless beam not full).
    Boolean operands cross the kernel boundary as int32."""
    B, d = q.shape
    R = nids.shape[1]
    ef = beam_id.shape[1]
    W = _next_pow2(ef + R)
    bt = min(b_tile, B)
    assert B % bt == 0, (B, bt)
    grid = (B // bt,)

    kern = functools.partial(_expand_merge_kernel, ef=ef, W=W, n=n)
    out_shapes = (
        jax.ShapeDtypeStruct((B, ef), jnp.int32),
        jax.ShapeDtypeStruct((B, ef), jnp.float32),
        jax.ShapeDtypeStruct((B, ef), jnp.int32),
    )
    oid, od, ock = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, d), lambda i: (i, 0)),
            pl.BlockSpec((bt, R, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((bt, R), lambda i: (i, 0)),
            pl.BlockSpec((bt, R), lambda i: (i, 0)),
            pl.BlockSpec((bt, ef), lambda i: (i, 0)),
            pl.BlockSpec((bt, ef), lambda i: (i, 0)),
            pl.BlockSpec((bt, ef), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((bt, ef), lambda i: (i, 0)),
            pl.BlockSpec((bt, ef), lambda i: (i, 0)),
            pl.BlockSpec((bt, ef), lambda i: (i, 0)),
        ),
        out_shape=out_shapes,
        interpret=resolve_interpret(interpret),
    )(q, nvecs, nids, fresh.astype(jnp.int32), beam_id, beam_d,
      beam_ck.astype(jnp.int32))
    return oid, od, ock != 0
