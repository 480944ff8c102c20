"""jit'd wrappers around the Pallas kernels.

``fes_select`` is the full TPU FES path: route → group-by-cluster (one
argsort; the TPU replacement for the GPU kernel's per-row skip) → dense tiled
kernel → mask/top-L → scatter back to query order.  Numerically identical to
``repro.core.fes.fes_select_ref``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.fes_kernel import fes_distances
from repro.kernels.topk_kernel import fused_expand_merge
from repro.kernels.traversal_kernel import fused_traversal_hop


def _pad_to(x: jax.Array, axis: int, size: int, value=0):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg, constant_values=value)


@functools.partial(jax.jit, static_argnames=("L", "qc", "interpret"))
def fes_select(queries: jax.Array, centroids: jax.Array, entries: jax.Array,
               entry_ids: jax.Array, valid: jax.Array, *, L: int,
               qc: Optional[int] = None, interpret: Optional[bool] = None,
               entries_scale: Optional[jax.Array] = None,
               entries_codebook: Optional[jax.Array] = None,
               tombstone: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """queries (B, d); centroids (r, d); entries (r, C, d) — stored fp32,
    bf16 or int8 with per-dim ``entries_scale``, nibble-packed int4
    (``entries_scale`` wider than the stored rows), or PQ codes with
    ``entries_codebook`` (d, m·ksub) (core/quant.py; the kernel
    dequantizes / ADC-scores in VMEM).  Routing always runs on the fp32
    centroids — only the entry payloads are compressed.
    Returns (ids (B, L), sq-dists (B, L)) — top-L entries of each query's
    routed cluster.  ``qc``: per-cluster query capacity (defaults to B —
    always-safe; production tune: ~4B/r).  ``tombstone``: optional deletion
    bitmap in the entry-id space; tombstoned entries fold into the validity
    mask before the kernel (DESIGN.md §6 — bit-exact when ``None``)."""
    if tombstone is not None:
        from repro.core.fes import mask_tombstoned
        valid = mask_tombstoned(valid, entry_ids, tombstone)
    B, d = queries.shape
    r, C, _ = entries.shape
    qc = qc or B
    q = queries.astype(jnp.float32)

    # ---- route ----
    qn = jnp.sum(q * q, -1)[:, None]
    cn = jnp.sum(centroids * centroids, -1)[None, :]
    d2c = qn + cn - 2.0 * jnp.matmul(q, centroids.T,
                                     precision=jax.lax.Precision.HIGHEST)
    route = jnp.argmin(d2c, axis=1).astype(jnp.int32)      # (B,)

    # ---- group queries by cluster (sort once, pad per cluster to qc) ----
    order = jnp.argsort(route, stable=True)                # (B,)
    sroute = route[order]
    counts = jnp.sum(jax.nn.one_hot(route, r, dtype=jnp.int32), axis=0)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(B, dtype=jnp.int32) - starts[sroute]
    ok = rank < qc                                          # capacity guard
    slot = jnp.where(ok, sroute * qc + rank, r * qc)
    # slot -> original query index (sentinel B)
    q_at_slot = jnp.full((r * qc + 1,), B, jnp.int32).at[slot].set(
        jnp.where(ok, order, B))[: r * qc]
    qpad = jnp.concatenate([q, jnp.zeros((1, d), q.dtype)], axis=0)
    q_grouped = qpad[q_at_slot].reshape(r, qc, d)

    # ---- dense tiled kernel (entries stay in their stored encoding;
    # dequantization / ADC happens in-kernel) ----
    cpad = -(-C // 128) * 128
    packed = (entries_codebook is not None or
              (entries_scale is not None
               and entries.shape[2] < entries_scale.shape[0]))
    if packed:
        # int4/pq rows keep their packed width; the fes kernel owns any
        # query-side padding (padded entry rows are zero codes / zero
        # nibbles, masked below by the validity bitmap anyway)
        qg, ev, scale = q_grouped, _pad_to(entries, 1, cpad), entries_scale
    else:
        dpad = -(-d // 128) * 128 if d > 128 else d
        qg = _pad_to(q_grouped, 2, dpad)
        ev = _pad_to(_pad_to(entries, 2, dpad), 1, cpad)
        scale = None
        if entries_scale is not None:
            scale = _pad_to(entries_scale.astype(jnp.float32), 0, dpad,
                            value=1.0)
    dist = fes_distances(qg, ev, scale=scale, codebook=entries_codebook,
                         interpret=interpret)

    # ---- mask padding, top-L, scatter back ----
    vmask = _pad_to(valid, 1, cpad, value=False)            # (r, cpad)
    dist = jnp.where(vmask[:, None, :], dist, jnp.inf)
    neg, idx = jax.lax.top_k(-dist.reshape(r * qc, cpad), L)
    ids_pad = _pad_to(entry_ids, 1, cpad, value=entry_ids.max())
    sel_ids = jnp.take_along_axis(
        ids_pad.reshape(r, cpad)[jnp.arange(r * qc) // qc], idx, axis=1)

    out_ids = jnp.zeros((B + 1, L), jnp.int32).at[q_at_slot].set(sel_ids)[:B]
    out_d = jnp.full((B + 1, L), jnp.inf, jnp.float32).at[q_at_slot].set(-neg)[:B]
    return out_ids, out_d


__all__ = ["fes_select", "fes_distances", "fused_expand_merge",
           "fused_traversal_hop"]
