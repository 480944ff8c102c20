"""Fast Entry Selection (PilotANN §5).

Entry vectors are organised into a small number r of coarse clusters
(r = 32 in the paper, matching the GPU warp width; on TPU the same r keeps
the per-cluster tile count aligned with 128-wide MXU tiles).  Queries are
routed to their nearest centroid and distances are computed only against that
cluster's entries, with GEMM-like density  mn / (r(m+n))  (Table 2).

This module holds the clustering/build side and the pure-jnp reference
selection (identical math to the Pallas kernel in kernels/fes_kernel.py —
the kernel is tested against ``fes_select_ref``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph_build import kmeans, pairwise_sq_dists


@dataclass
class FESIndex:
    centroids: np.ndarray   # (r, d)
    entries: np.ndarray     # (r, C, d)  cluster-bucketed entry vectors (padded)
    entry_ids: np.ndarray   # (r, C)     original node ids (sentinel = n)
    valid: np.ndarray       # (r, C)     padding mask
    n: int

    @property
    def r(self) -> int:
        return self.centroids.shape[0]

    @property
    def capacity(self) -> int:
        return self.entries.shape[1]


def fes_capacity_cap(n_entry: int, r: int, align: int = 128) -> int:
    """Upper bound on the padded per-cluster capacity: 2× the mean bucket
    size, align-rounded.  ``build_fes`` enforces it (overflow entries from
    skewed kmeans buckets are dropped — the pool is a random sample, so
    this only thins over-dense regions) and ``engine.ResidencyPlanner``
    uses the same formula, which makes the planner's FES byte estimate a
    true upper bound on the realized table (DESIGN.md §4)."""
    return max(align, -(-max(1, (2 * n_entry) // r) // align) * align)


def build_fes(vectors: np.ndarray, candidate_ids: np.ndarray, *, r: int = 32,
              n_entry: int = 8192, seed: int = 0, align: int = 128,
              max_capacity: int = None) -> FESIndex:
    """Sample ``n_entry`` entry vectors from candidate_ids, cluster into r
    coarse buckets, pad buckets to a common 128-aligned capacity (bounded
    by ``max_capacity`` when given; entries past it in an over-full bucket
    are dropped)."""
    rng = np.random.default_rng(seed)
    n = vectors.shape[0]
    n_entry = min(n_entry, len(candidate_ids))
    ids = rng.choice(candidate_ids, size=n_entry, replace=False)
    ev = vectors[ids].astype(np.float32)
    cent = kmeans(ev, r, seed=seed)
    assign = np.argmin(pairwise_sq_dists(ev, cent), axis=1)
    counts = np.bincount(assign, minlength=r)
    C = int(max(1, -(-counts.max() // align) * align))
    if max_capacity is not None:
        C = min(C, max(align, max_capacity))
    buckets = np.zeros((r, C, vectors.shape[1]), np.float32)
    bucket_ids = np.full((r, C), n, np.int32)
    valid = np.zeros((r, C), bool)
    for c in range(r):
        members = np.flatnonzero(assign == c)[:C]
        buckets[c, :len(members)] = ev[members]
        bucket_ids[c, :len(members)] = ids[members]
        valid[c, :len(members)] = True
    return FESIndex(centroids=cent, entries=buckets, entry_ids=bucket_ids,
                    valid=valid, n=n)


def mask_tombstoned(valid: jax.Array, entry_ids: jax.Array,
                    tombstone: jax.Array) -> jax.Array:
    """Drop tombstoned entries from an FES validity mask (DESIGN.md §6):
    ``tombstone`` is the (n+1,) deletion bitmap in ``entry_ids``' id space.
    Shared by the jnp reference and the Pallas wrapper (kernels/ops.py) so
    both honor deletes identically; all-false bitmaps are bit-exact."""
    t = tombstone[jnp.clip(entry_ids, 0, tombstone.shape[0] - 1)]
    return valid & ~t


def fes_select_ref(queries: jax.Array, centroids: jax.Array, entries: jax.Array,
                   entry_ids: jax.Array, valid: jax.Array, L: int,
                   entries_scale: jax.Array = None,
                   entries_codebook: jax.Array = None,
                   tombstone: jax.Array = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Pure-jnp reference: route each query to its nearest centroid, score
    only that cluster's entries, return top-L (ids, sq-dists).

    queries (B, d); centroids (r, d); entries (r, C, d); -> (B, L) ids/dists.
    ``entries`` may be stored bf16, int8, nibble-packed int4 or PQ codes
    (core/quant.py) — pass the per-dim ``entries_scale`` for int8/int4 and
    ``entries_codebook`` for pq; centroids stay fp32 (they are tiny
    and routing quality is budget-irrelevant).  ``tombstone``: optional
    deletion bitmap in the entry-id space — tombstoned entries are treated
    as padding (DESIGN.md §6).
    """
    from repro.core import quant

    if tombstone is not None:
        valid = mask_tombstoned(valid, entry_ids, tombstone)
    q = queries.astype(jnp.float32)
    # route
    qc = _xdist(q, centroids)                         # (B, r)
    route = jnp.argmin(qc, axis=1)                    # (B,)
    rows = entries[route]                             # (B, C, ...)  gather
    if entries_codebook is not None or (
            entries_scale is not None
            and entries.shape[-1] < entries_scale.shape[-1]):
        ev = quant.decode_rows(rows, entries_scale,
                               codebook=entries_codebook)
    else:
        ev = rows.astype(jnp.float32)
        if entries_scale is not None:
            ev = ev * entries_scale.astype(jnp.float32)
    iv = entry_ids[route]                             # (B, C)
    mv = valid[route]
    d = _rowdist(q, ev)                               # (B, C)
    d = jnp.where(mv, d, jnp.inf)
    neg_d, idx = jax.lax.top_k(-d, L)
    return jnp.take_along_axis(iv, idx, axis=1), -neg_d


def fes_select_bruteforce(queries: jax.Array, entries: jax.Array,
                          entry_ids: jax.Array, valid: jax.Array, L: int,
                          entries_scale: jax.Array = None
                          ) -> Tuple[jax.Array, jax.Array]:
    """1-block degenerate case of Table 2: score ALL entries (no routing)."""
    r, C, d_ = entries.shape
    ev = entries.reshape(r * C, d_).astype(jnp.float32)
    if entries_scale is not None:
        ev = ev * entries_scale.astype(jnp.float32)
    d = _xdist(queries.astype(jnp.float32), ev)
    d = jnp.where(valid.reshape(-1)[None, :], d, jnp.inf)
    neg_d, idx = jax.lax.top_k(-d, L)
    return entry_ids.reshape(-1)[idx], -neg_d


def _xdist(a: jax.Array, b: jax.Array) -> jax.Array:
    an = jnp.sum(a * a, axis=-1)[:, None]
    bn = jnp.sum(b * b, axis=-1)[None, :]
    dot = jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(an + bn - 2.0 * dot, 0.0)


def _rowdist(q: jax.Array, ev: jax.Array) -> jax.Array:
    qn = jnp.sum(q * q, axis=-1)[:, None]
    en = jnp.sum(ev * ev, axis=-1)
    dot = jnp.einsum("bd,bcd->bc", q, ev,
                     precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(qn + en - 2.0 * dot, 0.0)
