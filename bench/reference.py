"""The plain exact reference: brute-force top-k under squared L2.

It imports nothing of the program.  The corpus is swept on the device in
blocks of rows (``|q|^2 + |x|^2 - 2 q.x`` with the contraction at
``Precision.HIGHEST``), each block keeps a shortlist of the ``shortlist``
nearest live rows per query, and the shortlist is re-ranked on the host in
float64 from the vectors themselves.  A true top-k row drops out of the
shortlist only if float32 rounding moves it past ``shortlist - k`` nearer
rows.

Liveness is given per row as an epoch interval: row ``r`` is live for a
query of epoch ``e`` when ``born[r] <= e < died[r]``.  A static corpus has
every row born at 0 and never dying; a streaming run gives each inserted
row the epoch its insert became visible and each deleted row the epoch its
delete did.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEVER = np.int32(2 ** 30)
HIGHEST = jax.lax.Precision.HIGHEST


def _bf16_trunc(a):
    """``a`` cut to its bfloat16 part by masking the low 16 bits (a bit
    operation, which no compiler folds away as it may a round trip through
    bfloat16)."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32) & jnp.uint32(
        0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def dot_t(q, x, precision):
    """``q @ x.T`` at a contraction precision, or, for ``"bf16x3"``, as
    three bfloat16 products (high*high + high*low + low*high) summed in
    float32: the three-pass bfloat16 of ``Precision.HIGH``, emulated for
    backends that ignore the precision of a float32 contraction."""
    if precision != "bf16x3":
        return jnp.dot(q, x.T, precision=precision)
    qh, xh = _bf16_trunc(q), _bf16_trunc(x)
    ql, xl = _bf16_trunc(q - qh), _bf16_trunc(x - xh)
    return (jnp.dot(qh, xh.T, precision=HIGHEST)
            + jnp.dot(qh, xl.T, precision=HIGHEST)
            + jnp.dot(ql, xh.T, precision=HIGHEST))


@partial(jax.jit, static_argnames=("kk", "precision"))
def _block_topk(q, x, born, died, epoch, *, kk: int, precision):
    qsq = jnp.sum(q * q, axis=1)
    xsq = jnp.sum(x * x, axis=1)
    d = qsq[:, None] + xsq[None, :] - 2.0 * dot_t(q, x, precision)
    live = (born[None, :] <= epoch[:, None]) & (epoch[:, None] < died[None, :])
    neg, idx = jax.lax.top_k(-jnp.where(live, d, jnp.inf), kk)
    return -neg, idx


@partial(jax.jit, static_argnames=("kk",))
def _merge(d_a, i_a, d_b, i_b, *, kk: int):
    d = jnp.concatenate([d_a, d_b], axis=1)
    i = jnp.concatenate([i_a, i_b], axis=1)
    neg, pos = jax.lax.top_k(-d, kk)
    return -neg, jnp.take_along_axis(i, pos, axis=1)


def shortlist_topk(corpus: np.ndarray, queries: np.ndarray, kk: int, *,
                   born: Optional[np.ndarray] = None,
                   died: Optional[np.ndarray] = None,
                   epoch: Optional[np.ndarray] = None,
                   precision=HIGHEST, row_block: int = 65536,
                   query_block: int = 1024) -> Tuple[np.ndarray, np.ndarray]:
    """The ``kk`` nearest live rows of each query by the device sweep:
    (float32 distances, row ids), each (Q, kk), nearest first; ids of
    queries with fewer than ``kk`` live rows end in +inf distances."""
    n, d = corpus.shape
    nq = len(queries)
    born = np.zeros(n, np.int32) if born is None else born.astype(np.int32)
    died = (np.full(n, NEVER, np.int32) if died is None
            else died.astype(np.int32))
    epoch = np.zeros(nq, np.int32) if epoch is None else epoch.astype(np.int32)
    rb = min(row_block, -(-n // 8) * 8)
    n_pad = -(-n // rb) * rb
    xs = np.zeros((n_pad, d), np.float32)
    xs[:n] = corpus
    b_pad = np.full(n_pad, NEVER, np.int32)      # padding rows are never live
    b_pad[:n] = born
    d_pad = np.full(n_pad, NEVER, np.int32)
    d_pad[:n] = died
    blocks = [(jnp.asarray(xs[s:s + rb]), jnp.asarray(b_pad[s:s + rb]),
               jnp.asarray(d_pad[s:s + rb])) for s in range(0, n_pad, rb)]
    qb = min(query_block, -(-nq // 8) * 8)
    out_d = np.empty((nq, kk), np.float32)
    out_i = np.empty((nq, kk), np.int64)
    for s in range(0, nq, qb):
        m = min(qb, nq - s)
        qq = np.zeros((qb, d), np.float32)
        qq[:m] = queries[s:s + m]
        ee = np.zeros(qb, np.int32)
        ee[:m] = epoch[s:s + m]
        qq, ee = jnp.asarray(qq), jnp.asarray(ee)
        best_d = best_i = None
        for bi, (x, b, dd) in enumerate(blocks):
            dist, idx = _block_topk(qq, x, b, dd, ee, kk=kk,
                                    precision=precision)
            idx = idx + bi * rb
            if best_d is None:
                best_d, best_i = dist, idx
            else:
                best_d, best_i = _merge(best_d, best_i, dist, idx, kk=kk)
        out_d[s:s + m] = np.asarray(best_d)[:m]
        out_i[s:s + m] = np.asarray(best_i)[:m]
    return out_d, out_i


def sq_dists64(corpus: np.ndarray, queries: np.ndarray, ids: np.ndarray,
               chunk: int = 512) -> np.ndarray:
    """Squared L2 distances in float64 of each query to the rows ``ids``
    (Q, m); ids below 0 give +inf."""
    out = np.full(ids.shape, np.inf, np.float64)
    for s in range(0, len(ids), chunk):
        i = ids[s:s + chunk]
        ok = i >= 0
        x = corpus[np.where(ok, i, 0)].astype(np.float64)
        q = queries[s:s + chunk].astype(np.float64)[:, None, :]
        out[s:s + chunk] = np.where(ok, ((x - q) ** 2).sum(-1), np.inf)
    return out


def exact_topk(corpus: np.ndarray, queries: np.ndarray, k: int, *,
               shortlist: int = 64, **live) -> Tuple[np.ndarray, np.ndarray]:
    """Exact top-k live rows of each query: (ids (Q, k), float64 squared
    distances (Q, k)), ties broken by the smaller id."""
    kk = min(shortlist, len(corpus))
    d32, cand = shortlist_topk(corpus, queries, kk, **live)
    cand = np.where(np.isfinite(d32), cand, -1)
    d64 = sq_dists64(corpus, queries, cand)
    order = np.lexsort((cand, d64), axis=-1)[:, :k]
    return (np.take_along_axis(cand, order, 1),
            np.take_along_axis(d64, order, 1))
