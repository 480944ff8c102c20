"""The benchmark's own DEEP-like corpus generator.

The distribution is that of ``repro.data.synthetic_vectors`` with the
``deep`` preset (spectral decay 0.6): anisotropic Gaussian clusters with
heavy-tailed sizes, 30% broad background rows, a random rotation, and
queries that are corpus rows perturbed by 5% of the mean row norm.  It is
kept here so that no change to the program can move the data the
benchmark measures on.

The mixture itself (per-dimension scales, cluster sizes and centres, the
rotation) is one fixed model per corpus size, as a deployment has one
corpus distribution; the run's seed draws the rows, with exactly the
model's share of rows in each cluster and in the background, in an order
of its own.  Left to the seed, the heavy-tailed cluster sizes moved the
search's work, and with it the served rate, by several percent from seed
to seed.  The model is drawn on the host; the rows on the device in one
jitted call, so a run's set-up pays for neither a host loop nor a large
host-to-device copy.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def seed_words(seed: int, n: int = 2) -> np.ndarray:
    """``n`` 31-bit words derived from any non-negative integer seed."""
    return np.random.SeedSequence(int(seed)).generate_state(n) >> 1


@dataclass
class Corpus:
    base: np.ndarray       # (n, d) float32, the index is built over these
    extra: np.ndarray      # (m, d) float32, same mixture, inserted later
    queries: np.ndarray    # (q, d) float32, perturbed base rows


MODEL_SEED = 0


def _mixture(n: int, rows: int, d: int, decay: float):
    """The fixed model of an ``n``-row corpus: scales, the cluster of each
    of ``rows`` rows in cluster order (the exact shares of the cluster
    sizes), cluster centres, the rotation."""
    rng = np.random.default_rng(MODEL_SEED)
    n_clusters = max(8, int(np.sqrt(n) / 8))
    scales = np.arange(1, d + 1, dtype=np.float32) ** (-decay)
    scales /= np.sqrt((scales ** 2).mean())
    sizes = np.minimum(rng.zipf(1.5, size=n_clusters), 50).astype(np.float64)
    share = rows * sizes / sizes.sum()
    counts = np.floor(share).astype(np.int64)
    counts[np.argsort(counts - share)[:rows - counts.sum()]] += 1
    assign = np.repeat(np.arange(n_clusters, dtype=np.int32), counts)
    centers = rng.normal(size=(n_clusters, d)).astype(np.float32) * scales
    qmat, _ = np.linalg.qr(rng.normal(size=(d, d)))
    return scales, assign, centers, qmat.astype(np.float32)


@partial(jax.jit, static_argnames=("n_queries", "query_pool"))
def _draw(key, scales, assign, centers, qmat, *, n_queries: int,
          query_pool: int):
    k_assign, k_x, k_bg, k_bgx, k_qi, k_qn = jax.random.split(key, 6)
    rows, d = assign.shape[0], scales.shape[0]
    assign = jax.random.permutation(k_assign, assign)
    x = jax.random.normal(k_x, (rows, d)) * scales * 0.6 + centers[assign]
    bg = jax.random.permutation(k_bg, jnp.arange(rows)) < round(0.3 * rows)
    x = jnp.where(bg[:, None], jax.random.normal(k_bgx, (rows, d)) * scales
                  * 1.4, x)
    x = jnp.dot(x, qmat, precision=HIGHEST)
    qi = jax.random.randint(k_qi, (n_queries,), 0, query_pool)
    sigma = (0.05 * jnp.linalg.norm(x[:query_pool], axis=1).mean()
             / np.sqrt(d))
    q = x[qi] + jax.random.normal(k_qn, (n_queries, d)) * sigma
    return x, q


def deep_like(seed: int, n: int, d: int, *, n_extra: int, n_queries: int,
              decay: float = 0.6) -> Corpus:
    """``n`` base rows and ``n_extra`` rows to insert later, all from one
    mixture, and ``n_queries`` queries near the base rows; the same seed
    gives the same arrays on every backend."""
    w = seed_words(seed, 2)
    scales, assign, centers, qmat = _mixture(n, n + n_extra, d, decay)
    key = jax.random.fold_in(jax.random.PRNGKey(int(w[0])), int(w[1]))
    x, q = _draw(key, scales, assign, centers, qmat, n_queries=n_queries,
                 query_pool=n)
    x = np.asarray(x)
    return Corpus(base=x[:n], extra=x[n:], queries=np.asarray(q))
