"""Properties for the graph-construction prune/augment helpers — hypothesis
when available, a seeded pseudo-random sweep otherwise (the container pins
dependencies, so the property tests must not require installing anything;
same policy as test_resilience.py).

These helpers are reused one node at a time by the streaming-insert repair
path (core/segments.py, DESIGN.md §6), so their invariants are pinned here
first: occlusion-pruned degree never exceeds the cap, kept edges are a
subset of the candidates, the occlusion predicate is monotone in alpha (at
the first divergence of two greedy scans the larger alpha is always the
one that keeps — the localized form of "larger alpha keeps more"; the
*global* kept-set superset claim is false once earlier keeps feed back
into later occlusion tests), and reverse-edge augmentation never exceeds
the degree bound.

The device build/repair mirrors (core/device_build.py, DESIGN.md §9) are
held to the same invariants plus two cross-path properties: the bulk
occlusion prune must agree with the host scan decision-for-decision, and
NN-descent candidate distances must be monotone non-increasing across
rounds (the merge keeps the best of every duplicate, so each rank can
only improve)."""

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                            # pragma: no cover - env dep
    HAVE_HYPOTHESIS = False

    class _S:
        """A sampler standing in for one hypothesis strategy."""

        def __init__(self, draw):
            self.draw = draw

    class _St:
        @staticmethod
        def integers(lo, hi):
            return _S(lambda rng: int(rng.integers(lo, hi + 1)))

        @staticmethod
        def sampled_from(xs):
            return _S(lambda rng: xs[int(rng.integers(len(xs)))])

        @staticmethod
        def floats(lo, hi):
            return _S(lambda rng: float(rng.uniform(lo, hi)))

        @staticmethod
        def booleans():
            return _S(lambda rng: bool(rng.integers(2)))

    st = _St()

    def settings(**_kw):
        return lambda f: f

    def given(*strats):
        """Seeded fallback for @given: run the test body on a fixed tape
        of pseudo-random draws from the same parameter shapes."""
        def deco(f):
            def wrapper():
                rng = np.random.default_rng(0xC0FFEE)
                for _ in range(12):
                    f(*(s.draw(rng) for s in strats))
            # keep the name/doc but NOT the signature (pytest would try
            # to resolve the sample parameters as fixtures)
            wrapper.__name__ = f.__name__
            wrapper.__doc__ = f.__doc__
            return wrapper
        return deco

from repro.core.device_build import (build_graph_device, nn_descent,
                                     occlusion_prune_device, prune_batch)
from repro.core.graph_build import (add_reverse_edges, brute_knn, occludes,
                                    occlusion_prune, patch_reverse_edges,
                                    prune_one)


def random_lists(x, K, seed):
    """Each row's K distinct random other rows, sorted by exact squared
    distance: NN-descent's classic random start."""
    n = len(x)
    rng = np.random.default_rng(seed)
    ids = np.stack([rng.permutation(np.delete(np.arange(n), i))[:K]
                    for i in range(n)])
    d = ((x[ids] - x[:, None, :]) ** 2).sum(-1)
    o = np.argsort(d, axis=1)
    return (np.take_along_axis(ids, o, 1).astype(np.int32),
            np.take_along_axis(d, o, 1).astype(np.float32))


def descent_rounds(x, ids, dd, rounds, S, *, flags=True, use_pallas=False):
    """The lists after each of ``rounds`` NN-descent rounds from (ids, dd).
    ``flags=False`` clears the sampled flags every round, so each round
    samples the S nearest entries again."""
    from repro.core.device_build import _nn_descent_round
    n, dim = x.shape
    x_pad = jnp.asarray(np.concatenate([x, np.zeros((1, dim), np.float32)]))
    xsq = jnp.sum(x_pad * x_pad, axis=-1)
    ids, dd = jnp.asarray(ids), jnp.asarray(dd)
    used = jnp.zeros(ids.shape, bool)
    out = []
    for _ in range(rounds):
        ids, dd, used = _nn_descent_round(
            x_pad, xsq, ids, dd, used if flags else jnp.zeros_like(used),
            n=n, S=S, block=min(1024, n), use_pallas=use_pallas,
            interpret=True if use_pallas else None)
        out.append((np.asarray(ids), np.asarray(dd)))
    return out


def list_recall(ids, gt):
    return float(np.mean([len(set(a) & set(b))
                          for a, b in zip(ids.tolist(), gt.tolist())])
                 ) / gt.shape[1]


def _dataset(seed, n=48, d=6, K=16):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    ids, dd = brute_knn(x, K)
    return x, ids, dd


@settings(deadline=None, max_examples=25)
@given(st.integers(0, 10_000), st.sampled_from([4, 6, 8]),
       st.floats(1.0, 1.6), st.booleans())
def test_occlusion_prune_degree_and_subset(seed, R, alpha, keep_pruned):
    """Degree ≤ cap; every kept id is one of that node's candidates; no
    duplicates; with keep_pruned the slots fill to min(R, #candidates)."""
    x, ids, dd = _dataset(seed)
    n = len(x)
    nb = occlusion_prune(x, ids, dd, R, alpha=alpha, keep_pruned=keep_pruned)
    real = nb < n
    deg = real.sum(axis=1)
    assert (deg <= R).all()
    for i in range(n):
        kept = nb[i][real[i]]
        assert len(set(kept.tolist())) == len(kept)
        assert set(kept.tolist()) <= set(ids[i].tolist())
    if keep_pruned:
        avail = (ids < n).sum(axis=1)
        assert (deg == np.minimum(R, avail)).all()


@settings(deadline=None, max_examples=25)
@given(st.integers(0, 10_000), st.floats(1.0, 1.4), st.floats(0.01, 0.6))
def test_alpha_monotone_at_first_divergence(seed, a_lo, gap):
    """Greedy occlusion scans at alpha_lo < alpha_hi over the same
    candidate list: wherever the two kept sequences first diverge, it must
    be alpha_hi keeping a candidate alpha_lo pruned — never the reverse.
    (Up to the first divergence both scans hold the identical kept prefix,
    so the decision reduces to the predicate, and ``occludes`` is monotone:
    the threshold d_qc/alpha**2 only shrinks as alpha grows.)"""
    a_hi = a_lo + gap
    x, ids, dd = _dataset(seed)
    n = len(x)
    for i in range(0, n, 5):
        K = (ids[i] < n).sum()
        cv, cd = x[ids[i][:K]], dd[i][:K]
        lo = set(prune_one(cv, cd, K, alpha=a_lo, keep_pruned=False).tolist())
        hi = set(prune_one(cv, cd, K, alpha=a_hi, keep_pruned=False).tolist())
        order = np.argsort(cd, kind="stable")
        for j in order:
            in_lo, in_hi = j in lo, j in hi
            if in_lo != in_hi:
                assert in_hi and not in_lo, \
                    f"first divergence kept by SMALLER alpha (cand {j})"
                break


@settings(deadline=None, max_examples=20)
@given(st.integers(0, 10_000))
def test_occludes_predicate_monotone(seed):
    rng = np.random.default_rng(seed)
    d_kc = rng.uniform(0, 4, 64)
    d_qc = rng.uniform(0, 4, 64)
    a1, a2 = sorted(rng.uniform(1.0, 2.0, 2))
    assert not (occludes(d_kc, d_qc, a2) & ~occludes(d_kc, d_qc, a1)).any()


@settings(deadline=None, max_examples=20)
@given(st.integers(0, 10_000), st.sampled_from([4, 6]))
def test_reverse_augmentation_degree_bound(seed, R):
    """add_reverse_edges (bulk build) and patch_reverse_edges (streaming
    repair, with occlusion re-prune on full rows) both respect the degree
    bound and keep edges in-range with no self loops."""
    x, ids, dd = _dataset(seed)
    n = len(x)
    nb = occlusion_prune(x, ids, dd, R, alpha=1.2)
    bulk = add_reverse_edges(nb.copy(), n, R)
    assert ((bulk < n).sum(axis=1) <= R).all()
    assert (bulk <= n).all() and (bulk >= 0).all()

    patched = nb.copy()
    new_src = np.arange(0, n, 7)
    patch_reverse_edges(patched, x, new_src, n, R, alpha=1.2)
    real = patched < n
    assert (real.sum(axis=1) <= R).all()
    rows = np.broadcast_to(np.arange(n)[:, None], patched.shape)
    assert not (real & (patched == rows)).any(), "self loop"
    # every row still holds a valid set (no duplicates among real edges)
    for i in range(n):
        kept = patched[i][real[i]]
        assert len(set(kept.tolist())) == len(kept)


@settings(deadline=None, max_examples=15)
@given(st.integers(0, 10_000), st.sampled_from([3, 5]))
def test_prune_one_occluder_only_candidates(seed, R):
    """edge_ok=False candidates (base-segment occluders in the insert
    repair) influence pruning but never become edges."""
    rng = np.random.default_rng(seed)
    K = 14
    cv = rng.normal(size=(K, 5)).astype(np.float32)
    cd = (cv * cv).sum(-1).astype(np.float32)
    edge_ok = rng.random(K) < 0.6
    kept = prune_one(cv, cd, R, alpha=1.2, edge_ok=edge_ok)
    assert len(kept) <= R
    assert edge_ok[kept].all()
    assert len(set(kept.tolist())) == len(kept)
    # with everything edge-eligible and keep_pruned, slots fill up
    full = prune_one(cv, cd, R, alpha=1.2)
    assert len(full) == min(R, K)


# ---------------------------------------------------------------------------
# device build/repair mirrors (core/device_build.py, DESIGN.md §9)
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=8)
@given(st.integers(0, 10_000), st.sampled_from([4, 6, 8]),
       st.floats(1.0, 1.6), st.booleans())
def test_occlusion_prune_host_device_invariance(seed, R, alpha, keep_pruned):
    """The jit'd bulk prune must make exactly the host scan's decisions:
    identical adjacency (ids AND order) for the same candidate lists."""
    x, ids, dd = _dataset(seed)
    host = occlusion_prune(x, ids, dd, R, alpha=alpha,
                           keep_pruned=keep_pruned)
    dev = occlusion_prune_device(x, ids, dd, R, alpha=alpha,
                                 keep_pruned=keep_pruned)
    assert np.array_equal(host, dev)


@settings(deadline=None, max_examples=8)
@given(st.integers(0, 10_000), st.sampled_from([3, 5]), st.booleans())
def test_prune_batch_matches_prune_one(seed, R, keep_pruned):
    """prune_batch row i == prune_one on row i, including the edge_ok
    occluder semantics and the keep-pruned backfill append order."""
    rng = np.random.default_rng(seed)
    B, K = 6, 14
    cv = rng.normal(size=(B, K, 5)).astype(np.float32)
    cd = ((cv - rng.normal(size=(B, 1, 5)).astype(np.float32)) ** 2
          ).sum(-1).astype(np.float32)
    ok = rng.random((B, K)) < 0.7
    got = prune_batch(cv, cd, R, alpha=1.2, edge_ok=ok,
                      keep_pruned=keep_pruned)
    for i in range(B):
        want = prune_one(cv[i], cd[i], R, alpha=1.2, edge_ok=ok[i],
                         keep_pruned=keep_pruned)
        have = got[i][got[i] >= 0]
        assert np.array_equal(have, want), (i, have, want)


@settings(deadline=None, max_examples=4)
@given(st.integers(0, 10_000), st.sampled_from([4, 6]))
def test_device_builder_graph_invariants(seed, R):
    """build_graph_device output: degree ≤ R, ids in [0, n], no self
    edges, no duplicate edges within a row."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(96, 8)).astype(np.float32)
    g = build_graph_device(x, R, rounds=4, repair=False)
    n = len(x)
    nb = g.neighbors
    real = nb < n
    assert (real.sum(axis=1) <= R).all()
    assert (nb >= 0).all() and (nb <= n).all()
    rows = np.broadcast_to(np.arange(n)[:, None], nb.shape)
    assert not (real & (nb == rows)).any(), "self loop"
    for i in range(n):
        kept = nb[i][real[i]]
        assert len(set(kept.tolist())) == len(kept)


@settings(deadline=None, max_examples=4)
@given(st.integers(0, 10_000), st.sampled_from([4, 6]))
def test_reverse_candidates_union(seed, R):
    """reverse_candidates: each row's list is its k-NN candidates plus
    every row whose pruned list points at it (up to R), deduplicated,
    sorted by exact distance, sentinel-padded."""
    from repro.core.device_build import reverse_candidates
    x, ids, dd = _dataset(seed, K=8)
    n = len(x)
    kept = occlusion_prune(x, ids, dd, R, keep_pruned=False)
    oi, od = reverse_candidates(x, ids, dd, kept)
    assert oi.shape == (n, 8 + R)
    for v in range(n):
        srcs = np.flatnonzero((kept == v).any(axis=1))[:R]
        want = set(ids[v][ids[v] < n].tolist()) | set(srcs.tolist())
        live = oi[v][oi[v] < n]
        assert len(set(live.tolist())) == len(live)
        assert set(live.tolist()) == want
        d = ((x[live] - x[v]) ** 2).sum(-1)
        np.testing.assert_allclose(od[v][:len(live)], d, rtol=1e-5,
                                   atol=1e-5)
        assert (np.diff(od[v][:len(live)]) >= 0).all()
        assert np.isinf(od[v][len(live):]).all()


@settings(deadline=None, max_examples=3)
@given(st.integers(0, 10_000))
def test_nn_descent_monotone_rounds(seed):
    """Per-rank candidate distances never increase from round r to r+1:
    the merge keeps the best of every duplicate, so each node's k-th best
    distance is monotone non-increasing (inf = empty slot may only fill)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(128, 8)).astype(np.float32)
    prev = None
    lists = descent_rounds(x, *random_lists(x, 8, seed), 4, 4)
    for r, (_, dd) in enumerate(lists, 1):
        if prev is not None:
            worse = dd > prev
            assert not worse.any(), \
                f"round {r}: {int(worse.sum())} ranks got worse"
        prev = dd


@pytest.mark.parametrize("seed", [0, 1])
def test_nn_descent_rounds_raise_list_recall(seed):
    """From random lists every round finds more of the true 8 nearest,
    and sampling each node's not-yet-sampled entries first (the "new"
    flags) ends ahead of sampling its S nearest every round, which
    re-joins the same neighbourhoods."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(512, 8)).astype(np.float32)
    gt, _ = brute_knn(x, 8)
    ids, dd = random_lists(x, 8, seed)
    flags = [list_recall(i, gt) for i, _ in descent_rounds(x, ids, dd, 6, 4)]
    nearest = [list_recall(i, gt) for i, _ in
               descent_rounds(x, ids, dd, 6, 4, flags=False)]
    recall = [list_recall(ids, gt)] + flags
    assert all(b > a for a, b in zip(recall, recall[1:])), recall
    assert flags[-1] > nearest[-1] + 0.03, (flags, nearest)
