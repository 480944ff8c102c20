"""Build-parity test layer for the device-resident graph build & repair
(core/device_build.py, DESIGN.md §9).

Four parity contracts, strongest first:

* **kernel vs oracle** — the fused Pallas candidate-merge must reproduce
  ``kernels/ref.candidate_merge_ref`` bit-for-bit (interpret mode on CPU),
  including duplicate-id dedupe and the (distance, id) tie order.
* **single-insert repair bit-parity** — ``SegmentedIndex.insert`` of one
  row at a time must leave an IDENTICAL delta adjacency under
  ``repair_method="host"`` and ``"device"`` (the batched primitives
  degenerate to the host scan for B=1).
* **post-insert search parity** — after the same insert stream, the host-
  and device-repaired indexes must return the same results (delta scoring
  below ``brute_threshold`` is exact, so this pins the bookkeeping; the
  adjacency bit-parity above pins the graphs).
* **build recall parity** — a ``build_method="nn_descent"`` index must
  search within ±1% recall of the ``"exact"`` host build at equal ef on a
  4k corpus.

The ``-m multidevice`` case reruns insert + search parity with the device
path on a ShardedSegmentedIndex over 8 forced CPU devices.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (IndexConfig, PilotANNIndex, SearchParams,
                        brute_force_topk, recall_at_k)
from repro.core import device_build
from repro.core.graph_build import build_graph
from repro.core.segments import SegmentedIndex, UpdateParams
from repro.data import synthetic_vectors
from repro.kernels.build_kernel import MAX_ID_EXACT, fused_candidate_merge
from repro.kernels.ref import candidate_merge_ref

CFG = dict(R=8, sample_ratio=0.5, svd_ratio=0.5, n_entry=64, fes_clusters=4,
           build_method="exact")


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------

def _merge_case(seed, B=12, K=16, P=24, n=1000):
    """Candidate/proposal lists with sentinels and cross-list duplicates."""
    rng = np.random.default_rng(seed)
    cid = rng.integers(0, n, (B, K)).astype(np.int32)
    pid = rng.integers(0, n, (B, P)).astype(np.int32)
    # duplicates across the two lists (the dedupe path under test)
    pid[:, :4] = cid[:, :4]
    # sentinel (empty) slots
    cid[:, K - 2:] = n
    pid[rng.random((B, P)) < 0.1] = n
    cd = rng.uniform(0, 4, (B, K)).astype(np.float32)
    pd_ = rng.uniform(0, 4, (B, P)).astype(np.float32)
    cd[cid >= n] = np.float32(np.inf)
    # duplicated ids carry different distances; the merge must keep min
    pd_[:, :2] = cd[:, :2] + 0.5
    pd_[:, 2:4] = np.maximum(cd[:, 2:4] - 0.25, 0)
    return cid, cd, pid, pd_, n


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fused_candidate_merge_matches_oracle(seed):
    cid, cd, pid, pd_, n = _merge_case(seed)
    ref_i, ref_d = candidate_merge_ref(jnp.asarray(cid), jnp.asarray(cd),
                                       jnp.asarray(pid), jnp.asarray(pd_), n)
    got_i, got_d = fused_candidate_merge(jnp.asarray(cid), jnp.asarray(cd),
                                         jnp.asarray(pid), jnp.asarray(pd_),
                                         n, interpret=True)
    assert np.array_equal(np.asarray(ref_i), np.asarray(got_i))
    ref_d, got_d = np.asarray(ref_d), np.asarray(got_d)
    live = np.asarray(ref_i) < n
    assert np.array_equal(ref_d[live], got_d[live])


def test_fused_merge_rejects_inexact_id_space():
    cid, cd, pid, pd_, _ = _merge_case(0)
    with pytest.raises(ValueError):
        fused_candidate_merge(jnp.asarray(cid), jnp.asarray(cd),
                              jnp.asarray(pid), jnp.asarray(pd_),
                              MAX_ID_EXACT, interpret=True)


def test_nn_descent_pallas_route_matches_jnp():
    """NN-descent rounds with the Pallas merge (interpret mode) produce
    the same candidate lists as the pure-jnp route, from random starting
    lists (the brute-force seed is exact on the CPU, so rounds after it
    would have nothing to change), and so does the whole nn_descent."""
    rng = np.random.default_rng(7)
    x = rng.normal(size=(300, 12)).astype(np.float32)
    n = len(x)
    ids = np.stack([rng.permutation(np.delete(np.arange(n), i))[:8]
                    for i in range(n)]).astype(np.int32)
    dd = ((x[ids] - x[:, None, :]) ** 2).sum(-1).astype(np.float32)
    o = np.argsort(dd, axis=1)
    ids, dd = np.take_along_axis(ids, o, 1), np.take_along_axis(dd, o, 1)
    x_pad = jnp.asarray(np.concatenate([x, np.zeros((1, 12), np.float32)]))
    xsq = jnp.sum(x_pad * x_pad, axis=-1)
    routes = {}
    for pallas in (False, True):
        li, ld = jnp.asarray(ids), jnp.asarray(dd)
        used = jnp.zeros(li.shape, bool)
        for _ in range(3):
            li, ld, used = device_build._nn_descent_round(
                x_pad, xsq, li, ld, used, n=n, S=4, block=n,
                use_pallas=pallas, interpret=True if pallas else None)
        routes[pallas] = (np.asarray(li), np.asarray(ld), np.asarray(used))
    (ij, dj, uj), (ip, dp, up) = routes[False], routes[True]
    assert not np.array_equal(ij, ids)        # the rounds changed lists
    assert np.array_equal(ij, ip) and np.array_equal(uj, up)
    assert np.array_equal(dj[ij < n], dp[ip < n])
    ids_j, dd_j = device_build.nn_descent(x, 8, rounds=3, S=4,
                                          use_pallas=False)
    ids_p, dd_p = device_build.nn_descent(x, 8, rounds=3, S=4,
                                          use_pallas=True, interpret=True)
    assert np.array_equal(ids_j, ids_p)
    live = ids_j < len(x)
    assert np.array_equal(dd_j[live], dd_p[live])


# ---------------------------------------------------------------------------
# insert-repair parity (host vs device)
# ---------------------------------------------------------------------------

def _fresh(method, base, **up_kw):
    up = UpdateParams(repair_method=method, repair_knn=8, repair_ef=32,
                      **up_kw)
    return SegmentedIndex(IndexConfig(**CFG), base, update_params=up)


def test_single_insert_repair_bit_parity():
    rng = np.random.default_rng(0)
    base = rng.normal(size=(500, 24)).astype(np.float32)
    stream = rng.normal(size=(32, 24)).astype(np.float32)
    idx_h, idx_d = _fresh("host", base), _fresh("device", base)
    for v in stream:
        gh = idx_h.insert(v)
        gd = idx_d.insert(v)
        assert np.array_equal(gh, gd)
    sh, sd = idx_h.deltas[-1], idx_d.deltas[-1]
    assert sh.m == sd.m == len(stream)
    assert np.array_equal(sh.neighbors[:sh.m], sd.neighbors[:sd.m]), \
        "single-insert device repair diverged from the host scan"


def test_post_insert_search_parity():
    """Same batched insert/delete stream through both repair paths: the
    searched ids/dists must agree (exact delta scoring + identical base)."""
    rng = np.random.default_rng(1)
    base = rng.normal(size=(600, 24)).astype(np.float32)
    stream = rng.normal(size=(48, 24)).astype(np.float32)
    q = rng.normal(size=(16, 24)).astype(np.float32)
    sp = SearchParams(k=10, ef=32, ef_pilot=32)
    idx_h, idx_d = _fresh("host", base), _fresh("device", base)
    for idx in (idx_h, idx_d):
        idx.insert(stream[:20])
        idx.insert(stream[20:21])
        idx.insert(stream[21:])
        idx.delete(np.arange(600, 610))
    ih, dh, _ = idx_h.search(q, sp)
    id_, dd, _ = idx_d.search(q, sp)
    assert np.array_equal(np.asarray(ih), np.asarray(id_))
    assert np.allclose(np.asarray(dh), np.asarray(dd), rtol=1e-5, atol=1e-5)


def test_repair_method_validation():
    rng = np.random.default_rng(2)
    base = rng.normal(size=(64, 8)).astype(np.float32)
    idx = _fresh("bogus", base)
    with pytest.raises(ValueError, match="repair_method"):
        idx.insert(base[:2])


def test_batched_device_repair_invariants():
    """Batched inserts (where the device path may legally diverge from the
    sequential host order): degree bound, no self loops, no duplicate
    edges, and every edge points at an appended row."""
    rng = np.random.default_rng(3)
    base = rng.normal(size=(400, 16)).astype(np.float32)
    idx = _fresh("device", base)
    for batch in np.split(rng.normal(size=(96, 16)).astype(np.float32), 4):
        idx.insert(batch)
    seg = idx.deltas[-1]
    nb = seg.neighbors[:seg.m]
    real = nb < seg.cap
    assert (real.sum(axis=1) <= seg.R).all()
    rows = np.broadcast_to(np.arange(seg.m)[:, None], nb.shape)
    assert not (real & (nb == rows)).any(), "self loop"
    assert (nb[real] < seg.m).all(), "edge to a never-appended row"
    for i in range(seg.m):
        kept = nb[i][real[i]]
        assert len(set(kept.tolist())) == len(kept)


# ---------------------------------------------------------------------------
# device build recall parity (the ±1% @ equal ef bar)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_device_build_recall_parity_4k():
    ds = synthetic_vectors(4000, 32, n_queries=128, seed=3)
    gt = brute_force_topk(ds.vectors, ds.queries, 10)
    sp = SearchParams(k=10, ef=48, ef_pilot=48)
    rec = {}
    for method in ("exact", "nn_descent"):
        cfg = IndexConfig(R=16, sample_ratio=0.4, svd_ratio=0.5,
                          n_entry=256, fes_clusters=8, build_method=method)
        idx = PilotANNIndex(cfg, ds.vectors)
        ids, _, _ = idx.search(ds.queries, sp)
        rec[method] = recall_at_k(np.asarray(ids), gt, 10)
    assert rec["nn_descent"] >= rec["exact"] - 0.01, rec
    assert rec["nn_descent"] >= 0.9, rec


def test_build_graph_dispatch_nn_descent():
    rng = np.random.default_rng(5)
    x = rng.normal(size=(200, 12)).astype(np.float32)
    g = build_graph(x, 8, method="nn_descent", seed=0)
    assert g.n == 200 and g.neighbors.shape[1] == 8
    with pytest.raises(ValueError, match="build method"):
        build_graph(x, 8, method="nope")


# ---------------------------------------------------------------------------
# sharded device repair (-m multidevice)
# ---------------------------------------------------------------------------

SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
from repro.core import IndexConfig, SearchParams
from repro.core.distributed import ShardParams, ShardedSegmentedIndex
from repro.core.segments import SegmentedIndex, UpdateParams

rng = np.random.default_rng(0)
x = rng.normal(size=(1024, 24)).astype(np.float32)
stream = rng.normal(size=(64, 24)).astype(np.float32)
q = rng.normal(size=(16, 24)).astype(np.float32)
cfg = IndexConfig(R=8, sample_ratio=0.5, svd_ratio=0.5, n_entry=64,
                  fes_clusters=4, build_method="exact")
up = UpdateParams(repair_method="device", repair_knn=8, repair_ef=32)
params = SearchParams(k=10, ef=32, ef_pilot=32)

ref = SegmentedIndex(cfg, x, up)
sh = ShardedSegmentedIndex(cfg, x, up, shard_params=ShardParams(n_shards=4))
for i in range(0, len(stream), 16):
    ref.insert(stream[i:i + 16])
    sh.insert(stream[i:i + 16], shard=(i // 16) % 4)
ref.delete(np.arange(100, 120))
sh.delete(np.arange(100, 120))

ri, rd, _ = ref.search(q, params)
si, sd, _ = sh.search(q, params)
print(json.dumps({
    "ids_equal": bool(np.array_equal(np.asarray(ri), np.asarray(si))),
    "dists_close": bool(np.allclose(np.asarray(rd), np.asarray(sd),
                                    rtol=1e-5, atol=1e-5)),
}))
"""


@pytest.mark.multidevice
def test_sharded_device_repair_matches_single_device(tmp_path):
    script = tmp_path / "sharded_device_repair.py"
    script.write_text(SHARDED_SCRIPT)
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath(os.path.join(
                   os.path.dirname(__file__), "..", "src")))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=1800)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res == {"ids_equal": True, "dists_close": True}, res
