"""Distributed-engine correctness on a small simulated mesh: the naive and
shardwise pod search steps must both match the single-device reference.

Runs in a subprocess so the 8-device XLA flag never leaks into other tests.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.distributed import (PodIndexSpec, make_pod_search_step,
                                    pod_shardings)
from repro.core import IndexConfig, PilotANNIndex, SearchParams, \
    brute_force_topk, recall_at_k
from repro.data import synthetic_vectors

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

# small real index -> pod arrays
ds = synthetic_vectors(2048, 16, n_queries=64, seed=0)
idx = PilotANNIndex(IndexConfig(R=8, sample_ratio=0.4, svd_ratio=0.5,
                                n_entry=512, fes_clusters=4,
                                build_method="exact"), ds.vectors)
n = idx.n
dp = idx.reducer.d_primary
keep_ids = idx.keep_ids
pilot_compact = {i: c for c, i in enumerate(keep_ids)}

# compact pilot arrays (distributed layout: pilot ids are compacted)
R = 8
np_pilot = len(keep_ids)
pilot_nb = np.full((np_pilot + 1, R), np_pilot, np.int32)
sub_nb = idx.sub_graph.neighbors
for c, i in enumerate(keep_ids):
    row = sub_nb[i]
    row = row[row < n]
    pilot_nb[c, :len(row)] = [pilot_compact[j] for j in row]
rot = np.asarray(idx.arrays["rot_vecs"])[:-1]
pilot_vecs = np.concatenate([rot[keep_ids][:, :dp],
                             np.zeros((1, dp), np.float32)], 0)
pilot_to_full = np.concatenate([keep_ids, [n]]).astype(np.int32)

Npad = ((n + 1 + 7) // 8) * 8
full_nb = np.full((Npad, R), Npad - 1, np.int32)
fg = idx.full_graph.neighbors[:, :R]
full_nb[:n] = np.where(fg < n, fg, Npad - 1)
full_vecs = np.zeros((Npad, rot.shape[1]), np.float32)
full_vecs[:n] = rot

fes = idx.fes_index
# remap fes entry ids into... they are full-corpus ids; pilot stage needs
# compact ids: build compact entry table
ent_ids = fes.entry_ids.copy()
for a in range(ent_ids.shape[0]):
    for b in range(ent_ids.shape[1]):
        v = ent_ids[a, b]
        ent_ids[a, b] = pilot_compact.get(int(v), np_pilot)

spec = PodIndexSpec(n=Npad - 1, d=rot.shape[1], d_primary=dp, R=R,
                    n_pilot=np_pilot, fes_r=fes.centroids.shape[0],
                    fes_capacity=fes.entries.shape[1], query_batch=64,
                    ef_pilot=16, ef=16, pilot_iters=24, final_iters=24,
                    bloom_bits=4096)
queries = np.asarray(idx.rotate_queries(ds.queries))

arrays = dict(
    pilot_neighbors=pilot_nb, pilot_vecs=pilot_vecs,
    pilot_scale=np.ones(dp, np.float32),
    pilot_to_full=pilot_to_full,
    fes_centroids=fes.centroids, fes_entries=fes.entries[..., :dp] if fes.entries.shape[-1] != dp else fes.entries,
    fes_scale=np.ones(dp, np.float32),
    fes_entry_ids=ent_ids, fes_valid=fes.valid,
    full_neighbors=full_nb, full_vecs=full_vecs, queries=queries)

gt = brute_force_topk(ds.vectors, ds.queries, 10)
results = {}
with mesh:
    for mode, cax, qspec in (("naive", ("data", "model"), None),
                             ("shardwise", ("model",), P("data", None))):
        shards = pod_shardings(spec, mesh, corpus_axes=cax,
                               query_axes=None if mode == "naive" else ("data",))
        fn = make_pod_search_step(spec, SearchParams(k=10, ef=16, ef_pilot=16,
                                                     fes_L=8, bloom_bits=4096),
                                  gather_mode=mode, unroll=False, mesh=mesh,
                                  corpus_axes=cax, query_spec=qspec)
        order = list(arrays.keys())
        jfn = jax.jit(fn, in_shardings=tuple(shards[k] for k in order))
        ids, dists = jfn(*[jnp.asarray(arrays[k]) for k in order])
        ids = np.asarray(ids)
        ids = np.where(ids < n, ids, 0)
        results[mode] = recall_at_k(ids, gt, 10)

print(json.dumps(results))
"""


@pytest.mark.slow
def test_pod_search_naive_and_shardwise_agree(tmp_path):
    script = tmp_path / "pod_test.py"
    script.write_text(SCRIPT)
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath(os.path.join(
                   os.path.dirname(__file__), "..", "src")))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["naive"] >= 0.7, res
    assert res["shardwise"] >= 0.7, res
    assert abs(res["naive"] - res["shardwise"]) < 0.1, res
