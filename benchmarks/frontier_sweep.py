"""Multi-frontier width sweep: hops-to-convergence, distance comps, us/query.

For W in {1, 2, 4, 8} runs the batched greedy traversal to convergence on the
benchmark index — unfused jnp rounds and the fused Pallas hop kernel — and
reports per-W: mean expansion rounds to convergence (``hops``), mean expanded
candidates (``exp``), mean distance computations (``dist``) and recall@10
against brute force.  A final section compares the persistent whole-search
kernel (one pallas_call for the entire search, DESIGN.md §3) against the
per-hop pallas_call chain at the same W.

The perf claim being tracked (§Perf hillclimb): W>1 trades a modest increase
in distance computations for a W-fold cut in rounds — the round count is the
serial depth of the search, which is what the accelerator latency follows —
at equal recall.  On a CPU backend the fused/persistent paths run through
the Pallas *interpreter* (kernels/backend.resolve_interpret), so their
absolute us/query measures emulation, not TPU silicon; on a TPU they are
compiled.  The hop/dist counters are load-bearing everywhere.

  PYTHONPATH=src python -m benchmarks.run --only frontier_sweep
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_line, get_gt, get_index, timed
from repro.core import recall_at_k
from repro.core import traversal as T

WIDTHS = (1, 2, 4, 8)
# small index + moderate degree: the fused paths run interpreted on CPU, and
# the interpreter's per-slot gather cost scales with B·n·W·R
SCALE = dict(n=4000, d=32, R=16)
B, EF = 32, 32


def _search_fn(spec: T.TraversalSpec, n: int):
    @jax.jit
    def run(q, nbrs, vecs, entries):
        st = T.greedy_search(spec, q, nbrs, vecs, n, entries)
        return st.cand_id, st.cand_d, st.n_dist, st.n_hops, st.n_exp
    return run


def run(n: int = None):
    index, vectors, queries = get_index(**SCALE)
    n_nodes = index.n
    gt = get_gt(SCALE["n"], SCALE["d"], 256)[:B]  # nq: benchmarks.common.SCALE
    q = index.rotate_queries(queries[:B])
    nbrs = index.arrays["full_neighbors"]
    vecs = index.arrays["rot_vecs"]
    entries = jnp.broadcast_to(index.arrays["default_entries"], (B, 1))

    base_hops = {}
    for fused in (False, True):
        for W in WIDTHS:
            spec = T.TraversalSpec(ef=EF, visited_mode="bloom",
                                   frontier_width=W, use_pallas=fused)
            fn = _search_fn(spec, n_nodes)
            dt, out = timed(lambda: jax.block_until_ready(
                fn(q, nbrs, vecs, entries)))
            ids, _, nd, nh, ne = (np.asarray(a) for a in out)
            rec = recall_at_k(ids[:, :10], gt, 10)
            tag = "fused" if fused else "unfused"
            base_hops[(fused, W)] = (dt, ids)
            print(csv_line(
                f"frontier_{tag}_w{W}", dt * 1e6 / B,
                f"hops={nh.mean():.1f};exp={ne.mean():.1f};"
                f"dist={nd.mean():.0f};recall={rec:.3f}"))

    # persistent whole-search kernel vs the per-hop pallas_call chain
    for W in (1, 4):
        spec = T.TraversalSpec(ef=EF, visited_mode="bloom", frontier_width=W,
                               use_pallas=True, use_persistent=True)
        fn = _search_fn(spec, n_nodes)
        dt, out = timed(lambda: jax.block_until_ready(
            fn(q, nbrs, vecs, entries)))
        dt_hop, ids_hop = base_hops[(True, W)]
        ids_equal = bool(np.array_equal(np.asarray(out[0]), ids_hop))
        print(csv_line(f"frontier_persistent_w{W}", dt * 1e6 / B,
                       f"per_hop_over_persistent={dt_hop / dt:.3f};"
                       f"ids_equal={ids_equal}"))


if __name__ == "__main__":
    run()
