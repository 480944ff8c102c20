"""Fused vs unfused stage-① hop throughput (pilot traversal kernel).

Runs a fixed number of pilot-stage expansion rounds over the subgraph +
SVD-primary vectors — once with the op-by-op jnp hop body and once with the
fused Pallas kernel (kernels/traversal_kernel.py) — and reports hops/s.

On a CPU backend the fused path runs through the Pallas *interpreter*
(kernels/backend.resolve_interpret), so its absolute numbers measure
emulation, not TPU silicon; on a TPU the kernel is compiled.

  PYTHONPATH=src python -m benchmarks.run --only pilot_kernel
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_line, get_index, timed
from repro.core import traversal as T


HOPS = 16


def _stage1_fn(spec: T.TraversalSpec, n: int):
    @jax.jit
    def run(q, sub_neighbors, primary, entries):
        st = T.greedy_search(spec, q, sub_neighbors, primary, n,
                             entries, iters=HOPS)
        return st.cand_id, st.cand_d, st.n_dist
    return run


def run(n: int = None, B: int = 64, ef: int = 64):
    index, vectors, queries = get_index(n=n)
    # stage ① runs in the compact pilot id space (DESIGN.md §4)
    n_nodes = index.n_pilot
    rng = np.random.default_rng(0)
    q = index.rotate_queries(queries[:B])[:, :index.reducer.d_primary]
    entries = jnp.asarray(
        rng.integers(0, n_nodes, size=(B, 4)).astype(np.int32))
    sub = index.arrays["sub_neighbors"]
    prim = index.arrays["primary"]

    results = {}
    for name, spec in [
        ("unfused", T.TraversalSpec(ef=ef, visited_mode="bloom")),
        ("fused", T.TraversalSpec(ef=ef, visited_mode="bloom",
                                  use_pallas=True)),
    ]:
        fn = _stage1_fn(spec, n_nodes)
        dt, out = timed(lambda: jax.block_until_ready(
            fn(q, sub, prim, entries)))
        hops_per_s = HOPS * B / dt
        results[name] = (dt, out)
        print(csv_line(f"pilot_hop_{name}", dt * 1e6 / (HOPS * B),
                       f"hops_per_s={hops_per_s:.0f}"))

    (dt_u, out_u), (dt_f, out_f) = results["unfused"], results["fused"]
    ids_equal = bool(np.array_equal(np.asarray(out_u[0]),
                                    np.asarray(out_f[0])))
    print(f"pilot_hop_fused_speedup,{dt_u / dt_f:.3f},"
          f"unfused_over_fused_walltime_ratio ids_equal={ids_equal}")


if __name__ == "__main__":
    run()
