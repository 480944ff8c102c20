"""Every Pallas kernel of the search path compiles for a TPU v5e.

The chip is described, not attached (``jax.experimental.topologies``), so
these tests need no accelerator: they lower and compile each kernel at the
widths of the DEEP-like one-chip deployment (d=96 split 48/48 by the SVD,
R=32, ef=128, a 128-query serving bucket, the 16384-bit bloom filter) and
check that Mosaic emitted the kernel (``tpu_custom_call``).  Interpret-mode
tests cannot show this: Mosaic refuses primitives (``rev``, ``cumsum``),
boolean vector layouts and VMEM overruns that the interpreter accepts.

The topology is described inside a fixture, never at import time: only one
process may hold the TPU library, and every pytest worker imports this
file.  The persistent compilation cache is off around these compiles (an
entry written for a described chip cannot be read back without one).
"""

import os

import pytest

import jax
import jax.numpy as jnp

B, DP, R, EF, BLOOM_BITS = 128, 48, 32, 128, 16384
# the largest pilot row counts (to 256 rows) at which the persistent kernel
# still fits its scoped VMEM (kernels/traversal_kernel.VMEM_LIMIT_BYTES);
# 256 rows more and the compiler refuses it (DESIGN.md §3)
PILOT_ROWS_MAX = {"float32": 56_832, "int8": 58_112}
SMALL_PILOT = 12_500          # the pilot of a 50k-row index at ratio 0.25


@pytest.fixture(scope="module")
def topo():
    old = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    if old is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: an abstract argument on one described chip."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)


def _compiles_to_kernel(fn, *args) -> bool:
    text = jax.jit(fn).lower(*args).compile().as_text()
    return "tpu_custom_call" in text


def _pilot_args(spec, n: int, pdt):
    """(q, nbr_table, vec_table, beam_id, beam_d, beam_ck, visited) for a
    pilot of ``n`` rows, ids in the width the index stores them."""
    idt = jnp.int16 if n + 1 <= jnp.iinfo(jnp.int16).max else jnp.int32
    return (spec((B, DP), jnp.float32), spec((n + 1, R), idt),
            spec((n + 1, DP), pdt), spec((B, EF), jnp.int32),
            spec((B, EF), jnp.float32), spec((B, EF), jnp.bool_),
            spec((B, BLOOM_BITS), jnp.bool_))


def test_fes_select_compiles(spec):
    from repro.core.fes import fes_capacity_cap
    from repro.kernels.ops import fes_select
    r = 32
    C = fes_capacity_cap(8192, r)
    fn = lambda *a: fes_select(*a, L=32, interpret=False)
    assert _compiles_to_kernel(
        fn, spec((B, DP), jnp.float32), spec((r, DP), jnp.float32),
        spec((r, C, DP), jnp.float32), spec((r, C), jnp.int32),
        spec((r, C), jnp.bool_))


def test_fused_expand_merge_compiles(spec):
    from repro.kernels.topk_kernel import fused_expand_merge
    n = SMALL_PILOT
    fn = lambda *a: fused_expand_merge(*a, n, interpret=False)
    assert _compiles_to_kernel(
        fn, spec((B, DP), jnp.float32), spec((B, R, DP), jnp.float32),
        spec((B, R), jnp.int32), spec((B, R), jnp.bool_),
        spec((B, EF), jnp.int32), spec((B, EF), jnp.float32),
        spec((B, EF), jnp.bool_))


@pytest.mark.parametrize("width", [1, 4])
def test_fused_traversal_hop_compiles(spec, width):
    from repro.kernels.traversal_kernel import fused_traversal_hop
    n = SMALL_PILOT
    fn = lambda *a: fused_traversal_hop(*a, n, width=width, interpret=False)
    assert _compiles_to_kernel(fn, *_pilot_args(spec, n, jnp.float32))


@pytest.mark.parametrize("pdt", ["float32", "int8"])
def test_fused_pilot_search_compiles_at_vmem_bound(spec, pdt):
    from repro.kernels.traversal_kernel import fused_pilot_search
    n = PILOT_ROWS_MAX[pdt]
    args = _pilot_args(spec, n, jnp.dtype(pdt))
    if pdt == "int8":
        fn = lambda *a: fused_pilot_search(*a[:-1], n, rounds=64,
                                           interpret=False, vec_scale=a[-1])
        args = args + (spec((DP,), jnp.float32),)
    else:
        fn = lambda *a: fused_pilot_search(*a, n, rounds=64, interpret=False)
    assert _compiles_to_kernel(fn, *args)


def test_fused_candidate_merge_compiles(spec):
    from repro.kernels.build_kernel import fused_candidate_merge
    K, S = 64, 16                     # NN-descent lists at R=32: K = 2R
    P = S * S + S                     # neighbours-of-neighbours + reverse
    n = 1_000_000
    fn = lambda *a: fused_candidate_merge(*a, n, interpret=False)
    assert _compiles_to_kernel(
        fn, spec((1024, K), jnp.int32), spec((1024, K), jnp.float32),
        spec((1024, P), jnp.int32), spec((1024, P), jnp.float32))
