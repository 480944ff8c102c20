"""Pallas kernel sweeps: shapes x dtypes against the pure-jnp oracles,
executed in interpret mode (kernel body runs in Python on CPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.fes import build_fes, fes_select_ref
from repro.kernels.fes_kernel import fes_distances
from repro.kernels.ops import fes_select
from repro.kernels.ref import expand_merge_ref, fes_distances_ref
from repro.kernels.topk_kernel import fused_expand_merge


@pytest.mark.parametrize("r,QC,C,d", [
    (2, 4, 128, 64), (4, 8, 128, 128), (8, 16, 256, 256),
    (32, 8, 128, 384), (1, 32, 512, 128),
])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_fes_distances_sweep(r, QC, C, d, dtype):
    rng = np.random.default_rng(42)
    qg = rng.normal(size=(r, QC, d)).astype(np.float32)
    ev = rng.normal(size=(r, C, d)).astype(np.float32)
    qj = jnp.asarray(qg).astype(dtype)
    ej = jnp.asarray(ev).astype(dtype)
    out = fes_distances(qj, ej, interpret=True)
    ref = fes_distances_ref(qj, ej)
    assert out.dtype == jnp.float32
    tol = 1e-3 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol * d)


@pytest.mark.parametrize("B,R,ef,d", [
    (64, 8, 16, 32), (128, 16, 32, 64), (128, 32, 64, 128), (256, 16, 48, 96),
])
def test_fused_expand_merge_sweep(B, R, ef, d):
    rng = np.random.default_rng(B + R)
    n = 5000
    q = rng.normal(size=(B, d)).astype(np.float32)
    nv = rng.normal(size=(B, R, d)).astype(np.float32)
    nid = rng.integers(0, n, (B, R)).astype(np.int32)
    fresh = rng.random((B, R)) > 0.3
    bid = rng.integers(0, n, (B, ef)).astype(np.int32)
    bd = np.sort(rng.random((B, ef)).astype(np.float32) * 50, axis=1)
    bck = rng.random((B, ef)) > 0.5
    args = [jnp.asarray(a) for a in (q, nv, nid, fresh, bid, bd, bck)]
    oi, od, oc = fused_expand_merge(*args, n, interpret=True)
    ri, rd, rc = expand_merge_ref(*args, n)
    np.testing.assert_array_equal(np.asarray(oi), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(od), np.asarray(rd), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(oc), np.asarray(rc))


@pytest.mark.parametrize("r,L", [(4, 4), (8, 8), (16, 16)])
def test_fes_select_ops_matches_core_ref(r, L):
    rng = np.random.default_rng(r)
    n, d = 4000, 48
    x = rng.normal(size=(n, d)).astype(np.float32)
    idx = build_fes(x, np.arange(n), r=r, n_entry=1024, align=128, seed=1)
    q = rng.normal(size=(64, d)).astype(np.float32)
    a = [jnp.asarray(t) for t in (idx.centroids, idx.entries, idx.entry_ids,
                                  idx.valid)]
    ids1, d1 = fes_select(jnp.asarray(q), *a, L=L, interpret=True)
    ids2, d2 = fes_select_ref(jnp.asarray(q), *a, L)
    np.testing.assert_array_equal(np.asarray(ids1), np.asarray(ids2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-4,
                               atol=1e-4)


def test_fes_distances_padding_safety():
    """Non-multiple C and d are padded by ops.fes_select; the raw kernel
    asserts alignment."""
    with pytest.raises(AssertionError):
        fes_distances(jnp.zeros((2, 4, 100)), jnp.zeros((2, 130, 100)),
                      interpret=True)


# ---------------------------------------------------------------------------
# where kernels run and where their compiles are kept
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,asked,expect", [
    ("cpu", None, True), ("cpu", True, True), ("cpu", False, False),
    ("tpu", None, False), ("tpu", False, False), ("tpu", True, ValueError),
])
def test_interpret_follows_backend(monkeypatch, backend, asked, expect):
    from repro.kernels import backend as KB
    monkeypatch.setattr(KB.jax, "default_backend", lambda: backend)
    if expect is ValueError:
        with pytest.raises(ValueError, match="interpret"):
            KB.resolve_interpret(asked)
    else:
        assert KB.resolve_interpret(asked) is expect


def test_fused_expand_merge_refuses_interpreter_on_tpu(monkeypatch):
    from repro.kernels import backend as KB
    monkeypatch.setattr(KB.jax, "default_backend", lambda: "tpu")
    B, R, ef, d, n = 8, 8, 16, 8, 100
    with pytest.raises(ValueError, match="interpret"):
        fused_expand_merge(
            jnp.zeros((B, d)), jnp.zeros((B, R, d)),
            jnp.zeros((B, R), jnp.int32), jnp.zeros((B, R), bool),
            jnp.full((B, ef), n, jnp.int32), jnp.zeros((B, ef)),
            jnp.ones((B, ef), bool), n, interpret=True)


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_directory(monkeypatch, tmp_path, env_dir):
    from pathlib import Path
    from repro.runtime import compile_cache as CC
    old = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(Path(CC.__file__).resolve().parents[3] / ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert CC.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
