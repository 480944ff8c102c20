"""Pallas TPU kernel for Fast Entry Selection (paper Algorithm 2).

TPU adaptation of the CUDA kernel (DESIGN.md §2):
  * the GPU version assigns one *thread block* per cluster and skips
    non-active queries inside the block (lines 9-11);  on TPU, dense MXU
    tiles make per-row skipping worthless, so the wrapper (ops.py) instead
    *groups queries by routed cluster* (one argsort) and pads each group to a
    fixed capacity QC — the kernel is then 100 % dense: zero wasted lanes,
    zero allocation, exactly the paper's "allocation-free tiled" property.
  * distances use the identity ‖q−e‖² = ‖q‖² + ‖e‖² − 2·q·eᵀ so the inner
    loop is a (QC×dt)·(dt×Ct) matmul on the MXU — the computational-density
    fix that is the whole point of FES (§5, Table 2).
  * grid = (r, C_tiles, d_tiles); the output block is revisited across the
    d_tiles axis and accumulated in VMEM (standard TPU matmul reduction).

Tile sizes are 128-aligned (MXU systolic dims / VREG lanes).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret


def _fes_tile_kernel(q_ref, ev_ref, s_ref, o_ref):
    """One (cluster, C-tile, d-tile) step: accumulate partial sq-distances.
    ``s_ref`` (1, dt): per-dim dequantization scale for this d-tile —
    all-ones for exact entry tables (bit-exact), the int8 scale row for
    quantized ones (DESIGN.md §4)."""
    kd = pl.program_id(2)
    q = q_ref[0].astype(jnp.float32)          # (QC, dt)
    e = ev_ref[0].astype(jnp.float32) * s_ref[0]   # (Ct, dt), dequantized
    qn = jnp.sum(q * q, axis=-1, keepdims=True)            # (QC, 1)
    en = jnp.sum(e * e, axis=-1, keepdims=True)            # (Ct, 1)
    dot = jax.lax.dot_general(q, e, (((1,), (1,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    part = qn + en.T - 2.0 * dot                           # (QC, Ct)

    @pl.when(kd == 0)
    def _init():
        o_ref[0] = part

    @pl.when(kd != 0)
    def _acc():
        o_ref[0] += part


def _fes_int4_kernel(q_ref, ev_ref, s_ref, o_ref):
    """One (cluster, C-tile) step for nibble-packed int4 entry tables
    (DESIGN.md §4): unpack the two half-planes in VMEM (lane concat, no
    shuffle), dequantize with the padded scale row, then the same norms
    identity as the dense kernel.  Single d step — the unpacked width 2·hp
    rides in one tile."""
    q = q_ref[0].astype(jnp.float32)               # (QC, 2·hp)
    v = ev_ref[0].astype(jnp.int32)                # (Ct, hp) packed bytes
    lo = v & 0xF
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = (v >> 4) & 0xF
    hi = jnp.where(hi >= 8, hi - 16, hi)
    e = jnp.concatenate([lo, hi], axis=1).astype(jnp.float32) * s_ref[0]
    qn = jnp.sum(q * q, axis=-1, keepdims=True)
    en = jnp.sum(e * e, axis=-1, keepdims=True)
    dot = jax.lax.dot_general(q, e, (((1,), (1,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    o_ref[0] = qn + en.T - 2.0 * dot


def _fes_pq_kernel(q_ref, ev_ref, cb_ref, o_ref, *, m: int, ksub: int):
    """One (cluster, C-tile) step for PQ code entry tables (DESIGN.md §4):
    build the per-query ADC LUT (``‖c‖² − 2·q @ codebook``, one MXU matmul)
    then score every entry through a multi-hot code matrix —
    ``dist = ‖q‖² + lut @ Hᵀ`` where H[c, s·ksub + code_s] = 1 — so the ADC
    gather is itself an MXU matmul over the m·ksub lanes."""
    q = q_ref[0].astype(jnp.float32)               # (QC, dp)
    cb = cb_ref[...].astype(jnp.float32)           # (dp, m·ksub)
    cn = jnp.sum(cb * cb, axis=0)
    dot = jax.lax.dot_general(q, cb, (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    lut = cn[None, :] - 2.0 * dot                  # (QC, m·ksub)
    codes = ev_ref[0].astype(jnp.int32)            # (Ct, m)
    ct = codes.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (ct, m * ksub), 1)
    hot = jnp.zeros((ct, m * ksub), bool)
    for s in range(m):
        hot = hot | (lane == (ksub * s + codes[:, s])[:, None])
    qn = jnp.sum(q * q, axis=-1, keepdims=True)
    adc = jax.lax.dot_general(lut, hot.astype(jnp.float32),
                              (((1,), (1,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    o_ref[0] = qn + adc


def fes_distances(q_grouped: jax.Array, entries: jax.Array, *,
                  scale: jax.Array = None, codebook: jax.Array = None,
                  c_tile: int = 128, d_tile: int = 128,
                  interpret: Optional[bool] = None) -> jax.Array:
    """q_grouped: (r, QC, d) cluster-grouped (padded) queries;
    entries: (r, C, d) cluster-bucketed entry vectors — stored fp32, bf16
    or int8 (pass the per-dim ``scale`` (d,) for int8), nibble-packed int4
    (``scale`` (d,) wider than the stored rows), or PQ codes (pass
    ``codebook`` (d, m·ksub); core/quant.py).  Returns squared distances
    (r, QC, C), fp32 — dequantization / ADC happens in-kernel.

    C and d must be multiples of the tile sizes (ops.py pads)."""
    r, QC, dq = q_grouped.shape
    _, C, w = entries.shape
    assert entries.shape[0] == r
    ct = min(c_tile, C)
    assert C % ct == 0, (C, ct)
    interpret = resolve_interpret(interpret)

    if codebook is not None:                       # pq: ADC LUT matmuls
        mk = codebook.shape[1]
        assert w and mk % w == 0, (w, mk)
        kern = functools.partial(_fes_pq_kernel, m=w, ksub=mk // w)
        return pl.pallas_call(
            kern,
            grid=(r, C // ct),
            in_specs=[
                pl.BlockSpec((1, QC, dq), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((1, ct, w), lambda i, j: (i, j, 0)),
                pl.BlockSpec(codebook.shape, lambda i, j: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, QC, ct), lambda i, j: (i, 0, j)),
            out_shape=jax.ShapeDtypeStruct((r, QC, C), jnp.float32),
            interpret=interpret,
        )(q_grouped, entries, codebook.astype(jnp.float32))

    if scale is not None and w < scale.shape[0]:   # int4: unpack in-kernel
        d2 = 2 * w
        if dq != d2:
            q_grouped = jnp.pad(q_grouped, ((0, 0), (0, 0), (0, d2 - dq)))
        s = jnp.pad(scale.astype(jnp.float32), (0, d2 - scale.shape[0]),
                    constant_values=1.0)
        return pl.pallas_call(
            _fes_int4_kernel,
            grid=(r, C // ct),
            in_specs=[
                pl.BlockSpec((1, QC, d2), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((1, ct, w), lambda i, j: (i, j, 0)),
                pl.BlockSpec((1, d2), lambda i, j: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, QC, ct), lambda i, j: (i, 0, j)),
            out_shape=jax.ShapeDtypeStruct((r, QC, C), jnp.float32),
            interpret=interpret,
        )(q_grouped, entries, s[None, :])

    d = dq
    dt = min(d_tile, d)
    assert d % dt == 0, (d, dt)
    grid = (r, C // ct, d // dt)
    s = (jnp.ones((d,), jnp.float32) if scale is None
         else scale.astype(jnp.float32))

    return pl.pallas_call(
        _fes_tile_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, QC, dt), lambda i, j, k: (i, 0, k)),
            pl.BlockSpec((1, ct, dt), lambda i, j, k: (i, j, k)),
            pl.BlockSpec((1, dt), lambda i, j, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((1, QC, ct), lambda i, j, k: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((r, QC, C), jnp.float32),
        interpret=interpret,
    )(q_grouped, entries, s[None, :])
