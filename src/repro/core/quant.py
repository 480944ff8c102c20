"""Quantized pilot payloads (DESIGN.md §4).

PilotANN's scale headline — serving datasets far larger than accelerator
memory — rests on shrinking the *stage-① resident set*: the pilot subgraph
CSR, the SVD-primary vectors and the FES entry buckets.  BANG and FusionANNS
(PAPERS.md) both compress the GPU-resident vectors; here the same lever is
applied to the SVD-primary split.  Five encodings for the stage-① vector
tables (``IndexConfig.pilot_dtype``), forming the compression ladder the
``ResidencyPlanner`` descends:

  * ``float32``  — identity (4 B/dim), the exact baseline.
  * ``bfloat16`` — truncation (2 B/dim), no side data.  bf16→f32 widening is
    exact, so the quantization error is purely the build-time rounding.
  * ``int8``     — symmetric per-dimension scale (1 B/dim + one fp32 scale
    row per table): ``data = round(x / scale)`` with
    ``scale[j] = max_i |x[i, j]| / 127``.  Dequantization is
    ``x̂ = data · scale`` and the per-element error is bounded by
    ``scale[j] / 2``.
  * ``int4``     — the same symmetric per-dim scheme at nibble width
    (``scale[j] = max_i |x[i, j]| / 7``), TWO dims packed per int8 lane:
    dim ``j`` in the low nibble, dim ``j + ceil(d/2)`` in the high nibble
    of byte ``j``.  The plane split (not adjacent-dim interleave) makes the
    in-kernel unpack a lane *concatenation* — TPU-friendly, no shuffle.
  * ``pq``       — m-subspace product quantization (1 code byte per
    subspace + one fp32 codebook per table): the host builds per-subspace
    centroids at encode time, and the kernels score via a per-query lookup
    table (ADC) instead of reconstructing vectors — one-hot LUT gathers,
    not MXU dot-products.  Centroid 0 of every subspace is pinned to the
    zero vector so all-zero rows (sentinels / padding) stay exactly zero.

Quantization is *only* applied to stage-① payloads.  Because the pilot beam
distances become approximate, stage ② must re-score candidates **exactly**
from the full-precision ``rot_vecs`` instead of reusing the residual
identity ``‖x−q‖² = ‖xp−qp‖² + ‖xr−qr‖²`` (which would add an exact residual
term to an inexact primary term) — see ``core/multistage.py`` and
DESIGN.md §4.  That gate fires on ``primary.dtype != float32``, which the
int8/int4/pq payloads (all int8-typed storage) satisfy alike.

The PQ codebook is stored *block-diagonal*: ``codebook (d, m·ksub)`` fp32,
where column ``s·ksub + c`` holds centroid ``c`` of subspace ``s`` (zero
outside the subspace's dim range).  This single layout serves every
consumer: ``codebook.shape[0]`` recovers the true primary width (the packed
codes are only ``m`` wide), the per-query LUT is one matmul
(``lut = cn − 2·q @ codebook``), and reconstruction is a multihot matmul
(``x̂ = H @ codebook.T``).

This module is numpy (build-time) + pure-jnp (reference math).  The in-kernel
dequant/LUT distance paths live in ``kernels/traversal_kernel.py`` and
``kernels/fes_kernel.py`` and are parity-tested against ``dequant_sq_dists``
/ the ``kernels/ref.py`` oracles.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Encodings accepted by IndexConfig.pilot_dtype / PodIndexSpec.pilot_dtype,
# widest first (the ResidencyPlanner's ladder order).
PILOT_DTYPES = ("float32", "bfloat16", "int8", "int4", "pq")

# Bytes per vector dimension for the *fixed-width* encodings.  int4 and pq
# have non-uniform layouts (packed nibbles / codes + codebook); all byte
# accounting goes through encoded_row_bytes / side_bytes, which cover every
# encoding exactly.
VEC_ITEMSIZE = {"float32": 4, "bfloat16": 2, "int8": 1}

# Fidelity rank used by the ResidencyPlanner's preference ladder (higher is
# more exact; the planner sacrifices fidelity before svd/sample ratios).
FIDELITY = {"float32": 4, "bfloat16": 3, "int8": 2, "int4": 1, "pq": 0}

# Product-quantization geometry: m subspaces × ksub centroids.  m·ksub = 128
# keeps the whole per-query LUT in one VREG lane dimension on TPU.
PQ_M = 8
PQ_KSUB = 16
_PQ_KMEANS_ITERS = 12


def pq_geometry(d: int) -> Tuple[int, int, int]:
    """(m, dsub, ksub) for a ``d``-dim table: at most ``PQ_M`` subspaces of
    ``dsub = ceil(d/min(PQ_M, d))`` dims each, with ``m = ceil(d/dsub)``
    recomputed so every subspace covers at least one real dimension (only
    the LAST one is zero-padded — e.g. d=9 gives 5 subspaces of 2, not 8
    subspaces where three lie wholly in padding).  ksub centroids per
    subspace.  Single source of truth shared by the encoder, the kernels
    and the byte estimators — which is what keeps ``memory_report()`` and
    ``ResidencyPlanner.estimate`` exact mirrors."""
    if d < 1:
        raise ValueError(f"pq needs d >= 1, got {d}")
    dsub = -(-d // min(PQ_M, d))
    m = -(-d // dsub)
    return m, dsub, PQ_KSUB


def int4_packed_width(d: int) -> int:
    """Packed byte width of an int4 row: ``ceil(d/2)`` (two nibbles/lane)."""
    if d < 2:
        raise ValueError(f"int4 needs d >= 2, got {d}")
    return -(-d // 2)


def encoded_row_bytes(d: int, dtype: str) -> int:
    """Bytes per encoded row of a ``d``-dim table (payload only)."""
    if dtype in VEC_ITEMSIZE:
        return d * VEC_ITEMSIZE[dtype]
    if dtype == "int4":
        return int4_packed_width(d)
    if dtype == "pq":
        return pq_geometry(d)[0]
    raise ValueError(f"pilot_dtype must be one of {PILOT_DTYPES}, "
                     f"got {dtype!r}")


def side_bytes(d: int, dtype: str) -> int:
    """Per-table side-data bytes: the fp32 scale row (int8/int4) or the
    block-diagonal fp32 codebook (pq); zero for exact encodings."""
    if dtype in ("int8", "int4"):
        return d * 4
    if dtype == "pq":
        m, _, ksub = pq_geometry(d)
        return d * m * ksub * 4
    if dtype in VEC_ITEMSIZE:
        return 0
    raise ValueError(f"pilot_dtype must be one of {PILOT_DTYPES}, "
                     f"got {dtype!r}")


def _pq_kmeans(xs: np.ndarray, ksub: int, seed: int) -> np.ndarray:
    """Deterministic Lloyd's kmeans for one subspace (rows, dsub) ->
    (ksub, dsub) centroids.  Centroid 0 is pinned to the zero vector so
    all-zero rows round-trip exactly (sentinel/padding contract); empty
    clusters keep their previous centroid."""
    rng = np.random.default_rng(seed)
    rows, dsub = xs.shape
    cent = np.zeros((ksub, dsub), np.float32)
    if rows:
        pick = rng.choice(rows, size=min(rows, ksub - 1), replace=False)
        cent[1:1 + len(pick)] = xs[pick]
    for _ in range(_PQ_KMEANS_ITERS):
        d2 = ((xs[:, None, :] - cent[None, :, :]) ** 2).sum(-1)  # (rows, ksub)
        assign = d2.argmin(1)
        for c in range(1, ksub):                 # centroid 0 stays pinned
            sel = assign == c
            if sel.any():
                cent[c] = xs[sel].mean(0)
    return cent.astype(np.float32)


def pq_encode(x: np.ndarray, seed: int = 0
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Encode a float32 table ``x`` (..., d) as ``(codes, codebook)``:
    ``codes`` (..., m) int8 centroid indices and the block-diagonal fp32
    ``codebook`` (d, m·ksub) described in the module docstring."""
    x = np.asarray(x, np.float32)
    d = x.shape[-1]
    m, dsub, ksub = pq_geometry(d)
    flat = x.reshape(-1, d)
    dpad = m * dsub
    if dpad != d:
        flat = np.concatenate(
            [flat, np.zeros((flat.shape[0], dpad - d), np.float32)], axis=1)
    codes = np.zeros(flat.shape[:1] + (m,), np.int8)
    codebook = np.zeros((d, m * ksub), np.float32)
    for s in range(m):
        lo, hi = s * dsub, (s + 1) * dsub
        xs = flat[:, lo:hi]
        cent = _pq_kmeans(xs, ksub, seed + s)
        d2 = ((xs[:, None, :] - cent[None, :, :]) ** 2).sum(-1)
        codes[:, s] = d2.argmin(1).astype(np.int8)
        # block-diagonal placement; rows beyond d (zero-padded dims) carry
        # provably-zero centroid components and are simply trimmed
        span = min(hi, d) - lo
        codebook[lo:lo + span, s * ksub:(s + 1) * ksub] = cent[:, :span].T
    return codes.reshape(x.shape[:-1] + (m,)), codebook


def quantize(x: np.ndarray, dtype: str
             ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Encode a float32 table ``x`` (..., d) as ``(data, side)``.

    ``side`` is the per-dimension float32 ``(d,)`` scale row for ``int8``
    and ``int4``, the block-diagonal ``(d, m·ksub)`` fp32 codebook for
    ``pq``, and ``None`` otherwise.  Zero rows (sentinels / padding) stay
    exactly zero under every encoding.
    """
    if dtype not in PILOT_DTYPES:
        raise ValueError(f"pilot_dtype must be one of {PILOT_DTYPES}, "
                         f"got {dtype!r}")
    x = np.asarray(x, np.float32)
    if dtype == "float32":
        return x, None
    if dtype == "bfloat16":
        return x.astype(jnp.bfloat16), None
    if dtype == "pq":
        return pq_encode(x)
    d = x.shape[-1]
    amax = np.abs(x.reshape(-1, d)).max(axis=0)
    if dtype == "int8":
        scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
        data = np.clip(np.round(x / scale), -127, 127).astype(np.int8)
        return data, scale
    # int4: nibble-quantize at the same symmetric per-dim scheme, then pack
    scale = np.where(amax > 0, amax / 7.0, 1.0).astype(np.float32)
    q4 = np.clip(np.round(x / scale), -7, 7).astype(np.int8)
    return int4_pack(q4), scale


def int4_pack(codes: np.ndarray) -> np.ndarray:
    """Pack signed nibble codes (..., d) in [-8, 7] into bytes
    (..., ceil(d/2)): dim j lands in the low nibble and dim j+hp in the
    high nibble of byte j (the two half-planes the kernels reassemble by
    lane concatenation; ``int4_unpack`` is the exact inverse)."""
    codes = np.asarray(codes, np.int8)
    d = codes.shape[-1]
    hp = int4_packed_width(d)
    if 2 * hp != d:
        codes = np.concatenate(
            [codes, np.zeros(codes.shape[:-1] + (2 * hp - d,), np.int8)],
            axis=-1)
    lo = codes[..., :hp].astype(np.uint8) & 0xF
    hi = codes[..., hp:].astype(np.uint8) & 0xF
    return (lo | (hi << 4)).astype(np.int8)


def int4_unpack(data, d: Optional[int] = None):
    """Unpack an int4-packed table (..., hp) -> signed nibble values
    (..., 2·hp) — or (..., d) when ``d`` is given — as the input library's
    int32.  Pure lane concatenation of the low/high planes; bit-identical
    between numpy (build) and jnp (kernel)."""
    xp = jnp if isinstance(data, jax.Array) else np
    v = xp.asarray(data).astype(xp.int32)
    lo = v & 0xF
    lo = xp.where(lo >= 8, lo - 16, lo)
    hi = (v >> 4) & 0xF
    hi = xp.where(hi >= 8, hi - 16, hi)
    out = xp.concatenate([lo, hi], axis=-1)
    return out if d is None else out[..., :d]


def table_encoding(table, side=None, *, codebook=None) -> str:
    """Classify a stored table: ``side``/``codebook`` discriminate the
    packed encodings — a codebook means ``pq``; a scale row wider than the
    stored rows means ``int4`` (packed width ceil(d/2) < d for d >= 2);
    otherwise the table is *dense* (fp32/bf16/int8 — all served by the
    elementwise scale multiply, with an all-ones scale for exact tables)."""
    if codebook is not None:
        return "pq"
    if side is not None and table.shape[-1] < side.shape[-1]:
        return "int4"
    return "dense"


def primary_dim(table, side=None, *, codebook=None) -> int:
    """True vector width of a stored (possibly packed) table: the codebook
    (pq) and the scale row (int8/int4) carry one entry per real dim, so they
    take precedence over the stored row width."""
    if codebook is not None:
        return codebook.shape[0]
    if side is not None:
        return side.shape[-1]
    return table.shape[-1]


def decode_rows(rows, side=None, *, codebook=None):
    """Decode gathered rows of any encoding back to float32 (numpy in,
    numpy out; jnp in, jnp out).  Identity for exact tables with no side
    data — the bit-exactness contract of the fp32/bf16 paths."""
    xp = jnp if isinstance(rows, jax.Array) else np
    if codebook is not None:                              # pq
        cb = xp.asarray(codebook, xp.float32)
        d = cb.shape[0]
        _, _, ksub = pq_geometry(d)
        codes = xp.asarray(rows).astype(xp.int32)
        flat = codes.reshape(-1, codes.shape[-1])
        cols = flat + ksub * xp.arange(flat.shape[-1], dtype=xp.int32)
        out = xp.take(cb.T, cols, axis=0).sum(axis=1)
        return out.reshape(codes.shape[:-1] + (d,))
    if side is not None and rows.shape[-1] < side.shape[-1]:   # int4 packed
        d = side.shape[-1]
        return (int4_unpack(rows, d).astype(xp.float32)
                * xp.asarray(side, xp.float32))
    if side is not None:                                  # int8 (dense)
        return (xp.asarray(rows).astype(xp.float32)
                * xp.asarray(side, xp.float32))
    return rows


def dequantize(data, scale: Optional[np.ndarray] = None, *,
               codebook: Optional[np.ndarray] = None):
    """Decode back to float32 (numpy in, numpy out; jnp in, jnp out).
    A 2-D ``scale`` is understood as the PQ codebook — scale rows are
    always 1-D — so ``dequantize(*reversed-quantize-output)`` round-trips
    every encoding."""
    if codebook is None and scale is not None and np.ndim(scale) == 2:
        scale, codebook = None, scale
    if codebook is not None or (scale is not None
                                and data.shape[-1] < scale.shape[-1]):
        return decode_rows(data, scale, codebook=codebook)
    xp = jnp if isinstance(data, jax.Array) else np
    x = xp.asarray(data).astype(xp.float32)
    return x if scale is None else x * xp.asarray(scale, xp.float32)


def pq_lut(q: jax.Array, codebook: jax.Array) -> jax.Array:
    """Per-query ADC lookup table: ``lut[b, s·ksub + c] = ‖c_s‖² − 2·q_s·c_s``
    so that ``dist(q, x) = ‖q‖² + Σ_s lut[b, s·ksub + code_s(x)]``.  One
    matmul on the block-diagonal codebook — the exact formulation the Pallas
    kernels use in VMEM (``kernels/traversal_kernel.py``)."""
    cb = codebook.astype(jnp.float32)
    cn = jnp.sum(cb * cb, axis=0)                          # (m·ksub,)
    dot = jax.lax.dot_general(q.astype(jnp.float32), cb,
                              (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    return cn[None, :] - 2.0 * dot


def roundtrip_error_bound(x: np.ndarray, dtype: str) -> np.ndarray:
    """Per-dimension bound on ``|x - dequantize(quantize(x))|``.

    Analytic for the fixed-width encodings (half a quantization step); for
    ``pq`` the error is data-dependent (distance to the nearest learned
    centroid), so the bound is the *achieved* per-dim reconstruction error
    of the deterministic encoder — still a sound bound for the encoding the
    build actually stores, which is what the residency maths needs."""
    x = np.asarray(x, np.float32)
    amax = np.abs(x.reshape(-1, x.shape[-1])).max(axis=0)
    if dtype == "float32":
        return np.zeros_like(amax)
    if dtype == "bfloat16":
        # bf16 keeps 8 significand bits: relative error <= 2**-8 of |x|.
        return amax * 2.0 ** -8
    if dtype == "int8":
        scale = np.where(amax > 0, amax / 127.0, 1.0)
        return scale * 0.5 + 1e-7
    if dtype == "int4":
        scale = np.where(amax > 0, amax / 7.0, 1.0)
        return scale * 0.5 + 1e-6
    if dtype == "pq":
        codes, codebook = pq_encode(x)
        err = np.abs(np.asarray(decode_rows(codes, codebook=codebook)) - x)
        return err.reshape(-1, x.shape[-1]).max(axis=0) + 1e-6
    raise ValueError(f"pilot_dtype must be one of {PILOT_DTYPES}, "
                     f"got {dtype!r}")


def dequant_sq_dists(q: jax.Array, table: jax.Array,
                     scale: Optional[jax.Array] = None, *,
                     codebook: Optional[jax.Array] = None) -> jax.Array:
    """Pure-jnp reference dequant-distance: squared euclidean between fp32
    queries ``(B, d)`` and an encoded table ``(m, ...)`` -> ``(B, m)``.

    This is the oracle the in-kernel dequantized paths are parity-tested
    against: decode the whole table, then the standard norms-minus-2dot
    identity (``core.traversal.sq_dists``).  For ``pq`` the decode is the
    centroid reconstruction, so this equals the ADC LUT distance exactly
    (same quantity, different association)."""
    from repro.core.traversal import sq_dists
    t = decode_rows(table, scale, codebook=codebook)
    return sq_dists(q, t.astype(jnp.float32))
