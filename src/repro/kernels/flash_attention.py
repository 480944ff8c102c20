"""Pallas TPU flash-attention forward kernel (causal, GQA).

The LM stack's jnp flash attention (models/layers.py) is the XLA-visible
implementation used for dry-run cost accounting; this kernel is the
TPU-serving hot path: one fused pass per (batch·head, q-block) grid cell with
the k/v stream tiled through VMEM, running max/sum-exp accumulators in fp32
registers, MXU matmuls for both contractions.  Tiles are 128-aligned.

Validated in interpret mode against the pure-jnp oracle
(ref.flash_attention_ref / tests/test_kernels_flash.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float,
                      causal: bool, bq: int, bk: int, seq_k: int):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale           # (bq, d)
    d = q.shape[-1]

    m = jnp.full((bq,), NEG_INF, jnp.float32)
    l = jnp.zeros((bq,), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)

    nk = seq_k // bk
    q_ids = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)   # (bk, d)
        v = v_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            k_ids = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_ids >= k_ids, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    if causal:
        # blocks strictly above the diagonal contribute nothing — skip them
        nk_eff = jnp.minimum(nk, ((qi + 1) * bq + bk - 1) // bk)
        m, l, acc = jax.lax.fori_loop(0, nk_eff, body, (m, l, acc))
    else:
        m, l, acc = jax.lax.fori_loop(0, nk, body, (m, l, acc))

    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_tpu(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, block_q: int = 128,
                        block_k: int = 128,
                        interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Sk, Hkv, D) with H % Hkv == 0.
    Returns (B, Sq, H, D).  Sq % block_q == 0 and Sk % block_k == 0
    (callers pad; see ops)."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk)
    scale = 1.0 / math.sqrt(D)

    # lay out as (B*H, S, D); kv heads repeat across their group
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, Sk, D)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, Sk, D)

    grid = (B * H, Sq // block_q)
    kern = functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                             bq=block_q, bk=block_k, seq_k=Sk)
    o = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, Sk, D), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, Sk, D), lambda h, i: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)
    return o.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
