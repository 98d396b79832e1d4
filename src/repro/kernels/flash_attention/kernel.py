"""Flash-attention forward Pallas TPU kernel (GQA, causal / sliding-window).

Grid: ``(B, Hq, n_q_blocks, n_kv_blocks)`` with the kv dimension innermost
(sequential).  Per (b, h, i) the kernel streams kv blocks through VMEM,
maintaining the online-softmax state (m, l, acc) in VMEM scratch, and writes
the normalized output on the last kv block.  Fully-masked (q, kv) block pairs
(beyond the causal diagonal or outside the sliding window) skip the matmul
via ``pl.when`` — the TPU analogue of not issuing the DRAM burst at all.

Block shapes are MXU/VMEM-aligned: ``block_q x d_head`` and
``block_kv x d_head`` tiles with d_head padded to a multiple of 128 by the
ops.py wrapper when needed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 scale: float, block_q: int, block_kv: int, n_kv: int,
                 causal: bool, window: int | None, softcap: float,
                 seq_kv: int):
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # kv block (innermost, sequential)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_lo = i * block_q
    k_lo = j * block_kv
    # static-shape block skip decision (computed on scalars)
    live = jnp.bool_(True)
    if causal:
        live &= k_lo <= q_lo + block_q - 1
    if window is not None:
        live &= k_lo + block_kv - 1 > q_lo - window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = k_pos < seq_kv
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,                  # (B, Hq, Sq, D)
    k: jax.Array,                  # (B, Hkv, Skv, D)
    v: jax.Array,                  # (B, Hkv, Skv, D)
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float = 0.0,
    block_q: int = 512,
    block_kv: int = 512,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    n_q = -(-Sq // block_q)
    n_kv = -(-Skv // block_kv)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _attn_kernel, scale=scale, block_q=block_q, block_kv=block_kv,
        n_kv=n_kv, causal=causal, window=window, softcap=softcap,
        seq_kv=Skv)

    return pl.pallas_call(
        kernel,
        grid=(B, Hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        # q and o move once; every q block re-reads its head's k and v
        cost_estimate=pl.CostEstimate(
            flops=4 * B * Hq * Sq * Skv * D,
            transcendentals=B * Hq * Sq * Skv,
            bytes_accessed=q.dtype.itemsize * B * Hq * D * (
                2 * Sq + 2 * n_q * n_kv * block_kv)),
        interpret=interpret,
    )(q, k, v)
