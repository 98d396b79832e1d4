"""Memory-bound GQA decode-attention Pallas TPU kernel.

One new token attends over a (B, S, Hkv, D) KV cache — per step the kernel
*streams the whole cache once* with zero reuse, which makes it the canonical
memory-bound workload of this framework (arithmetic intensity ~ G flops/byte
for G q-heads per kv head; far below the v5e ridge of ~241).

Grid: ``(B, Hkv, n_s_blocks)`` with the cache-block dimension innermost and
sequential; online-softmax state for the G grouped q heads lives in VMEM
scratch.  The cache keeps the model's native (B, S, Hkv, D) layout so decode
reads are contiguous (burst-coalesced-aligned class); positions ``>= kv_len``
are masked via the scalar-prefetch length.  The kernel reads the cache through
its free (B, S, Hkv * D) view, so one grid step's block is a (block_s, D)
tile: on the TPU that needs ``D`` to be a multiple of 128 (or ``Hkv == 1``)
and ``block_s`` a multiple of 8.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *,
                   scale: float, block_s: int, n_s: int, softcap: float):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_len = len_ref[0]
    live = j * block_s < kv_len

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (G, D)
        k = k_ref[0].astype(jnp.float32)               # (bs, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        pos = j * block_s + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < kv_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_s - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,                  # (B, Hkv, G, D) — grouped q heads
    k_cache: jax.Array,            # (B, S, Hkv, D)
    v_cache: jax.Array,            # (B, S, Hkv, D)
    kv_len: jax.Array,             # () int32 — valid cache length
    *,
    softcap: float = 0.0,
    block_s: int = 512,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    B, Hkv, G, D = q.shape
    S = k_cache.shape[1]
    block_s = min(block_s, S)
    n_s = -(-S // block_s)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    kernel = functools.partial(_decode_kernel, scale=scale, block_s=block_s,
                               n_s=n_s, softcap=softcap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, j, len_ref: (b, h, 0, 0)),
            pl.BlockSpec((1, block_s, D), lambda b, h, j, len_ref: (b, j, h)),
            pl.BlockSpec((1, block_s, D), lambda b, h, j, len_ref: (b, j, h)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, j, len_ref: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        # the whole cache streams once; q and o are one row per head
        cost_estimate=pl.CostEstimate(
            flops=4 * B * Hkv * G * S * D,
            transcendentals=B * Hkv * G * S,
            bytes_accessed=k_cache.dtype.itemsize * B * Hkv * D * (
                2 * n_s * block_s) + 2 * q.dtype.itemsize * B * Hkv * G * D),
        interpret=interpret,
    )(jnp.asarray(kv_len, jnp.int32).reshape(1), q,
      k_cache.reshape(B, S, Hkv * D), v_cache.reshape(B, S, Hkv * D))
