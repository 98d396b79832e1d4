"""Chunkwise-parallel mLSTM Pallas TPU kernel.

The XLA chunk scan carries the (dh x dh) matrix memory C through HBM every
chunk (the dominant memory term of the xlstm prefill cell after the
collective fixes — EXPERIMENTS.md SPerf Cell C).  This kernel keeps (C, n)
in VMEM scratch across the sequential chunk dimension, exactly as the
flash-attention kernel keeps the online-softmax state resident:

Grid: ``(B, H, n_chunks)`` (chunks innermost, sequential).  Per step it
loads one (c x dh) q/k/v chunk tile + the (c,) gate vectors, computes the
intra-chunk masked decay attention and the inter-chunk state contribution,
writes the (c x dh) output tile, and updates C/n in place.

Gating follows the model's sigmoid log-space form (log i, log f <= 0), so
every decay weight is exp(<=0) — overflow-free by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mlstm_kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, o_ref,
                  C_ref, n_ref, *, chunk: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        C_ref[...] = jnp.zeros_like(C_ref)
        n_ref[...] = jnp.zeros_like(n_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (c, dh)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    li = li_ref[0, 0].astype(jnp.float32)        # (1, c) row vectors
    lf = lf_ref[0, 0].astype(jnp.float32)

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    mask = row >= col
    # Prefix sums and row -> column moves as exact triangular / identity
    # matmuls: the TPU lowers neither cumsum nor a vector transpose.
    exact = dict(preferred_element_type=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST)
    nt = (((1,), (1,)), ((), ()))                # contract both last dims
    cum = jnp.dot(lf, (row <= col).astype(jnp.float32), **exact)   # (1, c)
    cum_c = jax.lax.dot_general(mask.astype(jnp.float32), lf, nt, **exact)
    li_c = jax.lax.dot_general((row == col).astype(jnp.float32), li, nt,
                               **exact)          # (c, 1) column vectors
    C = C_ref[...]
    n = n_ref[...]                               # (1, dh)

    qd = q * jnp.exp(cum_c)
    inter = jax.lax.dot_general(qd, C, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    n_inter = jax.lax.dot_general(qd, n, nt,
                                  preferred_element_type=jnp.float32)

    w_log = cum_c - cum + li
    w = jnp.where(mask, jnp.exp(w_log), 0.0)
    s = jax.lax.dot_general(q, k, nt,
                            preferred_element_type=jnp.float32) * w
    intra = jax.lax.dot_general(s, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    n_intra = jax.lax.dot_general(w, k, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    den = n_inter + jnp.sum(q * n_intra, axis=-1, keepdims=True)
    h = (inter + intra) / jnp.maximum(jnp.abs(den), 1.0)
    o_ref[0, 0] = h.astype(o_ref.dtype)

    # the chunk's total log decay, as a (c, 1) column and a (1, dh) row
    # (Mosaic cannot broadcast a (1, 1) value along sublanes and lanes)
    total_c = jax.lax.dot_general(jnp.ones((chunk, chunk), jnp.float32), lf,
                                  nt, **exact)
    total_r = jnp.dot(lf, jnp.ones((chunk, C.shape[1]), jnp.float32),
                      **exact)
    decay_to_end = jnp.exp(total_c - cum_c + li_c)  # (c, 1)
    kw = k * decay_to_end
    carry = jnp.exp(total_r)
    C_ref[...] = C * carry + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    n_ref[...] = n * carry + kw.sum(axis=0, keepdims=True)


def mlstm_chunk(
    q: jax.Array,                 # (B, H, S, dh)
    k: jax.Array,
    v: jax.Array,
    li: jax.Array,                # (B, H, S) log input gate (<= 0)
    lf: jax.Array,                # (B, H, S) log forget gate (<= 0)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """On the TPU ``chunk`` must be a multiple of 128 (or equal ``S``): the
    gates travel as (1, chunk) row tiles of a (B, H, 1, S) view."""
    B, H, S, dh = q.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk
    kernel = functools.partial(_mlstm_kernel, chunk=chunk)
    qkv_spec = pl.BlockSpec((1, 1, chunk, dh), lambda b, h, j: (b, h, j, 0))
    gate_spec = pl.BlockSpec((1, 1, 1, chunk), lambda b, h, j: (b, h, 0, j))
    return pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[qkv_spec, qkv_spec, qkv_spec, gate_spec, gate_spec],
        out_specs=qkv_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((dh, dh), jnp.float32),
            pltpu.VMEM((1, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        # q, k, v, o and the two gates move once; per chunk, two (c, dh) x
        # (dh, dh) and three (c, c) x (c, dh) matmuls dominate
        cost_estimate=pl.CostEstimate(
            flops=B * H * n_chunks * (4 * chunk * dh * dh
                                      + 6 * chunk * chunk * dh),
            transcendentals=B * H * n_chunks * chunk * (chunk + 2),
            bytes_accessed=B * H * S * (4 * dh * q.dtype.itemsize
                                        + 2 * li.dtype.itemsize)),
        interpret=interpret,
    )(q, k, v, li[:, :, None, :], lf[:, :, None, :])
