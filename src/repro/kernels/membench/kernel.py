"""The paper's SIV microbenchmarks as Pallas TPU kernels.

Three kernels mirror the three LSU classes of Listing 4 on the TPU memory
system (the access-class taxonomy of DESIGN.md S2):

* ``aligned_sum``   — ``z[i] = x1[i] + ... + xn[i]``: contiguous streaming,
  the burst-coalesced-aligned analogue; HBM-bandwidth bound.
* ``strided_sum``   — block-strided reads (stride delta at tile granularity,
  exactly like the paper's delta at DRAM-burst granularity): the
  burst-coalesced-non-aligned analogue.
* ``gather_sum``    — data-dependent block indices via scalar prefetch
  (paged-KV-style indirection): the Write-ACK analogue.

They are used by the fig4/fig5 benchmark harness to relate the TPU memory
model's per-class efficiency factors to real kernel structure, and are
validated against ``ref.py`` in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sum_kernel(*refs):
    o_ref = refs[-1]
    acc = refs[0][...].astype(jnp.float32)
    for r in refs[1:-1]:
        acc = acc + r[...].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def _sum_kernel_prefetch(idx_ref, *refs):
    del idx_ref  # consumed by the index maps
    _sum_kernel(*refs)


def _sum_cost(xs: list[jax.Array], n_out: int) -> pl.CostEstimate:
    """What one sum kernel moves: ``n_out`` elements of every input read
    and one output written — the bytes the compiled kernel's HLO reports."""
    itemsize = xs[0].dtype.itemsize
    return pl.CostEstimate(flops=(len(xs) - 1) * n_out, transcendentals=0,
                           bytes_accessed=(len(xs) + 1) * n_out * itemsize)


def aligned_sum(xs: list[jax.Array], *, block: int = 2048,
                interpret: bool = False) -> jax.Array:
    """z = sum of n contiguous arrays, tiled in `block`-element chunks."""
    n = xs[0].shape[0]
    block = min(block, n)
    assert n % block == 0
    spec = pl.BlockSpec((block,), lambda i: (i,))
    return pl.pallas_call(
        _sum_kernel,
        grid=(n // block,),
        in_specs=[spec] * len(xs),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n,), xs[0].dtype),
        cost_estimate=_sum_cost(xs, n),
        interpret=interpret,
    )(*xs)


def strided_sum(xs: list[jax.Array], *, delta: int, block: int = 2048,
                interpret: bool = False) -> jax.Array:
    """z[i-th block] = sum of x_g[delta * i-th block] — block-granularity
    stride, the Eq. 8 effective-burst picture."""
    n_out = xs[0].shape[0] // delta
    block = min(block, n_out)
    assert n_out % block == 0
    in_spec = pl.BlockSpec((block,), lambda i, d=delta: (i * d,))
    out_spec = pl.BlockSpec((block,), lambda i: (i,))
    return pl.pallas_call(
        _sum_kernel,
        grid=(n_out // block,),
        in_specs=[in_spec] * len(xs),
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((n_out,), xs[0].dtype),
        cost_estimate=_sum_cost(xs, n_out),
        interpret=interpret,
    )(*xs)


def gather_sum(xs: list[jax.Array], idx: jax.Array, *, block: int = 2048,
               interpret: bool = False) -> jax.Array:
    """z[i-th block] = sum of x_g[idx[i]-th block] — data-dependent block
    indirection via scalar prefetch."""
    n_blocks = idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((block,), lambda i, idx_ref: (idx_ref[i],))
                  ] * len(xs),
        out_specs=pl.BlockSpec((block,), lambda i, idx_ref: (i,)),
    )
    return pl.pallas_call(
        _sum_kernel_prefetch,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks * block,), xs[0].dtype),
        cost_estimate=_sum_cost(xs, n_blocks * block),
        interpret=interpret,
    )(idx.astype(jnp.int32), *xs)
