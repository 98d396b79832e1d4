"""RG-LRU sequence-scan Pallas TPU kernel.

The recurrence ``h_t = a_t * h_{t-1} + b_t`` is elementwise over the channel
dimension — pure VPU work streaming (B, S, W) once from HBM, i.e. strictly
memory-bound (arithmetic intensity ~0.5 FLOP/byte).  The kernel tiles
channels across the grid and keeps the carried state ``h`` in VMEM scratch
while marching over sequence blocks:

Grid: ``(B, n_w_blocks, n_s_blocks)`` (sequence innermost, sequential).
Within a block the time loop runs over rows of the (block_s, block_w) VMEM
tile, loading and storing one row per step straight from the block refs
(a value cannot be sliced at a traced index on the TPU) — sequential in time
but vectorized across the 128-lane channel tile,
which is how the TPU wants an elementwise recurrence (DESIGN.md S2
hardware-adaptation note: no warp-scan analogue; lane-parallel time-marching
instead).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, o_ref, h_ref, *, block_s: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def body(t, h):                          # h: (1, block_w) f32
        row = (0, pl.ds(t, 1), slice(None))
        h = (a_ref[row].astype(jnp.float32) * h
             + b_ref[row].astype(jnp.float32))
        o_ref[row] = h.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, block_s, body, h_ref[...])


def rglru_scan(
    a: jax.Array,                  # (B, S, W) per-step decay in (0,1)
    b: jax.Array,                  # (B, S, W) per-step input
    *,
    block_s: int = 256,
    block_w: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, S, W = a.shape
    block_s = min(block_s, S)
    block_w = min(block_w, W)
    assert S % block_s == 0 and W % block_w == 0, (S, W, block_s, block_w)
    n_s = S // block_s
    n_w = W // block_w
    kernel = functools.partial(_rglru_kernel, block_s=block_s)
    return pl.pallas_call(
        kernel,
        grid=(B, n_w, n_s),
        in_specs=[
            pl.BlockSpec((1, block_s, block_w), lambda b_, w, j: (b_, j, w)),
            pl.BlockSpec((1, block_s, block_w), lambda b_, w, j: (b_, j, w)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_w),
                               lambda b_, w, j: (b_, j, w)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * S * W, transcendentals=0,
            bytes_accessed=3 * B * S * W * a.dtype.itemsize),
        interpret=interpret,
    )(a, b)
