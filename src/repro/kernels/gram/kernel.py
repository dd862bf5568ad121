"""Pallas TPU kernel: blocked transpose-GEMM  out = x^T @ y.

This is the paper's dominant flop term — the (s*mu) x (s*mu) Gram matrix
G = Y^T Y plus the fused projections Y^T [ytil | ztil] (Alg. 2 lines
11-12), computed in ONE pass over Y per outer iteration.

TPU mapping:
  * grid = (p/bi, q/bj, m/bm); the m (reduction) axis is the innermost,
    "arbitrary" dimension so the f32 VMEM accumulator persists across its
    steps while (i, j) output tiles parallelize.
  * Block shapes (bm, bi)/(bm, bj) are chosen MXU-aligned (multiples of
    128 in the lane dim, 8 in the sublane dim) by ops.py.
  * Accumulation is always f32 (preferred_element_type), independent of
    the input dtype — bf16 inputs hit the MXU, f32 accumulate, matching
    how the paper's MKL GEMM accumulates in higher precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gram_kernel(x_ref, y_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], y_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),   # contract over m
        preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def gram_t_pallas(x, y, *, block_m: int = 256, block_i: int = 128,
                  block_j: int = 128, interpret: bool = False):
    """out[p, q] = sum_m x[m, p] * y[m, q]; shapes must divide the blocks
    (ops.py pads)."""
    m, p = x.shape
    m2, q = y.shape
    if m != m2:
        raise ValueError(f"contraction dims differ: {x.shape} vs {y.shape}")
    if m % block_m or p % block_i or q % block_j:
        raise ValueError(
            f"shapes ({m}, {p}) x ({m2}, {q}) do not divide blocks "
            f"({block_m}, {block_i}, {block_j})")

    grid = (p // block_i, q // block_j, m // block_m)
    return pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_i), lambda i, j, k: (k, i)),
            pl.BlockSpec((block_m, block_j), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_i, block_j), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((p, q), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_i, block_j), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, y)
