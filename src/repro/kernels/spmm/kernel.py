"""Pallas TPU kernel: blocked-ELL SpMM with a VMEM-resident dense
right operand.

This executes the paper's sparse-operand flop terms (Table I's density
factor f) instead of merely modeling them: the left operand is a padded
blocked-ELL matrix — per row, nonzero values and their indices padded to
a common width K that is a multiple of the ELL block ``bk``, plus the
per-row count of *active* K-blocks — and the right operand D is a small
dense matrix held entirely in VMEM. The two hot solver products both
have this shape:

  * Lasso (SA-)BCD:   A_h^T [A_h | r]   — rows = the s*mu sampled
    columns of A (gathered straight out of the column-major ELL arrays),
    D = the densified sample plus the residual-like vectors,
    (s*mu, s*mu + k) out;
  * SVM / K-SVM / logreg cross block:  A Y^T  — rows = all m data
    points (the row-major ELL arrays as stored), D = the densified
    (n_loc, s*mu) sample, (m, s*mu) out.

TPU mapping: grid = (R / 8,) over tiles of eight output rows, so every
output block is a whole (8, Qp) f32 tile. A tile's ELL indices and
values ride in SMEM (they are read one scalar at a time: the indices
address D, the values scale the gathered row), D stays resident in VMEM
for the whole grid, and each ELL slot gathers one row of D with a
dynamic sublane slice and accumulates it in f32. The slot loop of a
tile stops at the tile's active block count (the blocked-ELL nnz
metadata, scalar-prefetched), so fully padded blocks cost nothing.
Padded slots hold index 0 and value 0, so the slots a tile runs for its
shorter rows are exact.

VMEM budget: D at (C, Q) * 4 B dominates; ``dispatch.spmm_vmem_ok``
rejects configurations above 8 MiB (half of v5e's 16 MiB default scoped
VMEM).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_TILE_ROWS = 8


def _make_kernel(ell_block: int):
    def kernel(tile_blocks_ref, idx_ref, vals_ref, D_ref, o_ref):
        slots = tile_blocks_ref[pl.program_id(0)] * ell_block
        for i in range(_TILE_ROWS):
            def body(t, acc, i=i):
                row = D_ref[pl.ds(idx_ref[i, t], 1), :]
                return acc + vals_ref[i, t] * row

            o_ref[i:i + 1, :] = jax.lax.fori_loop(
                0, slots, body, jnp.zeros((1, o_ref.shape[1]), jnp.float32))

    return kernel


def ell_spmm_pallas(vals, idx, blocks, D, *, ell_block: int,
                    interpret: bool = False):
    """out = S @ D for S in padded blocked-ELL form; see ref.py for the
    semantics. ``blocks`` is the per-row active K-block count; K must be
    a multiple of ``ell_block`` (ops.py guarantees both). Returns f32."""
    R, K = vals.shape
    C, Q = D.shape
    if K % ell_block != 0:
        raise ValueError(f"K={K} is not a multiple of ell_block={ell_block}")
    pad = (-R) % _TILE_ROWS
    Rp = R + pad
    vals = jnp.pad(vals.astype(jnp.float32), ((0, pad), (0, 0)))
    idx = jnp.pad(idx.astype(jnp.int32), ((0, pad), (0, 0)))
    tile_blocks = jnp.pad(blocks.astype(jnp.int32), (0, pad)).reshape(
        Rp // _TILE_ROWS, _TILE_ROWS).max(axis=1)
    tile = pl.BlockSpec((_TILE_ROWS, K), lambda r, *_: (r, 0),
                        memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,      # per-tile active block counts
        grid=(Rp // _TILE_ROWS,),
        in_specs=[tile, tile,
                  pl.BlockSpec(memory_space=pltpu.VMEM)],   # resident D
        out_specs=pl.BlockSpec((_TILE_ROWS, Q), lambda r, *_: (r, 0)),
    )
    out = pl.pallas_call(
        _make_kernel(ell_block),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Rp, Q), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="spmm",
    )(tile_blocks, idx, vals, D.astype(jnp.float32))
    return out[:R]
