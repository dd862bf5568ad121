"""Operand-polymorphic block operations: the one place the solver
families branch on dense array vs :class:`~repro.core.types.SparseOperand`.

Each factory returns closures over the prepared operand, so the solver
bodies stay a single code path — they call ``take`` / ``gram`` /
``apply`` and never touch the layout. The dense closures are the exact
expressions the solvers used before sparse operands existed (same
operation order — the dense paths stay bit-identical); the sparse
closures execute only nnz work via ``repro.kernels.spmm``:

  * column layout (Lasso, A row-partitioned, COLUMNS sampled):
    ``col_block_ops`` — the fused (mu, mu + k) Gram/projection block
    A_B^T [A_B | vecs] and the deferred residual update A_B @ dx;
  * row layout (SVM / K-SVM / logreg, A column-partitioned, ROWS
    sampled): ``row_block_ops`` — the fused Y [Y^T | vecs] block, the
    densified sample Y^T (the cross product's right operand), and the
    deferred shard update Y^T @ coef;
  * ``cross_block`` — the (m, c) cross product A @ Y^T the kernel-SVM
    and logreg families communicate.

All local (pre-Allreduce) quantities; communication stays in the
solvers. ``use_pallas`` routes the SpMM through the blocked-ELL Pallas
kernel (``repro.kernels.spmm``), subject to its VMEM guard. A take
runs in the ``gather`` phase scope, a product and the building of its
operands in the ``gram`` scope (:mod:`repro.core.phases`).

A sparse operand keeps both orientations (column- and row-major ELL).
A dense one takes its second per solve: ``col_take`` gives a dense
column-sampling solve A^T, made once in its set-up, and reads the
sampled columns as rows of it (DESIGN.md "The dense operand's
orientation").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import phases
from repro.core.types import SparseOperand
from repro.kernels import spmm


def prep_operand(A, dtype):
    """Cast a problem's data matrix — dense or sparse — to the solver
    dtype (the sparse analogue of ``jnp.asarray(A, dtype)``)."""
    if isinstance(A, SparseOperand):
        return A.astype(dtype)
    return jnp.asarray(A, dtype)


def device_bytes_limit():
    """The local device's memory in bytes, or None where the backend
    reports none (the CPU)."""
    stats = jax.local_devices()[0].memory_stats()
    return (stats or {}).get("bytes_limit")


def _col_gathers(cfg, grouped: bool) -> int:
    """The column gathers a solve makes: one per outer group of the
    s-step schedule (``grouped``), else one per iteration."""
    return -(-cfg.iterations // cfg.s) if grouped else cfg.iterations


def gather_layout(A, gathers: int):
    """How a column-sampling solve that makes ``gathers`` gathers takes
    the columns of a dense A — the ``aux["gather_layout"]`` label, None
    for a sparse operand:

      * "feature_major": rows of A^T, made once in the set-up. Making it
        costs about one of the old gathers (each of which copies all of
        A), so it pays from two gathers on; it may hold a second A, so
        only where A fits in half of the device's memory (where the
        backend reports it);
      * "row_major": columns of A itself, at every gather.
    """
    if isinstance(A, SparseOperand):
        return None
    limit = device_bytes_limit()
    fits = limit is None or 2 * A.size * A.dtype.itemsize <= limit
    return "feature_major" if gathers >= 2 and fits else "row_major"


def col_take(A, cfg, grouped: bool = False):
    """take(idx) -> A[:, idx].T, the feature-major block (len(idx), m_loc)
    of a dense A's sampled columns, taken as :func:`gather_layout`
    decides; the values are A's, exactly.

    Feature-major, A^T is made here (in the caller's set-up), behind an
    optimization barrier so the compiler keeps it out of the loop's
    takes, and a take is one row slice of it per sampled column
    (``lax.map``): the TPU's gather splits a large operand (epsilon's
    3.2 GB A, a 0.8 GB shard) into slices of all of it, copied at every
    take, for ``A^T[idx]`` as for ``A[:, idx]``. A^T is (n, 1, m_loc)
    and the taken rows are kept apart by a second barrier: with a unit
    second-minor dimension the TPU tiles each row alone, so a row slice
    reads and writes one contiguous run."""
    if gather_layout(A, _col_gathers(cfg, grouped)) == "row_major":
        def take(idx):
            with phases.scope("gather"):
                return A[:, idx].T
        return take
    At = jax.lax.optimization_barrier(A.T[:, None, :])   # (n, 1, m_loc)

    def row(i):
        return jax.lax.dynamic_index_in_dim(At, i, keepdims=False)

    def take(idx):
        with phases.scope("gather"):
            rows = jax.lax.optimization_barrier(jax.lax.map(row, idx))
            return rows.reshape(idx.shape[0], A.shape[0])
    return take


def col_block_ops(A, cfg):
    """(block_gram, block_apply) for the column-sampling (Lasso) layout
    of a classical solve (one block per iteration).

    block_gram(idx, vecs) -> (handle, local) with
        local = A_B^T [A_B | vecs]   (mu, mu + k), LOCAL (pre-reduce);
    block_apply(handle, coef) -> A_B @ coef   (m_loc,).
    """
    if isinstance(A, SparseOperand):
        m_loc = A.shape[0]

        def block_gram(idx, vecs):
            with phases.scope("gather"):
                handle = A.gather_cols(idx)
            with phases.scope("gram"):
                rows, vals, nnb = handle
                Yd = spmm.scatter_dense(rows, vals, m_loc)
                local = spmm.ell_spmm(vals, rows, nnb,
                                      jnp.concatenate([Yd, vecs], axis=1),
                                      ell_block=A.ell_block,
                                      use_pallas=cfg.use_pallas)
                return handle, local.astype(A.dtype)

        def block_apply(handle, coef):
            rows, vals, _ = handle
            return spmm.scatter_add(jnp.zeros((m_loc,), A.dtype),
                                    rows, vals, coef)

        return block_gram, block_apply

    take = col_take(A, cfg)

    def block_gram(idx, vecs):
        Ah = take(idx).T
        with phases.scope("gram"):
            return Ah, Ah.T @ jnp.concatenate([Ah, vecs], axis=1)

    def block_apply(Ah, coef):
        return Ah @ coef

    return block_gram, block_apply


def row_block_ops(A, cfg):
    """(take, gram, densify, apply_t) for the row-sampling (SVM/logreg)
    layout.

    take(idx) -> handle for the sampled rows Y = A[idx];
    gram(handle, vecs) -> Y [Y^T | vecs]   (r, r + k), LOCAL;
    densify(handle) -> Y^T   (n_loc, r) dense (the cross product's
        right operand);
    apply_t(handle, coef) -> Y^T @ coef   (n_loc,).
    """
    if isinstance(A, SparseOperand):
        n_loc = A.shape[1]

        def take(idx):
            with phases.scope("gather"):
                return A.gather_rows(idx)

        def gram(handle, vecs):
            with phases.scope("gram"):
                cols, vals, nnb = handle
                local = spmm.ell_spmm(
                    vals, cols, nnb,
                    jnp.concatenate([spmm.scatter_dense(cols, vals, n_loc),
                                     vecs], axis=1),
                    ell_block=A.ell_block, use_pallas=cfg.use_pallas)
                return local.astype(A.dtype)

        def densify(handle):
            with phases.scope("gram"):
                cols, vals, _ = handle
                return spmm.scatter_dense(cols, vals, n_loc)

        def apply_t(handle, coef):
            cols, vals, _ = handle
            return spmm.scatter_add(jnp.zeros((n_loc,), A.dtype),
                                    cols, vals, coef)

        return take, gram, densify, apply_t

    def take(idx):
        with phases.scope("gather"):
            return A[idx]

    def gram(Y, vecs):
        with phases.scope("gram"):
            return Y @ jnp.concatenate([Y.T, vecs], axis=1)

    def densify(Y):
        with phases.scope("gram"):
            return Y.T

    def apply_t(Y, coef):
        return Y.T @ coef

    return take, gram, densify, apply_t


def operand_aux(A, cfg, kind: str, H=None, extra: int = 0) -> dict:
    """The operand's execution labels: ``aux["spmm_impl"]`` for a sparse
    solve, ``aux["gather_layout"]`` (:func:`gather_layout`) for a dense
    "col_gram" one, nothing else for a dense operand. ONE place derives
    the (R, K, C, Q) SpMM shape from the layout, so the surfaced label
    cannot drift from the shapes the solver actually dispatches:

      * "col_gram" — Lasso fused  A_B^T [A_B | vecs]  (columns sampled);
      * "row_gram" — SVM fused    Y [Y^T | vecs]      (rows sampled);
      * "cross"    — K-SVM/logreg cross block  A Y^T.

    ``extra`` is the appended-vector count k. H=None labels a classical
    (one block per iteration) solve; otherwise the grouped main+tail
    label over the SA schedule (H, cfg.s).
    """
    if not isinstance(A, SparseOperand):
        if kind != "col_gram":
            return {}
        return {"gather_layout": gather_layout(
            A, _col_gathers(cfg, grouped=H is not None))}
    mu = cfg.block_size
    if kind == "col_gram":
        K, C = A.col_rows.shape[1], A.shape[0]
        def shape(g):
            return (g * mu, K, C, g * mu + extra)
    elif kind == "row_gram":
        K, C = A.row_cols.shape[1], A.shape[1]
        def shape(g):
            return (g * mu, K, C, g * mu + extra)
    elif kind == "cross":
        K, C = A.row_cols.shape[1], A.shape[1]
        def shape(g):
            return (A.shape[0], K, C, g * mu)
    else:
        raise ValueError(f"unknown spmm layout kind {kind!r}")
    itemsize = jnp.dtype(cfg.dtype).itemsize
    if H is None:
        return {"spmm_impl": spmm.spmm_impl(*shape(1), cfg.use_pallas,
                                            itemsize)}
    return {"spmm_impl": spmm.grouped_spmm_label(H, cfg.s, shape,
                                                 cfg.use_pallas,
                                                 itemsize)}


def cross_block(A, YT, use_pallas: bool = False):
    """LOCAL cross product A @ Y^T: the (m, c) block the kernel-SVM and
    logreg families Allreduce. ``YT`` is the (n_loc, c) dense right
    operand (``densify(handle)`` for a sampled block, ``A.T`` for the
    full-matrix oracle paths); a sparse A contracts its row-major ELL
    arrays — O(nnz * c) instead of O(m * n_loc * c)."""
    if isinstance(A, SparseOperand):
        local = spmm.ell_spmm(A.row_vals, A.row_cols, A.row_blocks, YT,
                              ell_block=A.ell_block,
                              use_pallas=use_pallas)
        return local.astype(A.dtype)
    return A @ YT
