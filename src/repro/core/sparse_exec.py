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
"""
from __future__ import annotations

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


def col_block_ops(A, cfg):
    """(block_gram, block_apply) for the column-sampling (Lasso) layout.

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

    def block_gram(idx, vecs):
        with phases.scope("gather"):
            Ah = A[:, idx]
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


def spmm_aux(A, cfg, kind: str, H=None, extra: int = 0) -> dict:
    """The ``aux["spmm_impl"]`` entry for a sparse solve — empty for
    dense operands. ONE place derives the (R, K, C, Q) SpMM shape from
    the layout, so the surfaced label cannot drift from the shapes the
    solver actually dispatches:

      * "col_gram" — Lasso fused  A_B^T [A_B | vecs]  (columns sampled);
      * "row_gram" — SVM fused    Y [Y^T | vecs]      (rows sampled);
      * "cross"    — K-SVM/logreg cross block  A Y^T.

    ``extra`` is the appended-vector count k. H=None labels a classical
    (one block per iteration) solve; otherwise the grouped main+tail
    label over the SA schedule (H, cfg.s).
    """
    if not isinstance(A, SparseOperand):
        return {}
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
