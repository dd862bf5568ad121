"""LIBSVM ``rcv1.binary`` as an L1-SVM (hinge loss, lam = 1), at its
published shape.

20,242 rows by 47,236 features with about 1.5M nonzeros, rows scaled
to unit norm as rcv1's tf-idf rows are. The labels are
b = sign(A w) for a Gaussian w. A reaches the solver as the program's
sparse operand, built from COO triplets on the host, in ELL arrays of
fixed widths: the widths are shapes of the compiled program, and widths
taken from each seed's longest row and column would compile every run
anew. A row holds at most 160 nonzeros and a column at most 80: rows
average 74 (the longest over 52 seeds held 132) and columns 32 (the
longest 62), so the cap, which drops a row's or column's nonzeros past
it, almost never drops one, and no seed can fail the layout.
"""
import jax
import jax.numpy as jnp
import numpy as np

SOURCE = ("https://www.csie.ntu.edu.tw/~cjlin/libsvmtools/datasets/"
          "binary.html#rcv1.binary (Lewis et al., JMLR 5, 2004)")
FAMILY = "svm"
SHAPE = {"m": 20_242, "n": 47_236, "nnz": 1_500_000}
ROW_WIDTH, COL_WIDTH = 160, 80     # ELL widths, multiples of the block 8
DTYPE = "float32"
REDUCED = []
ASSUMED = [
    "nonzeros placed uniformly at random, where rcv1's are skewed over "
    "the columns",
    "nonzero values uniform in [0.05, 1) before the row scaling",
    "labels b = sign(A w) for a Gaussian w in place of rcv1's own",
    "at most 160 nonzeros a row and 80 a column (the ELL widths)",
]
REFERENCE = "bdcd_svm"


def make(seed, shardings=None, m=SHAPE["m"], n=SHAPE["n"],
         nnz=SHAPE["nnz"]):
    """COO triplets (rows, cols, vals) on the host and labels b, all
    from ``seed``. The program's sharded backend splits a sparse operand
    itself, so ``shardings`` is not used."""
    kk, kv, kw = jax.random.split(jax.random.key(seed), 3)
    keys = np.unique(np.asarray(jax.random.randint(kk, (nnz,), 0, m * n)))
    rows, cols = keys // n, keys % n
    widths = (ROW_WIDTH, COL_WIDTH) if (m, n) == (SHAPE["m"], SHAPE["n"]) \
        else (None, None)
    if widths[0]:
        keep = _rank_in_group(rows, m) < ROW_WIDTH
        keep &= _rank_in_group(cols, n) < COL_WIDTH
        keys, rows, cols = keys[keep], rows[keep], cols[keep]
    vals = np.asarray(jax.random.uniform(kv, (keys.size,), jnp.float32,
                                         0.05, 1.0))
    norms = np.sqrt(np.bincount(rows, vals * vals, minlength=m))
    vals = (vals / np.maximum(norms, 1e-12)[rows]).astype(np.float32)
    w = np.asarray(jax.random.normal(kw, (n,)), np.float64)
    y = np.bincount(rows, vals * w[cols], minlength=m)
    b = np.where(y >= 0, 1.0, -1.0).astype(np.float32)
    return {"rows": rows.astype(np.int32), "cols": cols.astype(np.int32),
            "vals": vals, "b": b, "shape": (m, n), "lam": 1.0,
            "widths": widths}


def _rank_in_group(group, size):
    """Each entry's place among the entries of its group, in order."""
    order = np.argsort(group, kind="stable")
    first = np.searchsorted(group[order], np.arange(size))
    rank = np.empty(group.size, np.int64)
    rank[order] = np.arange(group.size) - first[group[order]]
    return rank


def problem(data):
    from repro.api import SVMProblem
    from repro.core.types import SparseOperand
    row_width, col_width = data["widths"]
    A = SparseOperand.from_coo(data["rows"], data["cols"], data["vals"],
                               data["shape"], row_width=row_width,
                               col_width=col_width)
    return SVMProblem(A=A, b=jnp.asarray(data["b"]), lam=data["lam"],
                      loss="l1")
