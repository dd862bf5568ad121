"""LIBSVM ``epsilon`` as a Lasso problem, at its published shape.

400,000 rows by 2,000 dense features in f32 (3.2 GB on the device),
rows scaled to unit norm as in the published set. The targets are
b = A x* + 0.01 noise with a 32-sparse x* whose entries are +-1, and
lam = 0.1 m / n, which is 0.1 ||A^T b||_inf to within a few percent on
every seed (a column of A has squared norm m / n). lam is a constant of
the compiled program, so it is held fixed: a lam drawn from each seed
would compile every run anew. On four chips A's rows are sharded,
100,000 to a chip.
"""
import jax
import jax.numpy as jnp

SOURCE = ("https://www.csie.ntu.edu.tw/~cjlin/libsvmtools/datasets/"
          "binary.html#epsilon (PASCAL Large Scale Learning Challenge 2008)")
FAMILY = "lasso"
SHAPE = {"m": 400_000, "n": 2_000}
DTYPE = "float32"          # data and solver dtype, as the configuration states
REDUCED = []
ASSUMED = [
    "Gaussian feature values in place of epsilon's own, rows scaled to "
    "unit norm",
    "a regression target b = A x* + 0.01 noise with a 32-sparse x* in "
    "place of epsilon's binary labels",
    "x* with 32 entries of +-1 at random places and signs",
    "lam = 0.1 m / n in place of 0.1 ||A^T b||_inf, to which it is equal "
    "to within a few percent",
]
REFERENCE = "bcd_lasso"


def make(seed, shardings=None, m=SHAPE["m"], n=SHAPE["n"]):
    """(A, b, lam) drawn on the device from ``seed`` in one jitted call;
    ``shardings`` lays out A and b, or leaves them on the default device
    when None."""
    def draw(key):
        kA, ki, kx, ke = jax.random.split(key, 4)
        A = jax.random.normal(kA, (m, n), jnp.float32)
        A = A / jnp.linalg.norm(A, axis=1, keepdims=True)
        support = jax.random.choice(ki, n, (min(32, n),), replace=False)
        xs = jnp.zeros((n,), jnp.float32).at[support].set(
            jax.random.rademacher(kx, support.shape, jnp.float32))
        return A, A @ xs + 0.01 * jax.random.normal(ke, (m,))

    draw = jax.jit(draw) if shardings is None else jax.jit(
        draw, out_shardings=(shardings["A"], shardings["b"]))
    with jax.default_matmul_precision("highest"):
        A, b = draw(jax.random.key(seed))
    return {"A": A, "b": b, "lam": 0.1 * m / n}


def problem(data):
    from repro.api import LassoProblem
    return LassoProblem(A=data["A"], b=data["b"], lam=data["lam"])
