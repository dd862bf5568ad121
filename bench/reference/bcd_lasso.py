"""Plain randomized block coordinate descent for the Lasso.

The reference the Lasso cells are judged by. It follows the textbook
method one block at a time, with none of the s-step reformulation, in
plain ``jax.numpy`` at the dtype it is given (float32 matmuls at
"highest"), and imports nothing of the program under test. What it
shares with the program is the method's definition: the block drawn
at iteration h is the top ``block_size`` of ``uniform(fold_in(key(seed),
h), (n,))``, the step is 1 / lambda_max of the block's Gram matrix by
``power_iters`` power steps from the normalised ones vector, and the
update is a soft-thresholded gradient step.

    min_x  1/2 ||A x - b||^2 + lam ||x||_1
"""
import jax
import jax.numpy as jnp


def _power_max_eig(G, iters):
    mu = G.shape[0]
    if mu == 1:
        return G[0, 0]
    v = jnp.ones((mu,), G.dtype) / jnp.sqrt(jnp.asarray(mu, G.dtype))

    def body(v, _):
        w = G @ v
        return w / jnp.maximum(jnp.linalg.norm(w), 1e-30), None

    v, _ = jax.lax.scan(body, v, None, length=iters)
    return v @ (G @ v)


def solve(data, solver, seed, dtype=jnp.float32):
    """(x, objective after each iteration) of ``solver["iterations"]``
    block steps from x = 0. ``data`` holds A (m, n), b (m,) and lam."""
    if solver.get("accelerated", False):
        raise NotImplementedError("the accelerated method has no "
                                  "reference here")
    mu = int(solver["block_size"])
    H = int(solver["iterations"])
    iters = int(solver.get("power_iters", 32))
    lam = float(data["lam"])
    dev = jax.devices()[0]                 # the whole problem on one chip
    A = jax.device_put(data["A"], dev)
    b = jax.device_put(data["b"], dev)
    precision = "highest" if jnp.dtype(dtype) == jnp.float32 else None

    # A^T as an array of its own, so that a block's columns are rows
    # read whole; left to fuse, the transpose turns every step's read of
    # mu rows into a pass over all of A.
    AT = jax.jit(lambda A: A.T.astype(dtype))(A)       # (n, m)

    @jax.jit
    def run(AT, b):
        n = AT.shape[0]
        key = jax.random.key(seed)

        def step(carry, h):
            x, r = carry
            idx = jax.lax.top_k(jax.random.uniform(
                jax.random.fold_in(key, h), (n,)), mu)[1]
            Ab = AT[idx]                               # (mu, m)
            G = Ab @ Ab.T
            grad = Ab @ r
            eta = 1.0 / jnp.maximum(_power_max_eig(G, iters),
                                    jnp.finfo(dtype).tiny)
            g = x[idx] - eta * grad
            new = jnp.sign(g) * jnp.maximum(jnp.abs(g) - eta * lam, 0.0)
            dx = (new - x[idx]).astype(dtype)
            x = x.at[idx].add(dx)
            r = r + dx @ Ab
            obj = 0.5 * jnp.sum(r * r) + lam * jnp.sum(jnp.abs(x))
            return (x, r), obj

        x0 = jnp.zeros((n,), dtype)
        (x, _), objs = jax.lax.scan(step, (x0, (-b).astype(dtype)),
                                    jnp.arange(1, H + 1))
        return x, objs

    with jax.default_matmul_precision(precision):
        return run(AT, b)
