"""repro.optim.compress invariants, deterministically (the
hypothesis-based error-bound property lives in test_optim.py, which
skips wholesale when hypothesis is unavailable — these must always
run): exact int8 roundtrip on the quantization grid, and the
error-feedback identities that make compressed allreduce safe."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.optim.compress import (ErrorFeedback, dequantize_int8,
                                  quantize_int8)


def test_quantize_int8_roundtrip_exact_on_grid():
    """Values already on the quantization grid survive the int8 roundtrip
    exactly, and requantizing a dequantized tensor is idempotent (the
    codec is a projection)."""
    scale0 = 0.5
    x = jnp.asarray([-127, -64, 0, 1, 127], jnp.float32) * scale0
    q, scale = quantize_int8(x)
    back = dequantize_int8(q, scale)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
    q2, scale2 = quantize_int8(back)
    assert np.array_equal(np.asarray(q2), np.asarray(q))
    assert float(scale2) == float(scale)


def test_error_feedback_step_identity():
    """The defining EF invariant, per step and exactly: the corrected
    gradient splits into applied + residual with no leakage —
    (g + r_in) == q * scale + r_out bitwise in f32. This is what makes
    the cumulative applied update track the cumulative true gradient."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.optim.compress import compressed_psum

    mesh = jax.make_mesh((1,), ("dp",))
    rng = np.random.default_rng(0)

    def body(gg, res):
        out, ef2 = compressed_psum({"g": gg},
                                   ErrorFeedback(residual={"g": res}),
                                   "dp", n_shards=1)
        return out["g"], ef2.residual["g"]

    fn = shard_map(body, mesh=mesh, in_specs=(P(), P()),
                   out_specs=(P(), P()), check_vma=False)
    r = jnp.zeros(16)
    for i in range(8):
        g = jnp.asarray(rng.standard_normal(16), jnp.float32) * (10.0 ** (i - 4))
        applied, r_new = fn(g, r)
        np.testing.assert_array_equal(np.asarray(g + r),
                                      np.asarray(applied + r_new))
        # residual bounded by half a quantization step of the corrected
        # tensor (the EF contraction property).
        step = float(jnp.max(jnp.abs(g + r))) / 127.0
        assert float(jnp.max(jnp.abs(r_new))) <= 0.5 * step + 1e-7
        r = r_new


def test_error_feedback_accumulation_drains():
    """A constant gradient too small to survive quantization alone is
    NOT lost: the residual accumulates until it crosses a quantization
    step and drains into the applied update (EF's raison d'etre)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.optim.compress import compressed_psum

    mesh = jax.make_mesh((1,), ("dp",))

    def body(gg, res):
        out, ef2 = compressed_psum({"g": gg},
                                   ErrorFeedback(residual={"g": res}),
                                   "dp", n_shards=1)
        return out["g"], ef2.residual["g"]

    fn = shard_map(body, mesh=mesh, in_specs=(P(), P()),
                   out_specs=(P(), P()), check_vma=False)
    # one dominant coordinate sets the scale; the tiny coordinate is far
    # below scale/2 and would round to zero every step without EF.
    g = jnp.asarray([1.0, 1e-3], jnp.float32)
    r = jnp.zeros(2)
    applied_tiny = 0.0
    for _ in range(40):
        applied, r = fn(g, r)
        applied_tiny += float(applied[1])
    # without EF: applied_tiny == 0 after every step. With EF the
    # cumulative applied value tracks 40 * 1e-3 to one quantization step.
    assert abs(applied_tiny - 40e-3) <= 1.0 / 127.0 + 1e-6
    assert applied_tiny > 0.0
