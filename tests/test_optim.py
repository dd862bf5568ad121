import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.optim.adamw import AdamW, cosine_schedule
from repro.optim.compress import (ErrorFeedback, dequantize_int8,
                                  quantize_int8)


def test_adamw_minimizes_quadratic():
    opt = AdamW(learning_rate=0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"w": 2.0 * params["w"]}
        params, state = opt.update(grads, state, params)
    assert float(jnp.max(jnp.abs(params["w"]))) < 1e-2


def test_adamw_weight_decay_shrinks():
    opt = AdamW(learning_rate=0.01, weight_decay=0.5, clip_norm=0.0)
    params = {"w": jnp.asarray([1.0])}
    state = opt.init(params)
    grads = {"w": jnp.asarray([0.0])}
    params2, _ = opt.update(grads, state, params)
    assert float(params2["w"][0]) < 1.0


def test_clipping_bounds_update():
    opt = AdamW(learning_rate=1.0, clip_norm=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    state = opt.init(params)
    g_small = {"w": jnp.full(4, 1e-3)}
    g_huge = {"w": jnp.full(4, 1e6)}
    p1, _ = opt.update(g_small, state, params)
    p2, _ = opt.update(g_huge, state, params)
    # clipped huge gradient produces a comparable (not 1e9x) step
    assert float(jnp.max(jnp.abs(p2["w"]))) < 10 * max(
        float(jnp.max(jnp.abs(p1["w"]))), 1e-3)


def test_cosine_schedule_shape():
    lr = cosine_schedule(1.0, warmup_steps=10, total_steps=100)
    assert float(lr(0)) == 0.0
    assert abs(float(lr(10)) - 1.0) < 1e-6
    assert float(lr(100)) < float(lr(50)) < float(lr(10))
    assert float(lr(100)) >= 0.099            # min_ratio floor


@given(st.lists(st.floats(-100, 100, width=32), min_size=1, max_size=64))
@settings(max_examples=50, deadline=None)
def test_quantize_int8_error_bound(vals):
    x = jnp.asarray(vals, jnp.float32)
    q, scale = quantize_int8(x)
    back = dequantize_int8(q, scale)
    max_abs = float(jnp.max(jnp.abs(x)))
    # elementwise error bounded by half a quantization step
    assert float(jnp.max(jnp.abs(back - x))) <= max_abs / 127.0 + 1e-6


def test_error_feedback_preserves_sum():
    """EF property: quantization errors don't accumulate — the cumulative
    applied update tracks the cumulative true gradient."""
    from repro.optim.compress import compressed_psum
    import functools
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    # single-device axis: psum over a size-1 mesh axis is identity, but
    # exercises the full codepath.
    mesh = jax.make_mesh((1,), ("dp",))
    grads_seq = [jnp.asarray([0.3, -0.7, 0.01]) * (i + 1)
                 for i in range(20)]
    ef = ErrorFeedback.init({"g": grads_seq[0]})
    applied = jnp.zeros(3)
    for g in grads_seq:
        def body(gg, res):
            out, ef2 = compressed_psum({"g": gg},
                                       ErrorFeedback(residual={"g": res}),
                                       "dp", n_shards=1)
            return out["g"], ef2.residual["g"]
        fn = shard_map(body, mesh=mesh, in_specs=(P(), P()),
                       out_specs=(P(), P()), check_vma=False)
        out, res = fn(g, ef.residual["g"])
        ef = ErrorFeedback(residual={"g": res})
        applied = applied + out
    true_sum = sum(grads_seq)
    np.testing.assert_allclose(np.asarray(applied), np.asarray(true_sum),
                               rtol=0.02, atol=0.05)
