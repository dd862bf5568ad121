"""Compile rehearsal: the four solver kernels, compiled by the TPU
compiler for a described (not attached) v5e chip, at the shapes
``chip_smoke.py`` runs and at the edge of each VMEM guard.

Interpret mode cannot see what Mosaic refuses (unaligned tiles, dynamic
lane indexing, more VMEM than a kernel may use); these compiles can,
without a chip. Each asserts that the compiled HLO holds the kernel
(``tpu_custom_call``), i.e. that the wrapper did not fall back to the
jnp reference.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU compiler's library,
so every pytest worker must collect the same tests and only the worker
that runs this file may touch it.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import dispatch
from repro.kernels.gram import gram_t
from repro.kernels.sa_inner import sa_inner_loop
from repro.kernels.spmm import ell_spmm
from repro.kernels.svm_inner import svm_inner_loop


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("m,c", [
    (400_000, 128),     # dense Lasso at epsilon's shape: Y^T [Y | r]
    (2_000, 128),       # dense SVM at epsilon's width: Y [Y^T | x]
])
def test_gram_compiles_for_v5e(one_chip, m, c):
    text = _compile_text(lambda x, y: gram_t(x, y, use_pallas=True),
                         one_chip, ((m, c), F32), ((m, c + 1), F32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("R,K,C,Q", [
    (128, 112, 4_096, 129),     # the kernel-parity shape
    (128, 112, 7_952, 129),     # largest C the guard admits
])
def test_spmm_compiles_for_v5e(one_chip, R, K, C, Q):
    assert dispatch.spmm_vmem_ok(R, K, C, Q)
    text = _compile_text(
        lambda v, i, b, d: ell_spmm(v, i, b, d, ell_block=8,
                                    use_pallas=True),
        one_chip, ((R, K), F32), ((R, K), I32), ((R,), I32), ((C, Q), F32))
    assert "tpu_custom_call" in text


# (s, mu): the chip_smoke shape, and the largest s*mu the (s*mu)^2 * 4 B
# guard admits with and without the power iteration (mu = 1 skips it).
_INNER = [(16, 8), (181, 8), (1448, 1)]


@pytest.mark.parametrize("s,mu", _INNER)
def test_sa_inner_compiles_for_v5e(one_chip, s, mu):
    assert dispatch.vmem_ok(s, mu)
    text = _compile_text(
        lambda G, yp, zp, zv, idx, th, cu: sa_inner_loop(
            G, yp, zp, zv, idx, th, cu, q=16.0, lam1=0.1,
            use_pallas=True),
        one_chip, ((s * mu, s * mu), F32), ((s, mu), F32), ((s, mu), F32),
        ((s, mu), F32), ((s, mu), I32), ((s,), F32), ((s,), F32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("s,mu", _INNER)
def test_svm_inner_compiles_for_v5e(one_chip, s, mu):
    assert dispatch.vmem_ok(s, mu)
    text = _compile_text(
        lambda G, pr, b, av, idx: svm_inner_loop(
            G, pr, b, av, idx, gamma=1e-3, nu=1.0, use_pallas=True),
        one_chip, ((s * mu, s * mu), F32), ((s, mu), F32), ((s, mu), F32),
        ((s, mu), F32), ((s, mu), I32))
    assert "tpu_custom_call" in text
