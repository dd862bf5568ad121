"""Compile rehearsal: the four solver kernels, compiled by the TPU
compiler for a described (not attached) v5e chip, at the shapes
``chip_smoke.py`` runs and at the edge of each VMEM guard.

Interpret mode cannot see what Mosaic refuses (unaligned tiles, dynamic
lane indexing, more VMEM than a kernel may use); these compiles can,
without a chip. Each asserts that the compiled HLO holds the kernel
(``tpu_custom_call``), i.e. that the wrapper did not fall back to the
jnp reference, and that the kernel's instruction carries the kernel's
name (``gram_t``, ``spmm``, ``sa_inner``, ``svm_inner``): the chip
benchmark finds a kernel's trace events by that name.

Whole dense Lasso solves are compiled at epsilon's shape too, to show
that the column gather of every iteration reads only the sampled
columns (``repro.core.sparse_exec.col_take``).

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU compiler's library,
so every pytest worker must collect the same tests and only the worker
that runs this file may touch it.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import sparse_exec
from repro.core.types import LassoProblem, SolverConfig
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


def _assert_kernel(text, word):
    """The compiled HLO holds Mosaic kernels, each named ``word.N``."""
    names = [m.group(1) for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln
             for m in [re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", ln)] if m]
    assert names
    assert all(re.fullmatch(re.escape(word) + r"\.\d+", n) for n in names), \
        names


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("m,c", [
    (400_000, 128),     # dense Lasso at epsilon's shape: Y^T [Y | r]
    (2_000, 128),       # dense SVM at epsilon's width: Y [Y^T | x]
])
def test_gram_compiles_for_v5e(one_chip, m, c):
    text = _compile_text(lambda x, y: gram_t(x, y, use_pallas=True),
                         one_chip, ((m, c), F32), ((m, c + 1), F32))
    _assert_kernel(text, "gram_t")


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
    _assert_kernel(text, "spmm")


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
    _assert_kernel(text, "sa_inner")


@pytest.mark.parametrize("s,mu", _INNER)
def test_svm_inner_compiles_for_v5e(one_chip, s, mu):
    assert dispatch.vmem_ok(s, mu)
    text = _compile_text(
        lambda G, pr, b, av, idx: svm_inner_loop(
            G, pr, b, av, idx, gamma=1e-3, nu=1.0, use_pallas=True),
        one_chip, ((s * mu, s * mu), F32), ((s, mu), F32), ((s, mu), F32),
        ((s, mu), F32), ((s, mu), I32))
    _assert_kernel(text, "svm_inner")


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(")
_ARRAY = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
# instructions that hold no buffer of their own
_VIEWS = {"parameter", "get-tuple-element", "tuple", "bitcast", "constant",
          "while"}


def _largest_in_loops(text):
    """The most elements any one array result holds among the
    instructions of the program's loop bodies, apart from the Gram's
    operands (``phase.gram``)."""
    comps, cur = {}, None
    for ln in text.splitlines():
        m = _COMPUTATION.match(ln)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and ln.startswith("  "):
            cur.append(ln)
    bodies = {b for lines in comps.values() for ln in lines
              for b in re.findall(r"body=%([\w.\-]+)", ln)}
    largest = 0
    for body in bodies:
        for ln in comps[body]:
            m = _INSTRUCTION.match(ln)
            if not m or m.group(2) in _VIEWS or "phase.gram" in ln:
                continue
            for dims in _ARRAY.findall(m.group(1)):
                largest = max(largest, int(np.prod(
                    [int(d) for d in dims.split(",") if d])))
    return largest


@pytest.mark.parametrize("form", ["feature_major", "row_major"])
@pytest.mark.parametrize("s,H,use_pallas", [
    (16, 512, True),     # sa_bcd_lasso, as epsilon-lasso.sa16 runs it
    (1, 128, False),     # bcd_lasso, as epsilon-lasso.s1 runs it
])
def test_dense_lasso_loop_gathers_only_the_block(one_chip, monkeypatch,
                                                 form, s, H, use_pallas):
    """No loop instruction holds more than the s mu sampled columns: A
    is not relayouted at every gather. The row-major form (where a
    second A would not fit: a device of one byte) shows the test bites:
    its gather copies all of A in slices at every iteration."""
    if form == "row_major":
        monkeypatch.setattr(sparse_exec, "device_bytes_limit", lambda: 1)
    m, n, mu = 400_000, 2_000, 8
    cfg = SolverConfig(s=s, block_size=mu, iterations=H,
                       use_pallas=use_pallas)
    labels = {}

    def run(A, b):
        res = api.solve(LassoProblem(A=A, b=b, lam=0.1), cfg)
        labels.update(res.aux)
        return res.x, res.objective

    text = _compile_text(run, one_chip, ((m, n), F32), ((m,), F32))
    assert labels["gather_layout"] == form
    if form == "feature_major":
        assert _largest_in_loops(text) <= s * mu * m
    else:
        assert _largest_in_loops(text) > s * mu * m
