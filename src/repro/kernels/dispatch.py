"""Shared Pallas-vs-ref dispatch policy for the fused solver kernels
(``sa_inner`` for Lasso, ``svm_inner`` for SVM/K-SVM, ``spmm`` for the
sparse-operand products).

The inner-loop kernels hold the (s*mu, s*mu) replicated Gram/kernel
block resident in VMEM; the blocked-ELL SpMM holds its dense right
operand (plus the gathered values/indices and the output tile) resident.
Both share one budget: reject configurations that would not leave room
(v5e's default scoped VMEM limit is 16 MiB — the TPU compiler accepts a
kernel resident set up to it and refuses one above it; we cap the
modeled resident working set at half of it). The
chosen implementation is an explicit, queryable decision that warns
ONCE per configuration when a requested Pallas route has to fall back
— the solvers surface it in ``SolverResult.aux["inner_impl"]`` /
``aux["spmm_impl"]`` so benchmarks never mislabel ref timings as Pallas.

The guards' resident-set formulas live in ONE queryable table,
:func:`kernel_vmem_model` — consumed by the ``vmem_ok`` /
``spmm_vmem_ok`` dispatch guards below AND by the static kernel safety
pass (``repro.analysis.kernels``), which re-derives each package's true
footprint from its BlockSpecs and flags any drift between the two.
Historically the formulas were literals duplicated here, which is
exactly how the f64 2x-VMEM dispatch bug (PR 5) crept in.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Dict, Mapping, Tuple

_VMEM_G_BYTES_CAP = 8 * 1024 * 1024

_warned = set()


@dataclasses.dataclass(frozen=True)
class KernelVmemEntry:
    """One kernel package's modeled VMEM residency.

    resident_bytes: keyword-only callable mapping the package's
        configuration parameters (named in ``params``) to the modeled
        resident working set in bytes. This is the number the dispatch
        guard compares against ``cap`` — and the number the kernel
        safety pass cross-checks against the footprint it derives from
        the package's BlockSpecs/operand shapes.
    params: the keyword names ``resident_bytes`` accepts, documented so
        callers can introspect the table.
    cap: admission threshold in bytes (the shared budget).
    doc: what the model counts (and deliberately over-counts).
    """

    kernel: str
    params: Tuple[str, ...]
    resident_bytes: Callable[..., float]
    cap: int = _VMEM_G_BYTES_CAP
    doc: str = ""

    def ok(self, **kw) -> bool:
        return self.resident_bytes(**kw) <= self.cap


def _inner_bytes(s: int, mu: int, itemsize: int = 4) -> float:
    return float((s * mu) ** 2 * itemsize)


def _spmm_bytes(R: int, K: int, C: int, Q: int,
                itemsize: int = 4) -> float:
    qp = -(-Q // 128) * 128
    return float((C * qp + R * qp + R * K) * itemsize + R * K * 4)


def _gram_bytes(block_m: int = 256, block_i: int = 128,
                block_j: int = 128, itemsize: int = 4) -> float:
    # double-buffered input tiles + the output tile + the f32 scratch
    # accumulator (scratch is always f32 regardless of input dtype).
    return float((2 * (block_m * block_i + block_m * block_j)
                  + block_i * block_j) * itemsize
                 + block_i * block_j * 4)


def _flash_bytes(block_q: int = 128, block_k: int = 128,
                 head_dim: int = 128, itemsize: int = 4) -> float:
    # double-buffered q/k/v tiles + the output tile + the f32 running
    # (acc, m, l) online-softmax scratch.
    return float((2 * (block_q + 2 * block_k) * head_dim
                  + block_q * head_dim) * itemsize
                 + (block_q * head_dim + 2 * block_q) * 4)


_VMEM_MODEL: Dict[str, KernelVmemEntry] = {
    "sa_inner": KernelVmemEntry(
        "sa_inner", ("s", "mu", "itemsize"), _inner_bytes,
        doc="the dominant resident: the (s*mu)^2 Gram block (the "
            "O(s*mu) projections/schedule arrays ride within the "
            "budget's 2x headroom)"),
    "svm_inner": KernelVmemEntry(
        "svm_inner", ("s", "mu", "itemsize"), _inner_bytes,
        doc="the (s*mu)^2 regularized Gram/kernel block, as sa_inner"),
    "spmm": KernelVmemEntry(
        "spmm", ("R", "K", "C", "Q", "itemsize"), _spmm_bytes,
        doc="the lane-padded dense right operand (C, Qp), the output "
            "(R, Qp), the gathered values (R, K) at itemsize, plus "
            "int32 indices (R, K) — conservatively counting ALL R row "
            "tiles although only one is block-resident at a time"),
    "gram": KernelVmemEntry(
        "gram", ("block_m", "block_i", "block_j", "itemsize"),
        _gram_bytes,
        doc="double-buffered (block_m, block_i)/(block_m, block_j) "
            "input tiles, the (block_i, block_j) output tile and its "
            "f32 accumulator scratch"),
    "flash_attention": KernelVmemEntry(
        "flash_attention", ("block_q", "block_k", "head_dim",
                            "itemsize"), _flash_bytes,
        doc="double-buffered (block_q, D) query and (block_k, D) "
            "key/value tiles, the output tile and the f32 online-"
            "softmax (acc, m, l) scratch"),
}


def kernel_vmem_model() -> Mapping[str, KernelVmemEntry]:
    """The queryable VMEM residency table: one
    :class:`KernelVmemEntry` per kernel package under ``repro.kernels``.
    The SINGLE source of the guard formulas — dispatch admission
    (:func:`vmem_ok`, :func:`spmm_vmem_ok`) and the static kernel
    safety pass (``repro.analysis.kernels``) both read it, so a formula
    edit cannot drift the two apart."""
    return dict(_VMEM_MODEL)


def vmem_ok(s: int, mu: int, itemsize: int = 4) -> bool:
    """Does the (s*mu)^2 Gram block fit the budget at ``itemsize``
    bytes/element? The guards were historically dtype-blind (hardcoded
    4 B/element) — an f64 solve holds f64 residents, so near-cap configs
    dispatched Pallas with TWICE the modeled VMEM. Callers thread the
    solve dtype's itemsize through."""
    return _VMEM_MODEL["sa_inner"].ok(s=s, mu=mu, itemsize=itemsize)


def reset_fallback_warnings() -> None:
    """Forget which fallback configurations have already warned.

    The warn-once memo is process-global, which is right for a solver
    run but leaks across tests: whichever test first trips a fallback
    swallows the warning every later test asserts on (order-dependent
    flakiness). The test suite resets it around every test (see
    tests/conftest.py); long-lived drivers can call it to re-arm the
    warnings after reconfiguring."""
    _warned.clear()


def _warn_fallback(key, message: str) -> None:
    if key in _warned:
        return
    _warned.add(key)
    warnings.warn(message, stacklevel=4)


def choose_inner_impl(name: str, s: int, mu: int,
                      use_pallas: bool, itemsize: int = 4) -> str:
    """"pallas" or "ref", warning once per (name, s, mu, itemsize) on a
    forced Pallas -> ref fallback."""
    if not use_pallas:
        return "ref"
    if vmem_ok(s, mu, itemsize):
        return "pallas"
    _warn_fallback(
        (name, s, mu, itemsize),
        f"{name}: use_pallas=True but (s*mu)^2 Gram "
        f"({(s * mu) ** 2 * itemsize} B at {itemsize} B/element) "
        f"exceeds the VMEM cap ({_VMEM_G_BYTES_CAP} B) for s={s}, "
        f"mu={mu}; falling back to the jnp reference path")
    return "ref"


def spmm_vmem_ok(R: int, K: int, C: int, Q: int,
                 itemsize: int = 4) -> bool:
    """Does the blocked-ELL SpMM working set — the VMEM-resident dense
    right operand (C, Q) (lane-padded), the output (R, Q), and the
    gathered values (R, K), all at ``itemsize`` bytes/element, plus the
    int32 indices (R, K) at 4 B — fit the budget?"""
    return _VMEM_MODEL["spmm"].ok(R=R, K=K, C=C, Q=Q, itemsize=itemsize)


def choose_spmm_impl(R: int, K: int, C: int, Q: int,
                     use_pallas: bool, itemsize: int = 4) -> str:
    """"pallas" or "ref" for an (R, K) x (C, Q) blocked-ELL SpMM,
    warning once per (shape, itemsize) on a forced Pallas -> ref
    fallback."""
    if not use_pallas:
        return "ref"
    if spmm_vmem_ok(R, K, C, Q, itemsize):
        return "pallas"
    _warn_fallback(
        ("spmm", R, K, C, Q, itemsize),
        f"spmm: use_pallas=True but the blocked-ELL working set for "
        f"R={R}, K={K}, C={C}, Q={Q} at {itemsize} B/element exceeds "
        f"the VMEM cap ({_VMEM_G_BYTES_CAP} B); falling back to the "
        f"jnp reference path")
    return "ref"
