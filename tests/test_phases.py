"""Phase scopes: every solver that ``repro.api.solve`` dispatches to
marks its phases with ``repro.core.phases.scope``. The scopes land in
the compiled HLO's ``op_name`` metadata, where the chip benchmark reads
them, and change nothing else in the compiled program."""
import contextlib
import dataclasses
import functools
import re

import jax
import numpy as np
import pytest

from repro import api
from repro.core import phases
from repro.core.sfista import SFISTAProblem
from repro.core.types import (LassoProblem, LogRegProblem, SVMProblem,
                              SolverConfig, SparseOperand)

M, N, MU, H = 48, 32, 2, 8

PROBLEMS = {
    "lasso": lambda A, b: LassoProblem(A=A, b=b, lam=0.1),
    "svm": lambda A, b: SVMProblem(A=A, b=b, lam=1.0),
    "ksvm": lambda A, b: SVMProblem(A=A, b=b, lam=1.0, kernel="rbf",
                                    kernel_params={"gamma": 0.1}),
    "logreg": lambda A, b: LogRegProblem(A=A, b=b, lam=1e-3),
    "sfista": lambda A, b: SFISTAProblem(A=A, b=b, lam=0.1),
}

_ALL = set(phases.NAMES)
# On one device a reduce that is only the Allreduce compiles to nothing,
# and so does a finalize that only hands the carry back.
_NO_FINALIZE = _ALL - {"finalize"}
# solver: (family, registry variant, SolverConfig fields, phases present)
SOLVERS = {
    "bcd_lasso": ("lasso", "classical", dict(s=1, accelerated=False),
                  _NO_FINALIZE),
    "acc_bcd_lasso": ("lasso", "accelerated", dict(s=1, accelerated=True),
                      _ALL),
    "sa_bcd_lasso": ("lasso", "sa", dict(s=4, accelerated=False),
                     _NO_FINALIZE),
    "sa_acc_bcd_lasso": ("lasso", "sa_accelerated",
                         dict(s=4, accelerated=True), _ALL),
    "bdcd_svm": ("svm", "classical", dict(s=1), _NO_FINALIZE),
    "sa_bdcd_svm": ("svm", "sa", dict(s=4), _NO_FINALIZE),
    "kbdcd_svm": ("ksvm", "classical", dict(s=1), _NO_FINALIZE),
    "sa_kbdcd_svm": ("ksvm", "sa", dict(s=4), _NO_FINALIZE),
    "bcd_logreg": ("logreg", "classical", dict(s=1),
                   _NO_FINALIZE - {"reduce"}),
    "sa_bcd_logreg": ("logreg", "sa", dict(s=4),
                      _NO_FINALIZE - {"reduce"}),
    "sfista": ("sfista", "classical", dict(s=1), _NO_FINALIZE),
    "ca_sfista": ("sfista", "sa", dict(s=4), _NO_FINALIZE),
}
CASES = [(name, operand) for name in SOLVERS for operand in ("dense",
                                                             "sparse")]


def _operands(family, operand):
    rng = np.random.default_rng(0)
    A = rng.standard_normal((M, N)).astype(np.float32)
    A *= rng.random((M, N)) < 0.3
    if family in ("lasso", "sfista"):
        b = rng.standard_normal(M).astype(np.float32)
    else:
        b = np.where(rng.random(M) < 0.5, -1.0, 1.0).astype(np.float32)
    return (SparseOperand.from_dense(A) if operand == "sparse" else A), b


@functools.lru_cache(maxsize=None)
def _compiled_text(name, operand, scoped=True):
    """The compiled HLO text of one solve with A and b as arguments."""
    family, variant, fields, _ = SOLVERS[name]
    A, b = _operands(family, operand)
    problem = PROBLEMS[family](A, b)
    assert api.resolve_family(problem).variants[variant].endswith(
        ":" + name)
    cfg = SolverConfig(block_size=MU, iterations=H, **fields)

    def run(A, b):
        res = api.solve(dataclasses.replace(problem, A=A, b=b), cfg)
        return res.x, res.objective

    with contextlib.ExitStack() as stack:
        if not scoped:
            mp = stack.enter_context(pytest.MonkeyPatch.context())
            mp.setattr(phases, "scope",
                       lambda name: contextlib.nullcontext())
        return jax.jit(run).lower(A, b).compile().as_text()


def _phases_in(text):
    return {part[len("phase."):]
            for op in re.findall(r'op_name="([^"]*)"', text)
            for part in op.split("/") if part.startswith("phase.")}


def _stripped(text):
    """The program without metadata: the module line and the
    computations, with each instruction's ``metadata={...}`` dropped
    (the stack-frame tables between them are metadata too)."""
    lines = text.splitlines()
    first = next(i for i, ln in enumerate(lines)
                 if ln.startswith(("%", "ENTRY ")))
    return "\n".join(re.sub(r", metadata=\{[^}]*\}", "", ln)
                     for ln in lines[:1] + lines[first:])


@pytest.mark.parametrize("name,operand", CASES)
def test_every_phase_lands_in_the_compiled_hlo(name, operand):
    found = _phases_in(_compiled_text(name, operand))
    assert found == SOLVERS[name][3]


@pytest.mark.parametrize("name,operand", CASES)
def test_scopes_change_nothing_but_metadata(name, operand):
    bare = _compiled_text(name, operand, scoped=False)
    assert _phases_in(bare) == set()
    assert _stripped(_compiled_text(name, operand)) == _stripped(bare)
