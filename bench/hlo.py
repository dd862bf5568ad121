"""Places in a compiled program's HLO text: the loops of the s-step
schedule, the instructions inside them, and the collectives one trip
of the group loop issues.

The group loop is the outermost ``while`` whose body holds the solver's
work; the inner stage is each ``while`` directly in its body (the s
dependent updates). A device trace names its operations by these
instruction names, so a set from here selects trace events.
"""
from __future__ import annotations

import re
from typing import Dict, List, Set

_HEAD = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_CALLS = re.compile(r"(?:body|condition|calls|to_apply|"
                    r"branch_computations)=(\{[^}]*\}|%?[\w.\-]+)")


def computations(hlo: str) -> Dict[str, List[str]]:
    comps, name = {}, None
    for line in hlo.splitlines():
        head = _HEAD.match(line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    return comps


def entry_name(hlo: str) -> str:
    for line in hlo.splitlines():
        if line.startswith("ENTRY "):
            return _HEAD.match(line).group(1)
    raise ValueError("HLO text has no ENTRY computation")


def _called(line: str) -> List[str]:
    out = []
    for ref in _CALLS.findall(line):
        out += re.findall(r"%?([\w.\-]+)", ref)
    return out


def reachable(comps: Dict[str, List[str]], roots) -> Set[str]:
    seen, stack = set(), list(roots)
    while stack:
        c = stack.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for ln in comps[c]:
            stack += _called(ln)
    return seen


def _whiles(comps, comp: str) -> List[str]:
    """The ``while`` instructions of one computation: their bodies and
    conditions."""
    return [m for ln in comps.get(comp, ()) if " while(" in ln
            for m in re.findall(r"(?:body|condition)=%?([\w.\-]+)", ln)]


def _top_whiles(comps, root: str) -> List[str]:
    """Bodies and conditions of the ``while`` loops reachable from
    ``root`` without passing through another loop."""
    out, seen, stack = [], set(), [root]
    while stack:
        c = stack.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        loops = set(_whiles(comps, c))
        out += sorted(loops)
        for ln in comps[c]:
            if " while(" in ln:
                continue
            stack += _called(ln)
    return out


def group_loop_bodies(hlo: str) -> List[str]:
    """Bodies of the outermost loops that themselves hold a loop: the
    s-step group loop (one trip per outer iteration)."""
    comps = computations(hlo)
    return [b for b in _top_whiles(comps, entry_name(hlo))
            if _top_whiles(comps, b)]


def instructions(comps, names) -> Set[str]:
    out = set()
    for c in reachable(comps, names):
        for ln in comps[c]:
            m = _INSTR.match(ln)
            if m:
                out.add(m.group(1))
    return out


def inner_loop_ops(hlo: str) -> Set[str]:
    """Instruction names inside the loops that the group loop's body
    runs (with the ``while`` instructions themselves)."""
    comps = computations(hlo)
    out = set()
    for body in group_loop_bodies(hlo):
        inner = _top_whiles(comps, body)
        out |= instructions(comps, inner)
        for ln in comps[body]:
            m = _INSTR.match(ln)
            if m and " while(" in ln:
                out.add(m.group(1))
    return out


def custom_calls(hlo: str, target: str = "tpu_custom_call") -> Dict[str, str]:
    """{instruction name: kernel name} of the custom calls to ``target``;
    the kernel name is the Mosaic kernel's own, where the HLO gives it."""
    out = {}
    for ln in hlo.splitlines():
        if f'custom_call_target="{target}"' not in ln:
            continue
        m = _INSTR.match(ln)
        if not m:
            continue
        k = re.search(r'kernel_name\\?"\s*:\s*\\?"([^"\\]+)', ln) or \
            re.search(r'op_name="([^"]+)"', ln)
        out[m.group(1)] = k.group(1) if k else ""
    return out


def kernels_named(hlo: str, word: str) -> Set[str]:
    """Instruction names of the Mosaic kernels whose instruction name or
    jax op name contains ``word`` (e.g. ``gram_t``, ``svm_inner``)."""
    return {name for name, op in custom_calls(hlo).items()
            if word in name or word in op}


def inner_stage_ops(hlo: str, kernel_word: str) -> Set[str]:
    """The s-step inner stage of a program whose group loop holds no
    other loop (the dense Lasso): the loops inside the group loop, plus
    the kernels named by ``kernel_word`` wherever they sit."""
    return inner_loop_ops(hlo) | kernels_named(hlo, kernel_word)


def all_reduces(hlo: str) -> Set[str]:
    """Instruction names of the all-reduces (``psum.N`` when jax's psum
    made them), and of their async starts and dones."""
    return {m.group(1) for ln in hlo.splitlines()
            if re.search(r" all-reduce(?:-start|-done)?\(", ln)
            for m in [_INSTR.match(ln)] if m}


def allreduces_per_outer(hlo: str) -> int:
    """all-reduce ops inside the computations a while loop runs (its
    body and everything that body calls): what one trip of the outer
    group loop issues."""
    comps = computations(hlo)
    bodies = {b for lines in comps.values() for ln in lines
              for b in re.findall(r"body=%?([\w.\-]+)", ln)}
    return sum(len(re.findall(r" all-reduce(?:-start)?\(", ln))
               for c in reachable(comps, bodies) for ln in comps[c])
