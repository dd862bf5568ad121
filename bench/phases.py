"""Engine phases on the device trace.

The solvers set one named scope per phase (``phase.setup``,
``phase.sample``, ``phase.assemble`` with ``phase.gather`` and
``phase.gram`` inside it, ``phase.reduce``, ``phase.inner``,
``phase.defer``, ``phase.finalize``). A scope lands in the ``op_name``
metadata of each compiled instruction, and the device trace names its
events by instruction, so every event here gets the phase of its
instruction:

- the innermost ``phase.<name>`` component of the instruction's
  ``op_name``; an ``op_name`` with none is ``unscoped``;
- an instruction with no ``op_name`` (a copy the compiler put in,
  ``copy-start``/``copy-done``, ``AllocateBuffer``) takes the phase of
  its nearest producer that has one, walking operands breadth first;
  failing that, of its nearest user that has one; failing that, it is
  ``unscoped``.

A program compiled without the scopes has no phases at all, and then
:func:`of` returns None and every metric that reads phases is left out.
"""
from __future__ import annotations

import collections
import functools
import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

import reduce_trace

UNSCOPED = "unscoped"
# The phases inside a solve's iteration or group loop.
LOOP = ("sample", "assemble", "gather", "gram", "reduce", "inner", "defer")

_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_BITS = re.compile(r"^[a-z]+(\d+)$")
_BYTES = {"pred": 1, "token": 0}


def scope_of(op_name: str) -> str:
    """The innermost ``phase.<name>`` component of an ``op_name``."""
    found = [part[len("phase."):] for part in op_name.split("/")
             if part.startswith("phase.")]
    return found[-1] if found else UNSCOPED


def _balanced(text: str, start: int) -> int:
    """The index just past the parenthesis that closes ``text[start]``."""
    depth = 0
    for i in range(start, len(text)):
        depth += {"(": 1, ")": -1}.get(text[i], 0)
        if depth == 0:
            return i + 1
    return len(text)


def _split(rhs: str) -> Tuple[str, str]:
    """An instruction's text after ``name = `` as (result shape, the
    rest: opcode, operands, attributes)."""
    if rhs.startswith("("):
        end = _balanced(rhs, 0)
        return rhs[:end], rhs[end:]
    shape, _, rest = rhs.partition(" ")
    return shape, rest


def _operands(rest: str) -> List[str]:
    """The ``%name`` operands in the opcode's parentheses."""
    start = rest.find("(")
    if start < 0:
        return []
    return _REF.findall(rest[start:_balanced(rest, start)])


def _bfs(start: str, edges: Dict[str, List[str]]) -> Iterable[str]:
    seen, queue = {start}, collections.deque(edges.get(start, ()))
    while queue:
        name = queue.popleft()
        if name in seen:
            continue
        seen.add(name)
        yield name
        queue.extend(edges.get(name, ()))


@functools.lru_cache(maxsize=4)
def instruction_phases(hlo: str) -> Dict[str, str]:
    """{instruction name: phase} for every instruction of the module."""
    own: Dict[str, Optional[str]] = {}
    refs: Dict[str, List[str]] = {}
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        own[name] = scope_of(op.group(1)) if op else None
        refs[name] = _operands(_split(rest)[1])
    producers = {n: [r for r in rs if r in own and r != n]
                 for n, rs in refs.items()}
    users: Dict[str, List[str]] = collections.defaultdict(list)
    for n, ps in producers.items():
        for p in ps:
            users[p].append(n)
    out = {}
    for name, phase in own.items():
        if phase is None:
            phase = next((own[p] for p in _bfs(name, producers)
                          if own[p] is not None), None) or \
                next((own[u] for u in _bfs(name, users)
                      if own[u] is not None), UNSCOPED)
        out[name] = phase
    return out


def of(ctx) -> Optional[Dict[str, str]]:
    """The phase of each instruction of the cell's program, or None
    where the program carries no phase scopes."""
    phases = instruction_phases(ctx.hlo)
    if all(p == UNSCOPED for p in phases.values()):
        return None
    return phases


def select(phases: Dict[str, str], names: Tuple[str, ...]
           ) -> Callable[[reduce_trace.Op], bool]:
    return lambda op: phases.get(op.name, UNSCOPED) in names


def device_seconds(ctx, names: Tuple[str, ...]) -> Optional[float]:
    """Device seconds of the named phases' operations in the window,
    averaged over the chips; None where the program has no phases."""
    phases = of(ctx)
    if phases is None or not ctx.trace.devices:
        return None
    secs = ctx.trace.op_seconds(select(phases, names))
    return sum(secs.values()) / len(ctx.trace.devices)


def ms_per(ctx, names: Tuple[str, ...], count: int) -> Optional[float]:
    """Milliseconds of the named phases per ``count`` (outer iterations
    or solves in the window)."""
    secs = device_seconds(ctx, names)
    if secs is None or count <= 0:
        return None
    return 1e3 * secs / count


def result_bytes(text: str) -> int:
    """Bytes of an instruction's result, from its text
    (``%name = <shape> opcode(...)``): the logical shape times the
    element size, summed over the elements of a tuple."""
    if " = " not in text:
        return 0
    shape = _split(text.split(" = ", 1)[1])[0]
    total = 0
    for dtype, dims in _ARRAY.findall(shape):
        bits = _BITS.match(dtype)
        size = int(bits.group(1)) // 8 if bits else _BYTES.get(dtype, 0)
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * size
    return total


def phase_bytes(ctx, names: Tuple[str, ...]) -> Optional[float]:
    """Result bytes of the named phases' events in the window, summed
    per chip and averaged over the chips."""
    phases = of(ctx)
    if phases is None or not ctx.trace.devices:
        return None
    keep = select(phases, names)
    total = sum(result_bytes(op.text) for ops in ctx.trace.devices.values()
                for op in ops if keep(op))
    return total / len(ctx.trace.devices)


def gaps_before(red: reduce_trace.Reduction, phases: Dict[str, str]
                ) -> Dict[str, List[Tuple[float, str]]]:
    """Each device's idle gaps in the window, in seconds, each with the
    phase of the operation that ends it (gaps that no operation ends,
    at the window's end, are left out)."""
    out = {}
    for d, ops in red.devices.items():
        starts = sorted((op.start, phases.get(op.name, UNSCOPED))
                        for op in ops)
        busy = red.busy(d)
        edges = [red.lo] + [x for iv in busy for x in iv]
        gaps, j = [], 0
        for lo, hi in zip(edges[0::2], edges[1::2]):
            if hi <= lo:
                continue
            while starts[j][0] < hi:
                j += 1
            gaps.append(((hi - lo) / 1e9, starts[j][1]))
        out[d] = gaps
    return out


def loop_idle_share(ctx) -> Optional[float]:
    """Share of the window in which the device is idle before an
    operation of a solve's loop: 100 * (idle gaps ended by a
    ``LOOP`` phase) / window, averaged over the chips. Gaps between
    solves end at the next solve's set-up and are not counted."""
    phases = of(ctx)
    if phases is None or not ctx.trace.devices or ctx.trace.window_s <= 0:
        return None
    gaps = gaps_before(ctx.trace, phases)
    idle = sum(g for gs in gaps.values() for g, p in gs if p in LOOP)
    return 100.0 * idle / len(gaps) / ctx.trace.window_s
