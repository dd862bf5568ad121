"""From a profiler trace to the numbers the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Device
operations are the events on the ``XLA Ops`` line of each
``/device:...`` plane; the benchmark's own host spans are the events
whose names start with ``bench.`` on the host planes. Both are read on
the profiler's one clock, so a device gap can be put beside what the
host was doing in it.

Everything is clipped to the ``bench.window`` span: busy time is the
union of the operation intervals inside it, idle time the rest. A
``while`` event spans its body's operations, idle moments between them
included, so loops count only through the operations they run.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]
# A TPU op event is named by its HLO instruction's text: "%fusion.7 = ...".
_INSTR = re.compile(r"^%?([\w.\-]+) = ")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str          # the HLO instruction's name, e.g. "fusion.7"
    start: float       # ns
    end: float         # ns
    text: str = ""     # the event's own name: the instruction's text

    @property
    def is_loop(self) -> bool:
        """A ``while`` event spans the operations of its body."""
        return " while(" in self.text


def _op_name(event) -> str:
    m = _INSTR.match(event.name)
    if m:
        return m.group(1)
    try:
        return str(dict(event.stats).get("hlo_op", event.name))
    except (TypeError, ValueError):
        return event.name


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the merged intervals ``a`` that no interval of the
    merged ``b`` covers."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


class Reduction:
    """The device operations and host spans of one trace, clipped to the
    benchmark's window."""

    def __init__(self, devices: Dict[str, List[Op]],
                 host: List[Tuple[str, float, float]]):
        windows = [(lo, hi) for name, lo, hi in host if name == WINDOW_SPAN]
        if not windows:
            raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
        self.lo, self.hi = windows[0]
        self.host = [(n, lo, hi) for n, lo, hi in host
                     if n != WINDOW_SPAN and hi > self.lo and lo < self.hi]
        self.devices = {
            d: [op for op in ops if op.end > self.lo and op.start < self.hi
                and not op.is_loop]
            for d, ops in sorted(devices.items())}

    @classmethod
    def from_xplane(cls, path: str) -> "Reduction":
        from jax.profiler import ProfileData
        with open(path, "rb") as f:
            return cls.from_profile(ProfileData.from_serialized_xspace(
                f.read()))

    @classmethod
    def from_profile(cls, pd) -> "Reduction":
        devices: Dict[str, List[Op]] = {}
        host: List[Tuple[str, float, float]] = []
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    ops = devices.setdefault(plane.name, [])
                    for e in line.events:
                        ops.append(Op(_op_name(e), e.start_ns,
                                      e.start_ns + e.duration_ns, e.name))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(HOST_PREFIX):
                            host.append((e.name, e.start_ns,
                                         e.start_ns + e.duration_ns))
        for ops in devices.values():
            ops.sort(key=lambda op: op.start)
        return cls(devices, host)

    # -- whole window ----------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy(self, device: str) -> List[Interval]:
        return merge(clip([(op.start, op.end) for op in self.devices[device]],
                          self.lo, self.hi))

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices that ran anything."""
        if not self.devices:
            return 0.0
        return sum(length(self.busy(d)) for d in self.devices) \
            / len(self.devices) / 1e9

    # -- selected operations ---------------------------------------------

    def op_seconds(self, select: Callable[[Op], bool]) -> Dict[str, float]:
        """Seconds of the selected operations inside the window, by
        device (devices where none was selected are left out)."""
        out = {}
        for d, ops in self.devices.items():
            t = length(merge(clip([(op.start, op.end) for op in ops
                                   if select(op)], self.lo, self.hi)))
            if t > 0:
                out[d] = t / 1e9
        return out

    def exposed_seconds(self, select: Callable[[Op], bool]
                        ) -> Dict[str, float]:
        """Seconds in which a selected operation runs and no other
        operation runs on the same device, by device."""
        out = {}
        for d, ops in self.devices.items():
            mine = merge(clip([(o.start, o.end) for o in ops if select(o)],
                              self.lo, self.hi))
            if not mine:
                continue
            rest = merge(clip([(o.start, o.end) for o in ops
                               if not select(o)], self.lo, self.hi))
            out[d] = length(subtract(mine, rest)) / 1e9
        return out

    # -- the breakdown ---------------------------------------------------

    def top_ops(self, n: int = 10, width: int = 120
                ) -> List[Tuple[str, float]]:
        """The operations that took most device time, in seconds summed
        over the window and averaged over the devices. Each is named by
        the start of its instruction's text (name, shape, kind)."""
        tot: Dict[str, float] = collections.defaultdict(float)
        for ops in self.devices.values():
            for op in ops:
                tot[op.text[:width]] += (min(op.end, self.hi)
                                         - max(op.start, self.lo)) / 1e9
        k = max(len(self.devices), 1)
        return sorted(((name, t / k) for name, t in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def host_activity(self, lo: float, hi: float) -> str:
        """The innermost benchmark span that covers the middle of
        [lo, hi], or "host: none"."""
        mid = 0.5 * (lo + hi)
        cover = [(b - a, name) for name, a, b in self.host if a <= mid <= b]
        return min(cover)[1] if cover else "host: none"

    def idle_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """The longest idle gaps of the first device, each named by what
        the host was doing in it."""
        if not self.devices:
            return []
        d = next(iter(self.devices))
        busy = self.busy(d)
        edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [(self.host_activity(lo, hi), (hi - lo) / 1e9)
                for lo, hi in gaps[:n]]


def reduce_dir(trace_dir: str) -> Reduction:
    return Reduction.from_xplane(find_xplane(trace_dir))


def per_outer_ms(seconds_by_device: Dict[str, float], outer: int
                 ) -> Optional[float]:
    """Milliseconds per outer iteration, averaged over the devices, or
    None where no device ran the selection."""
    if not seconds_by_device or outer <= 0:
        return None
    avg = sum(seconds_by_device.values()) / len(seconds_by_device)
    return 1e3 * avg / outer
