"""Roofline term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh) cell — all in seconds:

    compute    = HLO_FLOPs        / (chips * peak_FLOPs_per_chip)
    memory     = HLO_bytes        / (chips * HBM_bandwidth)
    collective = collective_bytes / (chips * ICI_link_bandwidth)

Sources and corrections (measured behaviours of jax 0.8.2 / XLA-CPU in
this container — see DESIGN.md §3):
  * ``compiled.cost_analysis()`` reports PER-DEVICE numbers and counts a
    ``scan`` body ONCE regardless of trip count -> we extract per-layer
    costs by a two-point fit over unrolled reduced-depth lowerings
    (cost = fixed + n_groups * per_group) and extrapolate to full depth.
  * XLA counts dot FLOPs as M*N*K (MACs). We convert MAC -> FLOP with x2
    on the reported total (matmuls dominate; elementwise undercount is
    <1% for these models). Verified in tests/test_roofline.py.
  * collective bytes are not in cost_analysis -> parsed from the
    post-SPMD ``compiled.as_text()`` by summing result-shape bytes of
    all-reduce / all-gather / reduce-scatter / all-to-all /
    collective-permute ops (same two-point fit for scan bodies).
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Dict, Mapping, Optional

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str
    peak_flops: float        # per chip, FLOP/s
    hbm_bw: float            # per chip, B/s
    ici_bw: float            # per link, B/s
    hbm_bytes: float         # per chip


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s (bf16),
# 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect
# (four links of 50 GB/s).
HW_V5E = Hardware(name="tpu-v5e", peak_flops=197e12, hbm_bw=819e9,
                  ici_bw=50e9, hbm_bytes=16e9)
HARDWARE: Mapping[str, Hardware] = {"TPU v5 lite": HW_V5E}


def hardware_for(device_kind: str) -> Hardware:
    """The published peaks of a device kind; an unknown kind is an
    error, never a default."""
    try:
        return HARDWARE[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(HARDWARE)})") from None

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# result-shape token, e.g.  bf16[16,4096,256]{2,1,0}
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    m = _SHAPE_RE.match(shape_str)
    if not m:
        return 0
    dt, dims = m.groups()
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


_HLO_OP_RE = re.compile(
    r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(?[^=]*?\)?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start|-done)?\(")


@dataclasses.dataclass(frozen=True)
class CollectiveStats:
    """Typed per-collective counts and result bytes parsed from an HLO
    dump (replaces the historical dict whose ``counts`` entry was
    smuggled past the ``Dict[str, float]`` annotation with a
    ``# type: ignore``)."""

    counts: Mapping[str, int]
    bytes: Mapping[str, float]

    @property
    def total_bytes(self) -> float:
        return float(sum(self.bytes.values()))

    @property
    def total_count(self) -> int:
        return int(sum(self.counts.values()))


def collective_stats_from_hlo(hlo_text: str) -> CollectiveStats:
    """Count collective ops and sum their result bytes in a (post-SPMD)
    HLO dump.

    ONE rule covers every form an op can take:

      * plain:        ``bf16[128,256]{1,0} all-reduce(...)`` — count the
        op once, sum every result shape (tuple results are variadic
        collectives: each element is a distinct reduced buffer);
      * ``-start``:   the async launch half of a ``-start``/``-done``
        pair. When its result is a 2k-tuple whose halves match, the
        first half aliases the operand buffers and only the second half
        is the communicated result — count the op once with the result
        half's bytes (the historical parser summed both, double
        counting every async collective);
      * ``-done``:    the completion marker of a pair already counted at
        its ``-start`` — skipped entirely.

    Ops inside while bodies are counted once (caller applies trip-count
    fits).
    """
    counts = {c: 0 for c in _COLLECTIVES}
    bytes_ = {c: 0.0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _HLO_OP_RE.match(line.strip())
        if not m:
            continue
        shapes_part, op, suffix = m.groups()
        if suffix == "-done":
            continue
        shapes = [_shape_bytes(sm.group(0))
                  for sm in _SHAPE_RE.finditer(shapes_part)]
        if suffix == "-start" and len(shapes) % 2 == 0 and \
                shapes[:len(shapes) // 2] == shapes[len(shapes) // 2:]:
            shapes = shapes[len(shapes) // 2:]
        counts[op] += 1
        bytes_[op] += float(sum(shapes))
    return CollectiveStats(counts=counts, bytes=bytes_)


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, Any]:
    """Legacy dict view of :func:`collective_stats_from_hlo` — per-op
    bytes keyed by op name, plus ``"total"`` (bytes) and ``"counts"``
    (the per-op count dict)."""
    stats = collective_stats_from_hlo(hlo_text)
    out: Dict[str, Any] = dict(stats.bytes)
    out["total"] = stats.total_bytes
    out["counts"] = dict(stats.counts)
    return out


def two_point_fit(cost1: float, cost2: float, n1: int, n2: int,
                  n_target: int) -> float:
    """cost(n) = fixed + n * per_unit, fit on (n1, cost1), (n2, cost2)."""
    per = (cost2 - cost1) / max(n2 - n1, 1)
    fixed = cost1 - n1 * per
    return fixed + n_target * per


def roofline_terms(flops_per_dev: float, bytes_per_dev: float,
                   coll_bytes_per_dev: float, hw: Hardware,
                   mac_correction: float = 1.0) -> Dict[str, float]:
    """The three terms (seconds) + the bound classification."""
    compute = flops_per_dev * mac_correction / hw.peak_flops
    memory = bytes_per_dev / hw.hbm_bw
    collective = coll_bytes_per_dev / hw.ici_bw
    dominant = max(("compute", compute), ("memory", memory),
                   ("collective", collective), key=lambda kv: kv[1])[0]
    total = max(compute, memory, collective)
    return {"compute_s": compute, "memory_s": memory,
            "collective_s": collective, "dominant": dominant,
            "bound_s": total,
            "roofline_fraction": compute / total if total > 0 else 0.0}


def model_flops(n_params_active: int, kind: str, tokens: int,
                batch: int = 1) -> float:
    """MODEL_FLOPS: 6*N*D for training (fwd+bwd), 2*N*D for inference.

    decode: D = batch (one token per sequence per step).
    """
    if kind == "train":
        return 6.0 * n_params_active * tokens
    if kind == "prefill":
        return 2.0 * n_params_active * tokens
    return 2.0 * n_params_active * batch        # decode: per step
