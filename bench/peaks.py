"""Published peaks of the chips the benchmark runs on, and the least
work of the kernels whose roofline share it reports.

A device kind that is not in the table is an error, never a default: a
roofline share against a guessed peak is a number nobody can check.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float      # FLOP/s, at the precision named in `flops_precision`
    flops_precision: str
    hbm_bytes_per_s: float


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16 (393 TOP/s
# in int8), 16 GB of HBM at 819 GB/s. No f32 peak is published; the
# bf16 peak bounds an f32 matmul's rate from above, so a share taken
# against it can only read low, never above 100%.
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, flops_precision="bf16",
                         hbm_bytes_per_s=819e9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None


def gram_flops(m_loc: int, smu: int) -> float:
    """FLOPs of one fused Gram + projection product Y^T [Y | v]:
    Y is (m_loc, smu), v is (m_loc, 1); a multiply-add counts as 2."""
    return 2.0 * m_loc * smu * (smu + 1)


def gram_least_bytes(m_loc: int, smu: int, itemsize: int = 4) -> float:
    """Bytes no implementation can avoid: Y and v each read once from
    HBM, the (smu, smu + 1) result written once."""
    return float(itemsize) * (m_loc * smu + m_loc + smu * (smu + 1))


def roofline_seconds(flops: float, nbytes: float, peaks: Peaks):
    """(least seconds, bound): the larger of the compute and the memory
    time at peak, and which of the two it is."""
    t_flops = flops / peaks.flops
    t_bytes = nbytes / peaks.hbm_bytes_per_s
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")
