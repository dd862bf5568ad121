"""The peaks table and the Gram kernel's least work."""
import math

import pytest

import peaks
from repro.core import cost_model

# (rows a chip holds, s, mu) of the cells that report gram_roofline.
CELL_SHAPES = [(400_000, 16, 8), (100_000, 16, 8)]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


def test_v5e_peaks_and_their_precision():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p.flops, p.flops_precision, p.hbm_bytes_per_s) == (
        197e12, "bf16", 819e9)


@pytest.mark.parametrize("m_loc,s,mu", CELL_SHAPES)
def test_gram_flops_match_cost_model(m_loc, s, mu):
    """cost_model's Lasso F counts the Gram of one outer iteration as
    (s mu)^2 m / P multiply-adds (plus H mu^3 for the subproblems); the
    benchmark counts 2 FLOPs a multiply-add and the projection column."""
    P, H = 400_000 // m_loc, 512
    dims = cost_model.ProblemDims(m=400_000, n=2_000, f=1.0)
    F = cost_model.lasso_costs(dims, H, mu, s, P)["F"] - H * mu ** 3
    per_outer = F / (H / s)
    smu = s * mu
    assert math.isclose(peaks.gram_flops(m_loc, smu),
                        2 * (per_outer + m_loc * smu), rel_tol=1e-12)


@pytest.mark.parametrize("m_loc,s,mu", CELL_SHAPES)
def test_gram_is_bound_by_hbm_at_the_cells_shapes(m_loc, s, mu):
    smu = s * mu
    nbytes = peaks.gram_least_bytes(m_loc, smu)
    assert nbytes == 4 * (m_loc * smu + m_loc + smu * (smu + 1))
    t, bound = peaks.roofline_seconds(peaks.gram_flops(m_loc, smu), nbytes,
                                      peaks.peaks_for("TPU v5 lite"))
    assert bound == "hbm" and t == nbytes / 819e9
