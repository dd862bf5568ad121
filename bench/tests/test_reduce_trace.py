"""The trace reduction, on interval arithmetic and on traces recorded on
a TPU v5 lite chip (``data/``: a short window of the cells named in the
file names, with the compiled program's HLO text beside each)."""
import gzip
import os
import types

import pytest

import reduce_trace
import run
from reduce_trace import merge, subtract

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# What the reduction read from each recorded trace when it was recorded.
EXPECTED = {
    "epsilon-lasso.sa16.4": {"device_idle_share": 1.7141570626105884,
                             "gram_roofline": 22.019443372673713,
                             "sa_inner_ms": 0.34008053125},
    "epsilon-lasso.sa16.p4.1": {"device_idle_share": 7.55513398036306,
                                "gram_roofline": 30.478481049955064,
                                "sa_inner_ms": 0.340144390625,
                                "allreduce_exposed_ms": 0.013958671875},
}


def test_interval_arithmetic():
    assert merge([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]
    assert subtract([(0, 2)], []) == [(0, 2)]


def _op(name, lo, hi):
    return reduce_trace.Op(name, lo, hi, f"%{name} = f32[] op()")


def test_busy_exposed_and_gaps_on_a_made_up_trace():
    loop = reduce_trace.Op("while.1", 0, 100, "%while.1 = () while(%t)")
    red = reduce_trace.Reduction(
        {"/device:TPU:0": [loop, _op("fusion.1", 10, 40),
                           _op("all-reduce.2", 30, 60),
                           _op("fusion.3", 70, 80)]},
        [("bench.window", 0, 100), ("bench.block_until_ready", 40, 100)])
    assert red.window_s == 100e-9
    assert red.busy_s() == pytest.approx(60e-9)      # the loop adds nothing
    ar = red.exposed_seconds(lambda op: "all-reduce" in op.name)
    assert ar == {"/device:TPU:0": pytest.approx(20e-9)}
    gaps = red.idle_gaps()
    assert gaps[0] == ("bench.block_until_ready", pytest.approx(20e-9))
    assert [g for _, g in gaps] == sorted((g for _, g in gaps), reverse=True)


def _recorded():
    out = []
    for f in sorted(os.listdir(DATA)):
        if f.endswith(".xplane.pb.gz"):
            out.append(f[:-len(".xplane.pb.gz")])
    return out


@pytest.mark.parametrize("name", _recorded())
def test_recorded_trace(name, tmp_path):
    """``<workload>.<solves>.xplane.pb.gz``: the reduction finds the
    window, every chip, and each of the cell's per-layer metrics, and
    no share leaves [0, 100]."""
    workload, solves = name.rsplit(".", 1)
    path = tmp_path / "t.xplane.pb"
    with gzip.open(os.path.join(DATA, name + ".xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    with gzip.open(os.path.join(DATA, name + ".hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    red = reduce_trace.Reduction.from_xplane(str(path))
    cell = run.load_cell(workload)
    assert len(red.devices) == cell.chips
    assert 0 < red.busy_s() <= red.window_s
    cfg, _ = run.solver_config(cell.traffic)
    m = cell.config.SHAPE["m"]
    devices = [types.SimpleNamespace(device_kind="TPU v5 lite")] * cell.chips
    got = run.per_layer(cell, red, hlo, cfg, m, int(solves), devices)
    assert set(got) == {metric["name"] for metric in cell.per_layer}
    for k, v in EXPECTED.get(name, {}).items():
        assert got[k]["value"] == pytest.approx(v, rel=1e-9)
    for metric in got.values():
        assert metric["value"] > 0
        if metric["unit"] == "%":
            assert metric["value"] <= 100
    ops, gaps = red.top_ops(), red.idle_gaps()
    assert 0 < len(ops) <= 10 and len(gaps) <= 10
    assert all(t > 0 for _, t in ops)
