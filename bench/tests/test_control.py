"""The control: the plain reference computed in bfloat16, the precision
below the configurations' float32, put in the program's place. At a
size a test run can hold it must fail the cell's limits, where the
float32 reference run twice passes them."""
import json
import os

import jax.numpy as jnp
import pytest

import run

SIZES = {"epsilon-lasso": dict(m=4096, n=256),
         "rcv1-svm": dict(m=1024, n=4096, nnz=60_000)}
with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_the_limits(workload):
    cell = run.load_cell(workload)
    name = cell.config.__name__.split("bench_config_")[-1].replace("_", "-")
    cfg, _ = run.solver_config(cell.traffic)
    data = cell.config.make(2_147_483_703, None, **SIZES[name])
    ref = run.reference(cell, data, cfg)
    same = run.readings([run.reference(cell, data, cfg)], ref)
    assert all(same[k] <= cell.limits[k] for k in cell.limits)
    control = run.readings([run.reference(cell, data, cfg, jnp.bfloat16)],
                           ref)
    assert any(control[k] > cell.limits[k] for k in cell.limits), control
