"""Engine phases from the program's named scopes (``bench/phases.py``):
the phase of each instruction, with its fallbacks, on a program compiled
here and on hand-written HLO; time, bytes and idle gaps by phase on
made-up traces; and the readers on traces recorded on a TPU v5 lite chip
(``data/``)."""
import dataclasses
import gzip
import os
import re
import types

import numpy as np
import pytest

import phases
import reduce_trace
import run
from reduce_trace import Op

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ("sample_ms", "gather_ms", "gram_ms", "inner_ms", "defer_ms",
       "prologue_ms", "unscoped_ms", "gather_bytes_x", "loop_idle_share")
# What the readers read from each recorded trace of a program with
# phase scopes when it was recorded.
EXPECTED = {
    "epsilon-lasso.sa16.1": {"sample_ms": 0.00900409375,
                             "gather_ms": 11.23768275,
                             "gram_ms": 4.57911903125,
                             "inner_ms": 0.34129421875,
                             "defer_ms": 0.741858125,
                             "prologue_ms": 0.005465,
                             "unscoped_ms": 0.040085,
                             "gather_bytes_x": 31.6250225,
                             "loop_idle_share": 1.4272644895755608},
    "rcv1-svm.sa16.1": {"sample_ms": 0.021327343749999998,
                        "gather_ms": 0.18486459375,
                        "gram_ms": 0.20713928125,
                        "inner_ms": 0.013320125,
                        "defer_ms": 0.16190153125,
                        "prologue_ms": 24.521914,
                        "unscoped_ms": 0.118655,
                        "loop_idle_share": 11.002044216754285},
}


@pytest.fixture(scope="module")
def compiled_hlo():
    """The HLO text of a small SA-BCD Lasso solve, compiled here."""
    import jax
    from repro import api
    rng = np.random.default_rng(0)
    A = rng.standard_normal((64, 16)).astype(np.float32)
    b = rng.standard_normal(64).astype(np.float32)
    problem = api.LassoProblem(A=A, b=b, lam=0.1)
    cfg = api.SolverConfig(s=4, block_size=2, iterations=8,
                           accelerated=False)

    def solve(A, b):
        res = api.solve(dataclasses.replace(problem, A=A, b=b), cfg)
        return res.x, res.objective

    return jax.jit(solve).lower(A, b).compile().as_text()


def _instructions(hlo):
    """{instruction name: its text as a trace event names it}."""
    out = {}
    for line in hlo.splitlines():
        m = phases._INSTR.match(line)
        if m:
            out[m.group(1)] = f"%{m.group(1)} = {m.group(2)}"
    return out


def test_innermost_scope():
    assert phases.scope_of(
        "jit(run)/while/body/closed_call/phase.assemble/phase.gather/"
        "gather") == "gather"
    assert phases.scope_of("jit(run)/phase.setup/jit(f)/dot_general") \
        == "setup"
    assert phases.scope_of("jit(run)/while/cond/lt") == phases.UNSCOPED


def test_phases_of_a_compiled_program(compiled_hlo):
    of = phases.instruction_phases(compiled_hlo)
    assert {"setup", "sample", "gather", "gram", "reduce", "inner",
            "defer"} <= set(of.values())
    texts = _instructions(compiled_hlo)
    for name, text in texts.items():
        op = re.search(r'op_name="([^"]*)"', text)
        if op and "phase." in op.group(1):
            assert of[name] == op.group(1).split("phase.")[-1].split("/")[0]
    # the gather nests in assemble: the innermost scope wins
    assert any("phase.assemble/phase.gather" in t and of[n] == "gather"
               for n, t in texts.items())


HAND = """\
ENTRY %main (p.0: f32[8,4]) -> f32[8] {
  %p.0 = f32[8,4] parameter(0)
  %gather.1 = f32[8,4] gather(%p.0), metadata={op_name="jit(run)/phase.assemble/phase.gather/gather"}
  %copy.2 = f32[8,4] copy(%gather.1)
  %copy.3 = f32[8,4] copy(%copy.2)
  %slice-start.9 = ((f32[8,4]), f32[8,2], s32[]) slice-start(%copy.3), slice={[0:8], [0:2]}
  %copy-start.4 = (f32[8], f32[8], u32[]) copy-start(%p.0)
  %copy-done.5 = f32[8] copy-done(%copy-start.4)
  %fusion.6 = f32[8] fusion(%copy-done.5, %copy.3), kind=kLoop, calls=%fused.6, metadata={op_name="jit(run)/phase.inner/mul"}
  %custom-call.7 = f32[8] custom-call(), custom_call_target="AllocateBuffer"
  ROOT %add.8 = f32[8] add(%fusion.6, %custom-call.7), metadata={op_name="jit(run)/while/body/add"}
}
"""


def test_producer_and_user_fallback():
    of = phases.instruction_phases(HAND)
    assert of["gather.1"] == "gather"
    # no op_name: the nearest producer with one, two steps up for copy.3
    assert of["copy.2"] == of["copy.3"] == "gather"
    # a nested tuple result does not hide the operands
    assert of["slice-start.9"] == "gather"
    # producers without one (a parameter): the nearest user with one
    assert of["copy-start.4"] == of["copy-done.5"] == "inner"
    # an op_name outside every scope, and an instruction whose only
    # neighbour has one
    assert of["add.8"] == of["custom-call.7"] == phases.UNSCOPED
    assert of["p.0"] == "gather"


def test_result_bytes():
    assert phases.result_bytes(
        "%fusion.220 = (f32[30848,2000]{0,1:T(8,128)}, /*index=1*/"
        "s32[16,8]{1,0:T(8,128)S(1)}, u32[]{:S(2)}, pred[3]{0}) "
        "fusion(f32[400000,2000]{0,1:T(8,128)} %p)") \
        == 30848 * 2000 * 4 + 16 * 8 * 4 + 4 + 3
    assert phases.result_bytes(
        "%copy.83 = f32[400000,128]{0,1:T(8,128)} copy(f32[400000,128]"
        "{1,0:T(8,128)} %b)") == 400000 * 128 * 4
    assert phases.result_bytes("%c = bf16[] constant(0)") == 2
    assert phases.result_bytes("fusion.7") == 0


def _pick(of, phase):
    return next(n for n, p in sorted(of.items()) if p == phase)


def test_time_bytes_and_gaps_by_phase(compiled_hlo):
    """Two solves of a made-up trace, named by the compiled program's
    instructions: gaps before a set-up operation fall between solves and
    stay out of ``loop_idle_share``; the phases' time adds up to the
    busy time."""
    of = phases.instruction_phases(compiled_hlo)
    texts = _instructions(compiled_hlo)
    setup, gather, inner, other = (_pick(of, p) for p in (
        "setup", "gather", "inner", phases.UNSCOPED))

    def op(name, lo, hi):
        return Op(name, lo, hi, texts[name])

    red = reduce_trace.Reduction(
        {"/device:TPU:0": [op(setup, 0, 10), op(gather, 12, 30),
                           op(inner, 35, 50), op(other, 50, 52),
                           op(setup, 60, 70), op(inner, 71, 80)]},
        [("bench.window", 0, 100)])
    cfg = types.SimpleNamespace(iterations=8, block_size=2, s=4,
                                dtype=np.float32)
    ctx = types.SimpleNamespace(trace=red, hlo=compiled_hlo, cfg=cfg,
                                solves=2, outer=4, m_loc=64, chips=1)
    secs = {p: phases.device_seconds(ctx, (p,))
            for p in set(of.values())}
    assert secs["setup"] == pytest.approx(20e-9)
    assert secs["gather"] == pytest.approx(18e-9)
    assert secs["inner"] == pytest.approx(24e-9)
    assert sum(secs.values()) == pytest.approx(red.busy_s())
    assert phases.ms_per(ctx, ("inner",), ctx.outer) == \
        pytest.approx(1e3 * 24e-9 / 4)
    gaps = phases.gaps_before(red, of)["/device:TPU:0"]
    assert gaps == [(pytest.approx(2e-9), "gather"),
                    (pytest.approx(5e-9), "inner"),
                    (pytest.approx(8e-9), "setup"),
                    (pytest.approx(1e-9), "inner")]
    assert phases.loop_idle_share(ctx) == pytest.approx(8.0)
    reader = run.load_module(os.path.join(run.BENCH, "metrics",
                                          "gather_bytes_x.py"), "gbx")
    assert reader.read(ctx) == pytest.approx(
        phases.result_bytes(texts[gather]) / (2 * 8 * 2 * 64 * 4))


def _recorded(with_phases):
    out = []
    for f in sorted(os.listdir(DATA)):
        if f.endswith(".xplane.pb.gz"):
            name = f[:-len(".xplane.pb.gz")]
            with gzip.open(os.path.join(DATA, name + ".hlo.txt.gz"),
                           "rt") as fh:
                if ("phase." in fh.read()) == with_phases:
                    out.append(name)
    return out


def _ctx(name, tmp_path):
    workload, solves = name.rsplit(".", 1)
    path = tmp_path / "t.xplane.pb"
    with gzip.open(os.path.join(DATA, name + ".xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    with gzip.open(os.path.join(DATA, name + ".hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    cell = run.load_cell(workload)
    cfg, _ = run.solver_config(cell.traffic)
    m = cell.config.SHAPE["m"]
    devices = [types.SimpleNamespace(device_kind="TPU v5 lite")] \
        * cell.chips
    red = reduce_trace.Reduction.from_xplane(str(path))
    got = run.per_layer(cell, red, hlo, cfg, m, int(solves), devices)
    ctx = types.SimpleNamespace(trace=red, hlo=hlo, cfg=cfg,
                                solves=int(solves), chips=cell.chips,
                                outer=int(solves) * cfg.outer_iterations,
                                m_loc=m // cell.chips)
    return cell, got, ctx


@pytest.mark.parametrize("name", _recorded(with_phases=False))
def test_no_phases_no_metric(name, tmp_path):
    """A program compiled without the scopes (the recorded traces of
    the benchmark's first programs) reads none of the phase metrics."""
    _, got, _ = _ctx(name, tmp_path)
    assert not set(got) & set(NEW)


@pytest.mark.parametrize("name", _recorded(with_phases=True))
def test_recorded_trace_by_phase(name, tmp_path):
    """Every phase metric of the cell reads, as when recorded; the
    phases' device time adds up to the busy time within 2%."""
    cell, got, ctx = _ctx(name, tmp_path)
    mine = {m["name"] for m in cell.per_layer if m["name"] in NEW}
    assert mine and mine <= set(got)
    for k, v in EXPECTED[name].items():
        assert got[k]["value"] == pytest.approx(v, rel=1e-9)
    of = phases.of(ctx)
    total = sum(phases.device_seconds(ctx, (p,)) for p in set(of.values()))
    assert total == pytest.approx(ctx.trace.busy_s(), rel=0.02)
