import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.analysis import (HW_V5E, collective_bytes_from_hlo,
                                     collective_stats_from_hlo,
                                     model_flops, roofline_terms, two_point_fit)

SAMPLE_HLO = """
ENTRY %main {
  %p0 = bf16[128,256]{1,0} parameter(0)
  %ar = bf16[128,256]{1,0} all-reduce(%p0), replica_groups={}
  %ag = f32[64,64]{1,0} all-gather(%p0), dimensions={0}
  %rs = f32[8,8]{1,0} reduce-scatter(%ag), dimensions={0}
  %a2a = f32[16]{0} all-to-all(%rs), dimensions={0}
  %cp = s32[4,4]{1,0} collective-permute(%a2a), source_target_pairs={{0,1}}
  %add = f32[64,64]{1,0} add(%ag, %ag)
  ROOT %out = f32[64,64]{1,0} copy(%add)
}
"""


def test_collective_parser_counts_each_type():
    out = collective_bytes_from_hlo(SAMPLE_HLO)
    assert out["all-reduce"] == 128 * 256 * 2
    assert out["all-gather"] == 64 * 64 * 4
    assert out["reduce-scatter"] == 8 * 8 * 4
    assert out["all-to-all"] == 16 * 4
    assert out["collective-permute"] == 4 * 4 * 4
    assert out["total"] == sum(out[k] for k in (
        "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
        "collective-permute"))


def test_collective_parser_ignores_non_collectives():
    out = collective_bytes_from_hlo("%x = f32[8]{0} add(%a, %b)")
    assert out["total"] == 0


def test_collective_parser_tuple_shapes():
    hlo = "%t = (f32[8]{0}, f32[8]{0}) all-gather(%a, %b)"
    out = collective_bytes_from_hlo(hlo)
    assert out["all-gather"] == 2 * 8 * 4


# verbatim shape of a real jax 0.4.x XLA-CPU post-SPMD dump (4 forced
# host devices, psum of an (8, 8) f32 inside shard_map): ROOT-prefixed
# op, typed operands, channel/replica metadata trailing the call.
REAL_CPU_HLO = """\
HloModule jit_fn, entry_computation_layout={(f32[8,8]{1,0})->f32[8,8]{1,0}}

%region_0.4 (Arg_0.5: f32[], Arg_1.6: f32[]) -> f32[] {
  %Arg_0.5 = f32[] parameter(0)
  %Arg_1.6 = f32[] parameter(1)
  ROOT %add.7 = f32[] add(f32[] %Arg_0.5, f32[] %Arg_1.6)
}

ENTRY %main.9 (Arg_0.1: f32[8,8]) -> f32[8,8] {
  %Arg_0.1 = f32[8,8]{1,0} parameter(0), metadata={op_name="x"}
  %dot.1 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %Arg_0.1, f32[8,8]{1,0} %Arg_0.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %all-reduce.1 = f32[8,8]{1,0} all-reduce(f32[8,8]{1,0} %dot.1), channel_id=1, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%region_0.4, metadata={op_name="jit(fn)/jit(main)/psum"}
}
"""


def test_collective_parser_real_cpu_hlo_root_prefix():
    """The historical parser's regex missed ROOT-prefixed collectives
    entirely — which is exactly how XLA prints an all-reduce that is the
    computation's result. Pin the real-dump form."""
    stats = collective_stats_from_hlo(REAL_CPU_HLO)
    assert stats.counts["all-reduce"] == 1
    # operand shape f32[8,8] inside all-reduce(...) must NOT be summed:
    # only the result buffer travels.
    assert stats.bytes["all-reduce"] == 8 * 8 * 4
    assert stats.total_count == 1


def test_collective_parser_start_done_counted_once():
    """Async collectives appear as a -start/-done pair whose -start
    result tuple aliases the operand buffers in its first half; the op
    is ONE transfer of the result half's bytes."""
    hlo = """\
  %ar-start = (f32[128,64]{1,0}, f32[128,64]{1,0}) all-reduce-start(f32[128,64]{1,0} %p0), replica_groups={{0,1}}, to_apply=%sum
  %ar-done = f32[128,64]{1,0} all-reduce-done((f32[128,64]{1,0}, f32[128,64]{1,0}) %ar-start)
"""
    stats = collective_stats_from_hlo(hlo)
    assert stats.counts["all-reduce"] == 1
    assert stats.bytes["all-reduce"] == 128 * 64 * 4


def test_collective_parser_typed_counts():
    """CollectiveStats keeps counts and bytes in separate typed fields;
    the legacy dict view mirrors them under \"counts\"/\"total\"."""
    stats = collective_stats_from_hlo(SAMPLE_HLO)
    assert stats.counts == {"all-reduce": 1, "all-gather": 1,
                            "reduce-scatter": 1, "all-to-all": 1,
                            "collective-permute": 1}
    legacy = collective_bytes_from_hlo(SAMPLE_HLO)
    assert legacy["counts"] == dict(stats.counts)
    assert legacy["total"] == stats.total_bytes


def test_two_point_fit_exact_linear():
    # cost(n) = 10 + 3n
    assert two_point_fit(13, 16, 1, 2, 32) == pytest.approx(10 + 3 * 32)


def test_roofline_terms_classification():
    t = roofline_terms(flops_per_dev=1e15, bytes_per_dev=1e9,
                       coll_bytes_per_dev=1e9, hw=HW_V5E)
    assert t["dominant"] == "compute"
    t = roofline_terms(flops_per_dev=1e9, bytes_per_dev=1e13,
                       coll_bytes_per_dev=1e9, hw=HW_V5E)
    assert t["dominant"] == "memory"
    t = roofline_terms(flops_per_dev=1e9, bytes_per_dev=1e9,
                       coll_bytes_per_dev=1e13, hw=HW_V5E)
    assert t["dominant"] == "collective"
    assert 0 < t["roofline_fraction"] <= 1.0


def test_model_flops_conventions():
    assert model_flops(1e9, "train", tokens=1000) == 6e12
    assert model_flops(1e9, "prefill", tokens=1000) == 2e12
    assert model_flops(1e9, "decode", tokens=0, batch=64) == 2e9 * 64


def test_xla_flops_convention_is_2mnk():
    """Pin the XLA cost-model convention the roofline relies on:
    cost_analysis reports 2*M*N*K FLOPs for a dot (per device)."""
    a = jax.ShapeDtypeStruct((256, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 64), jnp.float32)
    c = jax.jit(lambda x, y: x @ y).lower(a, b).compile()
    flops = c.cost_analysis()["flops"]
    assert flops == pytest.approx(2 * 256 * 128 * 64, rel=0.05)


def test_xla_scan_body_counted_once():
    """Pin the scan-counting behaviour that motivates the two-point fit."""
    def f(x):
        def body(c, _):
            return jnp.tanh(c @ c), None
        out, _ = jax.lax.scan(body, x, None, length=8)
        return out

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    fl8 = jax.jit(f).lower(x).compile().cost_analysis()["flops"]

    def f1(x):
        def body(c, _):
            return jnp.tanh(c @ c), None
        out, _ = jax.lax.scan(body, x, None, length=1)
        return out

    fl1 = jax.jit(f1).lower(x).compile().cost_analysis()["flops"]
    assert fl8 == pytest.approx(fl1, rel=0.01)


def test_hardware_lookup_by_device_kind():
    from repro.roofline.analysis import hardware_for
    assert hardware_for("TPU v5 lite") is HW_V5E
    with pytest.raises(ValueError, match="no published peaks"):
        hardware_for("cpu")
