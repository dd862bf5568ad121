"""Places in HLO text, on a hand-written module shaped like the s-step
program: a group loop whose body holds a kernel and an inner loop."""
import hlo

HLO = """\
%inner_body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %fusion.7 = f32[8] fusion(%p), kind=kLoop, calls=%fused.7
  ROOT %tuple.2 = (s32[], f32[8]) tuple(%p, %fusion.7)
}

%fused.7 (a: f32[8]) -> f32[8] {
  ROOT %add.3 = f32[8] add(%a, %a)
}

%inner_cond (p: (s32[], f32[8])) -> pred[] {
  ROOT %lt.1 = pred[] compare(%p, %p), direction=LT
}

%group_body (q: (s32[], f32[8])) -> (s32[], f32[8]) {
  %q = (s32[], f32[8]) parameter(0)
  %gram_t.9 = f32[128,256] custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/while/body/jit(gram_t)/pallas_call"}
  %all-reduce.4 = f32[128,256] all-reduce(%gram_t.9), to_apply=%sum
  %while.5 = (s32[], f32[8]) while(%q), condition=%inner_cond, body=%inner_body
  ROOT %tuple.3 = (s32[], f32[8]) tuple(%q, %q)
}

%group_cond (q: (s32[], f32[8])) -> pred[] {
  ROOT %lt.2 = pred[] compare(%q, %q), direction=LT
}

ENTRY %main.1 (A.1: f32[64,8]) -> f32[8] {
  %A.1 = f32[64,8] parameter(0)
  %while.9 = (s32[], f32[8]) while(%A.1), condition=%group_cond, body=%group_body
  ROOT %gte.1 = f32[8] get-tuple-element(%while.9), index=1
}
"""


def test_group_loop_and_inner_stage():
    assert hlo.group_loop_bodies(HLO) == ["group_body"]
    assert hlo.inner_loop_ops(HLO) == {"p", "fusion.7", "add.3", "tuple.2",
                                       "lt.1", "while.5"}


def test_kernels_by_name_and_collectives():
    assert hlo.kernels_named(HLO, "gram_t") == {"gram_t.9"}
    assert hlo.kernels_named(HLO, "svm_inner") == set()
    assert "gram_t.9" in hlo.inner_stage_ops(HLO, "gram_t")
    assert hlo.allreduces_per_outer(HLO) == 1
