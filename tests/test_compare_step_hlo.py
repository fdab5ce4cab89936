"""tools/compare_step_hlo.py: two optimized HLO texts of one program from
two checkouts compare equal once source locations are cut out."""
import importlib.util
import os

import pytest

TOOL = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools", "compare_step_hlo.py")


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location("compare_step_hlo", TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TEXT = '''HloModule jit_whole_step

FileNames
1 "{root}/mxnet_tpu/gluon/model_zoo/decoder.py"

StackFrames
1 {{file_location_id=1 parent_frame_id=1}}

ENTRY main {{
  p = f32[8]{{0}} parameter(0), metadata={{op_name="x" source_file="{root}/a.py" source_line={line} stack_frame_id=3}}
  k = f32[8]{{0}} custom-call(p), custom_call_target="tpu_custom_call", backend_config={{"custom_call_config":{{"body":"{body}"}}}}
  ROOT r = f32[8]{{0}} add(p, k), metadata={{op_name="{op}" source_file="{root}/b.py" source_line=7}}
}}
'''


def test_source_locations_and_kernel_bodies_are_cut_out(tool):
    a = TEXT.format(root="/root/repo", line=12, body="QUJD", op="add")
    b = TEXT.format(root="/root/scratch/parent", line=340, body="WFla",
                    op="add")
    assert a != b
    assert tool.outside_kernels(a) == tool.outside_kernels(b)
    assert any("custom_call_target" in l for l in tool.outside_kernels(a))


def test_another_instruction_is_a_difference(tool):
    a = TEXT.format(root="/root/repo", line=12, body="QUJD", op="add")
    b = TEXT.format(root="/root/repo", line=12, body="QUJD", op="ut_step/add")
    assert tool.outside_kernels(a) != tool.outside_kernels(b)


def test_a_renumbering_alone_leaves_no_line_once_numbers_are_stripped(tool):
    """One more operand of a kernel renumbers what follows it: line for
    line most lines differ, as a multiset without instruction numbers
    only the lines the change touched are left."""
    a = ["  %constant.7 = s32[] constant(0)",
         "  %add.12 = f32[8]{0} add(%p.1, %constant.7)",
         "  %k = f32[8]{0} custom-call(%constant.7, %add.12)"]
    b = ["  %constant.9 = s32[] constant(0)",
         "  %constant.10 = s32[16]{0} constant({...})",
         "  %add.15 = f32[8]{0} add(%p.1, %constant.9)",
         "  %k = f32[8]{0} custom-call(%constant.9, %constant.10, %add.15)"]
    assert sum(x != y for x, y in zip(a, b)) == 3
    ua, ub = tool.unnumbered(a), tool.unnumbered(b)
    assert sorted(ua - ub) == [
        "  %k = f32[8]{0} custom-call(%constant, %add)"]
    assert sorted(ub - ua) == [
        "  %constant = s32[16]{0} constant({...})",
        "  %k = f32[8]{0} custom-call(%constant, %constant, %add)"]


def test_what_is_computed_ignores_names_placement_and_plumbing(tool):
    """A fusion that went away moves numbers, memory spaces and prefetches
    of lines it never touched; as (opcode, result type, scope) only the
    work that came or went is left."""
    a = ['  %param_2.7814 = f32[64,8192,1]{2,1,0:T(8,128)} parameter(2)',
         '  %copy.4 = f32[64,8192,1]{2,1,0:T(8,128)} copy(%bitcast.9)',
         '  %fusion.7 = f32[2,32,8192]{2,1,0:T(8,128)} fusion(%p.1, %p.2), '
         'kind=kInput, calls=%fused_computation.7, '
         'metadata={op_name="jit(step)/attention/reduce_sum"}',
         '  %fusion.9 = bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)} fusion(%x.3), '
         'kind=kLoop, metadata={op_name="jit(step)/mlp/mul"}',
         '  %k.1 = (bf16[64,8,1024,128]{3,2,1,0}, f32[64,8192,1]{2,1,0}) '
         'custom-call(%q.1), custom_call_target="tpu_custom_call", '
         'metadata={op_name="jit(step)/attention/pallas_call"}']
    b = ['  %copy-start.2 = (f32[8]{0:S(1)}, f32[8]{0}) copy-start(%w.1)',
         '  %fusion.5 = bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)S(1)} '
         'fusion(%x.2), kind=kLoop, metadata={op_name="jit(step)/mlp/mul"}',
         '  %k.1 = (bf16[64,8,1024,128]{3,2,1,0}, f32[64,8,8,128]{3,2,1,0}) '
         'custom-call(%q.1), custom_call_target="tpu_custom_call", '
         'metadata={op_name="jit(step)/attention/pallas_call"}']
    ca, cb = tool.computed(a), tool.computed(b)
    assert sorted(ca - cb) == [
        ("custom-call", "(bf16[64,8,1024,128], f32[64,8192,1])",
         "jit(step)/attention/pallas_call"),
        ("fusion", "f32[2,32,8192]", "jit(step)/attention/reduce_sum")]
    assert sorted(cb - ca) == [
        ("custom-call", "(bf16[64,8,1024,128], f32[64,8,8,128])",
         "jit(step)/attention/pallas_call")]


def test_a_fused_computations_parameter_is_renumbered_too(tool):
    a = ["  %add.3 = f32[8]{0} add(%param_2.7814, %param_0.12)"]
    b = ["  %add.9 = f32[8]{0} add(%param_2.7501, %param_0.33)"]
    assert tool.unnumbered(a) == tool.unnumbered(b)
    assert tool.unnumbered(["  %x = f32[8]{0} add(%param_1.5, %p)"]) != \
        tool.unnumbered(["  %x = f32[8]{0} add(%param_2.5, %p)"])
