"""The step programs' device time by named scope (bench/scopes.py): the
map from a compiled program's HLO text, the reduction of a trace by it,
and the step programs rebuilt at a size the CPU holds."""
import re

import jax.numpy as jnp
import pytest

from bench import harness, scopes as S
from bench.tests.tiny import make_ctx, tiny_cell
from bench.xplane import Ev, Trace

HLO = """HloModule jit_serve_step_c8, is_scheduled=true

FileNames
1 "transformer.py"

%fused_computation.3 (param_0.1: bf16[8]) -> bf16[8] {
  %param_0.1 = bf16[8]{0} parameter(0)
  ROOT %gather.2 = bf16[8]{0} gather(%param_0.1), metadata={op_type="gather" op_name="jit(serve_step_c8)/while/body/attn.paged/gather"}
}

ENTRY %main.9 (p: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0)
  %fusion.3 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.3, metadata={op_type="gather" op_name="jit(serve_step_c8)/while/body/attn.paged/gather" source_file="attention.py" source_line=600}
  %fused_gemv_allreduce.1 = bf16[8]{0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"mesh_axes":"[\\"model\\"]"
}}, metadata={op_type="pallas_call" op_name="jit(serve_step_c8)/while/body/mlp/shard_map/fused_gemv_allreduce/jit(fused_matmul_allreduce_pallas)/fused_gemv_allreduce/pallas_call"}, backend_config={"custom_call_config":{"body":"TUzv"}}
  %copy.4 = bf16[8]{0} copy(%fused_gemv_allreduce.1)
  ROOT %dot.5 = bf16[8]{0} dot(%copy.4, %p), metadata={op_type="dot_general" op_name="jit(serve_step_c8)/lm_head/dot_general"}
}
"""


def ev(name, start, end):
    return Ev(name, float(start), float(end))


def test_scope_map_from_hlo_text():
    m = S.scope_map(HLO)
    assert m["fusion.3"] == m["gather.2"] == "attn.paged"
    # a kernel's scope and its pallas_call name, after line breaks in its
    # attributes, resolve once under the layer that calls it
    assert m["fused_gemv_allreduce.1"] == "mlp/fused_gemv_allreduce"
    assert m["dot.5"] == "lm_head"
    # no metadata, or no known scope in it
    assert m["copy.4"] == m["p"] == "other"
    assert S.scope_of("jit(f)/while/body/attn.paged_extra/x") == "other"


def test_scope_seconds_by_program():
    """Ops of the C=8 program by its map; an op the map lacks is
    unmapped; a while is a container; ops of other programs and outside
    the window are not step-program time."""
    ops = {0: [ev("%while.7 = (s32[]) while(x)", 0, 90),
               ev("%fusion.3 = bf16[8] fusion(x)", 0, 40),
               ev("%fused_gemv_allreduce.1 = bf16[8] custom-call(x), "
                  'custom_call_target="tpu_custom_call"', 40, 50),
               ev("%copy.4 = bf16[8] copy(x)", 50, 60),
               ev("%fusion.99 = bf16[8] fusion(x)", 60, 70),
               ev("%dot.5 = bf16[8] dot(x)", 70, 90),
               ev("%fusion.3 = bf16[8] fusion(x)", 100, 110),
               ev("%dot.5 = bf16[8] dot(x)", 190, 210)]}
    modules = {0: [ev("jit_serve_step_c8(1)", 0, 90),
                   ev("jit__argmax(2)", 100, 110),
                   ev("jit_serve_step_c8(1)", 190, 210)]}
    t = Trace(ops, modules, [ev("bench.trace_window", 0, 200)])
    got = dict(S.scope_seconds(t, {8: S.scope_map(HLO)}))
    assert got == pytest.approx({"attn.paged": 40e-9,
                                 "mlp/fused_gemv_allreduce": 10e-9,
                                 "other": 10e-9, "unmapped": 10e-9,
                                 "lm_head": 20e-9})
    # a program with no map: every op unmapped
    assert dict(S.scope_seconds(t, {1: {}})) == pytest.approx(
        {"unmapped": 90e-9})


@pytest.mark.parametrize("config,chips", [("chatglm3-6b", 1),
                                          ("phi3-medium-14b", 4)])
def test_rebuilt_step_programs_are_the_served_ones(config, chips):
    """What the metric compiles is, instruction for instruction and with
    the same op names, what the harness served; the gather of the
    tables' blocks is paged attention's and every matmul has a scope."""
    cell = tiny_cell(config, chips=chips)
    served = harness.build(cell, 2147483653, make_ctx(chips))
    harness.warm_up_shapes(served)
    rebuilt = S.step_programs_hlo(cell, make_ctx(chips))
    eng = served.engine
    b = eng.batch
    assert sorted(rebuilt) == [1, eng.chunk]
    for c, fn in served.steps.jits.items():
        text = fn.lower(served.params, jnp.zeros((b, c), jnp.int32),
                        eng.pool, jnp.zeros((b, eng.kv.max_blocks), jnp.int32),
                        jnp.zeros(b, jnp.int32),
                        jnp.zeros(b, jnp.int32)).compile().as_text()

        def instructions(t):
            return [re.sub(r" stack_frame_id=\d+", "", line)
                    for line in t.splitlines() if S.HLO_INSTR.match(line)]

        assert instructions(text) == instructions(rebuilt[c])
        m = S.scope_map(rebuilt[c])
        kinds = {}
        for line in rebuilt[c].splitlines():
            k = re.match(r"^\s*(?:ROOT\s+)?%?([\w.-]+) = (?:\(.*?\)|\S+) "
                         r"([\w-]+)\(", line)
            if k:
                kinds.setdefault(k.group(2), []).append(m[k.group(1)])
        assert "attn.paged" in kinds["gather"]
        assert kinds["dot"] and "other" not in kinds["dot"]
        assert {"attn.qkv", "attn.paged", "attn.out", "mlp",
                "lm_head"} <= set(kinds["dot"])
        assert {"embed", "attn.kv_write"} <= set(m.values())


def test_program_without_placements_reads_nothing(monkeypatch):
    from repro.launch import mesh

    monkeypatch.delattr(mesh, "param_placements")
    assert S.step_programs_hlo(tiny_cell("chatglm3-6b"), make_ctx(1)) == {}
