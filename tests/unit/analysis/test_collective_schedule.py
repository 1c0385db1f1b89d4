"""The forms the TPU compiler writes a loop collective in, read from short
recorded snippets (``analysis/hlo.py::collective_schedule``), and what the
``overlap`` pass makes of each.

The lines are cut from the ZeRO-3 layer loops of GPT-2 XL's step compiled for
a described ``v5e:2x2`` (jax 0.9.0 / libtpu 0.0.34): shapes, layouts and
attributes as the compiler wrote them, operand lists and backend configs
shortened. A CPU module has none of these forms: every collective is a plain
instruction and no schedule exists, which is the old rule's case.
"""

from __future__ import annotations

import pytest

from deepspeed_tpu.analysis.hlo import (
    collective_schedule,
    is_tpu_module,
    loop_schedule_summary,
    parse_computations,
)
from deepspeed_tpu.analysis.passes import ProgramArtifact, overlap_pass

_T = "{1,0:T(8,128)(2,1)}"
_T3 = "{2,1,0:T(8,128)(2,1)}"

# a matmul fusion's computation, and the matmul fusion itself: what an asynchronous collective can stand beside
_MATMUL = (
    "%fused_computation.147 (param_0: bf16[8,1024,1600], param_1: bf16[1600,1600]) -> bf16[8,1024,1600] {\n"
    f"  %param_0 = bf16[8,1024,1600]{_T3} parameter(0)\n"
    f"  %param_1 = bf16[1600,1600]{_T} parameter(1)\n"
    f"  ROOT %convolution.81 = bf16[8,1024,1600]{_T3} convolution(%param_0, %param_1), window={{size=1}}, dim_labels=0bf_io0->0bf\n"
    "}\n"
)
_MATMUL_CALL = f"  %convolution_add_fusion.25 = bf16[8,1024,1600]{_T3} fusion(%x, %w), kind=kOutput, calls=%fused_computation.147\n"

# --- the forms, each a (computations it calls, lines of the loop body) pair -------------------------------------
SYNC_GATHER_FOLDED_BACK = (
    "",
    f"  %all-gather.125 = bf16[1,1600,6400]{_T3} all-gather(%w), channel_id=9, replica_groups=[1,4]<=[4], dimensions={{2}}, "
    'use_global_device_ids=true, frontend_attributes={async_collective_name="all-gather-start.9"}\n' + _MATMUL_CALL,
)
FUSED_REDUCE_SCATTER = (
    "%all-reduce-scatter.clone.clone (input.15: bf16[6400,1600]) -> bf16[1632,1600] {\n"
    f"  %input.15 = bf16[6400,1600]{_T} parameter(0)\n"
    "  %constant.1578 = bf16[] constant(0)\n"
    f"  %pad.46 = bf16[6528,1600]{_T} pad(%input.15, %constant.1578), padding=0_128x0_0\n"
    f"  %all-reduce.42 = bf16[6528,1600]{_T} all-reduce(%pad.46), channel_id=53, replica_groups={{{{0,1,2,3}}}}, "
    'use_global_device_ids=true, to_apply=%add.1.clone, frontend_attributes={from-cross-replica-sharding="true"}\n'
    "  %partition-id.36 = u32[] partition-id()\n"
    f"  ROOT %dynamic-slice.218 = bf16[1632,1600]{_T} dynamic-slice(%all-reduce.42, %partition-id.36, %constant.1578), "
    "dynamic_slice_sizes={1632,1600}\n"
    "}\n",
    _MATMUL_CALL + f"  %fusion.382 = bf16[1632,1600]{_T} fusion(%w), kind=kCustom, calls=%all-reduce-scatter.clone.clone\n",
)
_CHAIN_STATE = "s32[2]{0:S(4)}, u32[]{:S(2)}, u32[]{:S(2)}, /*index=5*/u32[]{:S(2)}, u32[]{:S(2)}"
FUSION_CHAIN = (
    "%fused_computation.497 (param_0.1577: bf16[400,1600]) -> (bf16[400,1600], bf16[1600,1600], s32[2], u32[], u32[], u32[], u32[]) {\n"
    f"  %param_0.1577 = bf16[400,1600]{_T} parameter(0)\n"
    f"  %all-gather.73 = bf16[1600,1600]{_T} all-gather(%param_0.1577), channel_id=3, replica_groups=[1,4]<=[4], dimensions={{0}}, "
    'use_global_device_ids=true, frontend_attributes={chain_id="0"}\n'
    f"  ROOT %custom-call.49 = (bf16[400,1600]{_T}, bf16[1600,1600]{_T}, {_CHAIN_STATE}) custom-call(%all-gather.73), "
    'custom_call_target="AsyncCollectiveStart"\n'
    "}\n"
    "%async_collective_fusion.485 (param_0.1: bf16[400,1600], param_1.1: bf16[1600,1600], param_2.1: bf16[8,1024,1600], "
    "param_3.1: bf16[1600,1600]) -> (bf16[8,1024,1600], bf16[400,1600], bf16[1600,1600]) {\n"
    f"  %param_0.1 = bf16[400,1600]{_T} parameter(0)\n"
    f"  %param_1.1 = bf16[1600,1600]{_T} parameter(1)\n"
    f"  %param_2.1 = bf16[8,1024,1600]{_T3} parameter(2)\n"
    f"  %param_3.1 = bf16[1600,1600]{_T} parameter(3)\n"
    f"  %convolution.134 = bf16[8,1024,1600]{_T3} convolution(%param_2.1, %param_3.1), window={{size=1}}, dim_labels=0bf_io0->0bf\n"
    f"  %all-gather.129 = bf16[1600,1600]{_T} all-gather(%param_0.1), channel_id=3, replica_groups=[1,4]<=[4], dimensions={{0}}, "
    'use_global_device_ids=true, frontend_attributes={chain_id="0"}\n'
    f"  ROOT %tuple.323 = (bf16[8,1024,1600]{_T3}, bf16[400,1600]{_T}, bf16[1600,1600]{_T}) tuple(%convolution.134, %param_0.1, %all-gather.129)\n"
    "}\n"
    "%fused_computation.499 (param_0.2: bf16[400,1600], param_1.2: bf16[1600,1600]) -> bf16[1600,1600] {\n"
    f"  %param_0.2 = bf16[400,1600]{_T} parameter(0)\n"
    f"  %param_1.2 = bf16[1600,1600]{_T} parameter(1)\n"
    f"  %all-gather.75 = bf16[1600,1600]{_T} all-gather(%param_0.2), channel_id=3, replica_groups=[1,4]<=[4], dimensions={{0}}, "
    'use_global_device_ids=true, frontend_attributes={chain_id="0"}\n'
    f"  ROOT %custom-call.51 = bf16[1600,1600]{_T} custom-call(%all-gather.75), custom_call_target=\"AsyncCollectiveDone\"\n"
    "}\n",
    f"  %async-collective-start = (bf16[400,1600]{_T}, bf16[1600,1600]{_T}, {_CHAIN_STATE}) fusion(%w), kind=kCustom, "
    "output_to_operand_aliasing={{0}: (0, {})}, calls=%fused_computation.497\n"
    f"  %fusion.485 = (bf16[8,1024,1600]{_T3}, bf16[400,1600]{_T}, bf16[1600,1600]{_T}) fusion(%async-collective-start, %x, %w), "
    "kind=kOutput, calls=%async_collective_fusion.485\n"
    f"  %async-collective-done = bf16[1600,1600]{_T} fusion(%fusion.485), kind=kCustom, calls=%fused_computation.499\n",
)
_PERMUTE_START = (
    f"  %collective-permute-start = (bf16[1600,1600]{_T}, bf16[1600,1600]{_T}, u32[]{{:S(2)}}, u32[]{{:S(2)}}) "
    "collective-permute-start(%w), channel_id=17, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}\n"
)
_PERMUTE_DONE = f"  %collective-permute-done = bf16[1600,1600]{_T} collective-permute-done(%collective-permute-start)\n"
START_DONE_AROUND_A_MATMUL = ("", _PERMUTE_START + _MATMUL_CALL + _PERMUTE_DONE)
START_DONE_WITH_NOTHING_BETWEEN = ("", _MATMUL_CALL + _PERMUTE_START + _PERMUTE_DONE)


def _module(called: str, lines: str, tiled: bool = True) -> str:
    """A module whose while body holds ``lines`` (and reads the carry's ``%x``, ``%w``)."""
    text = (
        "HloModule jit_step, is_scheduled=true\n\n" + _MATMUL + called
        + "%wide.region_14.40_spmd.sunk (wide.param.1: (bf16[8,1024,1600], bf16[1600,1600])) -> (bf16[8,1024,1600], bf16[1600,1600]) {\n"
        f"  %wide.param.1 = (bf16[8,1024,1600]{_T3}, bf16[1600,1600]{_T}) parameter(0)\n"
        f"  %x = bf16[8,1024,1600]{_T3} get-tuple-element(%wide.param.1), index=0\n"
        f"  %w = bf16[1600,1600]{_T} get-tuple-element(%wide.param.1), index=1\n"
        + lines
        + f"  ROOT %tuple.9 = (bf16[8,1024,1600]{_T3}, bf16[1600,1600]{_T}) tuple(%x, %w)\n"
        "}\n"
        "%cond.1 (p: (bf16[8,1024,1600], bf16[1600,1600])) -> pred[] {\n"
        f"  %p = (bf16[8,1024,1600]{_T3}, bf16[1600,1600]{_T}) parameter(0)\n"
        "  ROOT %lt = pred[] constant(true)\n"
        "}\n"
        "ENTRY %main.47_spmd (p0: bf16[8,1024,1600], p1: bf16[1600,1600]) -> (bf16[8,1024,1600], bf16[1600,1600]) {\n"
        f"  %p0 = bf16[8,1024,1600]{_T3} parameter(0)\n"
        f"  %p1 = bf16[1600,1600]{_T} parameter(1)\n"
        f"  %init = (bf16[8,1024,1600]{_T3}, bf16[1600,1600]{_T}) tuple(%p0, %p1)\n"
        f"  ROOT %while.1 = (bf16[8,1024,1600]{_T3}, bf16[1600,1600]{_T}) while(%init), condition=%cond.1, "
        "body=%wide.region_14.40_spmd.sunk\n"
        "}\n"
    )
    if not tiled:  # what the CPU compiler writes: the same instructions, plain layouts
        text = text.replace(":T(8,128)(2,1)", "").replace("{0:S(4)}", "{0}").replace("{:S(2)}", "")
    return text


def _pass(text):
    art = ProgramArtifact("fixture", wrapper=None)
    art._hlo_text = text
    return overlap_pass(art)


FORMS = {
    # name: (snippet, op, form, compute_between, bytes, hidden on the TPU)
    "sync_gather_folded_back": (SYNC_GATHER_FOLDED_BACK, "all-gather", "sync", None, 1600 * 6400 * 2, False),
    "fused_reduce_scatter": (FUSED_REDUCE_SCATTER, "all-reduce-scatter", "fused_sync", None, 6528 * 1600 * 2, False),
    "fusion_chain": (FUSION_CHAIN, "all-gather", "fusion_chain", True, 1600 * 1600 * 2, True),
    "start_done_around_a_matmul": (START_DONE_AROUND_A_MATMUL, "collective-permute", "start_done", True, 1600 * 1600 * 2, True),
    "start_done_with_nothing_between": (START_DONE_WITH_NOTHING_BETWEEN, "collective-permute", "start_done", False, 1600 * 1600 * 2, False),
}


@pytest.mark.parametrize("name", sorted(FORMS))
def test_a_tpu_loop_collective_is_read_in_the_form_it_was_scheduled_in(name):
    (called, lines), op, form, compute_between, nbytes, _ = FORMS[name]
    text = _module(called, lines)
    assert is_tpu_module(text)
    (record,) = collective_schedule(text)  # one collective, whatever number of fusions carry it
    assert (record["op"], record["form"], record["compute_between"], record["bytes"], record["in_loop"]) == (
        op, form, compute_between, nbytes, True), record
    assert record["computation"] == "wide.region_14.40_spmd.sunk"
    assert record["folded_back"] == (name == "sync_gather_folded_back")
    hidden = bool(compute_between)
    assert loop_schedule_summary([record]) == {
        "loop_collectives": 1, "async_with_compute_between": int(hidden), "sync_on_core": int(not hidden),
        "sync_bytes": 0 if hidden else nbytes,
    }


@pytest.mark.parametrize("name", sorted(FORMS))
def test_overlap_pass_on_a_tpu_module_hides_only_what_runs_beside_a_matmul(name):
    """On the TPU's serial operations line a synchronous collective runs with
    nothing beside it: exposed, though the loop body holds a matmul with no
    dependency path to it (which the CPU rule would have counted as cover)."""
    (called, lines), op, _, compute_between, nbytes, hidden = FORMS[name]
    summary = _pass(_module(called, lines)).summary
    assert summary["loop_collectives"] == summary["collectives"] == 1
    assert summary["overlap_verified"] is hidden
    assert (summary["hidden_bytes"], summary["exposed_bytes"]) == ((nbytes, 0) if hidden else (0, nbytes))
    assert summary["async_pairs"] == int(compute_between is not None)
    assert [(e["op"], e["bytes"]) for e in summary["loop_exposed"]] == ([] if hidden else [(op, nbytes)])


def test_overlap_pass_keeps_the_feasibility_rule_where_no_schedule_exists():
    """The same loop body from the CPU compiler (plain layouts): the
    synchronous gather beside an independent matmul is hidden, as before."""
    called, lines = SYNC_GATHER_FOLDED_BACK
    cpu = _module(called, lines, tiled=False)
    assert not is_tpu_module(cpu)
    (record,) = collective_schedule(cpu)
    assert (record["form"], record["independent_compute"]) == ("sync", True)
    assert _pass(cpu).summary["overlap_verified"] is True
    # ... and exposed there too where every matmul of the body reads it
    serial = cpu.replace("fusion(%x, %w), kind=kOutput", "fusion(%x, %all-gather.125), kind=kOutput")
    assert _pass(serial).summary["overlap_verified"] is False


def test_a_chain_started_before_a_loop_belongs_to_where_it_starts():
    """The compiler lets a gather of the entry computation ride a loop's
    matmuls: its pieces in the loop body are no loop collective."""
    called, lines = FUSION_CHAIN
    start, rider, done = lines.splitlines(keepends=True)
    text = _module(called, rider.replace("%async-collective-start", "%x"))
    text = text.replace("  %init = ", start.replace("%w)", "%p1)") + "  %init = ")
    text = text.replace("  ROOT %while.1", done.replace("%fusion.485", "%init") + "  ROOT %while.1")
    (record,) = collective_schedule(text)
    assert (record["computation"], record["in_loop"], record["form"], record["compute_between"]) == (
        "main.47_spmd", False, "fusion_chain", True)
    assert _pass(text).summary["loop_collectives"] == 0


def test_parse_computations_reads_tiled_layouts_and_indexed_tuples():
    """A TPU module's every line carries a tiled layout, parentheses inside
    the braces, and long tuples carry ``/*index=5*/`` marks: none is dropped."""
    called, lines = FUSION_CHAIN
    comps, entry = parse_computations(_module(called, lines))
    assert entry == "main.47_spmd"
    body = {i.name: i for i in comps["wide.region_14.40_spmd.sunk"]}
    assert list(body) == ["wide.param.1", "x", "w", "async-collective-start", "fusion.485", "async-collective-done", "tuple.9"]
    assert body["async-collective-start"].op == "fusion" and "/*index=5*/" in body["async-collective-start"].shape_str
    assert body["fusion.485"].operands[:3] == ["async-collective-start", "x", "w"]
    assert [i.op for i in comps["fused_computation.497"]] == ["parameter", "all-gather", "custom-call"]
