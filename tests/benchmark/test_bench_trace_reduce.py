"""The reduction from a profiler trace to numbers: interval arithmetic on
intervals small enough to check by hand, and the whole path on one small
trace recorded on a v5e chip (``benchmark/tools/record_small_trace.py``)."""

import os

import pytest

from benchmark import trace_reduce as tr
from benchmark.trace_reduce import Event

DATA = os.path.join(os.path.dirname(__file__), "data", "small_tpu.xplane.pb")


def test_busy_union_and_idle_share_by_hand():
    # [0,2] and [1,3] overlap, [5,6] stands alone: busy 4 of a 10 s slice
    busy = tr.union([(1.0, 3.0), (0.0, 2.0), (5.0, 6.0), (7.0, 7.0)])
    assert busy == [(0.0, 3.0), (5.0, 6.0)]
    assert tr.total(busy) == 4.0
    assert tr.gaps(busy, 0.0, 10.0) == [(3.0, 5.0), (6.0, 10.0)]
    assert 1.0 - tr.total(tr.clip(busy, 0.0, 10.0)) / 10.0 == pytest.approx(0.6)
    # a slice that cuts an interval counts only the part inside
    assert tr.total(tr.clip(busy, 2.0, 5.5)) == pytest.approx(1.5)


def test_kernel_sum_uses_leaves_not_the_loop_around_them():
    # a 10 s while holding two 3 s kernels and a 1 s copy; one more kernel after it
    line = [
        Event("%while.1 = (s32[]) while(%t), body=%b", 0.0, 10.0),
        Event("%k.1 = bf16[8] custom-call(bf16[8] %a)", 1.0, 4.0),
        Event("%copy.1 = bf16[8] copy(bf16[8] %a)", 4.0, 5.0),
        Event("%k.1 = bf16[8] custom-call(bf16[8] %a)", 6.0, 9.0),
        Event("%k.2 = bf16[8] custom-call(bf16[8] %a)", 10.0, 12.0),
    ]
    leaves, self_time = tr.leaf_and_self(line)
    assert [ev.name[:4] for ev in leaves] == ["%k.1", "%cop", "%k.1", "%k.2"]
    assert self_time[0] == pytest.approx(3.0)  # 10 - 3 - 1 - 3: the loop's own overhead
    by_name = tr.self_time_by_name(line)
    assert by_name["%k.1 = bf16[8] custom-call(bf16[8] %a)"] == pytest.approx(6.0)
    assert sum(by_name.values()) == pytest.approx(12.0)  # = the union: nothing counted twice
    assert tr.total((ev.start, ev.end) for ev in line) == pytest.approx(12.0)


def test_exposed_collective_arithmetic_by_hand():
    # all-gather in flight 0..4, compute covers 1..3: 2 s exposed; an
    # all-reduce 6..7 under nothing: 1 s exposed; one 8..9 fully under compute
    collectives = [(0.0, 4.0), (6.0, 7.0), (8.0, 9.0)]
    compute = [(1.0, 3.0), (7.5, 9.5)]
    exposed = tr.subtract(collectives, compute)
    assert exposed == [(0.0, 1.0), (3.0, 4.0), (6.0, 7.0)]
    assert tr.total(collectives) == 6.0 and tr.total(exposed) == 3.0


KERNEL = '%closed_call.7 = bf16[8] custom-call(bf16[8] %a), custom_call_target="tpu_custom_call"'


def device_with(kernel_starts, modules=((0.0, 10.0), (10.0, 20.0))):
    """Two whole executions of a step program; one 1 s kernel call at each of ``kernel_starts``."""
    leaves = [Event(KERNEL, t, t + 1.0) for t in kernel_starts]
    whole = [Event("jit_step(1)", s, e) for s, e in modules]
    return tr.DeviceTrace(ordinal=0, ops=leaves, leaves=leaves, modules=whole, async_ops=[], busy=tr.union((ev.start, ev.end) for ev in leaves), whole_modules=whole)


@pytest.mark.parametrize(
    "kernel_starts, error",
    [
        ([1.0, 3.0, 11.0, 13.0], None),  # two calls in each execution: what two layers need
        ([1.0, 3.0, 5.0, 11.0, 13.0], "holds kernel calls"),  # a third call of the same signature in one step
        ([1.0, 11.0], "holds kernel calls"),  # the kernel was fused: half the calls
        ([], "no whole execution"),  # the signature matches nothing any more
    ],
)
def test_kernels_found_by_signature_are_counted_against_the_model(kernel_starts, error):
    dev = device_with(kernel_starts)
    patterns, calls = {"k": r"bf16\[8\] custom-call\(bf16\[8\] %a\)"}, {"k": 2}
    if error is None:
        assert len(dev.checked_kernel_events(patterns, calls)["k"]) == 4
    else:
        with pytest.raises(ValueError, match=error):
            dev.checked_kernel_events(patterns, calls)


def test_collectives_on_the_core_and_in_flight_by_hand():
    from benchmark.files import load_module

    # per chip, a 10 s slice: a synchronous all-gather 1..2, an async all-reduce whose start runs 3..3.1 and whose
    # done waits 5..6, compute between them; chip 0 alone carries the async line (in flight 3..6)
    def chip(ordinal, with_async_line):
        leaves = [
            Event("%all-gather.1 = bf16[8]{0} all-gather(bf16[2]{0} %p), dimensions={0}", 1.0, 2.0),
            Event("%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %x), to_apply=%add", 3.0, 3.1),
            Event("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop", 3.1, 5.0),
            Event("%all-reduce-done.1 = f32[8]{0} all-reduce-done(f32[8]{0} %all-reduce-start.1)", 5.0, 6.0),
        ]
        in_flight = [Event("%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %x), to_apply=%add", 3.0, 6.0)] if with_async_line else []
        return tr.DeviceTrace(ordinal=ordinal, ops=leaves, leaves=leaves, modules=[], async_ops=in_flight, busy=tr.union((ev.start, ev.end) for ev in leaves))

    trace = tr.ReducedTrace(lo=0.0, hi=10.0, devices=[chip(0, True), chip(1, False)], host=[])
    on_core = load_module("layer_metrics", "collective_time_share").value(trace, {}, {})
    in_flight = load_module("layer_metrics", "collective_in_flight_share").value(trace, {}, {})
    assert on_core == pytest.approx(100.0 * (1.0 + 0.1 + 1.0) / 10.0)  # the same on both chips
    assert in_flight == pytest.approx(100.0 * (1.0 + 3.0) / 10.0)  # chip 0 only: the gather, and start to done
    one_chip = tr.ReducedTrace(lo=0.0, hi=10.0, devices=[chip(0, False)], host=[])
    assert load_module("layer_metrics", "collective_time_share").value(one_chip, {}, {}) is None
    assert load_module("layer_metrics", "collective_in_flight_share").value(one_chip, {}, {}) is None


@pytest.mark.parametrize(
    "name, op, collective",
    [
        ("%all-gather-start.3 = (bf16[4,8]{1,0}, bf16[16,8]{1,0:T(8,128)(2,1)}) all-gather-start(bf16[4,8]{1,0} %p), dimensions={0}", "all-gather-start", True),
        ("%all-reduce.5 = f32[8]{0} all-reduce(f32[8]{0} %x), to_apply=%add", "all-reduce", True),
        ("%reduce-scatter.1 = f32[2]{0} reduce-scatter(f32[8]{0} %x), dimensions={0}", "reduce-scatter", True),
        ("%fusion.9 = bf16[8]{0:T(1024)(128)(2,1)} fusion(bf16[8]{0} %all-gather.2), kind=kLoop", "fusion", False),
        ('%closed_call.71 = (bf16[96,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, f32[96,1024,128]{2,1,0}) custom-call(bf16[96,1024,64]{2,1,0} %b), custom_call_target="tpu_custom_call"', "custom-call", False),
        ("all-gather.7", "all-gather", True),
    ],
)
def test_operation_is_told_by_its_opcode(name, op, collective):
    assert tr.opcode(name) == op
    assert tr.is_collective(name) is collective


def test_short_name_drops_layouts():
    name = "%copy.9 = bf16[512,512]{1,0:T(8,128)(2,1)S(1)} copy(bf16[512,512]{1,0:T(8,128)(2,1)} %x.1)"
    assert tr.short_name(name) == "%copy.9 copy bf16[512,512]"


def test_clock_alignment_finds_the_shift_that_puts_work_inside_its_span():
    # three 10 ms executions, each recorded 1.2 ms before a 12 ms host span that waited for it
    spans = [(t, t + 0.012) for t in (0.100, 0.120, 0.140)]
    busy = [(s + 0.001 - 0.0012, s + 0.011 - 0.0012) for s, _ in spans]
    shift = tr.align_clock(busy, spans)
    assert 0.0002 <= shift <= 0.0022  # anything from "starts at the span's start" to "ends at its end"
    inside = sum(tr.total(tr.clip([(a + shift, b + shift)], s, e)) for (a, b), (s, e) in zip(busy, spans))
    assert inside == pytest.approx(0.030)
    assert tr.align_clock(busy, []) == 0.0


def test_recorded_tpu_trace_reduces():
    trace = tr.reduce_xplane(DATA, annotations=("train_step",), sync_annotations=("train_step",))
    assert len(trace.devices) == 1 and trace.devices[0].ordinal == 0
    # the slice holds two of the three recorded steps
    steps = trace.host_spans("train_step")
    assert len(steps) == 2
    assert len(trace.devices[0].modules) == 2
    assert all(m.name.startswith("jit_small_step(") for m in trace.devices[0].modules)
    # the device ran behind the host's clock by about a millisecond
    assert 0.0005 < trace.clock_shift < 0.003
    # each step: one while around four matmul fusions; leaves exclude the while
    assert sum(1 for ev in trace.devices[0].ops if tr.opcode(ev.name) == "while") == 2
    assert sum(1 for ev in trace.devices[0].leaves if tr.opcode(ev.name) == "fusion") == 8
    assert all(tr.opcode(ev.name) != "while" for ev in trace.devices[0].leaves)
    # two ~13 us executions in a ~6.5 ms slice: the device is idle nearly always
    assert trace.window_s == pytest.approx(0.00653, rel=0.01)
    assert 20e-6 < trace.busy_s() < 30e-6
    assert 0.99 < trace.idle_share() < 1.0
    # both executions fall inside the host spans that waited for them
    assert sum(trace.busy_inside((s.start, s.end)) for s in steps) == pytest.approx(trace.busy_s(), rel=1e-6)
    ops = trace.device_ops()
    assert ops[0][0].startswith("%fusion.13 fusion") and ops[0][1] > ops[1][1]
    # self times add up to the busy time: nothing counted twice
    assert sum(t for _, t in trace.device_ops(top=100)) == pytest.approx(trace.busy_s(), rel=1e-6)
    gaps = dict(trace.idle_gaps())
    assert gaps["train_step"] + gaps["host_other"] == pytest.approx(trace.window_s - trace.busy_s(), rel=1e-6)


def test_trace_without_a_device_plane_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no /device:TPU"):
        tr.reduce_xplane(tr.find_xplane(str(tmp_path)), annotations=())
