"""The readers PR 23 added, on a small trace recorded on a v5e chip from the
program itself (``benchmark/tools/record_named_trace.py``: three training
steps of a two-layer model, then a paged server's three steps): the program's
host spans with their attributes, the Pallas kernels by ``name=``, device time
by named scope, the modules named after their ``compile_stats()`` keys. And
on the older trace of a program that names nothing, where every reader has to
find nothing and say None."""

import dataclasses
import os
import re
import statistics

import pytest

from benchmark import files, flash_names, op_scopes, program_spans
from benchmark import trace_reduce as tr
from benchmark.kernels import flash_attention, ragged_paged_attention
from benchmark.program_spans import Span

HERE = os.path.dirname(__file__)
NAMED = os.path.join(HERE, "data", "named_tpu.xplane.pb")
UNNAMED = os.path.join(HERE, "data", "small_tpu.xplane.pb")
COUNTERS = {"model": {"num_layers": 2, "remat": True}}  # the recorded model
SERVE = {"name": "a_serving_cell", "config": {"engine": {"kind": "serve"}}}
TRAIN = {"name": "a_training_cell", "config": {"engine": {"kind": "train"}}}
NEW_READERS = [
    "step_admit_ms", "step_pack_ms", "step_dispatch_ms", "step_settle_ms", "rows_per_step", "kv_pages_in_use_share",
    "ragged_kernel_call_us", "flash_fwd_time_share", "flash_dq_time_share", "flash_dkv_time_share",
    "head_loss_time_share", "optimizer_time_share",
]


def reader(name):
    return files.load_module("layer_metrics", name)


@pytest.fixture
def named(monkeypatch):
    """The recorded trace, reduced; the readers find the file where ``run.py``
    would have left it."""
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: NAMED)
    trace = tr.reduce_xplane(NAMED, ("train_step", "server_step"), ("server_step",))
    # no bench_slice in this trace: the slice is the span of the device's events,
    # which starts after the first step's dispatch and ends before the last step's spans
    return dataclasses.replace(trace, lo=float("-inf"), hi=float("inf"))


@pytest.fixture
def unnamed(monkeypatch):
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: UNNAMED)
    return tr.reduce_xplane(UNNAMED, ("train_step",), ("train_step",))


def test_name_stacks_by_hand():
    backward = "jit(fused_step)/jit(main)/transpose(jvp(layers))/while/body/closed_call/attention/flash_bwd_dq/pallas_call:"
    assert op_scopes.components(backward)[-3:] == ["attention", "flash_bwd_dq", "pallas_call"]
    assert op_scopes.kernel_of(backward) == "flash_bwd_dq"
    assert op_scopes.in_scope(backward, "layers") and op_scopes.in_scope(backward, "attention")
    assert not op_scopes.in_scope(backward, "head_loss")
    # the primitive is no scope, and a name inside another is no match
    assert not op_scopes.in_scope("jit(f)/head_loss_extra/optimizer:", "optimizer")
    assert not op_scopes.in_scope("jit(f)/head_loss_extra/dot_general:", "head_loss")
    assert op_scopes.in_scope("jit(f)/jvp(head_loss)/dot_general:", "head_loss")
    assert op_scopes.kernel_of("jit(f)/mlp/dot_general:") is None
    # an unnamed kernel reads as whatever encloses it, never as a known name
    assert op_scopes.kernel_of("jit(f)/while/body/closed_call/pallas_call:") == "closed_call"
    assert op_scopes.module_of("jit_paged_ragged_r16_w128(1234)") == ("jit_paged_ragged_r16_w128", 1234)
    assert op_scopes.module_of("SFence") == ("SFence", None)


def test_the_trace_holds_the_names_the_program_gave(named):
    names = op_scopes.load(NAMED)
    dev = named.devices[0]
    modules = {op_scopes.module_of(m.name)[0] for m in dev.modules}
    assert {"jit_fused_step", "jit_paged_ragged_r4_w1", "jit_paged_ragged_r4_w128"} <= modules
    kernels = {}
    for ev in dev.leaves:
        if "tpu_custom_call" in ev.name:
            kernel = op_scopes.kernel_of(names.stack(0, ev.name))
            kernels[kernel] = kernels.get(kernel, 0) + 1
            assert ev.name.startswith(f"%{kernel}.")  # the HLO instruction carries the name too
    # 3 training steps x 2 layers (the forward again under remat); 3 serving steps x 2 layers
    assert kernels == {"flash_fwd": 12, "flash_bwd_dq": 6, "flash_bwd_dkv": 6, "ragged_paged_attention": 6}
    scopes = {"embed", "layers", "attention", "mlp", "head_loss", "optimizer", "kv_write", "head_sample"}
    seen = {s for ev in dev.leaves for s in scopes if op_scopes.in_scope(names.stack(0, ev.name), s)}
    assert seen == scopes
    # a kernel of the backward pass sits under its scopes, transformed
    stack = next(names.stack(0, ev.name) for ev in dev.leaves if ev.name.startswith("%flash_bwd_dkv."))
    assert stack.startswith("jit(fused_step)/") and stack.endswith("/attention/flash_bwd_dkv/pallas_call:")
    assert names.stats(0, "%no_such_instruction = f32[] add()") == {} and names.stack(1, dev.leaves[0].name) == ""


def test_flash_kernels_by_name_are_the_ones_the_signatures_find(named):
    shares = {kind: reader(f"flash_{short}_time_share").value(named, COUNTERS, TRAIN) for kind, short in (("forward", "fwd"), ("backward_dq", "dq"), ("backward_dkv", "dkv"))}
    dev = named.devices[0]
    for kind, share in shares.items():
        by_signature = sum(ev.duration for ev in dev.kernel_events(flash_attention.EVENTS[kind]))
        assert share == pytest.approx(100.0 * by_signature / dev.busy_s(), rel=1e-12)
    assert sum(shares.values()) == pytest.approx(reader("flash_attn_time_share").value(named, COUNTERS, TRAIN), rel=1e-12)
    # a model that needs another count of calls is a changed program: the reader raises
    with pytest.raises(ValueError, match="by name"):
        flash_names.time_share(named, {"model": {"num_layers": 2, "remat": False}}, TRAIN, "forward")


def test_ragged_kernel_by_name(named):
    dev = named.devices[0]
    by_signature = dev.kernel_events(ragged_paged_attention.EVENTS["ragged"])
    assert len(by_signature) == 6
    value = reader("ragged_kernel_call_us").value(named, COUNTERS, SERVE)
    assert value == pytest.approx(1e6 * statistics.median(ev.duration for ev in by_signature))
    with pytest.raises(ValueError, match="jit_paged_ragged_r4_w"):
        reader("ragged_kernel_call_us").value(named, {"model": {"num_layers": 3}}, SERVE)
    # the count check covers every execution of a jit_paged_ragged program, both widths
    names = op_scopes.load(NAMED)
    assert op_scopes.checked_kernel_events(names, dev, {"ragged_paged_attention": 2}, "jit_paged_ragged") is not None
    with pytest.raises(ValueError, match="no whole execution"):
        op_scopes.checked_kernel_events(names, dev, {"ragged_paged_attention": 2}, "jit_no_such_program")


def test_scope_shares_by_hand(named):
    names, dev = op_scopes.load(NAMED), named.devices[0]
    for scope, file in (("head_loss", "head_loss_time_share"), ("optimizer", "optimizer_time_share")):
        by_hand = sum(ev.duration for ev in dev.leaves if re.search(rf"[/(]{scope}[)/]", names.stack(0, ev.name)))
        assert by_hand > 0
        assert reader(file).value(named, COUNTERS, TRAIN) == pytest.approx(100.0 * by_hand / dev.busy_s())
    assert op_scopes.scope_share(named, TRAIN, "no_such_scope") is None
    # the scopes of the training step do not overlap: embed + layers + head_loss + optimizer <= busy
    total = sum(op_scopes.scope_self_time(names, dev, s) for s in ("embed", "layers", "head_loss", "optimizer"))
    assert 0.3 * dev.busy_s() < total <= dev.busy_s()


def test_program_spans_and_their_attributes(named):
    spans = program_spans.of_cell(named, SERVE)
    steps = [s for s in spans if s.name == "serve.step"]
    assert len(steps) == 3
    assert steps[0].attrs == {"waiting": 2, "running": 0, "pages_in_use": 0, "pages_total": 16}
    assert [s.attrs["running"] for s in steps] == [0, 2, 2]
    dispatches = [s.attrs for s in spans if s.name == "serve.dispatch"]
    assert dispatches[0] == {"rows": 2, "width": 128, "program": "paged_ragged_r4_w128"}
    assert [d["program"] for d in dispatches[1:]] == ["paged_ragged_r4_w1"] * 2
    # each dispatch names the program whose module the device then ran
    modules = [op_scopes.module_of(m.name)[0] for m in named.devices[0].modules if "paged_ragged" in m.name]
    assert modules == ["jit_" + d["program"] for d in dispatches]
    assert sum(s.attrs["tokens"] for s in spans if s.name == "serve.settle") == 6
    assert [s.attrs for s in spans if s.name == "train.dispatch"] == [{"program": "fused_step", "step": n} for n in (1, 2, 3)]
    # nesting: a step's phases lie inside it, and the fetch and the settle inside the emit
    first = steps[0]
    inside = [s.name for s in spans if s is not first and first.start <= s.start and s.end <= first.end]
    assert inside == ["serve.admit", "serve.pack", "serve.dispatch", "serve.emit", "serve.fetch", "serve.settle"]


def test_phase_readers(named):
    spans = program_spans.of_cell(named, SERVE)
    for phase in ("admit", "pack", "dispatch", "settle"):
        lengths = [s.duration for s in spans if s.name == f"serve.{phase}"]
        assert reader(f"step_{phase}_ms").value(named, COUNTERS, SERVE) == pytest.approx(1e3 * statistics.median(lengths))
    # one file, two span families: the configuration's engine.kind chooses
    train = [s.duration for s in spans if s.name == "train.dispatch"]
    assert reader("step_dispatch_ms").value(named, COUNTERS, TRAIN) == pytest.approx(1e3 * statistics.median(train))
    assert reader("step_settle_ms").value(named, COUNTERS, TRAIN) is None  # no train.settle
    assert reader("rows_per_step").value(named, COUNTERS, SERVE) == 2.0
    assert reader("kv_pages_in_use_share").value(named, COUNTERS, SERVE) == pytest.approx(100.0 * statistics.mean(s.attrs["pages_in_use"] / 16 for s in spans if s.name == "serve.step"))
    # self time: the emit's own part is what the fetch and the settle leave of it
    emits = [s for s in spans if s.name == "serve.emit"]
    own = program_spans.self_seconds(spans, "serve.emit")
    assert all(0 <= o < 0.2 * e.duration for o, e in zip(own, emits))
    # a slice that cuts a step leaves it out whole
    cut = dataclasses.replace(named, hi=emits[-1].start)
    assert len([s for s in program_spans.of_cell(cut, SERVE) if s.name == "serve.step"]) == 2


def test_self_time_by_hand():
    spans = [
        Span("serve.step", 0.0, 10.0, "main", {}),
        Span("serve.admit", 1.0, 2.0, "main", {}),
        Span("serve.emit", 4.0, 9.0, "main", {}),
        Span("serve.fetch", 4.5, 8.0, "main", {}),
        Span("ckpt.stage", 0.5, 9.5, "writer", {}),  # another thread: nobody's child here
    ]
    # the step loses the admit and the emit (the fetch lies inside the emit: counted once)
    assert program_spans.self_seconds(spans, "serve.step") == [pytest.approx(10.0 - 1.0 - 5.0)]
    assert program_spans.self_seconds(spans, "serve.emit") == [pytest.approx(5.0 - 3.5)]
    assert program_spans.self_seconds(spans, "ckpt.stage") == [pytest.approx(9.0)]
    assert program_spans.self_seconds(spans, "serve.pack") == []


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_rehearsal_has_no_trace_and_gets_none(name):
    assert reader(name).value(None, COUNTERS, SERVE) is None
    assert reader(name).value(None, COUNTERS, TRAIN) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_program_that_names_nothing_gets_none_and_no_error(unnamed, name):
    """What the driver's traced runs of the parent commit need: the readers
    run against a program without the spans, the kernel names and the scopes."""
    assert reader(name).value(unnamed, COUNTERS, SERVE) is None
    assert reader(name).value(unnamed, COUNTERS, TRAIN) is None
