"""Telemetry-is-free guard (ISSUE 10 hard constraint).

Tracing must add ZERO host↔device transfers and ZERO new compiled programs
on the hot path, and its measured overhead must stay under 2% of a
bench-like step. Checks here:

* program-set guard — a traced training run compiles exactly the same
  program set as an untraced one, and continued traced stepping triggers
  no new compiles (compile telemetry is the witness);
* host-transfer guard — the analysis pass over the dispatched step
  programs stays clean with tracing on (spans are host-side bookkeeping;
  nothing it does can appear inside compiled HLO — ``tracer.py`` never
  imports jax — but the pass proves the programs themselves are unchanged);
* overhead guard — the measured per-span cost times a generous
  spans-per-step budget is under 2% of a measured bench-like step (the
  bound is computed from stable minima so the fast tier never flakes);
* the merged ``observability()`` report + Perfetto trace for a training
  run (the serving-run counterparts live in test_request_spans.py).
"""

from __future__ import annotations

import json
import time

import jax
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.profiling.tracer import Tracer
from tests.unit.simple_model import SimpleModel, random_dataloader


def _engine(tracing_enabled=True, **extra):
    cfg = {
        "train_micro_batch_size_per_gpu": 8,
        "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
        "bf16": {"enabled": True},
        "tracing": {"enabled": tracing_enabled},
    }
    cfg.update(extra)
    engine, *_ = ds.initialize(
        model=SimpleModel(), config=cfg, dist_init_required=False
    )
    return engine


def _run_steps(engine, n):
    for i, batch in enumerate(random_dataloader(total_samples=8 * n, batch_size=8)):
        loss = engine(batch)
        engine.backward(loss)
        engine.step()


def test_tracing_compiles_zero_new_programs(eight_devices):
    """Same program set traced vs untraced; further traced steps add zero
    compiles (the tracer cannot retrace anything — it never touches jax)."""
    on = _engine(tracing_enabled=True)
    _run_steps(on, 2)
    traced_programs = {
        name: rec["compiles"] for name, rec in on.compile_stats().items()
    }
    off = _engine(tracing_enabled=False)
    _run_steps(off, 2)
    untraced_programs = {
        name: rec["compiles"] for name, rec in off.compile_stats().items()
    }
    assert traced_programs == untraced_programs
    # tracing actually ran
    assert on.tracer.phase_summary()["train.dispatch"]["count"] >= 2
    assert off.tracer.spans() == []
    # steady state: more traced steps, not one more compile anywhere
    _run_steps(on, 4)
    after = {name: rec["compiles"] for name, rec in on.compile_stats().items()}
    assert after == traced_programs


def test_tracing_adds_zero_host_transfers(eight_devices):
    """The analysis host-transfer pass over the dispatched step programs is
    clean with tracing on, via the MERGED observability report (which also
    proves the acceptance surface: timeline + metrics + compile + analysis
    + checkpoint in one call)."""
    engine = _engine(tracing_enabled=True)
    _run_steps(engine, 2)
    rep = engine.observability()  # analysis included
    assert set(rep) >= {"timeline", "metrics", "compile", "analysis", "checkpoint"}
    an = rep["analysis"]
    assert "error" not in an, an
    assert an["totals"]["violations"] == 0
    for name, prog in an["programs"].items():
        ht = prog["passes"].get("host_transfer")
        if ht is not None:
            assert ht["violations"] == [], (name, ht)
    # the timeline saw the run; metrics counted the steps
    assert rep["timeline"]["phases"]["train.step_commit"]["count"] >= 1
    assert rep["metrics"]["counters"]["train.steps"] >= 2


def test_trace_overhead_under_2pct_of_bench_step():
    """Deterministic overhead bound: measured per-span cost × a generous
    spans-per-step budget (16 — the engines place ~6 training / ~10
    serving spans per step) must be under 2% of a measured bench-like
    step (~10 ms of host compute). Minima over repeats make this stable
    where a raw wall-clock A/B flakes on a noisy box."""
    tr = Tracer(max_spans=50_000)
    N = 20_000
    per_span = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(N):
            with tr.span("x"):
                pass
        per_span = min(per_span, (time.perf_counter() - t0) / N)
        tr.clear()
    a = np.random.rand(384, 384)
    b = np.random.rand(384, 384)
    step_s = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(8):
            c = a @ b
            c = c @ b
            c = c @ b
            c = c @ b
        step_s = min(step_s, (time.perf_counter() - t0) / 8)
    overhead_pct = 16 * per_span / step_s * 100.0
    assert overhead_pct < 2.0, (
        f"per_span={per_span * 1e6:.2f}us step={step_s * 1e3:.2f}ms "
        f"-> {overhead_pct:.3f}%"
    )


def test_disabled_tracer_is_nanoscale():
    """tracing.enabled=False must cost one attribute read + one call —
    bound it at 1µs/span with a huge margin so a regression to 'always
    allocate' is caught."""
    tr = Tracer(enabled=False)
    N = 100_000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(N):
            with tr.span("x"):
                pass
        best = min(best, (time.perf_counter() - t0) / N)
    assert best < 1e-6, f"{best * 1e9:.0f}ns per disabled span"


def test_training_chrome_trace_perfetto_loadable(eight_devices, tmp_path):
    """Acceptance: a Perfetto-loadable trace JSON for a training run —
    well-formed Trace Event Format with the step phases present."""
    engine = _engine(tracing_enabled=True)
    _run_steps(engine, 3)
    path = engine.observability_hub.export_chrome_trace(str(tmp_path / "train.json"))
    obj = json.load(open(path))
    evs = obj["traceEvents"]
    assert isinstance(evs, list) and evs[0]["ph"] == "M"
    names = {e["name"] for e in evs}
    assert {"train.h2d", "train.dispatch", "train.step_commit"} <= names
    for e in evs:
        assert "ph" in e and "name" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0 and "ts" in e and "tid" in e
    assert obj["otherData"]["metrics"]["counters"]["train.steps"] == 3.0


def test_fused_accum_step_phase_breakdown(eight_devices):
    """gas>1 with fuse_grad_accum: the fused train_batch records the full
    phase chain (h2d → dispatch → loss_fetch inside train.step) and the
    step-time histogram."""
    engine = _engine(
        tracing_enabled=True,
        gradient_accumulation_steps=2,
        compile={"fuse_grad_accum": True},
    )
    data = random_dataloader(total_samples=32, batch_size=8)
    it = iter(data)
    for _ in range(2):
        engine.train_batch(data_iter=it)
    phases = engine.tracer.phase_summary()
    for name in ("train.step", "train.h2d", "train.dispatch", "train.loss_fetch",
                 "train.data_fetch"):
        assert phases[name]["count"] == 2, (name, phases.get(name))
    hist = engine.metrics.snapshot()["histograms"]["train.step_ms"]
    assert hist["count"] == 2 and hist["p50"] > 0
    # train.step is a span that was open around its phases (so that the sink,
    # jax.profiler.TraceAnnotation, sees it too), not a record stamped after
    recs = [r for r in engine.tracer.spans() if r["ph"] == "X"]
    steps = [r for r in recs if r["name"] == "train.step"]
    dispatches = [r for r in recs if r["name"] == "train.dispatch"]
    assert [r["depth"] for r in steps] == [0, 0] and [r["depth"] for r in dispatches] == [1, 1]
    assert all(s["t0"] <= d["t0"] and d["t1"] <= s["t1"] for s, d in zip(steps, dispatches))
    assert [d["attrs"] for d in dispatches] == [
        {"program": "fused_accum_step", "step": 0}, {"program": "fused_accum_step", "step": 1}
    ]
    assert engine.tracer.sink is jax.profiler.TraceAnnotation
    # the program's XLA module is named after its compile_stats() key
    assert "fused_accum_step" in engine.compile_stats()
    assert "module @jit_fused_accum_step " in engine._telemetry.lowered_text("fused_accum_step")[:200]


def test_ckpt_d2h_stall_span_and_writer_spans(eight_devices, tmp_path):
    """The async save's only step-loop cost (the D2H snapshot) is a span;
    the background writer's stage/commit land on the same timeline from
    its own thread."""
    engine = _engine(
        tracing_enabled=True,
        checkpoint={"async_snapshot": True},
    )
    _run_steps(engine, 1)
    engine.save_checkpoint(str(tmp_path))
    engine.wait_pending_checkpoint()
    phases = engine.tracer.phase_summary()
    assert phases["ckpt.d2h_stall"]["count"] == 1
    assert phases["ckpt.stage"]["count"] == 1
    assert phases["ckpt.commit"]["count"] == 1
    assert engine.metrics.snapshot()["histograms"]["ckpt.stall_ms"]["count"] == 1
