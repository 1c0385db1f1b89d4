"""Tracer / metrics core tests (ISSUE 10).

Load-bearing checks: span nesting depth and ordering, ring-buffer
wraparound with an exact dropped count, histogram percentiles against a
known distribution, thread-safety driven by the REAL async checkpoint
writer (spans recorded from its background thread while the main thread
traces), the Chrome-trace (Perfetto) export shape, the timer→tracer
routing (and the flipped ``stop(sync=...)`` default), and the hub's
monitor-event feed."""

from __future__ import annotations

import json
import threading

import pytest

from deepspeed_tpu.profiling.tracer import (
    Histogram,
    MetricsRegistry,
    ObservabilityHub,
    Tracer,
)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
def test_span_nesting_depth_and_order():
    tr = Tracer()
    with tr.span("outer", step=1):
        with tr.span("mid"):
            with tr.span("inner"):
                pass
    recs = tr.spans()
    # children complete (and land) before their parents
    assert [r["name"] for r in recs] == ["inner", "mid", "outer"]
    assert [r["depth"] for r in recs] == [2, 1, 0]
    outer = recs[-1]
    assert outer["attrs"] == {"step": 1}
    assert outer["t1"] >= outer["t0"]
    # parents fully contain their children in time
    inner = recs[0]
    assert outer["t0"] <= inner["t0"] and inner["t1"] <= outer["t1"]


def test_span_set_attrs_mid_flight_and_duration():
    tr = Tracer()
    with tr.span("pack") as sp:
        sp.set(rows=7)
    assert tr.spans()[-1]["attrs"] == {"rows": 7}
    assert sp.duration_ms >= 0.0


def test_ring_buffer_wraparound_exact_drop_count():
    tr = Tracer(max_spans=16)
    for i in range(100):
        with tr.span(f"s{i}"):
            pass
    recs = tr.spans()
    assert len(recs) == 16
    assert tr.dropped() == 84
    # the ring holds the NEWEST spans
    assert recs[-1]["name"] == "s99" and recs[0]["name"] == "s84"


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("x"):
        pass
    tr.event("e")
    tr.begin_async("request", 1, "r")
    tr.add_span("y", 0.0, 1.0)
    assert tr.spans() == []
    assert tr.phase_summary() == {}


# ---------------------------------------------------------------------------
# the sink: every span() also goes to the profiler's own trace
# ---------------------------------------------------------------------------
class _Scopes:
    """Stands in for ``jax.profiler.TraceAnnotation``: ``sink(name, **attrs)``
    returns a context manager with ``set_metadata``; every call is logged."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **attrs):
        scopes = self

        class Scope:
            def __enter__(self):
                scopes.log.append(("enter", name, attrs))

            def __exit__(self, *exc):
                scopes.log.append(("exit", name, exc[0]))

            def set_metadata(self, **more):
                scopes.log.append(("set", name, more))

        return Scope()


def test_sink_receives_enter_set_exit_in_nesting_order():
    tr, sink = Tracer(), _Scopes()
    tr.sink = sink
    with tr.span("serve.step", waiting=2) as step:
        with tr.span("serve.pack") as pack:
            pack.set(rows=3, width=1)
        step.set(running=1)
    with pytest.raises(RuntimeError):
        with tr.span("serve.emit"):
            raise RuntimeError("mid-span")
    assert sink.log == [
        ("enter", "serve.step", {"waiting": 2}),
        ("enter", "serve.pack", {}),
        ("set", "serve.pack", {"rows": 3, "width": 1}),
        ("exit", "serve.pack", None),
        ("set", "serve.step", {"running": 1}),
        ("exit", "serve.step", None),
        ("enter", "serve.emit", {}),
        ("exit", "serve.emit", RuntimeError),
    ]
    # the ring buffer holds the same spans with the same attributes
    assert [(r["name"], r["attrs"]) for r in tr.spans()] == [
        ("serve.pack", {"rows": 3, "width": 1}),
        ("serve.step", {"waiting": 2, "running": 1}),
        ("serve.emit", None),
    ]
    assert tr.open_spans() == []


def test_sink_gets_nothing_when_disabled_nor_from_stamped_records():
    sink = _Scopes()
    off = Tracer(enabled=False)
    off.sink = sink
    with off.span("serve.step", waiting=1) as sp:
        sp.set(running=1)
    on = Tracer()
    on.sink = sink
    # explicit stamps, instants and request lifecycles are ring-buffer only:
    # a scope that has ended, a point, or a span across steps is no annotation
    on.add_span("timer.fwd", 0.0, 1.0)
    on.event("fleet.join")
    on.begin_async("request", 1, "req1")
    on.end_async("request", 1, "req1")
    assert sink.log == []
    assert len(on.spans()) == 4


def test_phase_summary_aggregates():
    t = [0.0]

    def clock():
        t[0] += 0.001  # every call advances 1 ms
        return t[0]

    tr = Tracer(clock=clock)
    for _ in range(3):
        with tr.span("phase"):
            pass
    agg = tr.phase_summary()["phase"]
    assert agg["count"] == 3
    assert agg["mean_ms"] == pytest.approx(1.0)
    assert agg["total_ms"] == pytest.approx(3.0)


def test_async_lifecycle_events_keep_id_and_category():
    tr = Tracer()
    tr.begin_async("request", 42, "req42", tenant="a")
    tr.instant_async("request", 42, "first_token")
    tr.end_async("request", 42, "req42", tokens=9)
    phs = [(r["ph"], r["name"], r["id"]) for r in tr.spans()]
    assert phs == [("b", "req42", 42), ("n", "first_token", 42), ("e", "req42", 42)]


def test_open_spans_visible_across_threads():
    tr = Tracer()
    entered = threading.Event()
    release = threading.Event()

    def worker():
        with tr.span("bg.work"):
            entered.set()
            release.wait(5)

    t = threading.Thread(target=worker)
    t.start()
    entered.wait(5)
    names = [s["name"] for s in tr.open_spans()]
    assert "bg.work" in names  # the flight recorder's "what was it doing"
    release.set()
    t.join()
    assert tr.open_spans() == []


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
def test_counter_gauge_roundtrip():
    m = MetricsRegistry()
    m.counter("c").inc()
    m.counter("c").inc(2)
    m.gauge("g").set(3.5)
    snap = m.snapshot()
    assert snap["counters"]["c"] == 3.0
    assert snap["gauges"]["g"] == 3.5
    # same name, different kind → loud failure, not a shadowed series
    with pytest.raises(TypeError):
        m.gauge("c")


def test_histogram_percentiles_uniform():
    h = Histogram("h", buckets=[float(b) for b in range(0, 110, 10)])
    for v in range(1, 101):  # 1..100 uniform
        h.observe(float(v))
    assert h.percentile(50) == pytest.approx(50.0, abs=5.0)
    assert h.percentile(99) == pytest.approx(99.0, abs=5.0)
    snap = h.snapshot()
    assert snap["count"] == 100
    assert snap["min"] == 1.0 and snap["max"] == 100.0
    assert snap["mean"] == pytest.approx(50.5)


def test_histogram_percentile_clamped_to_observed_range():
    h = Histogram("h", buckets=[10.0, 1000.0])
    for _ in range(10):
        h.observe(42.0)
    # all mass in one wide bucket: interpolation must stay within [42, 42]
    assert h.percentile(50) == pytest.approx(42.0)
    assert h.percentile(99) == pytest.approx(42.0)
    assert Histogram("e", buckets=[1.0]).percentile(50) == 0.0


# ---------------------------------------------------------------------------
# thread safety — with the REAL async checkpoint writer
# ---------------------------------------------------------------------------
def test_thread_safety_with_async_ckpt_writer(tmp_path):
    """The engine's tracer is shared between the step loop and the async
    checkpoint writer thread (ckpt.stage/ckpt.commit spans). Drive the real
    AsyncCheckpointWriter with a tracing fake engine while the main thread
    traces concurrently: every span lands, no corruption, no deadlock."""
    from deepspeed_tpu.runtime.checkpoint_engine.async_snapshot import (
        AsyncCheckpointWriter,
    )

    tr = Tracer(max_spans=100_000)

    class FakeEngine:
        def save(self, state, path):
            with tr.span("ckpt.fake_save"):
                pass

        def commit(self, tag):
            pass

    writer = AsyncCheckpointWriter(FakeEngine(), max_inflight=2, tracer=tr)
    N = 50
    for i in range(N):
        with tr.span("train.step"):
            writer.submit({"i": i}, str(tmp_path / f"ck{i}"), f"ck{i}", None)
    writer.wait()
    summary = tr.phase_summary()
    assert summary["train.step"]["count"] == N
    assert summary["ckpt.stage"]["count"] == N
    assert summary["ckpt.commit"]["count"] == N
    assert summary["ckpt.fake_save"]["count"] == N
    # nesting stayed per-thread: stage spans wrap fake_save on the writer
    # thread, at depth 1 under ckpt.stage
    fake = [r for r in tr.spans() if r["name"] == "ckpt.fake_save"]
    assert all(r["depth"] == 1 for r in fake)
    assert tr.open_spans() == []


def test_many_threads_exact_span_count():
    tr = Tracer(max_spans=100_000)

    def work():
        for _ in range(500):
            with tr.span("t"):
                pass

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr.spans()) == 8 * 500
    assert tr.dropped() == 0


# ---------------------------------------------------------------------------
# Chrome-trace export
# ---------------------------------------------------------------------------
def test_chrome_trace_export_shape(tmp_path):
    tr = Tracer()
    m = MetricsRegistry()
    m.counter("tokens").inc(5)
    with tr.span("serve.step", rows=2):
        pass
    tr.begin_async("request", 7, "req7")
    tr.end_async("request", 7, "req7", tokens=3)
    tr.event("chaos.serve.mid_step")
    path = tr.export_chrome_trace(str(tmp_path / "trace.json"), metrics=m)
    obj = json.load(open(path))
    evs = obj["traceEvents"]
    assert evs[0]["ph"] == "M"  # process_name metadata
    by_ph = {}
    for e in evs[1:]:
        by_ph.setdefault(e["ph"], []).append(e)
    x = by_ph["X"][0]
    assert x["name"] == "serve.step" and "dur" in x and "ts" in x
    assert x["args"] == {"rows": 2}
    b, e = by_ph["b"][0], by_ph["e"][0]
    assert b["id"] == e["id"] == "7" and b["cat"] == "request"
    assert by_ph["i"][0]["name"] == "chaos.serve.mid_step"
    assert obj["otherData"]["metrics"]["counters"]["tokens"] == 5.0


# ---------------------------------------------------------------------------
# timer routing + flipped sync default (satellite 2)
# ---------------------------------------------------------------------------
def test_timer_stop_default_no_device_sync(monkeypatch):
    """The hot-path hazard: Timer.stop used to default sync=True (a full
    async-dispatch drain per stop). The default is now off; explicit
    sync=True still syncs."""
    import deepspeed_tpu.utils.timer as timer_mod

    calls = {"n": 0}
    monkeypatch.setattr(timer_mod, "_sync", lambda: calls.__setitem__("n", calls["n"] + 1))
    t = timer_mod.SynchronizedWallClockTimer()("x")
    t.start()
    t.stop()
    assert calls["n"] == 0
    t.start()
    t.stop(sync=True)
    assert calls["n"] == 1


def test_timer_routes_spans_into_tracer():
    from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer

    tr = Tracer()
    timers = SynchronizedWallClockTimer(tracer=tr)
    timers("fwd").start()
    timers("fwd").stop()
    timers("fwd").start()
    timers("fwd").stop()
    agg = tr.phase_summary()
    assert agg["timer.fwd"]["count"] == 2


# ---------------------------------------------------------------------------
# hub
# ---------------------------------------------------------------------------
def test_hub_report_merges_and_guards_sources():
    tr = Tracer()
    m = MetricsRegistry()
    hub = ObservabilityHub(tr, m)
    hub.add_source("compile", lambda: {"ok": 1})
    hub.add_source("broken", lambda: 1 / 0)
    with tr.span("p"):
        pass
    rep = hub.report()
    assert rep["compile"] == {"ok": 1}
    assert "error" in rep["broken"]  # one failing source never hides the rest
    assert rep["timeline"]["phases"]["p"]["count"] == 1
    assert hub.report(exclude=("compile",)).get("compile") is None


def test_hub_monitor_events_feed():
    tr = Tracer()
    m = MetricsRegistry()
    hub = ObservabilityHub(tr, m)
    with tr.span("serve.step"):
        pass
    m.counter("serve.tokens").inc(12)
    m.gauge("pool.util").set(0.5)
    h = m.histogram("ttft")
    h.observe(3.0)
    events = dict((name, val) for name, val, step in hub.monitor_events(step=7))
    assert "Trace/serve.step/mean_ms" in events
    assert events["Metrics/serve.tokens"] == 12.0
    assert events["Metrics/pool.util"] == 0.5
    assert "Metrics/ttft/p50" in events and "Metrics/ttft/p99" in events
    assert all(step == 7 for _, _, step in hub.monitor_events(step=7))


# ---------------------------------------------------------------------------
# names in the profiler's trace
# ---------------------------------------------------------------------------
def _repo_sources(*parts):
    import glob
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    for path in glob.glob(os.path.join(root, *parts, "**", "*.py"), recursive=True):
        with open(path, encoding="utf-8") as f:
            yield path, f.read()


def test_no_program_span_name_starts_with_a_benchmark_annotation():
    """Program spans and the benchmark's own ``TraceAnnotation``s share the
    host lines of one trace, and ``benchmark/trace_reduce.py`` keeps host
    events by name PREFIX: a program span called ``train_step...`` or
    ``submit...`` would be counted as the benchmark's wrapper by the accepted
    readers."""
    import re

    annotations = set()
    for _, text in _repo_sources("benchmark"):
        annotations |= set(re.findall(r"TraceAnnotation\(\s*\"([^\"]+)\"", text))
    assert {"bench_slice", "server_step", "train_step", "submit", "data_next"} <= annotations
    spans = set()
    for _, text in _repo_sources("deepspeed_tpu"):
        spans |= set(re.findall(r"\.span\(\s*f?\"([^\"{]+)", text))
    assert {"serve.step", "serve.fetch", "serve.settle", "train.dispatch", "train.step", "fleet.step", "ckpt.stage"} <= spans
    clashes = sorted((s, a) for s in spans for a in annotations if s.startswith(a))
    assert clashes == []
    # every family the benchmark's reader keeps is a dotted program prefix
    assert {s.split(".")[0] + "." for s in spans} <= {"serve.", "train.", "eval.", "ckpt.", "fleet."}


def test_instrumented_program_is_named_after_its_compile_stats_key():
    """``CompileTelemetry.instrument(name, fn)``: the XLA module is
    ``jit_<name>`` whatever the Python function is called, and the
    ``stats()`` key stays ``name``."""
    import jax.numpy as jnp

    from deepspeed_tpu.profiling.compile_telemetry import CompileTelemetry

    telemetry = CompileTelemetry()

    def _step(x):
        return x * 2

    narrow = telemetry.instrument("paged_ragged_r16_w1", _step)
    mixed = telemetry.instrument("paged_ragged_r16_w128", _step)
    assert narrow.lower(jnp.ones(1)).as_text().startswith("module @jit_paged_ragged_r16_w1 ")
    assert mixed.lower(jnp.ones(128)).as_text().startswith("module @jit_paged_ragged_r16_w128 ")
    mixed(jnp.ones(128))
    assert sorted(telemetry.stats()) == ["paged_ragged_r16_w1", "paged_ragged_r16_w128"]
    assert telemetry.stats()["paged_ragged_r16_w128"]["dispatches"] == 1
