"""Serving request-span lifecycle tests (ISSUE 10).

The PagedServer records per-step phase spans (admit / pack / dispatch /
emit / journal_sync) and per-request lifecycle spans (submit → admit →
first_token → finish, with preempt instants and tenant / prefix-hit /
spec-accept attributes) onto the engine's tracer. These tests drive the
real scheduler across admission, preemption, and speculative decoding and
assert the timeline tells the true story — plus the engine-surface
``observability()`` merge and the Perfetto trace export for a serving
run."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference.scheduler import PagedServer
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.config import TransformerConfig
from deepspeed_tpu.profiling.tracer import MetricsRegistry, Tracer

CFG = dict(
    vocab_size=128,
    hidden_size=64,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    max_seq_len=64,
    norm="rmsnorm",
    position="rope",
    activation="swiglu",
    use_bias=False,
    tie_embeddings=False,
    flash_attention=False,
    dtype="float32",
)


@pytest.fixture(scope="module")
def model_and_params():
    cfg = TransformerConfig(**CFG)
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), toks)
    return cfg, model, params


def _server(cfg, params, tracer=None, metrics=None, **kw):
    kw.setdefault("page_size", 8)
    kw.setdefault("max_slots", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("attn_impl", "xla")
    kw.setdefault("dtype", jnp.float32)
    return PagedServer(cfg, params, tracer=tracer, metrics=metrics, **kw)


def _lifecycle(tracer, uid):
    """(ph, name) sequence of the async records for one request uid."""
    return [
        (r["ph"], r["name"])
        for r in tracer.spans()
        if r["ph"] in ("b", "n", "e") and r.get("id") == uid
    ]


def _prompts(n, seed=0, lo=3, hi=20):
    rs = np.random.RandomState(seed)
    return [
        rs.randint(0, CFG["vocab_size"], (int(rs.randint(lo, hi)),)).astype(np.int32)
        for _ in range(n)
    ]


def test_request_lifecycle_submit_admit_first_token_finish(model_and_params):
    cfg, _, params = model_and_params
    tr, m = Tracer(), MetricsRegistry()
    server = _server(cfg, params, tracer=tr, metrics=m)
    uids = [server.submit(p, max_new_tokens=6, tenant="acme") for p in _prompts(3)]
    server.run()
    for uid in uids:
        names = _lifecycle(tr, uid)
        assert names[0] == ("b", f"req{uid}")
        assert ("n", "admit") in names
        assert ("n", "first_token") in names
        assert names[-1] == ("e", f"req{uid}")
        # chronology: admit before first_token before finish
        assert names.index(("n", "admit")) < names.index(("n", "first_token"))
    # finish attrs carry the serving story
    end = [r for r in tr.spans() if r["ph"] == "e" and r.get("id") == uids[0]][0]
    assert end["attrs"]["tenant"] == "acme"
    assert end["attrs"]["tokens"] == 6
    assert end["attrs"]["admissions"] == 1
    assert end["attrs"]["ttft_ms"] >= 0.0
    # step phases + metrics observed
    phases = tr.phase_summary()
    for name in ("serve.step", "serve.admit", "serve.pack", "serve.dispatch", "serve.emit"):
        assert phases[name]["count"] >= 1, name
    assert m.snapshot()["counters"]["serve.tokens"] == 18.0
    assert m.snapshot()["histograms"]["serve.ttft_ms"]["count"] == 3


def test_step_spans_reach_the_profiler_trace_with_their_attributes(model_and_params, tmp_path):
    """With the sink the engines install (``jax.profiler.TraceAnnotation``),
    a serving run under a profiler session leaves the scheduler's phases in
    the host plane of the ``.xplane.pb``, nested as the program nests them
    and carrying the step's counts; the same three readings are the
    registry's gauges; the request's admit instant carries its queue wait."""
    from jax.profiler import ProfileData

    cfg, _, params = model_and_params
    tr, m = Tracer(), MetricsRegistry()
    tr.sink = jax.profiler.TraceAnnotation
    server = _server(cfg, params, tracer=tr, metrics=m)
    server.serve(_prompts(1, seed=11), max_new_tokens=2)  # compile outside the session
    warm_steps = tr.phase_summary()["serve.step"]["count"]
    seq = server.stats["dispatches"]  # the number of the first step the session sees
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        uids = [server.submit(p, max_new_tokens=3) for p in _prompts(3, seed=12)]
        server.step()
        waiting_then = m.snapshot()["gauges"]["serve.waiting"]
        server.run()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    events = [
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
        for plane in ProfileData.from_file(str(path)).planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events if ev.name.startswith("serve.")
    ]
    steps = [e for e in events if e[0] == "serve.step"]
    assert len(steps) == tr.phase_summary()["serve.step"]["count"] - warm_steps  # every traced step, no other

    def inside(outer, name):
        return [e for e in events if e[0] == name and outer[1] <= e[1] and e[2] <= outer[2]]

    first = steps[0]
    assert first[3] == {"waiting": 3, "running": 0, "pages_in_use": 0, "pages_total": server.pool.num_pages - 1, "seq_enqueued": seq}
    assert waiting_then == 3.0 and m.snapshot()["gauges"]["serve.running"] > 0
    (admit,), (pack, pack_next), (dispatch,) = (inside(first, n) for n in ("serve.admit", "serve.pack", "serve.dispatch"))
    assert admit[3] == {"admitted": 3}
    program = f"paged_ragged_r{server.pool.max_slots}_w8"
    assert dispatch[3] == {"seq": seq, "rows": 3, "width": 8, "program": program, "ahead": 0}
    del dispatch[3]["ahead"]
    # three first chunks of at most a page each: a mixed step, its rows as the kernel gets them (``q:kv``, a first
    # chunk's keys are its own tokens) in one string that comes back from the event's stats as it went in
    chunks = [min(p.size, 8) for p in _prompts(3, seed=12)]
    row_lens = " ".join(f"{n}:{n}" for n in chunks)
    # and what the program computes: the chunks' tokens, in no token tile (a window of one tile at most)
    live_tokens = sum(chunks)
    assert pack[3] == {**dispatch[3], "mixed": 1, "kv_pages": 3, "kv_tokens": live_tokens, "live_tokens": live_tokens, "token_tiles": 0, "row_lens": row_lens}
    # the table the step sends is a constant of the pool, and no attribute of a step
    assert server.pool.page_table.shape == (server.pool.max_slots, server.pool.max_pages_per_slot)
    assert admit[2] <= pack[1] and pack[2] <= dispatch[1]
    # the first call packs the next step while the device runs this one, waits for the device and leaves the
    # settle to the call after it, which does it behind the enqueue of its own step
    (fetch,) = inside(first, "serve.fetch")
    assert inside(first, "serve.emit") == [] and dispatch[2] <= pack_next[1] and pack_next[2] <= fetch[1]
    # the jitted call alone is a span of its own inside the dispatch, and the step's number rides on all of them:
    # the step packed in this call is enqueued in the next, and settled in the one after
    (enqueue,) = inside(dispatch, "serve.enqueue")
    assert enqueue[3] == {"seq": seq, "program": program} and fetch[3] == {"seq": seq} and pack_next[3]["seq"] == seq + 1
    (dispatch2,), (emit,), (fetch2,) = (inside(steps[1], n) for n in ("serve.dispatch", "serve.emit", "serve.fetch"))
    assert dispatch2[3]["ahead"] == 1 and dispatch2[2] <= emit[1] and emit[2] <= fetch2[1]
    assert (dispatch2[3]["seq"], emit[3], fetch2[3], steps[1][3]["seq_enqueued"]) == (seq + 1, {"seq": seq}, {"seq": seq + 1}, seq + 1)
    (settle,) = inside(emit, "serve.settle")
    assert settle[3]["seq"] == seq
    emitted = sum(e[3]["tokens"] for e in events if e[0] == "serve.settle")
    assert emitted == 9 == sum(len(server.take_result(u)) for u in uids) - sum(p.size for p in _prompts(3, seed=12))
    # a later step sees the running set and its pages
    assert steps[-1][3]["running"] >= 1 and steps[-1][3]["pages_in_use"] >= 1
    admits = [r for r in tr.spans() if r["ph"] == "n" and r["name"] == "admit" and r.get("id") in uids]
    assert len(admits) == 3 and all(r["attrs"]["queue_wait_ms"] >= 0.0 for r in admits)
    # a lone decode row's ``row_lens`` keeps its ``1:``: the event's stats give a string of digits alone back as a number,
    # and a record has to come back as it went in
    jax.profiler.start_trace(str(tmp_path / "lone"), profiler_options=options)
    try:
        server.serve(_prompts(1, seed=13), max_new_tokens=3)
    finally:
        jax.profiler.stop_trace()
    (path,) = (tmp_path / "lone").glob("plugins/profile/*/*.xplane.pb")
    lone = [dict(ev.stats) for plane in ProfileData.from_file(str(path)).planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events if ev.name == "serve.pack"]
    assert len(lone) >= 3 and all(a["rows"] == 1 for a in lone) and sum(a["row_lens"].startswith("1:") for a in lone) >= 2
    assert [a["row_lens"] for a in lone] == [r["attrs"]["row_lens"] for r in tr.spans() if r["name"] == "serve.pack"][-len(lone):]


def test_preemption_leaves_preempt_instant_and_readmission(model_and_params):
    """A pool sized to force recompute-preemption: the victim's span trail
    shows preempt → admit again, and its finish attrs count both
    admissions. Output correctness is covered by the serving suites; here
    the TIMELINE is the contract."""
    cfg, _, params = model_and_params
    tr = Tracer()
    server = _server(cfg, params, tracer=tr, num_pages=7, max_slots=2)
    uids = [server.submit(p, max_new_tokens=16) for p in _prompts(2, seed=3, lo=10, hi=14)]
    server.run()
    assert server.stats["preempted"] >= 1
    preempted = [
        r.get("id") for r in tr.spans() if r["ph"] == "n" and r["name"] == "preempt"
    ]
    assert preempted, "no preempt instant recorded"
    uid = preempted[0]
    names = _lifecycle(tr, uid)
    i_pre = names.index(("n", "preempt"))
    assert ("n", "admit") in names[i_pre:], "no re-admission after preempt"
    end = [r for r in tr.spans() if r["ph"] == "e" and r.get("id") == uid][0]
    assert end["attrs"]["admissions"] >= 2


def test_spec_decode_attrs_on_finish(model_and_params):
    """With the n-gram drafter engaged, the request's finish span reports
    how many drafts it sent and how many were accepted (the per-request
    speculation story). The drafter proposes only where the context's own
    suffix occurred before, and this model does not continue the prompt's
    motif: its greedy stream enters a cycle of seven tokens from its third
    token on, so the budget has to reach the cycle's second turn."""
    cfg, _, params = model_and_params
    tr = Tracer()
    server = _server(
        cfg, params,
        spec_decode={"enable": True, "max_draft": 3, "ngram_order": 2},
    )
    server.tracer = tr
    motif = np.array([5, 9, 5, 9, 5, 9, 5, 9, 5, 9], np.int32)
    uid = server.submit(motif, max_new_tokens=24)
    server.run()
    assert server.stats["spec_drafted"] > 0  # the drafter engaged
    assert server.stats["spec_accepted"] > 0  # and the cycle made it right
    end = [r for r in tr.spans() if r["ph"] == "e" and r.get("id") == uid][0]
    assert end["attrs"]["spec_drafted"] == server.stats["spec_drafted"]
    assert end["attrs"]["spec_accepted"] == server.stats["spec_accepted"]


def test_journal_sync_phase_present(model_and_params, tmp_path):
    from deepspeed_tpu.inference.journal import RequestJournal

    cfg, _, params = model_and_params
    tr = Tracer()
    journal = RequestJournal(str(tmp_path / "j"))
    server = _server(cfg, params, tracer=tr, journal=journal)
    server.serve(_prompts(2, seed=5), max_new_tokens=4)
    assert tr.phase_summary()["serve.journal_sync"]["count"] >= 1


def test_prefix_cached_attr_rides_admit_event(model_and_params):
    """Second serve of a shared prompt attaches cached full pages; the
    admit instant reports how many context tokens the request did NOT
    re-prefill."""
    cfg, _, params = model_and_params
    tr = Tracer()
    server = _server(cfg, params, tracer=tr, prefix_cache=True)
    prompt = np.arange(1, 25, dtype=np.int32) % CFG["vocab_size"]
    server.serve([prompt], max_new_tokens=2)
    uid2 = server.submit(prompt, max_new_tokens=2)
    server.run()
    admit2 = [
        r for r in tr.spans()
        if r["ph"] == "n" and r["name"] == "admit" and r.get("id") == uid2
    ][0]
    assert admit2["attrs"]["prefix_cached"] > 0
    end = [r for r in tr.spans() if r["ph"] == "e" and r.get("id") == uid2][0]
    assert end["attrs"]["prefix_cached"] == admit2["attrs"]["prefix_cached"]


def test_engine_observability_merged_report_and_trace(model_and_params, tmp_path):
    """The acceptance surface: ONE observability() call returns the merged
    report (timeline + metrics + compile + analysis + serve stats), and
    the hub exports a Perfetto-loadable trace for the serving run."""
    cfg, model, params = model_and_params
    engine = ds.init_inference(
        model,
        dtype="fp32",
        paged_kv={"page_size": 8, "max_slots": 4, "prefill_chunk": 8,
                  "attn_impl": "xla"},
    )
    engine.set_params(params)
    engine._ds_config = cfg  # converted-family contract
    engine.serve(_prompts(3, seed=7), max_new_tokens=4)
    rep = engine.observability()
    assert set(rep) >= {"timeline", "metrics", "compile", "analysis", "serve"}
    assert rep["timeline"]["phases"]["serve.step"]["count"] >= 1
    assert rep["serve"]["finished"] == 3
    assert any(n.startswith("paged_") for n in rep["compile"])
    # a program's XLA module carries its compile_stats() key: what the
    # profiler's module line and the serve.dispatch span's `program` agree on
    for name in rep["compile"]:
        if name.startswith("paged_ragged"):
            assert f"module @jit_{name} " in engine._telemetry.lowered_text(name)[:200]
    assert engine.tracer.sink is jax.profiler.TraceAnnotation
    # the analysis merge is the real report (violations counted), not a stub
    assert rep["analysis"]["totals"]["violations"] == 0
    # Perfetto trace for a serving run
    path = engine.observability_hub.export_chrome_trace(str(tmp_path / "serve.json"))
    obj = json.load(open(path))
    phs = {e["ph"] for e in obj["traceEvents"]}
    assert {"X", "b", "e"} <= phs  # phase spans + request lifecycles
    names = {e["name"] for e in obj["traceEvents"]}
    assert "serve.dispatch" in names


def test_chaos_kill_mid_emit_leaks_no_open_spans(model_and_params, tmp_path):
    """A ChaosKilled fired from inside the emit path (the journal.append
    hook runs between serve.emit's enter and exit) must unwind through the
    span context managers without leaving phantom open spans — the
    flight-recorder's open_spans answer stays truthful for the rest of the
    process after an in-process recovery."""
    from deepspeed_tpu.inference.journal import RequestJournal
    from deepspeed_tpu.utils import chaos

    cfg, _, params = model_and_params
    tr = Tracer()
    server = _server(
        cfg, params, tracer=tr, journal=RequestJournal(str(tmp_path / "j"))
    )
    server.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=6)
    try:
        chaos.install(chaos.ChaosSchedule([chaos.ChaosRule("journal.append", hit=2)]))
        with pytest.raises(chaos.ChaosKilled):
            server.run()
    finally:
        chaos.uninstall()
    assert tr.open_spans() == []


def test_multi_tenant_server_exposes_tracer(model_and_params):
    from deepspeed_tpu.inference.traffic import MultiTenantServer

    cfg, _, params = model_and_params
    tr = Tracer()
    inner = _server(cfg, params, tracer=tr)
    mt = MultiTenantServer(inner, tenants=[{"name": "a", "weight": 1.0}])
    assert mt.tracer is tr
    mt.serve(_prompts(1, seed=9), max_new_tokens=2, tenant="a")
    assert tr.phase_summary()["serve.step"]["count"] >= 1
