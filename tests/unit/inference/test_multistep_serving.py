"""Multi-step in-program serving windows (ISSUE 11): N decode rounds fused
into ONE dispatch, host gap amortized to 1/N.

Load-bearing checks: with ``paged_kv.multi_step`` armed, steady-state
decode (no scheduling events) dispatches ONE ``build_ragged_multistep``
program per ``horizon`` tokens per row — measured through compile
telemetry as dispatches/token ≤ 1/horizon — while the greedy streams stay
BYTE-IDENTICAL to the single-step ragged path and dense lockstep
``decode.generate``; any scheduling event
(admission, prefill, drafts, pool pressure) breaks the window back to the
single-step path and ``window_break_reasons`` names it. EOS inside a
window, finish exactly at the window edge, admission breaking a window,
preemption + chunk-grid resume, and prefix-cache attach are each pinned
against the oracles. The companion analysis gate lives in
``tests/unit/analysis/test_passes.py::test_green_multistep_window_program_and_compile_gate``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference import decode
from deepspeed_tpu.inference.scheduler import PagedServer, compiled_serving_programs
from deepspeed_tpu.inference.spec_decode import Drafter
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.config import TransformerConfig
from deepspeed_tpu.profiling.compile_telemetry import CompileTelemetry

CFG = dict(
    vocab_size=128,
    hidden_size=64,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,  # GQA on the serving path
    max_seq_len=64,
    norm="rmsnorm",
    position="rope",
    activation="swiglu",
    use_bias=False,
    tie_embeddings=False,
    flash_attention=False,
    dtype="float32",
)
H = 4  # the armed horizon for every window server in this suite


@pytest.fixture(scope="module")
def model_and_params():
    cfg = TransformerConfig(**CFG)
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), toks)
    return cfg, model, params


def _prompts(n, seed=0, lo=3, hi=20):
    rs = np.random.RandomState(seed)
    return [
        rs.randint(0, CFG["vocab_size"], (int(rs.randint(lo, hi)),)).astype(np.int32)
        for _ in range(n)
    ]


def _dense(cfg, params, prompt, n, eos=None):
    return np.asarray(decode.generate(cfg, params, prompt[None], n, eos_token_id=eos))[0]


def _server(cfg, params, multi_step=True, horizon=H, **kw):
    kw.setdefault("page_size", 8)
    kw.setdefault("max_slots", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("attn_impl", "xla")
    kw.setdefault("dtype", jnp.float32)
    ms = {"enable": True, "horizon": horizon} if multi_step else None
    return PagedServer(cfg, params, multi_step=ms, **kw)


# --- token exactness: window vs single-step vs dense ------------------------
def test_window_matches_singlestep_and_dense(model_and_params):
    """The core exactness check: the same ragged request mix through the
    window path and the single-step ragged path — byte-identical streams,
    each its own dense decode, windows actually engaged, pool drained."""
    cfg, _, params = model_and_params
    prompts = _prompts(4, seed=2)
    budgets = [13, 9, 17, 12]
    windowed = _server(cfg, params)
    outs = windowed.serve(prompts, max_new_tokens=budgets)
    single = _server(cfg, params, multi_step=False)
    single_outs = single.serve(prompts, max_new_tokens=budgets)
    for p, n, a, b in zip(prompts, budgets, outs, single_outs):
        np.testing.assert_array_equal(a, _dense(cfg, params, p, n))
        np.testing.assert_array_equal(a, b)
    st = windowed.serve_stats()
    assert st["window_steps"] >= 2, st
    assert single.stats["window_steps"] == 0
    # the window server paid strictly fewer dispatches for the same tokens
    assert st["dispatches"] < single.stats["dispatches"]
    assert windowed.pool.used_pages() == 0 and windowed.pool.live_tokens() == 0
    windowed.pool.integrity_check()


def test_window_eos_inside(model_and_params):
    """EOS landing mid-window freezes the row in-program: it emits the EOS
    token and nothing after it, byte-identical to sequential decode, and
    the break is attributed to eos."""
    cfg, _, params = model_and_params
    prompts = _prompts(2, seed=7)
    futures = {i: _dense(cfg, params, p, 16) for i, p in enumerate(prompts)}
    # an EOS that fires a couple of windows in for row 0 — NOT on a window
    # edge (position prompt+6 with horizon 4: round 2 of window 2)
    eos = int(futures[0][prompts[0].size + 5])
    server = _server(cfg, params)
    outs = server.serve(prompts, max_new_tokens=16, eos_token_id=eos)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _dense(cfg, params, p, 16, eos=eos))
    st = server.serve_stats()
    assert st["window_steps"] >= 1
    assert st["window_break_reasons"]["eos"] >= 1, st["window_break_reasons"]


def test_window_finish_at_window_edge(model_and_params):
    """Budgets aligned so every row's last token lands exactly on a window
    edge: the fused program emits full windows, nothing falls back to the
    single-step tail, and no break is charged to budget."""
    cfg, _, params = model_and_params
    server = _server(cfg, params)
    prompts = _prompts(2, seed=3, lo=4, hi=7)  # single-chunk prompts
    # first token comes from the finishing prefill chunk; the remaining
    # 3*H tokens are exactly three full windows
    budget = 3 * H + 1
    outs = server.serve(prompts, max_new_tokens=budget)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _dense(cfg, params, p, budget))
    st = server.serve_stats()
    assert st["window_steps"] == 3, st
    assert st["window_break_reasons"]["budget"] == 0, st["window_break_reasons"]
    assert st["window_break_reasons"]["eos"] == 0


def test_window_admission_breaks(model_and_params):
    """A submission arriving while windows are running breaks the next
    window (its TTFT is never parked behind a fused dispatch): the break
    is attributed to admission, the late request's chunks ride single-step
    dispatches, and every stream stays exact."""
    cfg, _, params = model_and_params
    server = _server(cfg, params)
    prompts = _prompts(6, seed=4)
    # fill every slot so late submissions actually QUEUE
    first = [server.submit(p, max_new_tokens=14) for p in prompts[:4]]
    # run until windows have engaged
    while server.stats["window_steps"] < 1:
        server.step()
    late = [server.submit(p, max_new_tokens=14) for p in prompts[4:]]
    results = server.run()
    for uid, p in zip(first + late, prompts):
        np.testing.assert_array_equal(results[uid], _dense(cfg, params, p, 14))
    br = server.serve_stats()["window_break_reasons"]
    assert br["admission"] >= 1, br  # queued-but-unadmittable broke windows
    assert br["prefill"] >= 1, br  # the late chunks broke windows too


def test_window_preemption_and_chunk_grid_resume(model_and_params):
    """An undersized pool: window reservation (a whole horizon of pages
    per row) hits pool pressure, breaks to the single-step path — which
    preempts — and the recomputed continuations stay byte-identical to
    the window-off oracle and dense."""
    cfg, _, params = model_and_params
    kw = dict(page_size=4, num_pages=14, max_slots=3, prefill_chunk=8)
    prompts = _prompts(4, seed=4, lo=6, hi=14)
    windowed = _server(cfg, params, **kw)
    outs = windowed.serve(prompts, max_new_tokens=12)
    assert windowed.stats["preempted"] >= 1, "pool was sized to force preemption"
    oracle = _server(cfg, params, multi_step=False, **kw).serve(
        prompts, max_new_tokens=12
    )
    for p, a, b in zip(prompts, outs, oracle):
        np.testing.assert_array_equal(a, _dense(cfg, params, p, 12))
        np.testing.assert_array_equal(a, b)
    assert windowed.pool.used_pages() == 0
    windowed.pool.integrity_check()


def test_window_pool_pressure_attributed_to_pool_reason(model_and_params):
    """Reservation pressure with NO queue and no prefill: the window break
    lands on the dedicated "pool" counter — never on "budget" (token
    budgets and page-pool pressure need opposite remediations) — the
    single-step fallback preempts as usual, and streams stay exact."""
    cfg, _, params = model_and_params
    # 2 slots, both admit at once (queue never forms), pool sized so the
    # rows outgrow it mid-decode: 9 allocatable pages × 4 tokens < the
    # two streams' peak demand
    kw = dict(page_size=4, num_pages=10, max_slots=2, prefill_chunk=8)
    prompts = _prompts(2, seed=12, lo=6, hi=10)
    server = _server(cfg, params, **kw)
    outs = server.serve(prompts, max_new_tokens=14)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _dense(cfg, params, p, 14))
    br = server.serve_stats()["window_break_reasons"]
    assert br["pool"] >= 1, br
    assert server.stats["preempted"] >= 1
    server.pool.integrity_check()


def test_window_prefix_cache_attach(model_and_params):
    """Warm prefix attaches ride underneath windows unchanged: the second
    serve of shared-prefix prompts attaches pages, windows still form, and
    streams match sharing-off serving byte for byte."""
    cfg, _, params = model_and_params
    rs = np.random.RandomState(21)
    sys_tokens = rs.randint(0, 128, (19,)).astype(np.int32)  # 2 pages + 3 mid-grid
    prompts = [
        np.concatenate([sys_tokens, rs.randint(0, 128, (3 + i,)).astype(np.int32)])
        for i in range(4)
    ]
    server = _server(cfg, params, prefix_cache=True)
    first = server.serve(prompts[:1], max_new_tokens=9)
    rest = server.serve(prompts[1:], max_new_tokens=9)
    assert server.pool.stats["prefix_hit_pages"] > 0, "prefix cache never engaged"
    assert server.stats["window_steps"] >= 1
    off = _server(cfg, params, multi_step=False, prefix_cache=False)
    oracle = off.serve(prompts, max_new_tokens=9)
    for p, a, b in zip(prompts, first + rest, oracle):
        np.testing.assert_array_equal(a, _dense(cfg, params, p, 9))
        np.testing.assert_array_equal(a, b)
    server.pool.integrity_check()


class FadingDrafter(Drafter):
    """Drafts the precomputed greedy future only while the context is
    short: early rounds speculate (windows must break on 'draft'), later
    rounds propose nothing (windows must form). Exercises the
    window/speculation handoff incl. the one-proposal-per-step contract."""

    def __init__(self, futures, fade_at):
        self.futures = futures
        self.fade_at = fade_at
        self.calls = []  # (uid, context length) per proposal

    def propose(self, uid, context, k):
        self.calls.append((uid, context.size))
        if context.size >= self.fade_at:
            return np.zeros(0, np.int32)
        return self.futures[uid][context.size : context.size + k].astype(np.int32)


def test_window_coexists_with_spec_decode(model_and_params):
    """Speculation and windows share the serve: drafted rounds verify
    through the single-step path (break reason 'draft'), quiet rounds fuse
    into windows — and the streams stay byte-identical to dense. The
    drafter is consulted at most once per scheduler step (the failed
    window probe hands its proposals to the fallback)."""
    cfg, _, params = model_and_params
    prompts = _prompts(2, seed=5, lo=4, hi=7)
    budget = 18
    futures = {i: _dense(cfg, params, p, budget) for i, p in enumerate(prompts)}
    fade_at = max(p.size for p in prompts) + 4
    drafter = FadingDrafter(futures, fade_at)
    server = _server(
        cfg, params, drafter=drafter, spec_decode={"max_draft": 3}
    )
    outs = server.serve(prompts, max_new_tokens=budget)
    for i, out in enumerate(outs):
        np.testing.assert_array_equal(out, futures[i])
    st = server.serve_stats()
    assert st["spec_rounds"] >= 1, "speculation never engaged"
    assert st["window_steps"] >= 1, "windows never formed after the drafts faded"
    assert st["window_break_reasons"]["draft"] >= 1, st["window_break_reasons"]
    # the drafter is asked at most ONCE per request per step: a window
    # probe that breaks on 'draft' hands its proposals to the fallback
    # instead of re-asking — a double-ask would repeat the same
    # (uid, context length) pair, since no token lands in between
    assert len(drafter.calls) == len(set(drafter.calls)), drafter.calls


def test_window_forms_with_near_finished_row_at_seq_cap(model_and_params):
    """A row parked near max_seq_len whose remaining budget fits (but
    whose len + horizon would NOT) must not break windows forever: the
    reservation asks min(horizon, remaining budget) per row — the
    in-program budget freeze bounds the row's writes to its budget."""
    cfg, _, params = model_and_params
    rs = np.random.RandomState(30)
    # 61 + budget 2 = 63 ≤ max_seq_len 64, but 61 + horizon 4 = 65 > 64:
    # an un-clamped reservation can NEVER make this row writable
    long_p = rs.randint(0, 128, (61,)).astype(np.int32)
    short_p = rs.randint(0, 128, (6,)).astype(np.int32)
    server = _server(cfg, params)
    uids = [server.submit(short_p, max_new_tokens=3 * H + 1),
            server.submit(long_p, max_new_tokens=2)]
    # drive past prefill (the short row decodes inside the long row's
    # chunk dispatches there — single-step by design)
    while server._queue or any(r.pending is None for r in server._active):
        server.step()
    assert len(server._active) == 2  # the capped row is still live
    # the very first stable step must FUSE: the capped row's clamped
    # reservation (len + its 1-token budget) fits max_seq_len, so it
    # freezes at its budget inside the window — an un-clamped len + H
    # reservation overflows the cap and would force this step (and the
    # capped row's retirement) through a single-step decode dispatch
    server.step()
    assert server.stats["window_steps"] == 1, server.serve_stats()
    results = server.run()
    np.testing.assert_array_equal(
        results[uids[0]], _dense(cfg, params, short_p, 3 * H + 1)
    )
    np.testing.assert_array_equal(results[uids[1]], _dense(cfg, params, long_p, 2))


# --- the dispatch-amortization gate -----------------------------------------
def test_steady_state_dispatches_per_token_le_one_over_horizon(model_and_params):
    """THE acceptance gate: once the running set is stable (prefill done,
    queue empty), compile telemetry measures dispatches/token ≤ 1/horizon
    — each window is ONE ``paged_multistep_*`` dispatch covering horizon
    rounds — and the serving program set stays ≤ 4."""
    cfg, _, params = model_and_params
    telemetry = CompileTelemetry()
    server = _server(cfg, params, telemetry=telemetry)
    prompts = _prompts(2, seed=5, lo=4, hi=7)
    for p in prompts:
        server.submit(p, max_new_tokens=3 * H + 1)
    # drive to the steady state: everything admitted and past prefill
    while server._queue or any(r.pending is None for r in server._active):
        server.step()
    disp_before = sum(
        r["dispatches"] for n, r in telemetry.stats().items()
        if n.startswith("paged_")
    )
    tok_before = server.stats["emitted_tokens"]
    server.run()
    stats = telemetry.stats()
    disp = sum(
        r["dispatches"] for n, r in stats.items() if n.startswith("paged_")
    ) - disp_before
    toks = server.stats["emitted_tokens"] - tok_before
    assert toks == 2 * 3 * H
    assert disp / toks <= 1.0 / H, (disp, toks)
    # every steady-state dispatch was the fused window program
    assert disp == server.stats["window_steps"]
    assert compiled_serving_programs(stats) <= 4, stats
    assert any(n.startswith("paged_multistep_") for n in stats), stats.keys()


def test_window_retrace_guard_and_program_budget(model_and_params):
    """3 waves of shifting mixes through one telemetry: the window program
    compiles once (warmup aside, no wave adds a compile), total serving
    programs ≤ 4 (narrow + mixed + one window program for the single armed
    horizon), and telemetry dispatch counts reconcile with the scheduler's
    own dispatch counter."""
    cfg, _, params = model_and_params
    telemetry = CompileTelemetry()
    server = _server(cfg, params, telemetry=telemetry)
    waves = [_prompts(2, seed=6), _prompts(4, seed=7), _prompts(2, seed=8)]
    compiles = []
    for wave in waves:
        outs = server.serve(wave, max_new_tokens=11)
        for p, out in zip(wave, outs):
            np.testing.assert_array_equal(out, _dense(cfg, params, p, 11))
        compiles.append(sum(r["compiles"] for r in telemetry.stats().values()))
    stats = telemetry.stats()
    assert compiled_serving_programs(stats) <= 4, stats
    assert compiles[1] == compiles[0] and compiles[2] == compiles[0], compiles
    for name, rec in stats.items():
        assert rec["compiles"] <= 1, f"{name} recompiled: {rec}"
    assert server.stats["window_steps"] >= 1
    total = sum(r["dispatches"] for r in stats.values())
    assert total == server.stats["dispatches"]


def test_windows_add_zero_host_transfers_and_zero_programs_when_traced(
    model_and_params
):
    """Telemetry-free contract, window edition: serving the same trace
    with tracing ON compiles the identical program set (tracing adds zero
    programs), the streams match, and the fetch accounting closes — the
    packed token matrix is the ONE sanctioned fetch per window, so the
    window path's host fetches equal its dispatches exactly (no hidden
    per-token or per-round transfer)."""
    from deepspeed_tpu.profiling.tracer import MetricsRegistry, Tracer

    cfg, _, params = model_and_params
    prompts = _prompts(3, seed=9)
    sets = {}
    outs = {}
    for traced in (False, True):
        telemetry = CompileTelemetry()
        kw = {}
        if traced:
            kw = dict(tracer=Tracer(enabled=True), metrics=MetricsRegistry())
        server = _server(cfg, params, telemetry=telemetry, **kw)
        outs[traced] = server.serve(prompts, max_new_tokens=3 * H + 1)
        sets[traced] = sorted(telemetry.stats().keys())
        assert server.stats["window_steps"] >= 1
    assert sets[True] == sets[False], sets
    for a, b in zip(outs[True], outs[False]):
        np.testing.assert_array_equal(a, b)


# --- stats / config / engine surface ----------------------------------------
def test_window_stats_block(model_and_params):
    """serve_stats() carries the window observability block: window_steps,
    the armed horizon, dispatches_per_token (strictly amortized below the
    single-step path's), and the break-reason counters."""
    cfg, _, params = model_and_params
    server = _server(cfg, params)
    prompts = _prompts(2, seed=10, lo=4, hi=7)
    server.serve(prompts, max_new_tokens=3 * H + 1)
    st = server.serve_stats()
    assert st["window_horizon"] == H
    assert st["window_steps"] >= 1
    assert 0.0 < st["dispatches_per_token"] < 1.0
    assert set(st["window_break_reasons"]) == {
        "admission", "prefill", "draft", "eos", "budget", "pool"
    }
    single = _server(cfg, params, multi_step=False)
    single.serve(prompts, max_new_tokens=3 * H + 1)
    sst = single.serve_stats()
    assert sst["window_horizon"] == 0 and sst["window_steps"] == 0
    assert st["dispatches_per_token"] < sst["dispatches_per_token"]


def test_multistep_config_validation(model_and_params):
    cfg, _, params = model_and_params
    with pytest.raises(ValueError, match="horizon"):
        _server(cfg, params, horizon=1)
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig

    with pytest.raises(ValueError, match="horizon"):
        DeepSpeedInferenceConfig(
            paged_kv={"multi_step": {"enable": True, "horizon": 1}}
        )
    # horizon validates only when armed (parity with the other sub-blocks)
    DeepSpeedInferenceConfig(paged_kv={"multi_step": {"horizon": 1}})


def test_multistep_knob_through_engine(model_and_params, tmp_path):
    """inference.paged_kv.multi_step routes the engine's serve() through
    windows (byte-identical to the un-windowed engine), serve_stats()
    surfaces the window block, and the flight recorder's dump names the
    armed horizon so postmortems can read the window config."""
    cfg, model, params = model_and_params
    outs = {}
    for enable in (True, False):
        engine = ds.init_inference(
            model,
            dtype="fp32",
            paged_kv={"page_size": 8, "max_slots": 4, "prefill_chunk": 8,
                      "attn_impl": "xla",
                      "multi_step": {"enable": enable, "horizon": H}},
            tracing={"flight_recorder": True,
                     "flight_recorder_dir": str(tmp_path / str(enable))},
        )
        engine.set_params(params)
        engine._ds_config = cfg  # converted-family contract
        prompts = _prompts(3, seed=11)
        outs[enable] = engine.serve(prompts, max_new_tokens=3 * H + 1)
        st = engine.serve_stats()
        if enable:
            assert st["window_steps"] >= 1
            assert any(
                n.startswith("paged_multistep_") for n in engine.compile_stats()
            )
            rec = engine.observability_hub.flight_recorder
            assert rec.context["serve.multi_step"]["horizon"] == H
            import json

            path = rec.dump(reason="test")
            payload = json.loads(open(path).read())
            assert payload["context"]["serve.multi_step"]["horizon"] == H
        else:
            assert st["window_steps"] == 0
            # the context reflects the CURRENT build — a rebuild with
            # windows disabled must not leave a stale armed-horizon claim
            rec = engine.observability_hub.flight_recorder
            assert rec.context["serve.multi_step"]["enable"] is False
        engine.observability_hub.flight_recorder.uninstall()
    for a, b in zip(outs[True], outs[False]):
        np.testing.assert_array_equal(a, b)
