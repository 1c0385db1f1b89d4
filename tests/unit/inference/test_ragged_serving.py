"""One ragged serving program (ISSUE 8): unified prefill+decode+verify
dispatch that never retraces.

Load-bearing checks: every scheduler step is ONE dispatch of the unified
``build_ragged_step`` program and the greedy output streams are
BYTE-IDENTICAL to the dense lockstep ``decode.generate`` — across mid-stream admission, preemption+resume on
the chunk grid, prefix-cache attach, a per-request spec-K mix, and EOS
landing inside an accepted draft run. Compile telemetry must show ≤ 2
compiled serving programs for a full mixed serve and 1 dispatch per step
(the companion analysis gate lives in
``tests/unit/analysis/test_passes.py::test_green_ragged_serving_program_and_compile_gate``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


def _server(cfg, params, **kw):
    kw.setdefault("page_size", 8)
    kw.setdefault("max_slots", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("attn_impl", "xla")
    kw.setdefault("dtype", jnp.float32)
    return PagedServer(cfg, params, **kw)


class MixKDrafter(Drafter):
    """Per-request spec-K mix: request uid drafts its precomputed greedy
    future, but only ``uid % (cap+1)`` tokens of it — every ragged round
    carries rows with DIFFERENT draft counts (incl. zero) at once."""

    def __init__(self, futures, cap=3):
        self.futures = futures
        self.cap = cap

    def propose(self, uid, context, k):
        k = min(k, uid % (self.cap + 1))
        return self.futures[uid][context.size : context.size + k].astype(np.int32)


# --- token exactness: ragged vs dense -----------------------------------------
def test_ragged_matches_dense_mixed_serve(model_and_params):
    """The core exactness check: a ragged request mix, every stream
    byte-identical to its own dense decode, pool drained."""
    cfg, _, params = model_and_params
    prompts = _prompts(6, seed=2)
    budgets = [10, 3, 7, 12, 1, 5]
    ragged = _server(cfg, params)
    outs = ragged.serve(prompts, max_new_tokens=budgets)
    for p, n, a in zip(prompts, budgets, outs):
        np.testing.assert_array_equal(a, _dense(cfg, params, p, n))
    assert ragged.stats["finished"] == 6
    assert ragged.stats["ragged_steps"] >= 1
    assert ragged.pool.used_pages() == 0 and ragged.pool.live_tokens() == 0


def test_ragged_admission_mid_stream(model_and_params):
    """Requests submitted while others are mid-decode join the SAME ragged
    dispatch as running decoders: their prefill chunks ride along instead
    of stealing steps, and nothing disturbs in-flight streams."""
    cfg, _, params = model_and_params
    server = _server(cfg, params)
    prompts = _prompts(4, seed=3)
    first = [server.submit(p, max_new_tokens=12) for p in prompts[:2]]
    for _ in range(6):  # prefill + several decode steps for wave 1
        server.step()
    assert server.stats["decode_steps"] >= 1
    late = [server.submit(p, max_new_tokens=12) for p in prompts[2:]]
    results = server.run()
    for uid, p in zip(first + late, prompts):
        np.testing.assert_array_equal(results[uid], _dense(cfg, params, p, 12))
    # the late admissions' chunks coexisted with wave-1 decoders: every
    # step was still exactly one dispatch
    assert server.stats["ragged_steps"] >= server.stats["decode_steps"]


def test_ragged_prefill_coexists_with_decode(model_and_params):
    """A long multi-chunk prompt admitted next to a short one: once the
    short request starts decoding, the long one's remaining chunks share
    its dispatches — total dispatches stay well under chunks + decode
    steps."""
    cfg, _, params = model_and_params
    rs = np.random.RandomState(9)
    short = rs.randint(0, 128, (4,)).astype(np.int32)
    long = rs.randint(0, 128, (40,)).astype(np.int32)
    server = _server(cfg, params)
    uids = [server.submit(short, max_new_tokens=10),
            server.submit(long, max_new_tokens=4)]
    results = server.run()
    np.testing.assert_array_equal(results[uids[0]], _dense(cfg, params, short, 10))
    np.testing.assert_array_equal(results[uids[1]], _dense(cfg, params, long, 4))
    st = server.stats
    # 40-token prompt = 5 chunks; the short request decodes through 4+ of
    # those same dispatches — strictly fewer total dispatches than
    # chunks + decode steps
    assert st["prefill_chunks"] >= 6
    assert st["ragged_steps"] < st["prefill_chunks"] + st["decode_steps"]


def test_ragged_preemption_resume_on_chunk_grid(model_and_params):
    """An undersized pool forces preemption mid-stream; the resumed prefill
    realigns to the chunk grid and the recomputed continuation is exact."""
    cfg, _, params = model_and_params
    kw = dict(page_size=4, num_pages=14, max_slots=3, prefill_chunk=8)
    prompts = _prompts(4, seed=4, lo=6, hi=14)
    ragged = _server(cfg, params, **kw)
    outs = ragged.serve(prompts, max_new_tokens=12)
    assert ragged.stats["preempted"] >= 1, "pool was sized to force preemption"
    for p, a in zip(prompts, outs):
        np.testing.assert_array_equal(a, _dense(cfg, params, p, 12))
    assert ragged.pool.used_pages() == 0


def test_ragged_prefix_cache_attach(model_and_params):
    """Warm prefix attaches (chunk-grid realigned resume after an attach
    that lands mid-grid) ride the ragged path unchanged: second serve of
    shared-prefix prompts attaches pages AND stays byte-identical."""
    cfg, _, params = model_and_params
    rs = np.random.RandomState(21)
    sys_tokens = rs.randint(0, 128, (19,)).astype(np.int32)  # 2 pages + 3 mid-grid
    prompts = [
        np.concatenate([sys_tokens, rs.randint(0, 128, (3 + i,)).astype(np.int32)])
        for i in range(4)
    ]
    server = _server(cfg, params, prefix_cache=True)
    first = server.serve(prompts[:1], max_new_tokens=4)
    rest = server.serve(prompts[1:], max_new_tokens=4)
    assert server.pool.stats["prefix_hit_pages"] > 0, "prefix cache never engaged"
    off = _server(cfg, params, prefix_cache=False)
    oracle = off.serve(prompts, max_new_tokens=4)
    for p, a, b in zip(prompts, first + rest, oracle):
        np.testing.assert_array_equal(a, _dense(cfg, params, p, 4))
        np.testing.assert_array_equal(a, b)


def test_ragged_per_request_spec_k_mix(model_and_params):
    """Per-request spec-K inside one dispatch: rows drafting 0, 1, 2, and
    3 tokens verify together, streams stay byte-identical to spec-off
    serving and dense."""
    cfg, _, params = model_and_params
    prompts = _prompts(4, seed=5)
    futures = {i: _dense(cfg, params, p, 12) for i, p in enumerate(prompts)}
    server = _server(
        cfg, params, drafter=MixKDrafter(futures), spec_decode={"max_draft": 3}
    )
    outs = server.serve(prompts, max_new_tokens=12)
    for i, out in enumerate(outs):
        np.testing.assert_array_equal(out, futures[i])
    st = server.serve_stats()
    assert st["spec_rounds"] >= 1 and st["spec_accepted"] >= 1
    # the mix really was ragged: some rounds carried zero-draft rows next
    # to drafted ones (uid 0 never drafts; uids 1-3 do)
    assert st["decode_steps"] >= 1
    # all of it through ONE program width — no per-K verify programs
    assert server.pool.used_pages() == 0


def test_ragged_eos_in_accepted_run(model_and_params):
    """EOS landing inside an accepted draft run retires the request at the
    EOS token exactly like sequential decode, on the ragged path."""
    cfg, _, params = model_and_params
    prompts = _prompts(2, seed=7)
    futures = {i: _dense(cfg, params, p, 10) for i, p in enumerate(prompts)}
    eos = int(futures[0][prompts[0].size + 2])

    class FullDrafter(Drafter):
        def propose(self, uid, context, k):
            return futures[uid][context.size : context.size + k].astype(np.int32)

    server = _server(cfg, params, drafter=FullDrafter())
    outs = server.serve(prompts, max_new_tokens=10, eos_token_id=eos)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _dense(cfg, params, p, 10, eos=eos))
    assert server.stats["spec_rounds"] >= 1


# --- compile budget & dispatch contract -------------------------------------
def test_ragged_compile_budget_and_one_dispatch_per_step(model_and_params):
    """3-wave shifting mix through one telemetry: ≤ 2 compiled serving
    programs TOTAL (warmup aside, no wave adds a compile), exactly one
    ragged dispatch per scheduler step, and no other serving program."""
    cfg, _, params = model_and_params
    telemetry = CompileTelemetry()
    server = _server(cfg, params, telemetry=telemetry)
    waves = [_prompts(2, seed=6), _prompts(4, seed=7), _prompts(2, seed=8)]
    compiles = []
    for wave in waves:
        outs = server.serve(wave, max_new_tokens=6)
        for p, out in zip(wave, outs):
            np.testing.assert_array_equal(out, _dense(cfg, params, p, 6))
        compiles.append(sum(r["compiles"] for r in telemetry.stats().values()))
    stats = telemetry.stats()
    assert all(n.startswith("paged_ragged_") for n in stats), stats.keys()
    assert compiled_serving_programs(stats) <= 2, stats
    assert compiles[1] == compiles[0] and compiles[2] == compiles[0], compiles
    assert sum(r["dispatches"] for r in stats.values()) == server.stats["ragged_steps"]


