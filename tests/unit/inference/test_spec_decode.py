"""Speculative decoding for the paged serving engine (ISSUE 4).

Load-bearing checks: speculation-on serving is token-exact against
speculation-off serving AND the dense lockstep ``decode.generate`` across
occupancy levels, mid-stream admission, eviction, and
preemption-with-recompute; every speculative round rides the step's ONE
ragged dispatch; compiled programs stay at most two whatever the drafts'
lengths. Injected oracle drafters drive the accept-all / partial-accept /
reject-all verification paths deterministically (the n-gram drafter's hit
rate depends on the model's output, which a random init doesn't pin down).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference import decode
from deepspeed_tpu.inference.scheduler import PagedServer, compiled_serving_programs
from deepspeed_tpu.inference.spec_decode import Drafter, NGramDrafter
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


class OracleDrafter(Drafter):
    """Drafts each request's precomputed dense-greedy future — acceptance
    is total by construction. ``corrupt_at`` flips that index of every
    proposal, pinning the accepted-prefix length to it exactly."""

    def __init__(self, futures, corrupt_at=None, vocab=128):
        self.futures = futures  # uid -> full dense output (prompt + budget)
        self.corrupt_at = corrupt_at
        self.vocab = vocab

    def propose(self, uid, context, k):
        cont = self.futures[uid][context.size : context.size + k].copy()
        if self.corrupt_at is not None and cont.size > self.corrupt_at:
            cont[self.corrupt_at] = (cont[self.corrupt_at] + 1) % self.vocab
        return cont.astype(np.int32)


class ConstantDrafter(Drafter):
    """Always proposes the same token — a reject-(almost-)all workload that
    still forces a verify dispatch every round."""

    def __init__(self, token=0, k=None):
        self.token = int(token)
        self.k = k

    def propose(self, uid, context, k):
        k = k if self.k is None else min(k, self.k)
        return np.full(k, self.token, np.int32)


# --- drafter unit behavior --------------------------------------------------
def test_ngram_drafter_prompt_lookup():
    d = NGramDrafter(ngram_order=3)
    ctx = np.array([5, 6, 7, 5, 6, 7, 5, 6], np.int32)
    # suffix (7, 5, 6) last occurred at position 2 -> continuation [7, 5, 6]
    np.testing.assert_array_equal(d.propose(0, ctx, 4), [7, 5, 6])
    np.testing.assert_array_equal(d.propose(0, ctx, 2), [7, 5])  # k clamps
    # no repeated suffix anywhere: nothing proposed
    assert d.propose(1, np.array([1, 2, 3, 4], np.int32), 4).size == 0
    # falls back to shorter orders when the long suffix is novel
    np.testing.assert_array_equal(
        d.propose(2, np.array([9, 1, 2, 9, 8, 2], np.int32), 2), [9, 8]
    )


def test_ngram_drafter_state_is_per_request():
    d = NGramDrafter(ngram_order=2)
    a = np.array([1, 2, 1, 2], np.int32)
    b = np.array([7, 7, 7], np.int32)
    assert d.propose(0, a, 3).size == 2  # [1, 2]
    # the only earlier (7, 7) occurrence has one token of future left
    np.testing.assert_array_equal(d.propose(1, b, 3), [7])
    d.drop(0)
    assert 0 not in d._state and 1 in d._state
    # context grows incrementally between rounds (the serving pattern)
    a2 = np.concatenate([a, np.array([1], np.int32)])
    np.testing.assert_array_equal(d.propose(0, a2, 2), [2, 1])


def test_ngram_drafter_rejects_bad_order():
    with pytest.raises(ValueError, match="ngram_order"):
        NGramDrafter(ngram_order=0)


# --- token-exactness ---------------------------------------------------------
def test_spec_full_acceptance_matches_dense(model_and_params):
    """Oracle drafts (the true greedy future): every draft accepted, output
    byte-identical to dense AND to speculation-off serving, across more
    requests than slots."""
    cfg, _, params = model_and_params
    prompts = _prompts(6, seed=2)
    budgets = [10, 3, 7, 12, 1, 5]
    futures = {i: _dense(cfg, params, p, n) for i, (p, n) in enumerate(zip(prompts, budgets))}
    server = _server(cfg, params, drafter=OracleDrafter(futures))
    outs = server.serve(prompts, max_new_tokens=budgets)
    off = _server(cfg, params).serve(prompts, max_new_tokens=budgets)
    for p, n, out, out_off in zip(prompts, budgets, outs, off):
        np.testing.assert_array_equal(out, _dense(cfg, params, p, n))
        np.testing.assert_array_equal(out, out_off)
    st = server.serve_stats()
    assert st["spec_rounds"] >= 1
    assert st["spec_accepted"] == st["spec_drafted"] > 0
    assert st["spec_accept_rate"] == 1.0
    # speculation finished the mix in fewer dispatches than one-per-token
    assert st["spec_rounds"] + st["decode_steps"] < sum(budgets)
    assert server.pool.used_pages() == 0 and server.pool.live_tokens() == 0


def test_spec_partial_acceptance_and_rejection(model_and_params):
    """Corrupted oracles pin the accepted prefix below the draft length;
    outputs must still be exact and the rejected tail's pages must all
    come back (the pool drains to zero)."""
    cfg, _, params = model_and_params
    prompts = _prompts(4, seed=3)
    futures = {i: _dense(cfg, params, p, 9) for i, p in enumerate(prompts)}
    for corrupt_at in (0, 2):
        server = _server(
            cfg, params, drafter=OracleDrafter(futures, corrupt_at=corrupt_at)
        )
        outs = server.serve(prompts, max_new_tokens=9)
        for p, out in zip(prompts, outs):
            np.testing.assert_array_equal(out, _dense(cfg, params, p, 9))
        st = server.serve_stats()
        assert st["spec_rounds"] >= 1
        assert st["spec_accepted"] < st["spec_drafted"]
        # a corrupted index caps every round's accepted prefix at that index
        assert all(
            n == 0 for i, n in enumerate(st["spec_accept_hist"]) if i > corrupt_at
        )
        assert server.pool.used_pages() == 0 and server.pool.live_tokens() == 0


def test_spec_ngram_serving_matches_dense(model_and_params):
    """The real model-free drafter end to end: long budgets let greedy
    outputs go periodic, so the n-gram lookup actually drafts — and the
    stream stays exact."""
    cfg, _, params = model_and_params
    server = _server(
        cfg, params,
        spec_decode={"enable": True, "max_draft": 4, "ngram_order": 3},
    )
    prompts = _prompts(4, seed=5, lo=4, hi=10)
    outs = server.serve(prompts, max_new_tokens=40)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _dense(cfg, params, p, 40))
    st = server.serve_stats()
    assert st["spec_rounds"] >= 1, "n-gram drafter never engaged"
    assert st["spec_accepted"] >= 1


def test_spec_admission_mid_stream(model_and_params):
    """Requests submitted while speculative rounds are in flight join
    without disturbing the streams."""
    cfg, _, params = model_and_params
    prompts = _prompts(4, seed=6)
    futures = {i: _dense(cfg, params, p, 12) for i, p in enumerate(prompts)}
    server = _server(cfg, params, drafter=OracleDrafter(futures))
    first = [server.submit(p, max_new_tokens=12) for p in prompts[:2]]
    for _ in range(3):
        server.step()
    assert server.stats["spec_rounds"] >= 1
    late = [server.submit(p, max_new_tokens=12) for p in prompts[2:]]
    results = server.run()
    for uid, p in zip(first + late, prompts):
        np.testing.assert_array_equal(results[uid], _dense(cfg, params, p, 12))


def test_spec_preemption_token_exact(model_and_params):
    """An undersized pool forces preemption while drafts are widening each
    row's page demand; recompute on re-admission must stay exact."""
    cfg, _, params = model_and_params
    prompts = _prompts(4, seed=4, lo=6, hi=14)
    futures = {i: _dense(cfg, params, p, 12) for i, p in enumerate(prompts)}
    server = _server(
        cfg, params, page_size=4, num_pages=14, max_slots=3, prefill_chunk=8,
        drafter=OracleDrafter(futures),
    )
    outs = server.serve(prompts, max_new_tokens=12)
    assert server.stats["preempted"] >= 1, "pool was sized to force preemption"
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _dense(cfg, params, p, 12))


def test_spec_eos_inside_accepted_run(model_and_params):
    """EOS landing inside an accepted draft run must retire the request at
    the EOS token exactly like sequential decode."""
    cfg, _, params = model_and_params
    prompts = _prompts(2, seed=7)
    futures = {i: _dense(cfg, params, p, 10) for i, p in enumerate(prompts)}
    # an EOS the oracle will draft: request 0's 3rd generated token
    eos = int(futures[0][prompts[0].size + 2])
    server = _server(cfg, params, drafter=OracleDrafter(futures))
    outs = server.serve(prompts, max_new_tokens=10, eos_token_id=eos)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _dense(cfg, params, p, 10, eos=eos))


def test_spec_draft_clamped_to_budget(model_and_params):
    """A drafter offering more than the remaining budget must be clamped:
    a 1-token request decodes plainly (no verify), and no output ever
    exceeds max_new_tokens."""
    cfg, _, params = model_and_params
    server = _server(cfg, params, drafter=ConstantDrafter(token=1))
    prompts = _prompts(3, seed=8)
    outs = server.serve(prompts, max_new_tokens=[1, 2, 6])
    for p, n, out in zip(prompts, [1, 2, 6], outs):
        np.testing.assert_array_equal(out, _dense(cfg, params, p, n))
        assert out.size == p.size + n


def test_spec_16_request_ragged_mix_under_pool_pressure(model_and_params):
    """The bench-shaped acceptance mix: 16 ragged requests through 4 slots
    with an undersized pool (preemption fires), speculation on — the
    stream must match speculation-off paged serving AND dense generate,
    request for request."""
    cfg, _, params = model_and_params
    prompts = _prompts(16, seed=14, lo=3, hi=12)
    budgets = [max(1, 10 - (i * 10) // 32) for i in range(16)]  # ragged
    futures = {
        i: _dense(cfg, params, p, n) for i, (p, n) in enumerate(zip(prompts, budgets))
    }
    kw = dict(page_size=4, num_pages=14, max_slots=4, prefill_chunk=8)
    spec = _server(cfg, params, drafter=OracleDrafter(futures, corrupt_at=1), **kw)
    outs = spec.serve(prompts, max_new_tokens=budgets)
    off = _server(cfg, params, **kw).serve(prompts, max_new_tokens=budgets)
    for i, (p, n, a, b) in enumerate(zip(prompts, budgets, outs, off)):
        np.testing.assert_array_equal(a, futures[i])
        np.testing.assert_array_equal(a, b)
    st = spec.serve_stats()
    assert st["finished"] == 16 and st["spec_rounds"] >= 1
    assert spec.stats["preempted"] >= 1, "pool was sized to force preemption"
    assert spec.pool.used_pages() == 0 and spec.pool.live_tokens() == 0


# --- dispatch & compile budget ----------------------------------------------
def test_one_dispatch_per_spec_round_and_compile_bound(model_and_params):
    """3-wave schedule through one telemetry: exactly one paged_ragged
    dispatch per scheduler step, speculative or not, and at most two
    compiled serving programs whatever the drafts' lengths, neither
    recompiled across the waves."""
    cfg, _, params = model_and_params
    telemetry = CompileTelemetry()
    waves = [_prompts(2, seed=10), _prompts(4, seed=11), _prompts(2, seed=12)]
    futures = {}
    uid = 0
    for wave in waves:
        for p in wave:
            futures[uid] = _dense(cfg, params, p, 6)
            uid += 1
    server = _server(
        cfg, params, max_slots=4, telemetry=telemetry,
        spec_decode={"max_draft": 4},
        drafter=OracleDrafter(futures),
    )
    budgets = [6, 3, 4, 5]  # the budget clamp: drafts of 4, 1, 2 and 3 tokens
    for wave in waves:
        outs = server.serve(wave, max_new_tokens=budgets[: len(wave)])
        for p, n, out in zip(wave, budgets, outs):
            np.testing.assert_array_equal(out, _dense(cfg, params, p, n))
    stats = telemetry.stats()
    paged = {k: v for k, v in stats.items() if k.startswith("paged_")}
    assert paged and all(k.startswith("paged_ragged_") for k in paged), list(stats)
    for name, rec in paged.items():
        assert rec["compiles"] <= 1, f"{name} recompiled: {rec}"
    # exactly ONE device dispatch per scheduler step; drafts of every length
    # rode the one narrow width
    assert server.stats["spec_rounds"] >= 3
    assert sum(r["dispatches"] for r in paged.values()) == server.stats["ragged_steps"]
    assert server.stats["dispatches"] == server.stats["ragged_steps"]
    # (the oracle's drafts are accepted whole: hist[n] counts drafts of n)
    assert all(server.stats["spec_accept_hist"][1:])
    # program count bounded by the two widths (1 + max_draft, the chunk)
    assert set(paged) <= {"paged_ragged_r4_w5", "paged_ragged_r4_w8"}
    assert compiled_serving_programs(stats) <= 2


def test_spec_round_pages_roll_back(model_and_params):
    """Pool accounting mid-stream: after a reject-all verify round the
    pool must hold exactly the accepted tokens (tail pages freed), not the
    full drafted width."""
    cfg, _, params = model_and_params
    server = _server(cfg, params, page_size=4, drafter=ConstantDrafter(token=3))
    prompt = _prompts(1, seed=13, lo=5, hi=6)[0]  # one prefill chunk
    uid = server.submit(prompt, max_new_tokens=12)
    server.step()  # the prompt's one chunk
    assert server.stats["spec_rounds"] == 0 and server.stats["prefill_chunks"] == 1
    server.step()  # the FIRST speculative round
    assert server.stats["spec_rounds"] == 1
    req = server._active[0]
    acc = server.stats["spec_accepted"]
    got = int(server.pool.seq_lens[req.slot])
    # live tokens = prompt + accepted drafts + bonus; the drafted-but-
    # rejected tail's pages are back in the free list
    assert got == prompt.size + acc + 1
    assert server.pool._owned[req.slot] == server.pool.pages_for(got)
    server.step()
    assert server.stats["spec_rounds"] == 2 and not req.done
    got2 = int(server.pool.seq_lens[req.slot])
    assert got2 == got + (server.stats["spec_accepted"] - acc) + 1
    assert server.pool._owned[req.slot] == server.pool.pages_for(got2)
    server.run()
    assert server.result(uid) is not None


# --- engine surface ----------------------------------------------------------
def test_engine_spec_serve_and_stats(model_and_params):
    """inference.spec_decode config knobs through init_inference: exact
    output, spec observability in engine.serve_stats()."""
    cfg, model, params = model_and_params
    engine = ds.init_inference(
        model,
        dtype="fp32",
        paged_kv={"page_size": 8, "max_slots": 4, "prefill_chunk": 8, "attn_impl": "xla"},
        spec_decode={"enable": True, "max_draft": 4, "ngram_order": 3},
    )
    engine.set_params(params)
    engine._ds_config = cfg  # converted-family contract (containers set this)
    prompts = _prompts(3, seed=9, lo=4, hi=10)
    outs = engine.serve(prompts, max_new_tokens=24)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _dense(cfg, params, p, 24))
    st = engine.serve_stats()
    for key in (
        "spec_rounds", "spec_accept_rate", "spec_mean_accepted_per_round",
        "spec_accept_hist", "pool_utilization",
    ):
        assert key in st, key
    assert st["finished"] == 3
    assert len(st["spec_accept_hist"]) == 5  # 0..max_draft
