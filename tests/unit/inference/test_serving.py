"""Continuous-batching paged-KV serving tests.

Load-bearing checks (ISSUE 2 acceptance): paged greedy decode is
token-exact against the dense lockstep ``decode.generate`` across varying
occupancy, mid-stream admission, and eviction/preemption; and over a
3-wave admit/finish/admit schedule the compile telemetry shows at most
two compiled serving programs, none recompiled, and exactly one
``paged_ragged_*`` dispatch per scheduler step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference import decode
from deepspeed_tpu.inference.scheduler import PagedServer, compiled_serving_programs
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


def test_paged_matches_dense_varying_occupancy(model_and_params):
    """More requests than slots, ragged prompt lengths, ragged budgets:
    every output must equal the request's standalone dense greedy decode."""
    cfg, _, params = model_and_params
    server = _server(cfg, params)
    prompts = _prompts(6, seed=2)
    budgets = [10, 3, 7, 12, 1, 5]
    outs = server.serve(prompts, max_new_tokens=budgets)
    for p, n, out in zip(prompts, budgets, outs):
        np.testing.assert_array_equal(out, _dense(cfg, params, p, n))
    assert server.stats["finished"] == 6
    # occupancy varied: 6 requests through 4 slots means a second wave
    assert server.stats["admitted"] == 6
    # pool fully drained once everything finished
    assert server.pool.used_pages() == 0 and server.pool.live_tokens() == 0


def test_admission_mid_stream(model_and_params):
    """Requests submitted while others are mid-decode join the running
    batch without disturbing in-flight sequences."""
    cfg, _, params = model_and_params
    server = _server(cfg, params)
    prompts = _prompts(4, seed=3)
    first = [server.submit(p, max_new_tokens=12) for p in prompts[:2]]
    for _ in range(4):  # prefill + a few decode steps for wave 1
        server.step()
    assert server.stats["decode_steps"] >= 2
    late = [server.submit(p, max_new_tokens=12) for p in prompts[2:]]
    results = server.run()
    for uid, p in zip(first + late, prompts):
        np.testing.assert_array_equal(results[uid], _dense(cfg, params, p, 12))


def test_eviction_preemption_is_token_exact(model_and_params):
    """An undersized pool forces preemption mid-stream; recompute on
    re-admission must reproduce the exact greedy continuation."""
    cfg, _, params = model_and_params
    server = _server(
        cfg, params, page_size=4, num_pages=14, max_slots=3, prefill_chunk=8
    )
    prompts = _prompts(4, seed=4, lo=6, hi=14)
    outs = server.serve(prompts, max_new_tokens=12)
    assert server.stats["preempted"] >= 1, "pool was sized to force preemption"
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _dense(cfg, params, p, 12))


def test_eos_finishes_request_early(model_and_params):
    cfg, _, params = model_and_params
    server = _server(cfg, params)
    prompts = _prompts(2, seed=5)
    # derive each prompt's first greedy token and use row 0's as "EOS"
    probe = _dense(cfg, params, prompts[0], 1)
    eos = int(probe[-1])
    outs = server.serve(prompts, max_new_tokens=10, eos_token_id=eos)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _dense(cfg, params, p, 10, eos=eos))
    # row 0 emitted eos immediately: prompt + the single eos token
    assert outs[0].size == prompts[0].size + 1 and outs[0][-1] == eos


def test_retrace_guard_and_single_dispatch_per_step(model_and_params):
    """3-wave admit/finish/admit schedule: at most two compiled serving
    programs (the narrow and the mixed width), none recompiled, and exactly
    one paged_ragged dispatch per scheduler step that had rows to serve."""
    cfg, _, params = model_and_params
    telemetry = CompileTelemetry()
    server = _server(cfg, params, max_slots=4, telemetry=telemetry)
    waves = [_prompts(2, seed=6), _prompts(4, seed=7), _prompts(2, seed=8)]
    for wave in waves:
        outs = server.serve(wave, max_new_tokens=6)
        for p, out in zip(wave, outs):
            np.testing.assert_array_equal(out, _dense(cfg, params, p, 6))
    stats = telemetry.stats()
    paged = {k: v for k, v in stats.items() if k.startswith("paged_")}
    assert paged and all(k.startswith("paged_ragged_") for k in paged), list(stats)
    for name, rec in paged.items():
        assert rec["compiles"] <= 1, f"{name} recompiled: {rec}"
    assert sum(rec["dispatches"] for rec in paged.values()) == server.stats["ragged_steps"]
    assert server.stats["dispatches"] == server.stats["ragged_steps"]
    assert server.stats["decode_steps"] >= 1 and server.stats["prefill_chunks"] >= 8
    # program count bounded by the two widths, not by traffic
    assert compiled_serving_programs(stats) <= 2


def test_engine_serve_and_compile_stats(model_and_params):
    """The engine-level surface: paged_kv config knobs, serve() (on the
    default RAGGED path), and the inference compile_stats() satellite
    (forward + decode loop programs)."""
    cfg, model, params = model_and_params
    engine = ds.init_inference(
        model,
        dtype="fp32",
        paged_kv={"page_size": 8, "max_slots": 4, "prefill_chunk": 8, "attn_impl": "xla"},
    )
    engine.set_params(params)
    engine._ds_config = cfg  # converted-family contract (containers set this)
    prompts = _prompts(3, seed=9)
    outs = engine.serve(prompts, max_new_tokens=6)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _dense(cfg, params, p, 6))
    stats = engine.compile_stats()
    assert any(k.startswith("paged_ragged_") for k in stats)
    sstats = engine.serve_stats()
    assert sstats["finished"] == 3 and sstats["decode_steps"] >= 1
    # acceptance: exactly ONE ragged dispatch per scheduler step, observed
    # through the engine's own compile_stats()
    assert sum(
        rec["dispatches"] for name, rec in stats.items()
        if name.startswith("paged_ragged_")
    ) == sstats["ragged_steps"]
    # satellite: the jitted forward and the kv decode loop are instrumented
    toks = jnp.asarray(np.stack([np.resize(prompts[0], 8)]))
    engine(toks)
    engine.generate(toks, max_new_tokens=4)
    stats = engine.compile_stats()
    assert stats["forward"]["dispatches"] >= 1
    assert "kv_prefill" in stats and "kv_decode_loop" in stats
    assert stats["kv_decode_loop"]["compiles"] <= 1


def test_no_serving_program_calls_the_flash_kernel(monkeypatch):
    """The paged programs attend through ``ragged_paged_attention`` whatever
    ``config.flash_attention`` says (the default: on): serving a dense model
    never reaches the training kernel, so a change to it cannot move a serving
    cell (ISSUE 33). The lowered text of both ``paged_ragged_*`` programs
    names no ``flash_fwd``, and the kernel's entry is not called while they
    are traced; the unpaged forward of the same engine does call it."""
    import sys

    import deepspeed_tpu.ops.transformer  # noqa: F401  (the package rebinds the module's name to the function)

    module = sys.modules["deepspeed_tpu.ops.transformer.flash_attention"]
    calls = []
    real = module.flash_attention

    def spy(*args, **kwargs):
        calls.append(args[0].shape)
        return real(*args, **kwargs)

    monkeypatch.setattr(module, "flash_attention", spy)
    cfg = TransformerConfig(**{**CFG, "flash_attention": True})
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert not calls  # drawing parameters does not trace the forward
    engine = ds.init_inference(model, dtype="fp32", paged_kv={"page_size": 8, "max_slots": 4, "prefill_chunk": 8})
    engine.set_params(params)
    engine._ds_config = cfg
    engine.serve(_prompts(3, seed=11), max_new_tokens=4)
    programs = [name for name in engine.compile_stats() if name.startswith("paged_ragged_")]
    assert len(programs) == 2, programs  # the narrow and the mixed width
    assert not calls
    for name in programs:
        text = engine.program_text(name)
        assert text and "flash_fwd" not in text and "flash_bwd" not in text, name
    engine(jnp.zeros((1, 16), jnp.int32))  # the control: the unpaged forward is the kernel's caller
    assert calls


def test_paged_matches_dense_gpt2_family():
    """Learned positions + tied embeddings + MHA (the gpt2 shape) through
    the paged path — per-row position gathers must stay exact."""
    from deepspeed_tpu.models.config import gpt2_config

    cfg = gpt2_config(
        "tiny", num_layers=2, max_seq_len=64, flash_attention=False,
        dtype="float32", vocab_size=128, hidden_size=64, num_heads=4,
    )
    model = TransformerLM(cfg)
    rs = np.random.RandomState(11)
    prompts = [rs.randint(0, cfg.vocab_size, (n,)).astype(np.int32) for n in (4, 11)]
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(prompts[0][None]))
    server = PagedServer(
        cfg, params, page_size=8, max_slots=2, prefill_chunk=8,
        attn_impl="xla", dtype=jnp.float32,
    )
    outs = server.serve(prompts, max_new_tokens=5)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _dense(cfg, params, p, 5))


def test_prefill_chunk_one_and_results_drain(model_and_params):
    """prefill_chunk=1 (the mixed width equals the narrow one: a chunk row
    and a decode row look alike but for their bookkeeping), and serve() must
    drain its results so a long-lived server never accumulates past
    outputs."""
    cfg, _, params = model_and_params
    server = _server(cfg, params, max_slots=1, prefill_chunk=1)
    prompts = _prompts(2, seed=12, lo=2, hi=4)
    outs = server.serve(prompts, max_new_tokens=2)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _dense(cfg, params, p, 2))
    assert server._results == {}  # drained by serve()
    with pytest.raises(ValueError, match="max_new_tokens"):
        server.serve(prompts, max_new_tokens=[2])


def test_prefill_pad_tail_never_aliases_live_pages():
    """Regression: a final prompt chunk whose pad positions run past the
    table width used to clamp onto the LAST live column and overwrite real
    prompt k/v (positions 112..127 -> table slot 7 -> clamped to column 6 =
    positions 96..111 here). Pad slots must write to the trash page."""
    cfg = TransformerConfig(**{**CFG, "max_seq_len": 112})
    model = TransformerLM(cfg)
    rs = np.random.RandomState(15)
    prompt = rs.randint(0, cfg.vocab_size, (104,)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(prompt[None, :8]))
    server = PagedServer(
        cfg, params, page_size=16, max_slots=2, prefill_chunk=32,
        attn_impl="xla", dtype=jnp.float32,
    )
    out = server.serve([prompt], max_new_tokens=8)[0]
    np.testing.assert_array_equal(out, _dense(cfg, params, prompt, 8))


def test_serve_rejects_oversized_requests(model_and_params):
    cfg, _, params = model_and_params
    server = _server(cfg, params)
    with pytest.raises(ValueError, match="max_seq_len"):
        server.submit(np.zeros(60, np.int32), max_new_tokens=10)
    # a request that could never fit the pool is rejected at submit, not
    # discovered by an unfixable preemption loop mid-stream
    tiny = PagedServer(
        cfg, params, page_size=4, num_pages=3, max_slots=2,
        prefill_chunk=8, attn_impl="xla", dtype=jnp.float32, max_seq_len=64,
    )
    with pytest.raises(ValueError, match="pages"):
        tiny.submit(np.zeros(4, np.int32), max_new_tokens=20)
