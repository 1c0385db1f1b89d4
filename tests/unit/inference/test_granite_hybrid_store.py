"""The per-slot state store under a second kind of recurrent state, the
engine's path for a model of state-space and attention layers with a dense FFN
in every scanned layer, and what goes on being refused for it.
``test_granite_hybrid_serving.py`` holds what is about logits (and the toy
model both files use); this file runs beside it on another worker.

The store learns ONE thing from the kind: its two arrays' shapes
(``hybrid_decode.state_shapes``). Slots, the spare entry, ``fresh``,
``state_bytes_per_slot`` and ``state_bytes_in_use`` are the kind's alike.
"""

import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference import decode, hybrid_decode
from deepspeed_tpu.inference.kv_pool import PagePool, heads_per_group, page_shapes
from deepspeed_tpu.inference.scheduler import PagedServer
from deepspeed_tpu.models import hybrid_moe as hm
from deepspeed_tpu.models.hybrid_moe import HybridMoETransformerLM, granite_hybrid_config, nemotron_h_config, solar_open2_config
from tests.unit.inference.test_granite_hybrid_serving import CHUNK, F32_TOL, MAXLEN, PAGE, SLOTS, reference_logits, toy_model
from tests.unit.inference.hybrid_toys import _clear_jax_caches, _compiled_programs_live_as_long_as_the_file  # noqa: F401 (the two fixtures are taken by their import)


@pytest.fixture(scope="module")
def toy():
    return toy_model()


@pytest.mark.parametrize("kind", ["linear", "ssm", "neither"])
def test_the_stores_shapes_are_the_kinds(kind):
    """``linear``: as it was (a square state a head, three convolved streams a
    head on the lanes); ``ssm``: ``[heads, head_dim, state]`` and one stream of
    ``[x ; B ; C]`` a lane tile a row in whole sublane tiles; a model with
    neither keeps the empty arrays it had. One slot index, one
    ``state_bytes_per_slot``."""
    if kind == "linear":
        cfg = solar_open2_config("tiny", dtype="float32")
        state, conv = (3, SLOTS + 1, 8, 16, 16), (3, SLOTS + 1, 3, 3, 8, 16)
    elif kind == "ssm":
        cfg = granite_hybrid_config("tiny", dtype="float32")
        state, conv = (4, SLOTS + 1, 2, 64, 128), (4, SLOTS + 1, 3, 16, 128)  # 384 channels: 3 lane tiles in 16 rows
    else:
        cfg = solar_open2_config("tiny", dtype="float32", layer_types=["softmax"] * 4)
        state, conv = (0, SLOTS + 1, 8, 16, 16), (0, SLOTS + 1, 3, 3, 8, 16)
    assert cfg.state_kind == (None if kind == "neither" else kind)
    assert tuple(hybrid_decode.state_shapes(cfg, SLOTS)) == (state, conv)
    pool = PagePool(cfg, 9, PAGE, SLOTS, max_seq_len=MAXLEN, dtype=jnp.float32)
    assert pool.states.state.shape == state and pool.states.conv.shape == conv and pool.states.state.dtype == jnp.float32
    a_slot = (int(np.prod(state[2:])) + int(np.prod(conv[2:]))) * 4 * state[0]
    assert pool.state_bytes_per_slot == a_slot and pool.state_kind == cfg.state_kind
    rep = pool.memory_report()
    assert rep["state_kind"] == cfg.state_kind and rep["state_shape"] == list(state[2:]) and rep["state_layers"] == state[0]
    assert rep["state_total_bytes"] == (SLOTS + 1) * a_slot and rep["state_bytes_in_use"] == 0
    slot = pool.alloc_slot(20)
    assert pool.memory_report()["state_bytes_in_use"] == a_slot == pool.cache_bytes()["state_bytes_in_use"]
    pool.free_slot(slot)
    pool.integrity_check()


def test_the_published_store_is_the_issues():
    cfg = granite_hybrid_config()
    shapes = hybrid_decode.state_shapes(cfg, 64)
    assert shapes.state == (36, 65, 64, 64, 128) and shapes.conv == (36, 65, 3, 48, 128)
    assert int(np.prod(shapes.state[2:])) * 4 == 2_097_152  # a row's state in one layer
    # the four attention layers' pages: 8 KV heads of 64, two a 128-lane page, the bytes of one a page
    f = heads_per_group(cfg.head_dim, cfg.v_head_dim, cfg.num_kv_heads)
    assert f == 2 and page_shapes(4, 1537, cfg.num_kv_heads, 64, cfg.head_dim, cfg.v_head_dim, f) == ((4, 1537, 4, 64, 128),) * 2
    assert heads_per_group(64, 64, 3) == heads_per_group(24, 24, 4) == heads_per_group(64, 128, 8) == heads_per_group(128, 128, 8) == 1
    assert (heads_per_group(32, 32, 4), heads_per_group(32, 32, 2), heads_per_group(16, 16, 8)) == (4, 1, 8)


def test_the_pool_holds_two_heads_of_64_a_page():
    """A pool of the published head width: ``heads_per_group`` 2, K and V
    ``[layers, pages, NKV / 2, P, 128]``, a token's bytes what they were, and
    whatever installs new arrays (``set_cache``) keeps the grouping."""
    cfg = granite_hybrid_config("tiny", dtype="float32", head_dim=64)
    pool = PagePool(cfg, 9, PAGE, SLOTS, max_seq_len=MAXLEN, dtype=jnp.float32)
    assert pool.cache.heads_per_group == 2
    assert pool.cache.k_pages.shape == pool.cache.v_pages.shape == (2, 9, 1, PAGE, 128)
    assert pool.cache.bytes_per_token == 2 * 2 * cfg.num_kv_heads * 64 * 4
    pool.set_cache(pool.cache.k_pages + 1, pool.cache.v_pages)
    assert pool.cache.heads_per_group == 2 and pool.cache.page_size == PAGE
    narrow = PagePool(granite_hybrid_config("tiny", dtype="float32"), 9, PAGE, SLOTS, max_seq_len=MAXLEN, dtype=jnp.float32)
    assert narrow.cache.heads_per_group == 1 and narrow.cache.k_pages.shape == (2, 9, 2, PAGE, 16)  # 2 heads of 16: no whole lane tile


@pytest.mark.parametrize("what", ["both_kinds", "two_groups", "ragged_channels", "no_sizes", "experts_none_but_a_leading_layer", "experts_none_but_a_shared_one",
                                  "an_ffn_block_with_no_width", "ffn_blocks_only", "ffn_blocks_behind_a_leading_layer", "an_unknown_activation"])
def test_what_the_config_refuses_it_names(what):
    make = {
        "both_kinds": (NotImplementedError, "ONE kind of", lambda: granite_hybrid_config("tiny", layer_types=["ssm", "linear", "softmax"] * 2)),
        # two heads in two groups are served since PR 59; heads that are no whole number of groups are still refused
        "two_groups": (ValueError, "ssm_groups=2", lambda: granite_hybrid_config("tiny", ssm_num_heads=3, ssm_groups=2)),
        "ragged_channels": (ValueError, "whole lane tiles", lambda: granite_hybrid_config("tiny", ssm_head_dim=48)),
        "no_sizes": (ValueError, "needs ssm_num_heads", lambda: granite_hybrid_config("tiny", ssm_state=0)),
        "experts_none_but_a_leading_layer": (ValueError, "num_experts=0", lambda: granite_hybrid_config("tiny", leading_dense_layers=1)),
        "experts_none_but_a_shared_one": (ValueError, "num_experts=0", lambda: granite_hybrid_config("tiny", moe_shared_experts=1)),
        # what a list that names FFN blocks (a block is ONE sublayer) may not say
        "an_ffn_block_with_no_width": (ValueError, "needs its width", lambda: granite_hybrid_config("tiny", intermediate_size=None, layer_types=["ssm", "ffn", "softmax"] * 2)),
        "ffn_blocks_only": (ValueError, "FFN blocks only", lambda: granite_hybrid_config("tiny", layer_types=["ffn"] * 6)),
        "ffn_blocks_behind_a_leading_layer": (ValueError, "no leading_dense_layers", lambda: nemotron_h_config("tiny", leading_dense_layers=1)),
        "an_unknown_activation": (ValueError, "relu2", lambda: nemotron_h_config("tiny", activation="geglu")),
    }
    error, match, build = make[what]
    with pytest.raises(error, match=match):
        build()


def _server(lm, params, **kw):
    eng = ds.init_inference(lm, dtype="fp32", paged_kv={"page_size": PAGE, "max_slots": SLOTS, "prefill_chunk": CHUNK, "max_seq_len": MAXLEN, **kw})
    eng.set_params(params)
    return eng


def test_the_engine_serves_it_with_two_programs_and_preemption_changes_nothing(toy):
    """``init_inference`` -> ``serve``: two compiled programs, narrow and
    mixed steps, six requests on four slots (a slot reused by a second
    request); no routing row on the step's result and no ``moe_`` counter;
    the memory report names the kind and its state's shape; and with a pool
    too small for its rows (rows preempted in the middle and re-admitted from
    position 0) the streams are those of a pool that never preempts."""
    cfg, lm, params, section = toy
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (37, 5, 20, 16, 50, 3)]
    budgets = [40, 20, 9, 46, 7, 30]
    eng = _server(lm, params)
    outs = eng.serve(prompts, max_new_tokens=budgets)
    assert sorted(eng.compile_stats()) == ["paged_ragged_r4_w1", "paged_ragged_r4_w16"]
    stats = eng._paged_server.stats
    assert stats["preempted"] == 0 and stats["finished"] == 6 and not any(k.startswith("moe_") for k in stats)
    assert stats["prefill_chunks"] > 0 and stats["ragged_steps"] > stats["prefill_chunks"]  # mixed steps and narrow ones
    pool = eng._paged_server.pool
    assert pool.states.state.shape == (4, SLOTS + 1, 2, 64, 128) and pool.cache.k_pages.shape[0] == 2
    by_name = {b["name"]: b for b in eng.memory_report(enforce=False)["entries"]}
    state = by_name["recurrent_state"]
    assert state["per_chip_bytes"] == pool.states.state.nbytes + pool.states.conv.nbytes
    assert state["detail"]["state_kind"] == "ssm" and state["detail"]["state_shape"] == [2, 64, 128] and state["detail"]["state_layers"] == 4
    # every served token is the reference's arg-max at its position (float32, no near-tie at this size)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        lg = reference_logits(section, params, o)
        gap = lg[p.size - 1 : o.size - 1].max(-1) - np.take_along_axis(lg[p.size - 1 : o.size - 1], o[p.size :, None], -1)[:, 0]
        assert gap.max() < F32_TOL, i
    tight = _server(lm, params, num_pages=14)
    squeezed = tight.serve(prompts, max_new_tokens=budgets)
    assert tight._paged_server.stats["preempted"] > 0
    for a, b in zip(outs, squeezed):
        assert np.array_equal(a, b)


def test_the_step_span_keeps_the_state_bytes_true_for_the_kind(toy):
    """``serve.step`` carries ``state_bytes_in_use`` (slots in use x a slot's
    states and tails over the four state-space layers) and ``serve.pack``
    the rows; the gauge of slots in use follows."""
    from deepspeed_tpu.profiling.tracer import Tracer

    cfg, _, params, _ = toy
    tracer = Tracer()
    srv = PagedServer(cfg, params, page_size=PAGE, max_slots=SLOTS, prefill_chunk=CHUNK, max_seq_len=MAXLEN, tracer=tracer)
    assert srv.pool.cache_bytes() == {"state_bytes_in_use": 0, "latent_bytes_in_use": 0}
    srv.submit(np.arange(11, dtype=np.int32), max_new_tokens=8)
    srv.submit(np.arange(3, dtype=np.int32), max_new_tokens=2)
    while srv.has_work():
        srv.step()
    steps = [s["attrs"] for s in tracer.spans() if s["name"] == "serve.step"]
    a_slot = 4 * (2 * 64 * 128 * 4 + 3 * 16 * 128 * 4)  # float32 state and, in a float32 engine, float32 tails
    assert srv.pool.state_bytes_per_slot == a_slot
    assert steps[0]["state_bytes_in_use"] == 0 and steps[1]["state_bytes_in_use"] == 2 * a_slot
    assert {s["state_bytes_in_use"] for s in steps} == {0, a_slot, 2 * a_slot}  # the short row leaves, the other runs on
    assert all(s["latent_bytes_in_use"] == 0 for s in steps)
    assert srv.pool.cache_bytes()["state_bytes_in_use"] == 0


FEATURES = ["prefix_cache", "forks", "spec_decode", "generate", "beam_generate", "rollback", "attach_prefix", "train", "tensor_parallel"]


@pytest.mark.parametrize("feature", FEATURES)
def test_what_needs_a_state_snapshot_is_refused(toy, feature):
    """Each raises where it is built, as for the delta-rule layers: the store
    keeps a row's state at its newest position only. (A fork is a shared page
    written to: pages are shared through the prefix cache alone, whose
    refusal names it.)"""
    cfg, lm, params, _ = toy
    tokens = np.arange(8, dtype=np.int32)[None]
    kw = dict(page_size=PAGE, max_slots=SLOTS, prefill_chunk=CHUNK, max_seq_len=MAXLEN)
    calls = {
        "prefix_cache": lambda: PagedServer(cfg, params, prefix_cache=True, **kw),
        "forks": lambda: PagedServer(cfg, params, prefix_cache=True, **kw),
        "spec_decode": lambda: PagedServer(cfg, params, spec_decode={"enable": True}, **kw),
        "generate": lambda: decode.generate(cfg, params, tokens, 4),
        "beam_generate": lambda: decode.beam_generate(cfg, params, tokens, 4, num_beams=2),
        "rollback": lambda: PagedServer(cfg, params, **kw).pool.rollback(0, 1),
        "attach_prefix": lambda: PagedServer(cfg, params, **kw).pool.alloc_slot(8, prefix_tokens=tokens[0]),
        "train": lambda: lm.apply(params, (tokens, tokens), train=True),
        "tensor_parallel": lambda: decode.build_ragged_step(cfg, SLOTS, 1, PAGE, attn_impl="xla", tp=SimpleNamespace(degree=2, quantized_allreduce=False, quantized_weights=False, comm_chunks=2, cache_key=lambda: 2)),
    }
    with pytest.raises(NotImplementedError, match="copy-on-write forks" if feature == "forks" else "state|not supported"):
        calls[feature]()


def _jaxpr_of_a_step(cfg, monkeypatch=None):
    params = jax.eval_shape(lambda: HybridMoETransformerLM(cfg).init(jax.random.PRNGKey(0), None))
    pool = PagePool(cfg, 9, PAGE, SLOTS, max_seq_len=MAXLEN, dtype=jnp.float32)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    forward = lambda p: hybrid_decode.hybrid_forward(
        cfg, p, i32(SLOTS, 1), pool.cache.k_pages, pool.cache.v_pages, pool.states.state, pool.states.conv, i32(SLOTS, MAXLEN // PAGE), i32(SLOTS), i32(SLOTS), i32(SLOTS), attn_impl="xla"
    )
    return str(jax.make_jaxpr(forward)(params))


def test_at_one_the_multipliers_trace_nothing(monkeypatch):
    """An existing configuration's step (Solar-Open2's toy: softmax and linear
    layers, a routed FFN) traces to the same text whether ``scaled`` is called
    or is not there at all, and its head has no multiply; with the multipliers
    set, each adds its own."""
    cfg = solar_open2_config("tiny", dtype="float32")
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.logits_scaling) == (1.0, 1.0, 1.0)
    traced = _jaxpr_of_a_step(cfg)
    monkeypatch.setattr(hm, "scaled", lambda x, by: x)  # what the program was before it knew of multipliers
    assert _jaxpr_of_a_step(cfg) == traced
    monkeypatch.undo()
    x = jnp.zeros((2, 1, cfg.hidden_size), jnp.float32)
    params = {"final_norm_scale": jnp.ones(cfg.hidden_size), "lm_head": jnp.zeros((cfg.hidden_size, cfg.vocab_size))}
    head = lambda c: str(jax.make_jaxpr(lambda p, x: decode._final_logits(c, p, x))(params, x))
    assert head(dataclasses.replace(cfg, logits_scaling=8.0)).count(" mul ") == head(cfg).count(" mul ") + 1
    for name in ("embedding_multiplier", "residual_multiplier"):
        assert _jaxpr_of_a_step(dataclasses.replace(cfg, **{name: 3.0})).count(" mul ") > traced.count(" mul "), name
