"""OLMoE through the program against its plain reference
(``benchmark/reference/olmoe_decoder.py``), on the CPU at a small size
(8 experts, H 64, 2 layers, QK-norm, untied head), seeded random weights.

(a) the training forward: logits and loss; (b) serving: chunked prefill and
then decoding through the paged cache, step by step, against the reference's
ONE full forward, logits and not tokens; the dead slots of a ragged window
are not routed; a shifting routing mix compiles nothing; a dense model's step
is as it was.

Tolerances. Both sides in float32 differ by the order of float32 sums: 2e-4
on logits of order 1 (measured 1e-6). The bfloat16 program against the
float32 reference on the same (bfloat16-rounded) weights: measured over
three seeds worst 0.04-0.08, mean 0.0025-0.0027; the limits are 0.16 (twice
the worst) and 0.008 (three times the mean), and each of a dropped expert
(mean 0.016-0.020), renormalised gates (0.032-0.038) and un-normed q and k
(0.14-0.15) moves the mean past 0.008, so the limit catches any of them.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.reference import olmoe_decoder
from deepspeed_tpu.inference import decode
from deepspeed_tpu.models import MoETransformerLM, TransformerLM, olmoe_config
from deepspeed_tpu.models.config import TransformerConfig
from deepspeed_tpu.models.moe_transformer import MoETransformerConfig

KWARGS = dict(
    vocab_size=512, hidden_size=64, intermediate_size=32, num_layers=2, num_heads=4, num_kv_heads=4, head_dim=16, max_seq_len=256,
    norm="rmsnorm", norm_eps=1e-5, position="rope", rope_theta=10000, activation="swiglu", use_bias=False, tie_embeddings=False,
    qk_norm="projection", num_experts=8, moe_top_k=3, moe_layer_freq=1, moe_drop_tokens=False, moe_norm_topk_prob=False,
)
ROUTINGS = [(1, False), (2, False), (2, True), (3, False), (3, True), (8, False), (8, True)]
PAGE, MAXP, ROWS, CHUNK = 8, 6, 4, 16  # a pool of 4 rows x 48 tokens


def build(k=3, norm=False, dtype="float32", **over):
    kwargs = dict(KWARGS, moe_top_k=k, moe_norm_topk_prob=norm, dtype=dtype, **over)
    model = MoETransformerLM(MoETransformerConfig(**kwargs, remat=False, flash_attention=False))
    return model, {"kwargs": kwargs}


@functools.lru_cache(maxsize=None)
def weights(seed, dtype=jnp.float32):
    """Seeded weights of the small model (the routing's k and normalisation
    change no shape, so every case shares them), made in one jitted call.
    ``init`` leaves norm scales at 1: every leaf is perturbed so that a
    dropped scale (the q and k norms among them) would show."""

    @jax.jit
    def make(key):
        params = build()[0].init(key, np.zeros((1, 8), np.int32))
        leaves, tree = jax.tree_util.tree_flatten(params)
        keys = jax.random.split(jax.random.fold_in(key, 1), len(leaves))
        return jax.tree_util.tree_unflatten(tree, [(a + 0.05 * jax.random.normal(k, a.shape)).astype(dtype) for a, k in zip(leaves, keys)])

    return make(jax.random.PRNGKey(seed))


def tokens_of(shape, seed=0):
    return np.random.default_rng(seed).integers(0, KWARGS["vocab_size"], shape, dtype=np.int32)


# --- (a) the training forward ------------------------------------------------


@pytest.mark.parametrize("k, norm", ROUTINGS)
def test_training_forward_matches_the_reference(k, norm):
    model, section = build(k, norm)
    tokens = tokens_of((2, 25))
    params = weights(1)
    batch = {"input_ids": tokens[:, :-1], "labels": tokens[:, 1:]}

    @jax.jit
    def forward(p):
        return model.apply(p, batch["input_ids"], train=False), model.apply(p, batch, train=False), model.apply(p, batch, train=True)

    with jax.default_matmul_precision("highest"):
        ours, eval_loss, train_loss = (np.asarray(a) for a in forward(params))
    ref = np.asarray(olmoe_decoder.logits(section, params, tokens[:, :-1]))
    np.testing.assert_allclose(ours, ref, atol=2e-4, rtol=0)
    assert np.abs(ref).max() > 0.1  # not a comparison of zeros
    ref_loss = float(olmoe_decoder.loss(section, params, tokens))
    assert eval_loss == pytest.approx(ref_loss, abs=2e-4)
    # in training the router's auxiliary term, scaled by moe_aux_loss_coef (0.01), comes on top of the
    # reference's cross-entropy: positive, and below 0.01 x (E = 8) a layer
    assert 0.0 < train_loss - ref_loss < 0.01 * 8 * 2


def test_the_olmoe_preset_holds_the_published_keys():
    cfg = olmoe_config()
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (16, 2048, 16, 16, 128)
    assert (cfg.num_experts, cfg.moe_top_k, cfg.expert_intermediate_size, cfg.moe_norm_topk_prob) == (64, 8, 1024, False)
    assert (cfg.vocab_size, cfg.max_seq_len, cfg.rope_theta, cfg.norm_eps, cfg.tie_embeddings) == (50304, 4096, 10000.0, 1e-5, False)
    assert cfg.qk_norm == "projection" and cfg.moe_drop_tokens is False and cfg.use_bias is False
    shapes = jax.eval_shape(lambda: MoETransformerLM(cfg).init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes)) == 6_919_161_856  # 6.92 B
    assert shapes["layers"]["q_norm_scale"].shape == (16, 2048) and shapes["layers"]["moe"]["gate"]["wg"].dtype == jnp.float32
    assert MoETransformerLM(cfg).keep_fp32_params(shapes)["layers"]["moe"]["gate"]["wg"] is True  # float32 in training


def test_top_k_above_two_is_dropless_only():
    with pytest.raises(ValueError, match="moe_drop_tokens"):
        MoETransformerConfig(**dict(KWARGS, moe_drop_tokens=True))
    with pytest.raises(ValueError, match="qk_norm"):
        TransformerConfig(qk_norm="per_head")


# --- (b) serving through the paged cache -------------------------------------


def ragged_forward(cfg, width):
    """``decode._paged_forward``'s ragged entry (what ``build_ragged_step``
    wraps), jitted, returning the logits instead of their argmax."""

    @jax.jit
    def forward(params, window, kp, vp, table, lengths, q_lens):
        positions = lengths[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
        kv_lens = jnp.where(q_lens > 0, lengths + q_lens, 0)
        return decode._paged_forward(cfg, params, window, kp, vp, table, positions, None, "xla",
                                     prefill_kv_lens=kv_lens, ragged_q_lens=q_lens)

    return forward


def paged_logits(cfg, params, tokens, prompt_len, dtype=jnp.float32, dead_fill=0):
    """``tokens`` [2, T] through ``decode._paged_forward``'s ragged entry in a
    4-row window whose rows 1 and 3 are live: the prompt in chunks of 16 (the
    last one part dead slots), then one token a step, each step feeding the
    sequence's own next token. Dead rows and dead slots hold ``dead_fill``.
    Returns the logits of every position [2, T, V] and, per step, the
    per-layer expert counts."""
    T = tokens.shape[1]
    live_rows = np.array([1, 3])
    pool_shape = (cfg.num_layers, ROWS * MAXP + 1, cfg.num_kv_heads, PAGE, cfg.head_dim)
    kp, vp = jnp.zeros(pool_shape, dtype), jnp.zeros(pool_shape, dtype)
    table = np.zeros((ROWS, MAXP), np.int32)  # dead rows point at the trash page 0
    for r in live_rows:
        table[r] = 1 + r * MAXP + np.arange(MAXP)
    out = np.zeros((2, T, cfg.vocab_size), np.float32)
    forward = {width: ragged_forward(cfg, width) for width in (CHUNK, 1)}
    counts, done = [], 0
    while done < T:
        width = CHUNK if done < prompt_len else 1
        real = min(width, prompt_len - done) if done < prompt_len else 1
        window = np.full((ROWS, width), dead_fill, np.int32)
        window[live_rows, :real] = tokens[:, done : done + real]
        lengths = np.zeros(ROWS, np.int32)
        lengths[live_rows] = done
        q_lens = np.zeros(ROWS, np.int32)
        q_lens[live_rows] = real
        logits, kp, vp, moe_counts = forward[width](params, window, kp, vp, table, lengths, q_lens)
        out[:, done : done + real] = np.asarray(logits, np.float32)[live_rows, :real]
        counts.append(np.asarray(moe_counts))
        done += real
    return out, counts


@pytest.mark.parametrize("k, norm", ROUTINGS)
def test_prefill_then_decode_through_the_cache_matches_the_reference(k, norm):
    model, section = build(k, norm)
    tokens = tokens_of((2, 40), seed=3)
    params = weights(2)
    with jax.default_matmul_precision("highest"):
        ours, counts = paged_logits(model.config, params, tokens, prompt_len=24)  # chunks of 16 and 8, then 16 decode steps
    ref = np.asarray(olmoe_decoder.logits(section, params, tokens))
    np.testing.assert_allclose(ours, ref, atol=2e-4, rtol=0)
    # only live tokens were routed: 2 rows x the step's real tokens x k, in every layer
    for step_counts, real in zip(counts, [16, 8] + [1] * 16):
        assert step_counts.shape == (2, 8) and (step_counts.sum(axis=1) == 2 * real * k).all()


def test_the_bfloat16_program_stays_within_a_limit_that_a_wrong_block_fails():
    model, section = build(dtype="bfloat16")
    tokens = tokens_of((2, 40), seed=6)
    params = weights(3, jnp.bfloat16)
    ref = np.asarray(olmoe_decoder.logits(section, params, tokens))

    def gap(cfg):
        ours, _ = paged_logits(cfg, params, tokens, prompt_len=24, dtype=jnp.bfloat16)
        return np.abs(ours - ref).max(), np.abs(ours - ref).mean()

    worst, mean = gap(model.config)
    assert worst < 0.16 and mean < 0.008, (worst, mean)
    for wrong in (dict(moe_top_k=2), dict(moe_norm_topk_prob=True), dict(qk_norm=None)):
        _, wrong_mean = gap(dataclasses.replace(model.config, **wrong))
        assert wrong_mean > 0.008, (wrong, wrong_mean)


def test_dead_slots_are_not_routed_and_cannot_move_a_live_logit():
    model, _ = build()
    tokens = tokens_of((2, 30), seed=9)
    params = weights(2)
    a, counts_a = paged_logits(model.config, params, tokens, prompt_len=20, dead_fill=0)
    b, counts_b = paged_logits(model.config, params, tokens, prompt_len=20, dead_fill=311)
    np.testing.assert_array_equal(a, b)
    for ca, cb in zip(counts_a, counts_b):
        np.testing.assert_array_equal(ca, cb)


def serve_engine(model, params, max_slots=16):
    engine = ds.init_inference(
        model, dtype="fp32",
        paged_kv={"page_size": PAGE, "max_slots": max_slots, "prefill_chunk": CHUNK, "num_pages": 0, "max_seq_len": 96, "attn_impl": "xla"},
    )
    engine.set_params(params)
    return engine


def server_of(engine):
    server = engine._paged_server
    return getattr(server, "server", server)


def test_a_step_with_two_live_rows_of_sixteen_reports_its_live_assignments():
    model, _ = build()
    prompts = [tokens_of(21, seed=12), tokens_of(5, seed=13)]
    params = weights(1)
    engine = serve_engine(model, params)
    outs = engine.serve(prompts, max_new_tokens=[4, 7])
    assert [np.asarray(o).size for o in outs] == [25, 12]
    stats = server_of(engine).stats
    # every prompt token and every generated token but a stream's last went through the model
    # once: 2 of 16 rows live, so 14 rows (and the mixed window's dead slots) were never routed
    live_tokens = (21 + 3) + (5 + 6)
    assert stats["moe_assignments"] == live_tokens * 3 * 2  # x k x layers
    assert 0 < stats["moe_experts_hit"] <= stats["ragged_steps"] * 2 * 8
    assert 1 <= stats["moe_max_expert_load"] <= 16 * 3  # a mixed step's live tokens x ... at most
    assert set(engine.compile_stats()) == {"paged_ragged_r16_w1", "paged_ragged_r16_w16"}


def test_a_shifting_routing_mix_compiles_nothing():
    model, _ = build()
    prompts = [tokens_of(21, seed=16), tokens_of(5, seed=17)]
    spread = weights(2)
    # a router of zeros gives every expert the same gate: top-k takes experts 0, 1, 2 for every token
    one_place = jax.tree_util.tree_map_with_path(lambda path, a: jnp.zeros_like(a) if path[-1].key == "wg" else a, spread)
    engine = serve_engine(model, one_place, max_slots=4)
    engine.serve(prompts, max_new_tokens=[4, 7])
    stats = server_of(engine).stats
    assert stats["moe_experts_hit"] == stats["ragged_steps"] * 2 * 3  # three experts a layer, whatever the step
    compiled = {name: rec["compiles"] for name, rec in engine.compile_stats().items()}
    assert compiled == {"paged_ragged_r4_w1": 1, "paged_ragged_r4_w16": 1}
    hit_before = stats["moe_experts_hit"]
    engine.set_params(spread)
    engine.serve(prompts, max_new_tokens=[4, 7])
    stats = server_of(engine).stats
    assert stats["moe_experts_hit"] - hit_before > stats["ragged_steps"] // 2 * 2 * 3  # now spread over more experts
    assert {name: rec["compiles"] for name, rec in engine.compile_stats().items()} == compiled


def test_a_dense_model_carries_none_of_it():
    dense = TransformerConfig(**{k: v for k, v in KWARGS.items() if not k.startswith("moe_") and k not in ("num_experts", "qk_norm")},
                              dtype="float32", remat=False, flash_attention=False)
    model = TransformerLM(dense)
    prompts = [tokens_of(21, seed=20), tokens_of(5, seed=21)]
    engine = ds.init_inference(
        model, dtype="fp32",
        paged_kv={"page_size": PAGE, "max_slots": 4, "prefill_chunk": CHUNK, "num_pages": 0, "max_seq_len": 96, "attn_impl": "xla"},
    )
    engine.set_params(jax.jit(model.init)(jax.random.PRNGKey(22), prompts[0][None]))
    engine.serve(prompts, max_new_tokens=[4, 7])
    assert not [k for k in server_of(engine).stats if k.startswith("moe")]

    def result_shape(model_, cfg):
        params = jax.eval_shape(lambda: model_.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))
        pool = jax.ShapeDtypeStruct((cfg.num_layers, 4 * MAXP + 1, cfg.num_kv_heads, PAGE, cfg.head_dim), jnp.float32)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        step = decode.build_ragged_step(cfg, 4, 1, PAGE, attn_impl="xla")
        return jax.eval_shape(step, params, i32(4, 1), pool, pool, i32(4, MAXP), i32(4), i32(4))[0].shape

    assert result_shape(model, dense) == (4, 2)  # [rows, width + 1], as before
    moe, _ = build()
    assert result_shape(moe, moe.config) == (4 + decode.MOE_STAT_ROWS, 2)
