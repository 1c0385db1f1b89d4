"""A model of Mamba-2 state-space layers beside softmax-attention ones
through the paged server (``inference/hybrid_decode.py``): the state-space
layers' states ``[heads, head_dim, state]`` and ONE convolved stream's tails
on the per-slot state store at the kind's shapes, a DENSE FFN in every scanned
layer (``num_experts`` 0: no router, no routing rows on the step's result),
the scalar multipliers (embedding, both residual branches, logits), NoPE GQA
with a softmax scale of its own. Everything is compared with the plain
reference (``benchmark/reference/granite_hybrid_decoder.py``: float32, the
recurrence token by token, full causal attention) on seeded weights at a toy
size, two periods of ``[ssm, ssm, softmax]``, LOGITS and not tokens.

Tolerances. The toy model runs in float32 on the CPU, where the program and
the reference differ by the order of their sums and by the chunk form of the
recurrence: logits of standard deviation ~0.02 (the head divides by 8) agree
to 2e-8 (measured); the limit is 1e-6, and every wrong block below moves them
by more than a hundred times that. The bfloat16 run keeps float32 state but
rounds every activation to 8 bits of significand: its limit is 4e-3 (measured
1e-3).

This file holds what is about logits; ``test_granite_hybrid_store.py`` holds
the store, the engine and the refusals, so that the two spread over workers.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.files import load_module
from deepspeed_tpu.inference import decode, hybrid_decode
from deepspeed_tpu.inference.kv_pool import PagePool
from deepspeed_tpu.models import hybrid_moe as hm
from deepspeed_tpu.models.hybrid_moe import HybridMoETransformerLM, granite_hybrid_config
from deepspeed_tpu.ops.transformer import state_space
from tests.unit.inference.hybrid_toys import _clear_jax_caches, _compiled_programs_live_as_long_as_the_file, apply_logits, seeded  # noqa: F401 (the two fixtures are taken by their import)

REFERENCE = load_module("reference", "granite_hybrid_decoder")
PAGE, SLOTS, CHUNK, MAXLEN = 8, 4, 16, 96
F32_TOL = 1e-6


def toy_model(dtype="float32", **kw):
    """(config, model, parameters, the reference's ``model`` section): one
    jitted ``init``, then trained-like scales on three leaves: init's 0.02 makes
    the attention's softmax flat and the state-space layers' x, B and C so
    small that the state is a hundredth of ``D x``, and a state dropped, a
    wrong scale or a missing bias would hide in either."""
    cfg = granite_hybrid_config("tiny", dtype=dtype, **kw)
    lm = HybridMoETransformerLM(cfg)
    params = seeded(lm)
    params["periods"]["softmax"]["wq"] = params["periods"]["softmax"]["wq"] * 40.0
    params["periods"]["softmax"]["wk"] = params["periods"]["softmax"]["wk"] * 8.0
    params["periods"]["ssm"]["w_xbc"] = params["periods"]["ssm"]["w_xbc"] * 8.0
    section = {"kwargs": {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}}
    return cfg, lm, params, section


_FORWARDS = {}  # (id of the config, ssd_decode's form, token tile) -> (the config, kept alive for its id; its jitted forward)


class Driver:
    """Rows stepped by hand through ``hybrid_forward``: what the scheduler
    does, with the logits kept."""

    def __init__(self, cfg, params, dtype=jnp.float32, impl="xla"):
        self.cfg, self.params = cfg, params
        maxp = MAXLEN // PAGE
        pool = PagePool(cfg, SLOTS * maxp + 1, PAGE, SLOTS, max_seq_len=MAXLEN, dtype=dtype)
        self.pools = [pool.cache.k_pages, pool.cache.v_pages, pool.states.state, pool.states.conv]
        self.table = np.stack([1 + s * maxp + np.arange(maxp) for s in range(SLOTS)]).astype(np.int32)
        self.lengths = np.zeros(SLOTS, np.int32)
        key = (id(cfg), impl, decode.token_tile(cfg))  # drivers of one model share its two compiled programs
        if key not in _FORWARDS:
            _FORWARDS[key] = (cfg, jax.jit(lambda p, *a: hybrid_decode.hybrid_forward(cfg, p, *a, attn_impl="xla")))
        self.forward = _FORWARDS[key][1]

    def step(self, windows, width):
        """``windows``: {slot: tokens}; the rows are laid out in a shuffled
        order so that row and slot differ. Returns {slot: logits [n, V]}."""
        order = sorted(windows, key=lambda s: (s * 7) % 5)
        tokens = np.zeros((SLOTS, width), np.int32)
        q_lens = np.zeros(SLOTS, np.int32)
        slots = np.full(SLOTS, SLOTS, np.int32)
        table = np.full_like(self.table, -1)
        lengths = np.zeros(SLOTS, np.int32)
        for r, s in enumerate(order):
            w = np.asarray(windows[s], np.int32)
            tokens[r, : w.size], q_lens[r], slots[r], table[r], lengths[r] = w, w.size, s, self.table[s], self.lengths[s]
        logits, *self.pools, _ = self.forward(self.params, tokens, *self.pools, table, lengths, q_lens, slots)
        out = {}
        for r, s in enumerate(order):
            out[s] = np.asarray(logits[r, : q_lens[r]], np.float32)
            self.lengths[s] += q_lens[r]
        return out

    def run(self, seqs, decode_from):
        """Each slot's sequence: prefill ``[: decode_from[s]]`` in chunks of
        CHUNK beside whatever else is running, then one token a step. A row
        that has finished leaves the others running.
        Returns {slot: logits [len, V]}."""
        got = {s: [] for s in seqs}
        done = {s: 0 for s in seqs}
        while any(done[s] < len(seqs[s]) for s in seqs):
            windows = {}
            for s, seq in seqs.items():
                if done[s] >= len(seq):
                    continue
                n = min(CHUNK, decode_from[s] - done[s]) if done[s] < decode_from[s] else 1
                windows[s] = seq[done[s] : done[s] + n]
            wide = any(len(w) > 1 for w in windows.values())
            for s, lg in self.step(windows, CHUNK if wide else 1).items():
                got[s].append(lg)
                done[s] += lg.shape[0]
        return {s: np.concatenate(v) for s, v in got.items()}


def sequences(seed=0, lens=(61, 5, 40, 27)):
    rng = np.random.default_rng(seed)
    return {s: rng.integers(0, 512, n).astype(np.int32) for s, n in enumerate(lens)}


@pytest.fixture(scope="module")
def toy():
    return toy_model()


def reference_logits(section, params, seq):
    """The reference's logits [len, V] of one sequence, computed at MAXLEN
    (padded behind: the model is causal, so what follows a position does not
    move its logits), so that its jitted parts compile for one length."""
    padded = np.zeros((1, MAXLEN), np.int32)
    padded[0, : seq.size] = seq
    return np.asarray(REFERENCE.logits(section, params, padded))[0, : seq.size]


def test_the_preset_is_the_published_model():
    cfg = granite_hybrid_config()
    assert (cfg.num_layers, cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.tie_embeddings) == (40, 2048, 8192, 100352, True)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.attn_softmax_scale, cfg.position) == (32, 8, 64, 0.015625, "none")
    assert (cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_conv_kernel) == (64, 64, 128, 1, 4)
    assert (cfg.ssm_inner, cfg.ssm_conv_channels) == (4096, 4352)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.logits_scaling) == (12.0, 0.22, 8.0)
    assert [i for i, t in enumerate(cfg.layer_types) if t == "softmax"] == [5, 15, 25, 35] and cfg.layers_of("ssm") == 36
    # four whole periods of ten, [m m m m m A m m m m]; no expert, no leading layer: a dense FFN in every scanned layer
    assert cfg.period == ("ssm",) * 5 + ("softmax",) + ("ssm",) * 4 and cfg.num_periods == 4
    assert (cfg.num_experts, cfg.num_moe_layers, cfg.leading_dense_layers, cfg.state_kind) == (0, 0, 0, "ssm")
    shapes = jax.eval_shape(lambda: HybridMoETransformerLM(cfg).init(jax.random.PRNGKey(0), None))
    assert set(shapes["periods"]) == {"ssm", "softmax", "ffn"} and "leading" not in shapes and "lm_head" not in shapes
    assert shapes["periods"]["ffn"]["w_gate"].shape == (4, 10, 2048, 8192) and shapes["periods"]["ssm"]["w_xbc"].shape == (4, 9, 2048, 4352)
    per_layer = lambda tree: sum(a.size for a in jax.tree_util.tree_leaves(tree)) // (tree["attn_norm_scale"].shape[0] * tree["attn_norm_scale"].shape[1])
    assert (per_layer(shapes["periods"]["ssm"]), per_layer(shapes["periods"]["softmax"])) == (25_849_280, 10_487_808)
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) == 3_191_396_096  # 6.38 GB in bfloat16


def test_apply_is_the_reference(toy):
    """``apply`` (a scan over two periods of ``[ssm, ssm, softmax]``, the
    chunk form of the recurrence, a dense FFN out of the period's stacks, the
    four multipliers) against the reference, which walks the six layers one
    by one, the recurrence token by token."""
    cfg, lm, params, section = toy
    tokens = sequences(7, lens=(50,))[0][None]
    assert cfg.period == ("ssm", "ssm", "softmax") and cfg.num_periods == 2 and cfg.num_experts == 0
    assert np.abs(apply_logits(lm, params, tokens)[0] - reference_logits(section, params, tokens[0])).max() < F32_TOL


WRONG = ["gate_behind_the_norm", "conv_bias_dropped", "D_dropped", "dt_without_its_bias", "softmax_scale_rsqrt", "rotary",
         "no_embedding_multiplier", "no_residual_multiplier", "no_logits_scaling", "state_dropped"]


@pytest.mark.parametrize("wrong", WRONG)
def test_a_wrong_block_is_far_outside_the_tolerance(toy, wrong, monkeypatch):
    """What the tolerance is worth: each of these moves the logits by more
    than a hundred times ``F32_TOL``; the three multipliers each change them."""
    cfg, lm, params, section = toy
    tokens = sequences(7, lens=(50,))[0][None]
    want = reference_logits(section, params, tokens[0])[None]
    without = lambda leaf: {**params, "periods": {**params["periods"], "ssm": {**params["periods"]["ssm"], leaf: jnp.zeros_like(params["periods"]["ssm"][leaf])}}}
    if wrong == "gate_behind_the_norm":

        def gate_behind(cfg, p, z, y):
            normed = hm._norm(y.astype(jnp.float32), p["o_norm_scale"], None, "rmsnorm", cfg.norm_eps)
            return hm.qmatmul((normed * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype), p["wo"])

        monkeypatch.setattr(hm, "ssm_output", gate_behind)
    elif wrong == "conv_bias_dropped":
        params = without("conv_b")
    elif wrong == "D_dropped":
        params = without("D")
    elif wrong == "dt_without_its_bias":
        params = without("dt_bias")
    elif wrong == "softmax_scale_rsqrt":  # 16^-0.5 for the config's 1/16
        lm = HybridMoETransformerLM(dataclasses.replace(cfg, attn_softmax_scale=None))
    elif wrong == "rotary":
        lm = HybridMoETransformerLM(dataclasses.replace(cfg, position="rope"))
    elif wrong == "no_embedding_multiplier":
        lm = HybridMoETransformerLM(dataclasses.replace(cfg, embedding_multiplier=1.0))
    elif wrong == "no_residual_multiplier":
        lm = HybridMoETransformerLM(dataclasses.replace(cfg, residual_multiplier=1.0))
    elif wrong == "no_logits_scaling":
        lm = HybridMoETransformerLM(dataclasses.replace(cfg, logits_scaling=1.0))
    elif wrong == "state_dropped":  # y = D x alone: what a state that is not carried reads
        chunked = state_space.ssd_chunked
        monkeypatch.setattr(state_space, "ssd_chunked", lambda x, B, C, dt, A, D, S, **kw: chunked(x, jnp.zeros_like(B), C, dt, A, D, S, **kw))
    assert np.abs(apply_logits(lm, params, tokens) - want).max() > 100 * F32_TOL


@pytest.mark.parametrize("tiled", [False, True], ids=["slab", "token_tiles"])
def test_served_logits_match_the_reference(toy, tiled, monkeypatch):
    """Prefill in chunks beside decoding rows (a prompt of 2.5 chunks and more,
    its state and tail carried chunk to chunk), then decode through the state
    store and the pages, rows and slots in different orders, a row finishing
    while the others run: every position's logits are the reference's full
    forward's. ``token_tiles``: the wide window packed and computed in tiles
    of 16 tokens, a tile's tail dead."""
    cfg, _, params, section = toy
    if tiled:
        monkeypatch.setattr(decode, "DENSE_TOKEN_TILE", 16)
        assert decode.token_tile(cfg) == 16 < SLOTS * CHUNK
    seqs = sequences()
    got = Driver(cfg, params).run(seqs, decode_from={0: 40, 1: 3, 2: 33, 3: 27})
    for s, seq in seqs.items():
        want = reference_logits(section, params, seq)
        assert got[s].shape == want.shape
        assert np.abs(got[s] - want).max() < F32_TOL, s


def test_two_heads_of_64_a_page_serve_the_reference():
    """The published heads of 64 on the toy: the pool holds its two KV heads
    side by side on a page's 128 lanes (``kv_pool.heads_per_group``), the step
    hands the attention entry q, k and v at the true heads, and every
    position's logits, chunk rows and decode rows, are the reference's."""
    cfg, _, params, section = toy_model(head_dim=64, attn_softmax_scale=0.015625)
    seqs = sequences(2, lens=(45, 7, 30))
    driver = Driver(cfg, params)
    pages = SLOTS * (MAXLEN // PAGE) + 1
    assert driver.pools[0].shape == driver.pools[1].shape == (2, pages, 1, PAGE, 128)
    got = driver.run(seqs, decode_from={0: 36, 1: 3, 2: 30})
    assert driver.pools[0].shape == (2, pages, 1, PAGE, 128) and float(jnp.abs(driver.pools[0][:, 1:]).max()) > 0
    for s, seq in seqs.items():
        assert np.abs(got[s] - reference_logits(section, params, seq)).max() < F32_TOL, s


def test_the_store_holds_the_references_final_states(toy):
    """After a row's tokens the state store's entries are the reference's
    final states in LAYER order (period by period), at the row's slot, and
    every layer's convolution tail was written."""
    cfg, _, params, section = toy
    seq = sequences(5, lens=(43,))[0]
    driver = Driver(cfg, params)
    driver.run({2: seq}, decode_from={2: 37})
    want = REFERENCE.final_states(section, params, seq[None])
    assert len(want) == cfg.layers_of("ssm") == 4
    for layer, S in enumerate(want):
        assert np.abs(np.asarray(driver.pools[2][layer, 2]) - np.asarray(S[0])).max() < 1e-6, layer
    assert float(jnp.abs(driver.pools[3][:, 2]).max(axis=(1, 2, 3)).min()) > 0
    assert not np.asarray(driver.pools[3][:, 2, :, cfg.ssm_conv_channels // 128 :]).any()  # the rows past the channels


def test_bf16_serving_keeps_float32_state():
    """The served type: bfloat16 weights, activations, pages and tails,
    float32 state. The reference reads the same rounded weights in float32."""
    cfg, _, params, section = toy_model("bfloat16", num_layers=3, layer_types=["ssm", "ssm", "softmax"])
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    seqs = sequences(1, lens=(60, 9))
    driver = Driver(cfg, params, jnp.bfloat16)
    got = driver.run(seqs, decode_from={0: 41, 1: 4})
    assert driver.pools[2].dtype == jnp.float32 and driver.pools[3].dtype == jnp.bfloat16
    for s, seq in seqs.items():
        assert np.abs(got[s] - reference_logits(section, params, seq)).max() < 4e-3, s


def test_the_kernel_serves_what_the_xla_form_serves(toy, monkeypatch):
    """``ssd_decode``'s Pallas kernel (interpreted) inside the step, narrow
    and wide programs: the logits of the XLA form."""
    cfg, _, params, _ = toy
    seqs = sequences(4, lens=(21, 9))
    b = Driver(cfg, params).run(seqs, decode_from={0: 18, 1: 0})
    monkeypatch.setattr(hybrid_decode, "ssd_decode", functools.partial(state_space.ssd_decode, impl="pallas_interpret"))
    a = Driver(cfg, params, impl="pallas_interpret").run(seqs, decode_from={0: 18, 1: 0})
    for s in seqs:
        assert np.abs(a[s] - b[s]).max() < F32_TOL, s


def test_a_readmitted_row_starts_from_zero_state_inside_the_program(toy):
    """A slot reused by a second request (after a finish or a preemption) is
    prefilled from position 0: whatever the slot's states, tails and pages
    held is not read, so the second request's logits are an undisturbed
    row's."""
    cfg, _, params, _ = toy
    seqs = sequences(3, lens=(30, 75))
    undisturbed = Driver(cfg, params).run({1: seqs[0]}, decode_from={1: 22})[1]
    driver = Driver(cfg, params)
    driver.run({1: seqs[1]}, decode_from={1: 40})  # another request's state and tail are left in slot 1
    assert float(jnp.abs(driver.pools[2][:, 1]).max(axis=(1, 2, 3)).min()) > 0
    driver.lengths[1] = 0  # the slot is freed and given to the second request
    resumed = driver.run({1: seqs[0]}, decode_from={1: 22})[1]
    assert np.abs(resumed - undisturbed).max() < F32_TOL


def test_a_dead_row_leaves_every_state_alone(toy):
    cfg, _, params, _ = toy
    driver = Driver(cfg, params)
    driver.run(sequences(4, lens=(20, 17, 9)), decode_from={0: 16, 1: 10, 2: 5})
    before = [np.asarray(p) for p in driver.pools]
    driver.step({3: np.arange(5)}, CHUNK)
    driver.step({3: np.arange(1)}, 1)
    for b, a in zip(before[2:], driver.pools[2:]):
        assert np.array_equal(b[:, :3], np.asarray(a)[:, :3])
