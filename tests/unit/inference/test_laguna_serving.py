"""A model whose two kinds of attention layer have query-head counts, rotary
terms and nothing else of their own, through the paged server
(``inference/hybrid_decode.py``): full layers of 12 query heads on KV pages with
the leading half of a head rotated at YaRN frequencies, window layers of 18 on
a per-slot page ring with the whole head rotated at plain ones, both over 2 KV
heads (groups of 6 and 9, as Laguna-S-2.1's 48 / 72 over 8), one sigmoid gate
a head on every layer's output, a leading dense layer in front of two periods
of routed ones (softmax scores, top-3 of 16 at 4 held, times 2.5, a shared
expert). Everything is compared with the plain reference
(``benchmark/reference/laguna_decoder.py``: float32, masks from positions, the
frequencies from the published formula, the experts a loop) on seeded weights
at a toy size (window 8, page 8, ring 3), LOGITS and not tokens.

Tolerances. The toy model runs in float32 on the CPU, where the program and
the reference differ by the order of their sums alone: logits of standard
deviation ~0.16 agree to a few 1e-7 (measured 2e-7 for ``apply``, 6e-7 served,
at 9 layers); the limit is 5e-5, as the other hybrid models' tests. Every wrong
block the tests name (no gate, plain frequencies, no attention factor) differs
by 1e-3 or more.
"""

import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.files import load_module
from deepspeed_tpu.inference import decode, hybrid_decode
from deepspeed_tpu.inference.kv_pool import PagePool, window_ring_pages
from deepspeed_tpu.inference.scheduler import PagedServer
from deepspeed_tpu.models import hybrid_moe as hm
from deepspeed_tpu.models.hybrid_moe import HybridMoETransformerLM, laguna_config

REFERENCE = load_module("reference", "laguna_decoder")
PAGE, SLOTS, CHUNK, MAXLEN = 8, 4, 16, 96
RING = window_ring_pages(8, PAGE, CHUNK)
F32_TOL = 5e-5


def _section(cfg):
    return {"kwargs": {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}}


@pytest.fixture(scope="module")
def toy():
    """The toy model, its seeded weights, and ``hybrid_forward`` under one jit (its
    programs live for one test: ``conftest.py`` clears the caches), with a token tile of 16: the narrow window (4 slots) is then the
    whole slab and the wide one (4 x 16) packed into token tiles, which are the
    two forms the real size runs (64 x 1 and 64 x 128 over a tile of 512). The
    engine's test puts the tile back and runs the wide window as a slab."""
    cfg = laguna_config("tiny", dtype="float32")
    lm = HybridMoETransformerLM(cfg)
    params = jax.jit(lambda key: lm.init(key, None))(jax.random.PRNGKey(0))
    forward = jax.jit(lambda p, *a, window: hybrid_decode.hybrid_forward(cfg, p, *a, attn_impl="xla", window=window))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(decode, "DENSE_TOKEN_TILE", 16)
        assert decode.token_tile(cfg) == 16 < SLOTS * CHUNK
        yield cfg, lm, params, _section(cfg), forward


class Driver:
    """Rows stepped by hand through ``hybrid_forward``: what the scheduler
    does, with the logits kept."""

    def __init__(self, cfg, params, forward):
        self.params, self.forward = params, forward
        maxp = MAXLEN // PAGE
        shapes = hybrid_decode.state_shapes(cfg, SLOTS)
        pool = PagePool(cfg, SLOTS * maxp + 1, PAGE, SLOTS, max_seq_len=MAXLEN, dtype=jnp.float32, prefill_chunk=CHUNK)
        assert pool.window_ring == RING == 3
        self.pools = [pool.cache.k_pages, pool.cache.v_pages, jnp.zeros(shapes.state, jnp.float32), jnp.zeros(shapes.conv, jnp.float32)]
        self.rings = (pool.states.window_k, pool.states.window_v)
        self.table = np.stack([1 + s * maxp + np.arange(maxp) for s in range(SLOTS)]).astype(np.int32)
        self.lengths = np.zeros(SLOTS, np.int32)

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
        logits, *self.pools, _, self.rings = self.forward(self.params, tokens, *self.pools, table, lengths, q_lens, slots, window=self.rings)
        logits, out = np.asarray(logits, np.float32), {}  # fetched once: a slice on the device compiles a shape
        for r, s in enumerate(order):
            out[s] = logits[r, : q_lens[r]]
            self.lengths[s] += q_lens[r]
        return out

    def run(self, seqs, decode_from):
        """Each slot's sequence: prefill ``[: decode_from[s]]`` in chunks of
        CHUNK (on the chunk grid) beside whatever else is running, then one
        token a step. Returns {slot: logits [len, V]}."""
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


def _sequences(seed=0, lens=(61, 5, 80, 27)):
    rng = np.random.default_rng(seed)
    return {s: rng.integers(0, 512, n).astype(np.int32) for s, n in enumerate(lens)}


def _reference_logits(section, params, seqs):
    """The reference's full forward of each sequence. Causal, so a sequence's
    logits are those of its padding to the longest one's length, cut: one
    compilation of the reference for all."""
    longest = max(len(seq) for seq in seqs.values())
    padded = np.stack([np.pad(seq, (0, longest - len(seq))) for seq in seqs.values()])
    return {s: lg[: len(seqs[s])] for s, lg in zip(seqs, np.asarray(REFERENCE.logits(section, params, padded)))}


def test_the_preset_states_both_head_layouts_and_both_rotary_terms():
    cfg = laguna_config("tiny")
    assert (cfg.heads_of("softmax"), cfg.heads_of("window"), cfg.kv_heads_of("softmax"), cfg.kv_heads_of("window")) == (12, 18, 2, 2)
    assert (cfg.rope_dim_of("softmax"), cfg.rope_dim_of("window")) == (8, 16) and cfg.rope_frequencies("window") is None
    assert cfg.period == ("window",) * 3 + ("softmax",) and cfg.num_periods == 2 and cfg.num_moe_layers == 8
    big = laguna_config("s-2.1")
    assert (big.heads_of("softmax"), big.heads_of("window"), big.num_kv_heads, big.window, big.moe_top_k) == (48, 72, 8, 512, 10)
    assert big.layer_types[:5] == ("softmax", "window", "window", "window", "softmax") and big.layers_of("softmax") == 12
    with pytest.raises(ValueError, match="no multiple"):
        laguna_config("tiny", window_num_heads=7)
    with pytest.raises(ValueError, match="two forms of one gate"):
        laguna_config("tiny", attn_output_gate=True)


def test_yarn_frequencies_are_the_published_formula_at_the_published_numbers():
    """A NumPy transcription of the family's ``_compute_yarn_parameters`` over
    the rotated 64 features: pairs 0-9 keep their frequency, pairs 18-31 have
    it divided by 128, a linear ramp between; cos and sin times the config's
    ``attention_factor``, which is ``0.1 ln 128 + 1``."""
    cfg = laguna_config("s-2.1")
    dim, base, factor, original, fast, slow = 64, 5e5, 128.0, 8192, 32.0, 1.0
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    correction = lambda turns: dim * np.log(original / (turns * 2 * np.pi)) / (2 * np.log(base))
    low, high = max(np.floor(correction(fast)), 0), min(np.ceil(correction(slow)), dim - 1)
    assert (low, high) == (9, 18)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    want = interpolation * ramp + extrapolation * (1 - ramp)
    got, scale = cfg.rope_frequencies("softmax")
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:10], extrapolation[:10], rtol=1e-6)
    np.testing.assert_allclose(got[18:], extrapolation[18:] / 128, rtol=1e-6)
    assert np.all(np.diff(got[9:19] / extrapolation[9:19]) < 0)
    assert scale == 1.4852030263919618 and abs(scale - (0.1 * np.log(128) + 1)) < 1e-12
    # the reference's own, from the same published numbers
    np.testing.assert_allclose(REFERENCE.yarn_frequencies(dim, base, factor, original, fast, slow), want, rtol=1e-12)
    assert dataclasses.replace(cfg, rope_yarn_attention_factor=0.0).rope_frequencies("softmax")[1] == pytest.approx(scale, rel=1e-12)


def test_a_leading_dense_layer_and_two_periods_are_the_unrolled_stack(toy):
    """``apply`` (the leading layer, then a scan of two trips whose body holds
    four layers) against the reference, which walks the nine layers one by
    one: per-kind head counts, rotary terms and gates index the same weights."""
    cfg, lm, params, section, _ = toy
    tokens = _sequences(7, lens=(50,))[0][None]
    assert np.abs(np.asarray(jax.jit(lm.apply)(params, tokens)) - np.asarray(REFERENCE.logits(section, params, tokens))).max() < F32_TOL


@pytest.mark.parametrize("kind", ["softmax", "window", "moe"])
def test_a_layers_weights_are_one_slice_of_the_stack_at_its_period_and_place(toy, kind):
    """``hybrid_decode.layer_of`` (what the served scan's body reaches a
    layer's weights by: ``per`` a traced index, ``j`` the layer's place among
    its kind in the period) against ``stacks[kind][leaf][per, j]``, every leaf
    at every ``(per, j)`` of two periods of three window layers, one full
    layer and four routed FFNs."""
    cfg, _, params, _, _ = toy
    stacks = {k: v for k, v in params["periods"][kind].items() if k != "experts"}
    count = len(cfg.period) if kind == "moe" else cfg.period.count(kind)
    assert (cfg.num_periods, count) == (2, {"softmax": 1, "window": 3, "moe": 4}[kind])
    for leaf in jax.tree_util.tree_leaves(stacks):
        assert leaf.shape[:2] == (2, count)
    for j in range(count):
        at = jax.jit(lambda per: hybrid_decode.layer_of(stacks, per, j))
        for per in range(cfg.num_periods):
            got, want = at(jnp.int32(per)), jax.tree_util.tree_map(lambda a: a[per, j], stacks)
            assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
            for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
                assert g.shape == w.shape and np.array_equal(np.asarray(g), np.asarray(w))


def test_each_kinds_rotary_term_and_head_count_is_its_own():
    """``attn_heads`` on one token's projections at position 40: what a config
    with one piece wrong gives differs from the right one's in the kind it
    concerns, by far more than rounding, and in the other kind not at all."""
    cfg = laguna_config("tiny", dtype="float32")
    wrongs = {
        "plain_rotary": (dict(rope_yarn_factor=0.0), "softmax"),
        "no_attention_factor": (dict(rope_yarn_attention_factor=1.0), "softmax"),
        "full_theta_in_a_window_layer": (dict(window_rope_theta=cfg.rope_theta), "window"),
        "all_rotated": (dict(rope_dim=cfg.head_dim), "softmax"),
    }
    rng = np.random.default_rng(0)
    positions = np.full((1, 1), 40, np.int32)
    for k in ("softmax", "window"):
        flat = [jnp.asarray(rng.standard_normal((1, 1, n * 16)), jnp.float32) for n in (cfg.heads_of(k), 2, 2)]
        want = hm.attn_heads(cfg, k, *flat, positions)
        assert want[0].shape == (1, 1, cfg.heads_of(k), 16)
        for wrong, (change, kind) in wrongs.items():
            got = hm.attn_heads(dataclasses.replace(cfg, **change), k, *flat, positions)
            differs = max(float(jnp.abs(a - b).max()) for a, b in zip(got[:2], want[:2]))
            assert (differs > 0.05) if k == kind else (differs == 0.0), (wrong, k, differs)
            assert float(jnp.abs(got[2] - want[2]).max()) == 0.0
        with pytest.raises(TypeError, match="reshape"):  # the two kinds' head counts swapped
            hm.attn_heads(dataclasses.replace(cfg, num_heads=18, window_num_heads=12), k, *flat, positions)


def test_the_gate_is_one_scalar_a_head_and_carries_weight(toy):
    """``output_gate`` with ``wg_head`` [H, NH]: every feature of a head times
    the same sigmoid of the normed input; gates of a seeded layer spread (not a
    constant), and a layer without the leaf is left as it is."""
    cfg, _, params, _, _ = toy
    p = jax.tree_util.tree_map(lambda a: a[0, 0], params["periods"]["window"])
    rng = np.random.default_rng(1)
    h, attn = jnp.asarray(rng.standard_normal((5, 64)), jnp.float32), jnp.asarray(rng.standard_normal((5, 18 * 16)), jnp.float32)
    gate = 1 / (1 + np.exp(-np.asarray(h) @ np.asarray(p["wg_head"])))  # [5, 18]
    got = np.asarray(hm.output_gate(p, h, attn))
    np.testing.assert_allclose(got, (np.asarray(attn).reshape(5, 18, 16) * gate[..., None]).reshape(5, -1), rtol=1e-5, atol=1e-6)
    assert gate.std() > 0.02 and np.abs(got - np.asarray(attn)).max() > 0.1
    assert hm.output_gate({k: v for k, v in p.items() if k != "wg_head"}, h, attn) is attn


@pytest.fixture(scope="module")
def served(toy):
    """ONE pass of four rows through both paged caches and, in the slot the
    longest left, the shortest again, twice: all that the two cases below
    compare, served here because ``conftest.py`` drops the compiled programs
    after every test (a second test that steps the driver compiles both
    widths again, ~8 s). Returns the sequences, every position's logits of
    the pass, what the 80-token tenant left in slot 2's ring, and the
    27-token row's logits served again there."""
    cfg, _, params, _, forward = toy
    seqs = _sequences()
    driver = Driver(cfg, params, forward)
    got = driver.run(seqs, decode_from={0: 30, 1: 3, 2: 69, 3: 27})
    left = float(jnp.abs(driver.rings[0][:, 1 + 2 * RING : 1 + 3 * RING]).max())
    driver.lengths[2] = 0  # the slot is freed and given to the shorter row
    resumed = driver.run({2: seqs[3]}, decode_from={2: 22})[2]
    driver.lengths[2] = 0  # preempted after its 27 tokens, admitted again from position 0: all of it prefill now
    again = driver.run({2: seqs[3]}, decode_from={2: 27})[2]
    return SimpleNamespace(seqs=seqs, got=got, left=left, resumed=resumed, again=again)


def test_served_logits_match_the_reference(toy, served):
    """Prefill in chunks beside decoding rows, then decode, through both paged
    caches at groups of 6 and 9, rows and slots in different orders, contexts
    of up to ten windows and more than three times round the ring of 24
    positions: every position's logits are the reference's full forward's."""
    _, _, params, section, _ = toy
    want = _reference_logits(section, params, served.seqs)
    for s, got in served.got.items():
        assert got.shape == want[s].shape
        assert np.abs(got - want[s]).max() < F32_TOL, s


def test_a_readmitted_row_and_a_shorter_tenant_see_nothing_of_the_last(served):
    """Preemption frees the slot and the row prefills again from position 0;
    a slot is handed to a row shorter than its last tenant. What the slot's
    ring and pages hold of the last tenant lies past the new row's length or
    outside its window: the new row's logits are an undisturbed row's (slot
    3's 27 tokens in the shared pass, which the case above holds to the
    reference), served again in slot 2 over its 80-token tenant's keys."""
    assert served.left > 0  # the longer request's keys were still in the slot's ring
    assert served.resumed.shape == served.got[3].shape == (27, 512)
    assert np.abs(served.resumed - served.got[3]).max() < F32_TOL
    assert np.abs(served.again - served.got[3]).max() < F32_TOL


def _server(lm, params, **kw):
    eng = ds.init_inference(lm, dtype="fp32", paged_kv={"page_size": PAGE, "max_slots": SLOTS, "prefill_chunk": CHUNK, "max_seq_len": MAXLEN, **kw})
    eng.set_params(params)
    return eng


def test_the_engine_serves_it_with_two_programs_and_says_both_layouts(toy, monkeypatch):
    """``init_inference`` -> ``serve`` with a pool too small for its rows, so
    that rows are preempted and admitted again: two compiled programs; both
    kinds' query heads and the window layers' rings in the memory report;
    ``serve.pack`` says the rings its rows own; the routed layers' assignments
    counted (eight layers, not nine); and every served token is the arg-max of
    the reference's full forward, which knows no preemption."""
    cfg, lm, params, section, _ = toy
    monkeypatch.setattr(decode, "DENSE_TOKEN_TILE", 512)  # the wide window as a slab
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (37, 5, 20, 16, 50, 3)]
    budgets = [33, 65, 50, 54, 20, 67]  # every stream 70 tokens: one compilation of the reference
    eng = _server(lm, params, num_pages=14)
    outs = eng.serve(prompts, max_new_tokens=budgets)
    assert sorted(eng.compile_stats()) == ["paged_ragged_r4_w1", "paged_ragged_r4_w16"]
    stats = eng._paged_server.stats
    assert stats["preempted"] > 0
    assert stats["moe_routed_assignments"] >= (sum(p.size for p in prompts) + sum(budgets) - len(prompts)) * 8 * cfg.moe_top_k
    assert stats["moe_routed_assignments"] % (8 * cfg.moe_top_k) == 0  # eight routed layers: the leading one is dense
    assert 0.1 < stats["moe_assignments"] / stats["moe_routed_assignments"] < 0.45  # 4 of 16 held
    assert stats["moe_experts_hit"] > 0 and stats["moe_max_expert_load"] > 0
    pool = eng._paged_server.pool
    assert pool.cache.k_pages.shape == (3, 14, 2, PAGE, 16)
    assert pool.states.window_k.shape == (6, 1 + SLOTS * RING, 2, PAGE, 16) == pool.states.window_v.shape
    report = pool.memory_report()
    assert (report["paged_query_heads"], report["window_query_heads"], report["window_kv_heads"]) == (12, 18, 2)
    assert (report["window_ring_pages"], report["window_keys"], report["window_layers"]) == (RING, 8, 6)
    assert report["window_bytes_per_slot"] == 6 * RING * 2 * PAGE * (16 + 16) * 4
    ring = next(b for b in eng.memory_report(enforce=False)["entries"] if b["name"] == "window_kv")
    assert ring["per_chip_bytes"] == pool.states.window_bytes() and ring["detail"]["window_query_heads"] == 18
    for p, o, lg in zip(prompts, outs, np.asarray(REFERENCE.logits(section, params, np.stack(outs)))):
        gap = lg[p.size - 1 : o.size - 1].max(-1) - np.take_along_axis(lg[p.size - 1 : o.size - 1], o[p.size :, None], -1)[:, 0]
        assert gap.max() < F32_TOL


@pytest.mark.parametrize("feature", ["prefix_cache", "spec_decode", "generate", "beam_generate", "tensor_parallel"])
def test_what_assumes_a_rows_pages_hold_its_whole_past_is_refused(toy, feature):
    """Each raises where it is built, naming what the ring does not keep (or,
    for tensor parallelism, the layer stack it has no rules for: 8 KV heads
    split over 4 chips for both kinds, but nothing shards the two head counts)."""
    cfg, lm, params, _, _ = toy
    tokens = np.arange(8, dtype=np.int32)[None]
    kw = dict(page_size=PAGE, max_slots=SLOTS, prefill_chunk=CHUNK, max_seq_len=MAXLEN)
    calls = {
        "prefix_cache": lambda: PagedServer(cfg, params, prefix_cache=True, **kw),
        "spec_decode": lambda: PagedServer(cfg, params, spec_decode={"enable": True}, **kw),
        "generate": lambda: decode.generate(cfg, params, tokens, 4),
        "beam_generate": lambda: decode.beam_generate(cfg, params, tokens, 4, num_beams=2),
        "tensor_parallel": lambda: decode.build_ragged_step(cfg, SLOTS, 1, PAGE, attn_impl="xla", tp=SimpleNamespace(degree=2, quantized_allreduce=False, quantized_weights=False, comm_chunks=2, cache_key=lambda: 2)),
    }
    with pytest.raises(NotImplementedError, match="window|not supported"):
        calls[feature]()
