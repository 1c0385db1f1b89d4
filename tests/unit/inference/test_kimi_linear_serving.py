"""A model whose two caches are the two cheapest through the paged server
(``inference/hybrid_decode.py``): gated delta-rule layers on the per-slot
state store beside latent-attention layers on pages of one entry a token, no
layer with keys and values a head; a LEADING dense layer that is a linear one
(entry 0 of the state store, the scanned layers behind it), a latent query
with no low rank (``q_lora_rank`` 0: one ``wq``), nothing rotated
(``position "none"``: the shared features kept as projected), ``b`` a plain
sigmoid, a shared expert and one chip's share of the routed ones with a
scaling factor. Everything is compared with the plain reference
(``benchmark/reference/kimi_linear_decoder.py``: float32, the recurrence token
by token, the PUBLISHED expanded latent form, the experts a loop) on seeded
weights at a toy size, LOGITS and not tokens.

Tolerances. The toy model runs in float32 on the CPU, where the program and
the reference differ by the order of their sums, by the chunkwise form of the
recurrence and by the absorbed product's association: logits of standard
deviation ~0.25 agree to a few 1e-6 (measured 4e-6 at 9 layers); the limit is
5e-5. The bfloat16 run keeps float32 state but rounds every activation, the
absorbed query among them, to 8 bits of significand: its limit is 0.04
(measured 0.012).
"""

import dataclasses
import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.files import load_module
from deepspeed_tpu.inference import decode, hybrid_decode
from deepspeed_tpu.inference.kv_pool import PagePool, key_lanes
from deepspeed_tpu.inference.scheduler import PagedServer
from deepspeed_tpu.models.hybrid_moe import HybridMoETransformerLM, kimi_linear_config
from tests.unit.inference.hybrid_toys import _clear_jax_caches, _compiled_programs_live_as_long_as_the_file, apply_logits, seeded  # noqa: F401 (the two fixtures are taken by their import)

REFERENCE = load_module("reference", "kimi_linear_decoder")
PAGE, SLOTS, CHUNK, MAXLEN = 8, 4, 16, 96
F32_TOL = 5e-5


def _model(dtype="float32", **kw):
    cfg = kimi_linear_config("tiny", dtype=dtype, **kw)
    lm = HybridMoETransformerLM(cfg)
    params = seeded(lm)
    # trained-like scores: init's 0.02 gives a nearly flat softmax, in which a wrong rotary or a dropped part hides
    params["periods"]["latent"]["wq"] = params["periods"]["latent"]["wq"] * 40.0
    section = {"kwargs": {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}}
    return cfg, lm, params, section


_FORWARDS = {}  # (id of the config, kernel form, token tile) -> (the config, kept alive for its id; its jitted forward)


class Driver:
    """Rows stepped by hand through ``hybrid_forward``: what the scheduler
    does, with the logits kept."""

    def __init__(self, cfg, params, dtype=jnp.float32, attn_impl="xla"):
        self.cfg, self.params = cfg, params
        maxp = MAXLEN // PAGE
        pool = PagePool(cfg, SLOTS * maxp + 1, PAGE, SLOTS, max_seq_len=MAXLEN, dtype=dtype)
        assert pool.cache.k_pages.shape[0] == 0  # no layer keeps K and V a head
        assert pool.states.state.shape == (cfg.layers_of("linear"), SLOTS + 1, cfg.linear_num_heads, cfg.linear_head_dim, cfg.linear_head_dim)
        assert pool.states.latent.shape == (cfg.layers_of("latent"), SLOTS * maxp + 1, PAGE, key_lanes(cfg.latent_width))
        self.pools = [pool.cache.k_pages, pool.cache.v_pages, pool.states.state, pool.states.conv]
        self.latent = pool.states.latent
        self.table = np.stack([1 + s * maxp + np.arange(maxp) for s in range(SLOTS)]).astype(np.int32)
        self.lengths = np.zeros(SLOTS, np.int32)
        key = (id(cfg), attn_impl, decode.token_tile(cfg))  # drivers of one model share its two compiled programs
        if key not in _FORWARDS:
            _FORWARDS[key] = (cfg, jax.jit(lambda p, *a, latent: hybrid_decode.hybrid_forward(cfg, p, *a, attn_impl=attn_impl, latent=latent)))
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
        logits, *self.pools, _, self.latent = self.forward(self.params, tokens, *self.pools, table, lengths, q_lens, slots, latent=self.latent)
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


def _sequences(seed=0, lens=(61, 5, 80, 27)):
    rng = np.random.default_rng(seed)
    return {s: rng.integers(0, 512, n).astype(np.int32) for s, n in enumerate(lens)}


@pytest.fixture(scope="module")
def toy():
    return _model()


def _reference(section, params, seq):
    """The reference's logits [len, V] of one sequence, computed at MAXLEN
    (padded behind: the model is causal and routes token by token, so what
    follows a position does not move its logits), so that its jitted parts
    compile for one length and not for every sequence's."""
    padded = np.zeros((1, MAXLEN), np.int32)
    padded[0, : seq.size] = seq
    return np.asarray(REFERENCE.logits(section, params, padded))[0, : seq.size]


def _reference_logits(section, params, seqs):
    return {s: _reference(section, params, seq) for s, seq in seqs.items()}


def test_the_preset_is_the_published_model():
    cfg = kimi_linear_config()
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.head_dim, cfg.v_head_dim) == (27, 2304, 32, 192, 128)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.latent_width) == (0, 512, 128, 64, 576)
    assert (cfg.linear_num_heads, cfg.linear_head_dim, cfg.linear_conv_kernel, cfg.linear_allow_neg_eigval) == (32, 128, 4, False)
    # full_attn_layers 4, 8, ..., 24, 27 and kda_layers the rest, counted from 1
    assert [i + 1 for i, t in enumerate(cfg.layer_types) if t == "latent"] == [4, 8, 12, 16, 20, 24, 27]
    assert cfg.layers_of("linear") == 20 and cfg.leading_dense_layers == 1 and cfg.layer_types[0] == "linear"
    assert (cfg.num_experts, cfg.moe_top_k, cfg.moe_shared_experts, cfg.moe_routed_scaling) == (256, 8, 1, 2.446)
    assert cfg.position == "none" and key_lanes(cfg.latent_width) == 640
    # 1 + 4 n layers: the leading layer and whole periods [linear, linear, latent, linear]
    cut = kimi_linear_config(num_layers=13)
    assert cut.period == ("linear", "linear", "latent", "linear") and cut.num_periods == 3
    assert (cut.layers_of("linear"), cut.leading_of("linear"), cut.layers_of("latent"), cut.leading_of("latent")) == (10, 1, 3, 0)


@pytest.mark.parametrize("lifted", ["no_low_rank_query", "no_rotary", "both"])
def test_what_a_latent_layer_was_refused_for_is_built(lifted):
    """Until PR 49 ``HybridMoEConfig`` raised for a latent layer without
    ``q_lora_rank`` and for one under ``position="none"``: both are models
    now. Without a low rank the layer has ONE query matrix and no query norm."""
    from deepspeed_tpu.models.hybrid_moe import glm4_moe_lite_config

    kw = {"no_low_rank_query": dict(q_lora_rank=0), "no_rotary": dict(position="none"), "both": dict(q_lora_rank=0, position="none")}[lifted]
    cfg = glm4_moe_lite_config("tiny", num_layers=2, dtype="float32", **kw)
    lm = HybridMoETransformerLM(cfg)
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), None))
    mixer = params["periods"]["latent"]
    if cfg.q_lora_rank:
        assert {"wq_a", "q_norm_scale", "wq_b"} <= set(mixer) and "wq" not in mixer
    else:
        assert mixer["wq"].shape == (1, 1, 64, 4 * 24) and not {"wq_a", "q_norm_scale", "wq_b"} & set(mixer)
    # the forward is built (traced, not compiled: the toy below is compared with the reference number by number)
    assert jax.eval_shape(lm.apply, params, jnp.zeros((1, 12), jnp.int32)).shape == (1, 12, 512)
    with pytest.raises(ValueError, match="latent layer needs"):
        glm4_moe_lite_config("tiny", q_lora_rank=-1)
    with pytest.raises(ValueError, match="latent layer needs"):
        glm4_moe_lite_config("tiny", kv_lora_rank=0, **kw)


def test_apply_is_the_reference(toy):
    """``apply`` (the leading linear layer, then a scan over two periods of
    ``[linear, linear, latent, linear]``) against the reference, which walks
    the nine layers one by one, the recurrence token by token."""
    cfg, lm, params, section = toy
    tokens = _sequences(7, lens=(50,))[0][None]
    assert cfg.layer_types == ("linear",) + ("linear", "linear", "latent", "linear") * 2
    assert cfg.leading_dense_layers == 1 and cfg.num_periods == 2 and cfg.q_lora_rank == 0 and cfg.position == "none"
    assert np.abs(apply_logits(lm, params, tokens)[0] - _reference(section, params, tokens[0])).max() < F32_TOL


WRONG = ["rotary_on_q_r_and_k_r", "shared_features_dropped", "b_doubled", "decay_a_head", "no_scaling_factor", "no_shared_expert"]


@pytest.mark.parametrize("wrong", WRONG)
def test_a_wrong_block_is_far_outside_the_tolerance(toy, wrong, monkeypatch):
    """What the tolerance is worth: each of these moves the logits by
    hundreds of times ``F32_TOL``."""
    from deepspeed_tpu.models import hybrid_moe as hm

    cfg, lm, params, section = toy
    tokens = _sequences(7, lens=(50,))[0][None]
    want = _reference(section, params, tokens[0])[None]
    if wrong == "rotary_on_q_r_and_k_r":
        lm = HybridMoETransformerLM(dataclasses.replace(cfg, position="rope"))
    elif wrong == "shared_features_dropped":
        project = hm.latent_project

        def dropped(cfg, p, h, positions):
            q_nope, q_r, entry = project(cfg, p, h, positions)
            return q_nope, jnp.zeros_like(q_r), entry

        monkeypatch.setattr(hm, "latent_project", dropped)
    elif wrong == "b_doubled":
        lm = HybridMoETransformerLM(dataclasses.replace(cfg, linear_allow_neg_eigval=True))
    elif wrong == "decay_a_head":  # one decay a head (its channels' mean): a gated delta net, not KDA
        inputs = hm.linear_inputs

        def a_head(cfg, p, h):
            qkv, log_a, beta = inputs(cfg, p, h)
            heads = log_a.reshape(log_a.shape[:-1] + (cfg.linear_num_heads, cfg.linear_head_dim))
            return qkv, jnp.broadcast_to(heads.mean(-1, keepdims=True), heads.shape).reshape(log_a.shape), beta

        monkeypatch.setattr(hm, "linear_inputs", a_head)
    elif wrong == "no_scaling_factor":
        lm = HybridMoETransformerLM(dataclasses.replace(cfg, moe_routed_scaling=1.0))
    elif wrong == "no_shared_expert":
        params = jax.tree_util.tree_map(lambda a: a, params)
        del params["periods"]["moe"]["shared"]
    assert np.abs(apply_logits(lm, params, tokens) - want).max() > 100 * F32_TOL


@pytest.mark.parametrize("tiled", [False, True], ids=["slab", "token_tiles"])
def test_served_logits_match_the_reference(toy, tiled, monkeypatch):
    """Prefill in chunks beside decoding rows, then decode, through the state
    store and the latent pages, rows and slots in different orders, a row
    finishing while the others run: every position's logits are the
    reference's full forward's. ``token_tiles``: the wide window packed and
    computed in tiles of 16 tokens, a tile's tail dead."""
    cfg, _, params, section = toy
    if tiled:
        monkeypatch.setattr(decode, "DENSE_TOKEN_TILE", 16)
        assert decode.token_tile(cfg) == 16 < SLOTS * CHUNK
    seqs = _sequences()
    got = Driver(cfg, params).run(seqs, decode_from={0: 30, 1: 3, 2: 69, 3: 27})
    want = _reference_logits(section, params, seqs)
    for s in seqs:
        assert got[s].shape == want[s].shape
        assert np.abs(got[s] - want[s]).max() < F32_TOL, s


def test_the_leading_layers_state_is_entry_zero_of_the_store(toy):
    """After a row's tokens the state store's entries are the reference's
    final states in LAYER order: the leading linear layer's at entry 0, the
    scanned layers' behind it (period by period), and the convolution tails
    beside them are carried too (a tail dropped between steps moves every
    later logit, which ``test_served_logits_match_the_reference`` holds)."""
    cfg, _, params, section = toy
    seq = _sequences(5, lens=(43,))[0]
    driver = Driver(cfg, params)
    driver.run({2: seq}, decode_from={2: 37})
    want = REFERENCE.final_states(section, params, seq[None])
    assert len(want) == cfg.layers_of("linear") == 7
    for layer, S in enumerate(want):
        assert np.abs(np.asarray(driver.pools[2][layer, 2]) - np.asarray(S[0])).max() < 1e-5, layer
    assert float(jnp.abs(driver.pools[3][:, 2]).min(axis=(1, 2)).max()) > 0  # every layer's tail was written


def test_bf16_serving_keeps_float32_state():
    """The served type: bfloat16 weights, activations and latent pages,
    float32 state. The reference reads the same rounded weights in float32."""
    cfg, _, params, section = _model("bfloat16", num_layers=5)  # the leading layer and one period
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    seqs = _sequences(1, lens=(60, 9))
    driver = Driver(cfg, params, jnp.bfloat16)
    got = driver.run(seqs, decode_from={0: 41, 1: 4})
    assert driver.pools[2].dtype == jnp.float32 and driver.latent.dtype == jnp.bfloat16
    want = _reference_logits(section, params, seqs)
    for s in seqs:
        assert np.abs(got[s] - want[s]).max() < 0.04, s


def test_the_kernels_serve_what_the_xla_forms_serve(monkeypatch):
    """Both Pallas kernels (interpreted) inside the step, at a size whose
    latent entries are whole lane tiles (128 + 32 shared in pages of 256
    lanes) and whose linear heads fill a head block of 16: the logits of the
    XLA forms."""
    from deepspeed_tpu.ops.transformer.linear_attention import kda_decode

    cfg, _, params, _ = _model(num_layers=3, layer_types=["linear", "latent", "linear"], num_heads=2, num_kv_heads=2, kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=32, head_dim=64,
                               linear_num_heads=16)
    seqs = _sequences(4, lens=(21, 9))
    b = Driver(cfg, params).run(seqs, decode_from={0: 18, 1: 0})
    monkeypatch.setattr(hybrid_decode, "kda_decode", functools.partial(kda_decode, impl="pallas_interpret"))
    a = Driver(cfg, params, attn_impl="pallas").run(seqs, decode_from={0: 18, 1: 0})
    for s in seqs:
        assert np.abs(a[s] - b[s]).max() < F32_TOL, s


def test_a_readmitted_row_starts_from_zero_state_and_overwrites_its_pages(toy):
    """Preemption frees the slot and the row prefills again from position 0:
    whatever the slot's states, tails and pages held is not read, in the
    leading layer or behind it, so the resumed row's logits are an undisturbed
    row's."""
    cfg, _, params, _ = toy
    seqs = _sequences(3, lens=(30, 75))
    undisturbed = Driver(cfg, params).run({1: seqs[0]}, decode_from={1: 22})[1]
    driver = Driver(cfg, params)
    driver.run({1: seqs[1]}, decode_from={1: 40})  # another request's state and entries are left in slot 1
    assert float(jnp.abs(driver.pools[2][:, 1]).max(axis=(1, 2, 3)).min()) > 0 and float(jnp.abs(driver.latent[:, driver.table[1]]).max()) > 0
    driver.lengths[1] = 0  # the slot is freed and given to the resumed row
    resumed = driver.run({1: seqs[0]}, decode_from={1: 22})[1]
    assert np.abs(resumed - undisturbed).max() < F32_TOL


def _server(lm, params, **kw):
    eng = ds.init_inference(lm, dtype="fp32", paged_kv={"page_size": PAGE, "max_slots": SLOTS, "prefill_chunk": CHUNK, "max_seq_len": MAXLEN, **kw})
    eng.set_params(params)
    return eng


def test_the_engine_serves_it_with_two_programs_and_preemption_changes_nothing(toy):
    """``init_inference`` -> ``serve``: two compiled programs; the routed
    layers' assignments counted (eight layers, not nine); state and latent
    pages side by side in the memory report and no K or V page; and with a
    pool too small for its rows (rows preempted in the middle and re-admitted
    from position 0, while others finish) the streams are those of a pool that
    never preempts."""
    cfg, lm, params, section = toy
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (37, 5, 20, 16, 50, 3)]
    budgets = [40, 20, 9, 60, 7, 30]
    eng = _server(lm, params)
    outs = eng.serve(prompts, max_new_tokens=budgets)
    assert sorted(eng.compile_stats()) == ["paged_ragged_r4_w1", "paged_ragged_r4_w16"]
    stats = eng._paged_server.stats
    assert stats["preempted"] == 0
    assert stats["moe_routed_assignments"] == (sum(p.size for p in prompts) + sum(budgets) - len(prompts)) * 8 * cfg.moe_top_k
    assert 0.1 < stats["moe_assignments"] / stats["moe_routed_assignments"] < 0.45  # 4 of 16 held
    pool = eng._paged_server.pool
    pages = SLOTS * (MAXLEN // PAGE) + 1  # num_pages 0: every slot at max_seq_len, and the trash page
    assert pool.num_pages == pages and pool.cache.k_pages.shape[0] == 0 and pool.cache.hbm_bytes() == 0
    assert pool.states.state.shape == (7, SLOTS + 1, 4, 16, 16) and pool.states.latent.shape == (2, pages, PAGE, 40)
    report = eng.memory_report(enforce=False)
    by_name = {b["name"]: b for b in report["entries"]}
    assert by_name["latent_kv"]["per_chip_bytes"] == 2 * pages * PAGE * 40 * 4
    assert by_name["recurrent_state"]["per_chip_bytes"] == pool.states.state.nbytes + pool.states.conv.nbytes
    assert by_name["kv_pages"]["per_chip_bytes"] == 0
    # every served token is the reference's arg-max at its position (float32, no near-tie at this size)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        lg = _reference(section, params, o)
        gap = lg[p.size - 1 : o.size - 1].max(-1) - np.take_along_axis(lg[p.size - 1 : o.size - 1], o[p.size :, None], -1)[:, 0]
        assert gap.max() < F32_TOL, i
    tight = _server(lm, params, num_pages=14)
    squeezed = tight.serve(prompts, max_new_tokens=budgets)
    assert tight._paged_server.stats["preempted"] > 0
    for a, b in zip(outs, squeezed):
        assert np.array_equal(a, b)


def test_the_step_span_says_which_cache_the_bytes_are_in(toy):
    """``serve.step`` carries ``state_bytes_in_use`` (slots in use x a slot's
    state and convolution tails over the ten... here seven linear layers) and
    ``latent_bytes_in_use`` (pages in use x a page's entries over the latent
    layers) beside ``pages_in_use``; the pool's ``cache_bytes`` reads both."""
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
    a_slot = 7 * (4 * 16 * 16 * 4 + 3 * 3 * 4 * 16 * 4)  # float32 state and, in a float32 engine, float32 tails
    a_page = 2 * PAGE * 40 * 4
    assert srv.pool.state_bytes_per_slot == a_slot and srv.pool.latent_bytes_per_token * PAGE == a_page
    assert steps[0]["state_bytes_in_use"] == 0 and steps[0]["latent_bytes_in_use"] == 0  # read before the call admits
    assert steps[1]["state_bytes_in_use"] == 2 * a_slot and steps[1]["latent_bytes_in_use"] == steps[1]["pages_in_use"] * a_page
    assert {s["state_bytes_in_use"] for s in steps} == {0, a_slot, 2 * a_slot}  # the short row leaves, the other runs on
    assert max(s["latent_bytes_in_use"] for s in steps) == 3 * a_page  # 11 + 8 tokens: three pages of 8
    packs = [s["attrs"] for s in tracer.spans() if s["name"] == "serve.pack"]
    assert packs[0]["kv_tokens"] == 14 and packs[0]["row_lens"] == "11:11 3:3"  # both prompts in the first wide window
    assert srv.pool.cache_bytes() == {"state_bytes_in_use": 0, "latent_bytes_in_use": 0}


def test_num_pages_zero_sizes_the_pool_from_both_caches(toy):
    """``num_pages`` 0 is the worst case: every slot at ``max_seq_len`` in
    latent pages, every slot's state. The memory report gives the two side by
    side, and what a row of a given length costs in each."""
    cfg, _, params, _ = toy
    srv = PagedServer(cfg, params, page_size=PAGE, max_slots=SLOTS, prefill_chunk=CHUNK, max_seq_len=MAXLEN, num_pages=0)
    pool = srv.pool
    rep = pool.memory_report()
    assert pool.num_pages == SLOTS * (MAXLEN // PAGE) + 1
    assert rep["latent_total_bytes"] == pool.num_pages * PAGE * pool.latent_bytes_per_token
    assert rep["state_total_bytes"] == (SLOTS + 1) * pool.state_bytes_per_slot
    assert rep["state_bytes_in_use"] == 0 and rep["latent_live_bytes"] == 0
    slot = pool.alloc_slot(20)
    rep = pool.memory_report()
    assert rep["state_bytes_in_use"] == pool.state_bytes_per_slot and rep["latent_live_bytes"] == 3 * PAGE * pool.latent_bytes_per_token
    pool.free_slot(slot)
    pool.integrity_check()


def test_defrag_moves_the_latent_pages_and_leaves_the_states(toy):
    cfg, _, params, _ = toy
    pool = PagePool(cfg, 12, PAGE, SLOTS, max_seq_len=MAXLEN, dtype=jnp.float32)
    a, b = pool.alloc_slot(PAGE * 2), pool.alloc_slot(PAGE)
    marked = pool.states.latent.at[:, pool.page_table[b, 0]].set(7.0)
    pool.set_states(pool.states._replace(latent=marked, state=pool.states.state.at[:, b].set(3.0)))
    pool.free_slot(a)
    assert pool.defrag() == 1
    assert float(pool.states.latent[:, pool.page_table[b, 0]].min()) == 7.0 and pool.page_table[b, 0] == 1
    assert float(pool.states.state[:, b].min()) == 3.0  # a slot's state is where its slot is: defrag moves pages
    pool.integrity_check()


@pytest.mark.parametrize("feature", ["prefix_cache", "forks", "spec_decode", "generate", "beam_generate", "rollback", "attach_prefix", "train", "tensor_parallel"])
def test_what_needs_a_state_snapshot_or_knows_only_k_and_v_pages_is_refused(toy, feature):
    """Each raises where it is built: a state + latent pool has neither a
    snapshot of a row's state at an earlier position nor K and V pages to
    share, copy or roll back. (A fork is a shared page written to: pages are
    shared through the prefix cache alone, whose refusal names it.)"""
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
    with pytest.raises(NotImplementedError, match="copy-on-write forks" if feature == "forks" else "latent|state|not supported"):
        calls[feature]()


@pytest.mark.parametrize("kind", ["window", "linear"])
def test_a_leading_layer_of_any_kind_is_served(kind):
    """Until PR 49 the step raised for a leading dense layer with a linear
    mixer. A leading layer of the two kinds that no accepted model leads with
    (theirs are softmax and latent ones: the window and latent suites), in
    front of a period that holds the same kind again: the leading layer has
    entry 0 of its kind's cache, the scanned layer the entry behind it, and the
    served logits are ``apply``'s."""
    from deepspeed_tpu.models.hybrid_moe import mimo_v2_config, solar_open2_config

    if kind == "window":
        cfg = mimo_v2_config("tiny", num_layers=3, layer_types=["window", "softmax", "window"], dtype="float32")
    else:
        cfg = solar_open2_config("tiny", num_layers=3, layer_types=["linear", "softmax", "linear"], leading_dense_layers=1, dtype="float32")
    assert cfg.leading_of(kind) == 1 and cfg.layers_of(kind) == 2
    lm = HybridMoETransformerLM(cfg)
    params = seeded(lm)
    eng = _server(lm, params)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (20, 5)]
    outs = eng.serve(prompts, max_new_tokens=[6, 6])
    tokens = np.zeros((2, 32), np.int32)
    for i, o in enumerate(outs):
        tokens[i, : o.size] = o
    lg = apply_logits(lm, params, tokens)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        at = lg[i, p.size - 1 : o.size - 1]
        assert (at.max(-1) - np.take_along_axis(at, o[p.size :, None], -1)[:, 0]).max() < F32_TOL, i
