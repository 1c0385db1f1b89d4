"""A model with latent-attention layers through the paged server
(``inference/hybrid_decode.py``): one ``[c_kv ; k_rope]`` entry a token in
pages of their own under the pool's page table, the absorbed form in the step,
a rotated part that the key shares across heads, a leading dense layer, a
shared expert and one chip's share of the routed ones with a scaling factor.
Everything is compared with the plain reference
(``benchmark/reference/glm4_moe_lite_decoder.py``: float32, the PUBLISHED
expanded form, the experts a loop) on seeded weights at a toy size, LOGITS and
not tokens.

Tolerances. The toy model runs in float32 on the CPU, where the program and
the reference differ by the order of their sums and by the absorbed product's
association (``(q Wk^T) c`` for ``q (Wk^T c)``): logits of standard deviation
~0.2 agree to a few 1e-6 (measured 3e-6 at 4 layers); the limit is 5e-5. The
bfloat16 run rounds every activation, the absorbed query among them, to 8 bits
of significand: its limit is 0.03 (measured 0.009).
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
from deepspeed_tpu.inference.kv_pool import PagePool, key_lanes
from deepspeed_tpu.inference.scheduler import PagedServer
from deepspeed_tpu.models.hybrid_moe import HybridMoETransformerLM, glm4_moe_lite_config
from tests.unit.inference.hybrid_toys import _clear_jax_caches, _compiled_programs_live_as_long_as_the_file, apply_logits, seeded  # noqa: F401 (the two fixtures are taken by their import)

REFERENCE = load_module("reference", "glm4_moe_lite_decoder")
PAGE, SLOTS, CHUNK, MAXLEN = 8, 4, 16, 96
F32_TOL = 5e-5


def _model(dtype="float32", **kw):
    cfg = glm4_moe_lite_config("tiny", dtype=dtype, **kw)
    lm = HybridMoETransformerLM(cfg)
    params = seeded(lm)
    # trained-like scores: init's 0.02 gives a nearly flat softmax, in which a wrong rotary or scale hides
    for tree in [params["periods"]["latent"]] + [p["mixer"] for p in params["leading"]]:
        tree["wq_b"] = tree["wq_b"] * 40.0
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
        assert pool.cache.k_pages.shape[0] == 0 and pool.states.state.size == 0  # no K and V a head, no state
        assert pool.states.latent.shape == (cfg.num_layers, SLOTS * maxp + 1, PAGE, key_lanes(cfg.latent_width))
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
        CHUNK beside whatever else is running, then one token a step.
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
    cfg = glm4_moe_lite_config()
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.head_dim, cfg.v_head_dim) == (47, 2048, 20, 256, 256)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.latent_width) == (768, 512, 192, 64, 576)
    assert cfg.layer_types == ("latent",) * 47 and cfg.leading_dense_layers == 1 and cfg.period == ("latent",) and cfg.num_periods == 46
    assert (cfg.num_experts, cfg.moe_top_k, cfg.moe_shared_experts, cfg.moe_routed_scaling) == (64, 4, 1, 1.8)
    assert key_lanes(cfg.latent_width) == 640
    with pytest.raises(ValueError, match="latent layer needs"):
        glm4_moe_lite_config("tiny", qk_rope_head_dim=4)  # head_dim is no longer the two parts' sum


def test_apply_is_the_published_expanded_form(toy):
    """``apply`` (the leading layer, then a scan over three one-layer periods)
    against the reference, which walks the four layers one by one."""
    cfg, lm, params, section = toy
    tokens = _sequences(7, lens=(50,))[0][None]
    assert cfg.period == ("latent",) and cfg.num_periods == 3 and cfg.num_moe_layers == 3
    assert np.abs(apply_logits(lm, params, tokens)[0] - _reference(section, params, tokens[0])).max() < F32_TOL


@pytest.mark.parametrize("wrong", ["no_rotary_on_q", "no_rotary_on_k", "norm_over_all_of_kv_a", "scale_of_the_nope_part", "no_scaling_factor", "no_shared_expert"])
def test_a_wrong_block_is_far_outside_the_tolerance(toy, wrong, monkeypatch):
    """What the tolerance is worth: each of these moves the logits by
    hundreds of times ``F32_TOL``."""
    from deepspeed_tpu.models import hybrid_moe as hm
    from deepspeed_tpu.models import transformer

    cfg, lm, params, section = toy
    tokens = _sequences(7, lens=(50,))[0][None]
    want = _reference(section, params, tokens[0])[None]
    rope = transformer._rope
    if wrong == "no_rotary_on_q":
        monkeypatch.setattr(transformer, "_rope", lambda x, *a, **k: x if x.shape[-2] > 1 else rope(x, *a, **k))
    elif wrong == "no_rotary_on_k":
        monkeypatch.setattr(transformer, "_rope", lambda x, *a, **k: x if x.shape[-2] == 1 else rope(x, *a, **k))
    elif wrong == "norm_over_all_of_kv_a":
        norm = hm._norm

        def all_of_it(x, scale, *a):
            if x.shape[-1] == cfg.kv_lora_rank:  # as if the rotary part had been normed with it
                return norm(x, scale, *a) * np.sqrt(cfg.kv_lora_rank / cfg.latent_width)
            return norm(x, scale, *a)

        monkeypatch.setattr(hm, "_norm", all_of_it)
    elif wrong == "scale_of_the_nope_part":
        lm = HybridMoETransformerLM(dataclasses.replace(cfg, attn_softmax_scale=cfg.qk_nope_head_dim ** -0.5))
    elif wrong == "no_scaling_factor":
        lm = HybridMoETransformerLM(dataclasses.replace(cfg, moe_routed_scaling=1.0))
    elif wrong == "no_shared_expert":
        params = jax.tree_util.tree_map(lambda a: a, params)
        del params["periods"]["moe"]["shared"]
    assert np.abs(apply_logits(lm, params, tokens) - want).max() > 100 * F32_TOL


@pytest.mark.parametrize("tiled", [False, True], ids=["slab", "token_tiles"])
def test_served_logits_match_the_reference(toy, tiled, monkeypatch):
    """Prefill in chunks beside decoding rows, then decode, through the latent
    pages, rows and slots in different orders: every position's logits are
    the reference's full forward's."""
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


def test_the_absorbed_program_is_the_expanded_apply(toy):
    """The served step never makes a head's keys or values: its logits are
    those of ``apply``, which makes them all."""
    cfg, lm, params, _ = toy
    seqs = _sequences(2, lens=(45, 18))
    got = Driver(cfg, params).run(seqs, decode_from={0: 33, 1: 0})
    for s, seq in seqs.items():
        padded = np.zeros((1, 48), np.int32)  # one length for both rows (causal: what follows moves nothing)
        padded[0, : seq.size] = seq
        assert np.abs(got[s] - apply_logits(lm, params, padded)[0, : seq.size]).max() < F32_TOL, s


def test_bf16_serving():
    """The served type: bfloat16 weights, activations and pages. The
    reference reads the same rounded weights in float32."""
    cfg, _, params, section = _model("bfloat16")
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    seqs = _sequences(1, lens=(60, 9))
    got = Driver(cfg, params, jnp.bfloat16).run(seqs, decode_from={0: 41, 1: 4})
    want = _reference_logits(section, params, seqs)
    for s in seqs:
        assert np.abs(got[s] - want[s]).max() < 0.03, s


def test_the_kernel_serves_what_the_xla_form_serves():
    """The Pallas kernel (interpreted) inside the step, at a size whose
    entries are whole lane tiles (a latent of 128 + 32 rotated in pages of 256
    lanes): the logits of the XLA form."""
    cfg, _, params, _ = _model(num_layers=2, num_heads=2, kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=32, head_dim=64)
    seqs = _sequences(4, lens=(21, 9))
    a = Driver(cfg, params, attn_impl="pallas").run(seqs, decode_from={0: 18, 1: 0})
    b = Driver(cfg, params).run(seqs, decode_from={0: 18, 1: 0})
    for s in seqs:
        assert np.abs(a[s] - b[s]).max() < F32_TOL, s


def test_a_slot_reused_by_a_shorter_row_reads_nothing_of_the_last(toy):
    """A slot's pages keep what its last tenant wrote: a shorter row given the
    same pages from position 0 overwrites what it reaches and masks the rest,
    so its logits are an undisturbed row's."""
    cfg, _, params, _ = toy
    seqs = _sequences(3, lens=(30, 75))
    undisturbed = Driver(cfg, params).run({1: seqs[0]}, decode_from={1: 22})[1]
    driver = Driver(cfg, params)
    driver.run({1: seqs[1]}, decode_from={1: 40})  # another request's entries are left in slot 1's pages
    assert float(jnp.abs(driver.latent[:, driver.table[1]]).max()) > 0
    driver.lengths[1] = 0  # the slot is freed and given to the shorter row
    reused = driver.run({1: seqs[0]}, decode_from={1: 22})[1]
    assert np.abs(reused - undisturbed).max() < F32_TOL


def _server(lm, params, **kw):
    eng = ds.init_inference(lm, dtype="fp32", paged_kv={"page_size": PAGE, "max_slots": SLOTS, "prefill_chunk": CHUNK, "max_seq_len": MAXLEN, **kw})
    eng.set_params(params)
    return eng


def test_the_engine_serves_it_with_two_programs_and_preemption_changes_nothing(toy):
    """``init_inference`` -> ``serve``: two compiled programs; the routed
    layers' assignments counted (three layers, not four); a token stored once
    a layer in the memory report; and with a pool too small for its rows
    (preempted and re-admitted rows) the streams are those of a pool that
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
    assert stats["moe_routed_assignments"] == (sum(p.size for p in prompts) + sum(budgets) - len(prompts)) * 3 * cfg.moe_top_k
    assert 0.1 < stats["moe_assignments"] / stats["moe_routed_assignments"] < 0.45  # 4 of 16 held
    pool = eng._paged_server.pool
    pages = SLOTS * (MAXLEN // PAGE) + 1
    assert pool.cache.k_pages.shape[0] == 0 and pool.cache.hbm_bytes() == 0
    assert pool.states.latent.shape == (4, pages, PAGE, 40)
    report = eng.memory_report(enforce=False)
    entry = next(b for b in report["entries"] if b["name"] == "latent_kv")
    # ONE entry a token a layer: 32 + 8 numbers, float32, and no value array beside it
    assert entry["per_chip_bytes"] == 4 * pages * PAGE * 40 * 4
    assert entry["detail"]["latent_bytes_per_token"] == 4 * 40 * 4 and entry["detail"]["latent_lanes"] == 40
    assert next(b for b in report["entries"] if b["name"] == "kv_pages")["per_chip_bytes"] == 0
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


def test_the_pack_span_counts_the_tokens_under_latent_pages(toy):
    from deepspeed_tpu.profiling.tracer import Tracer

    cfg, _, params, _ = toy
    tracer = Tracer()
    srv = PagedServer(cfg, params, page_size=PAGE, max_slots=SLOTS, prefill_chunk=CHUNK, max_seq_len=MAXLEN, tracer=tracer)
    srv.submit(np.arange(11, dtype=np.int32), max_new_tokens=3)
    while srv.has_work():
        srv.step()
    packs = [s for s in tracer.spans() if s["name"] == "serve.pack"]
    assert [s["attrs"]["kv_tokens"] for s in packs][:3] == [11, 12, 13]
    # a lone decode row keeps its ``1:``: digits alone come back from the profiler's stats as a number
    assert [s["attrs"]["row_lens"] for s in packs][:3] == ["11:11", "1:12", "1:13"] and [s["attrs"]["mixed"] for s in packs][:3] == [1, 0, 0]
    assert srv.pool.live_hbm_bytes() == 0 and srv.pool.latent_bytes_per_token == 4 * 40 * 4


def test_defrag_moves_the_latent_pages_with_the_table(toy):
    cfg, _, params, _ = toy
    pool = PagePool(cfg, 12, PAGE, SLOTS, max_seq_len=MAXLEN, dtype=jnp.float32)
    a, b = pool.alloc_slot(PAGE * 2), pool.alloc_slot(PAGE)
    marked = pool.states.latent.at[:, pool.page_table[b, 0]].set(7.0)
    pool.set_states(pool.states._replace(latent=marked))
    pool.free_slot(a)
    assert pool.defrag() == 1
    assert float(pool.states.latent[:, pool.page_table[b, 0]].min()) == 7.0 and pool.page_table[b, 0] == 1
    pool.integrity_check()


@pytest.mark.parametrize("feature", ["prefix_cache", "spec_decode", "generate", "beam_generate", "rollback", "attach_prefix", "train", "tensor_parallel"])
def test_what_knows_only_k_and_v_pages_is_refused(toy, feature):
    """Each raises where it is built, naming the latent pages it does not know."""
    cfg, lm, params, _ = toy
    tokens = np.arange(8, dtype=np.int32)[None]
    kw = dict(page_size=PAGE, max_slots=SLOTS, prefill_chunk=CHUNK, max_seq_len=MAXLEN)
    calls = {
        "prefix_cache": lambda: PagedServer(cfg, params, prefix_cache=True, **kw),
        "spec_decode": lambda: PagedServer(cfg, params, spec_decode={"enable": True}, **kw),
        "generate": lambda: decode.generate(cfg, params, tokens, 4),
        "beam_generate": lambda: decode.beam_generate(cfg, params, tokens, 4, num_beams=2),
        "rollback": lambda: PagedServer(cfg, params, **kw).pool.rollback(0, 1),
        "attach_prefix": lambda: PagedServer(cfg, params, **kw).pool.alloc_slot(8, prefix_tokens=tokens[0]),
        "train": lambda: lm.apply(params, (tokens, tokens), train=True),
        "tensor_parallel": lambda: decode.build_ragged_step(cfg, SLOTS, 1, PAGE, attn_impl="xla", tp=SimpleNamespace(degree=2, quantized_allreduce=False, quantized_weights=False, comm_chunks=2, cache_key=lambda: 2)),
    }
    with pytest.raises(NotImplementedError, match="latent|not supported"):
        calls[feature]()


def test_the_other_hybrid_models_programs_have_no_latent_pages():
    """Configs without latent layers: the store's ``latent`` field is ``None``,
    which is no parameter of their programs, and nothing is sized for it."""
    from deepspeed_tpu.models.hybrid_moe import mimo_v2_config, solar_open2_config

    for cfg, leaves in ((solar_open2_config("tiny", dtype="float32"), 2), (mimo_v2_config("tiny", dtype="float32"), 4)):
        pool = PagePool(cfg, 9, PAGE, SLOTS, max_seq_len=MAXLEN, prefill_chunk=CHUNK)
        assert pool.states.latent is None and pool.latent_bytes_per_token == 0
        assert len(jax.tree_util.tree_leaves(pool.states)) == leaves
        assert "latent_total_bytes" not in pool.memory_report()
