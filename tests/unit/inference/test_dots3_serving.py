"""A model whose layers are latent-attention ones of two kinds, through the
paged server (``inference/hybrid_decode.py``): full layers of 4 heads that
attend the 8 keys their indexer scores highest, their latents in pages and the
indexer's keys in pages beside them; window layers of 2 heads over the newest
9 keys, latents of their own rank in a per-slot page ring; both low ranks
rescaled, one sigmoid gate a head on every layer's output, a leading dense
layer in front of two periods ``[full window window window]`` of routed ones
(sigmoid scores with a selection bias, top-3 of 16 at 4 held, a shared
expert): ``dots3_note_config("tiny")``, dots3-note-prev's shape. Everything is
compared with the plain reference (``benchmark/reference/dots3_note_decoder.py``:
float32, the expanded form, an ``argsort`` a query, masks from positions) on
seeded weights at a toy size (page 8, ring 3, chunk 16), LOGITS and not
tokens, at contexts of up to ten times ``index_topk`` and nine windows.

Tolerances. The toy model runs in float32 on the CPU, where the program and
the reference differ by the order of their sums alone: logits of standard
deviation ~0.16 agree to a few 1e-7 (measured 2e-7 for ``apply``, 2e-7
served); the limit is 5e-5, as the other hybrid models' tests. Every wrong
block the tests name (no selection, no rescale, no gate)
differs by 1e-2 or more.
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
from deepspeed_tpu.models.hybrid_moe import HybridMoETransformerLM, dots3_note_config
from tests.unit.inference.hybrid_toys import _clear_jax_caches, _compiled_programs_live_as_long_as_the_file, apply_logits, seeded  # noqa: F401

REFERENCE = load_module("reference", "dots3_note_decoder")
PAGE, SLOTS, CHUNK, MAXLEN = 8, 4, 16, 96
RING = window_ring_pages(9, PAGE, CHUNK)
F32_TOL = 5e-5


def _section(cfg):
    return {"kwargs": {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}}


@pytest.fixture(scope="module")
def toy():
    """The toy model, its seeded weights, and ``hybrid_forward`` under one jit,
    with a token tile of 16: the narrow window (4 slots) is then the whole
    slab and the wide one (4 x 16) packed into token tiles, the two forms the
    real size runs (32 x 1 and 32 x 512 over a tile of 512)."""
    cfg = dots3_note_config("tiny", dtype="float32")
    lm = HybridMoETransformerLM(cfg)
    params = seeded(lm)
    forward = jax.jit(lambda p, *a, **pools: hybrid_decode.hybrid_forward(cfg, p, *a, attn_impl="xla", **pools))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(decode, "DENSE_TOKEN_TILE", 16)
        assert decode.token_tile(cfg) == 16 < SLOTS * CHUNK
        yield cfg, lm, params, _section(cfg), forward


class Driver:
    """Rows stepped by hand through ``hybrid_forward``: what the scheduler does, with the logits kept."""

    def __init__(self, cfg, params, forward):
        self.params, self.forward = params, forward
        maxp = MAXLEN // PAGE
        pool = PagePool(cfg, SLOTS * maxp + 1, PAGE, SLOTS, max_seq_len=MAXLEN, dtype=jnp.float32, prefill_chunk=CHUNK)
        assert pool.window_ring == RING == 3
        st = pool.states
        self.pools = [pool.cache.k_pages, pool.cache.v_pages, st.state, st.conv]
        self.own = dict(latent=st.latent, index=st.index, latent_rings=st.window_latent)
        self.table = np.stack([1 + s * maxp + np.arange(maxp) for s in range(SLOTS)]).astype(np.int32)
        self.lengths = np.zeros(SLOTS, np.int32)

    def step(self, windows, width):
        """``windows``: {slot: tokens}; the rows are laid out in a shuffled order so that row and slot differ."""
        order = sorted(windows, key=lambda s: (s * 7) % 5)
        tokens = np.zeros((SLOTS, width), np.int32)
        q_lens = np.zeros(SLOTS, np.int32)
        slots = np.full(SLOTS, SLOTS, np.int32)
        table = np.full_like(self.table, -1)
        lengths = np.zeros(SLOTS, np.int32)
        for r, s in enumerate(order):
            w = np.asarray(windows[s], np.int32)
            tokens[r, : w.size], q_lens[r], slots[r], table[r], lengths[r] = w, w.size, s, self.table[s], self.lengths[s]
        logits, *rest = self.forward(self.params, tokens, *self.pools, table, lengths, q_lens, slots, **self.own)
        self.pools, self.own = list(rest[:4]), dict(latent=rest[5], index=rest[6], latent_rings=rest[7])
        logits, out = np.asarray(logits, np.float32), {}
        for r, s in enumerate(order):
            out[s] = logits[r, : q_lens[r]]
            self.lengths[s] += q_lens[r]
        return out

    def run(self, seqs, decode_from):
        """Each slot's sequence: prefill ``[: decode_from[s]]`` in chunks of CHUNK beside whatever else is running, then one token a step."""
        got = {s: [] for s in seqs}
        done = {s: 0 for s in seqs}
        while any(done[s] < len(seqs[s]) for s in seqs):
            windows = {}
            for s, seq in seqs.items():
                if done[s] < len(seq):
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
    return {s: np.asarray(REFERENCE.logits(section, params, seq[None]))[0] for s, seq in seqs.items()}


def test_the_preset_states_two_latent_kinds_an_indexer_and_the_rescale():
    cfg = dots3_note_config()
    assert cfg.layer_types[:6] == ("sparse_latent", "sparse_latent", "window_latent", "window_latent", "window_latent", "sparse_latent")
    assert (cfg.layers_of("sparse_latent"), cfg.layers_of("window_latent"), cfg.num_periods, cfg.remainder) == (13, 33, 11, ("sparse_latent",))
    full, window = cfg.latent_dims("sparse_latent"), cfg.latent_dims("window_latent")
    assert full[:7] == (128, 1024, 512, 128, 64, 128, 8e7) and window[:7] == (64, 1024, 1024, 192, 64, 128, 5e4)
    assert (full.q_rescale, full.kv_rescale, window.kv_rescale) == (5 ** 0.5, 10 ** 0.5, 5 ** 0.5) and (full.width, window.width) == (576, 1088)
    assert (full.scale, window.scale) == (192 ** -0.5, 256 ** -0.5)
    assert (cfg.index_num_heads, cfg.index_head_dim, cfg.index_topk, cfg.window, cfg.attn_head_gate) == (64, 128, 2048, 513, True)
    assert cfg.paged_latent_kind == "sparse_latent" and cfg.heads_of("window_latent") == 64
    # every other model's rescale is 1.0: nothing in any program
    assert hm.glm4_moe_lite_config().latent_dims()[7:] == (1.0, 1.0) and hm.kimi_linear_config().latent_dims()[7:] == (1.0, 1.0)
    with pytest.raises(NotImplementedError, match="ONE kind"):
        dataclasses.replace(dots3_note_config("tiny"), layer_types=("sparse_latent", "latent") + ("window_latent",) * 7)


def test_apply_is_the_reference_past_the_selection_and_the_window_and_every_new_block_carries_weight(toy):
    """``apply`` (the expanded form) against the reference at 70 tokens, nine
    times ``index_topk`` and eight windows; and with the selection off, the
    rescale off or the gate's leaf dropped the same
    weights give other logits."""
    cfg, lm, params, section, _ = toy
    tokens = np.random.default_rng(1).integers(0, 512, (2, 70)).astype(np.int32)
    want = np.asarray(REFERENCE.logits(section, params, tokens))
    assert np.abs(apply_logits(lm, params, tokens) - want).max() < F32_TOL
    for wrong in (dict(index_topk=1000), dict(latent_lora_rescale=False)):  # a window one key short: tests/unit/ops/test_sparse_latent_attention.py
        other = HybridMoETransformerLM(dataclasses.replace(cfg, **wrong))
        assert np.abs(apply_logits(other, params, tokens) - want).max() > 1e-2, wrong
    drop = lambda mixer: {k: v for k, v in mixer.items() if k != "wg_head"}
    ungated = {**params, "leading": [{**params["leading"][0], "mixer": drop(params["leading"][0]["mixer"])}]}
    assert np.abs(apply_logits(lm, ungated, tokens) - want).max() > 1e-2


def test_the_selection_is_exact_with_ties_to_the_lower_position():
    scores = jnp.asarray([[[3.0, 1.0, 1.0, 1.0, 2.0, 1.0]]])
    seen = jnp.asarray([[[True, True, True, True, True, False]]])
    assert np.asarray(hm.chosen_keys(scores, seen, 3))[0, 0].tolist() == [True, True, False, False, True, False]
    assert np.asarray(hm.chosen_keys(scores, seen, 4))[0, 0].tolist() == [True, True, True, False, True, False]
    assert np.array_equal(np.asarray(hm.chosen_keys(scores, seen, 6)), np.asarray(seen))  # no more keys than the selection keeps: all
    # against a stable argsort, on scores with many ties, both zeros and both signs, three scales
    rng = np.random.default_rng(0)
    for scale in (1e-3, 1.0, 30.0):
        drawn = (np.round(rng.standard_normal((3, 7, 200)) * scale * 2) / 2).astype(np.float32)
        drawn[0, 0, :5] = -0.0
        may = rng.random(drawn.shape) < 0.8
        rank = np.argsort(np.argsort(-np.where(may, drawn, -np.inf), axis=-1, kind="stable"), axis=-1, kind="stable")
        assert np.array_equal(np.asarray(hm.chosen_keys(jnp.asarray(drawn), jnp.asarray(may), 60)), may & (rank < 60)), scale


@pytest.fixture(scope="module")
def served(toy):
    """ONE pass of four rows through the latent pages, the indexer's pages and
    the rings and, in the slot the longest left, the 27-token row again, twice
    (handed over; preempted and admitted again from position 0)."""
    cfg, _, params, _, forward = toy
    seqs = _sequences()
    driver = Driver(cfg, params, forward)
    got = driver.run(seqs, decode_from={0: 30, 1: 3, 2: 69, 3: 27})
    left = float(jnp.abs(driver.own["latent_rings"][:, 1 + 2 * RING : 1 + 3 * RING]).max())
    driver.lengths[2] = 0  # the slot is freed and given to the shorter row
    resumed = driver.run({2: seqs[3]}, decode_from={2: 22})[2]
    driver.lengths[2] = 0  # preempted after its 27 tokens, admitted again from position 0: all of it prefill now
    again = driver.run({2: seqs[3]}, decode_from={2: 27})[2]
    return SimpleNamespace(seqs=seqs, got=got, left=left, resumed=resumed, again=again, index=driver.own["index"])


def test_served_logits_match_the_reference(toy, served):
    """Prefill in chunks (each query token's own selection under the masked
    walk) beside decoding rows (the sort and the gather of chosen entries),
    rows and slots in different orders, contexts of up to ten times
    ``index_topk`` and more than three times round the ring of 24 positions:
    every position's logits are the reference's full forward's."""
    _, _, params, section, _ = toy
    want = _reference_logits(section, params, served.seqs)
    for s, got in served.got.items():
        assert got.shape == want[s].shape
        assert np.abs(got - want[s]).max() < F32_TOL, s
    assert float(jnp.abs(served.index[:, 1:]).max()) > 0  # the indexer's keys were written beside the latents


def test_a_readmitted_row_and_a_shorter_tenant_see_nothing_of_the_last(served):
    assert served.left > 0  # the longer request's latents were still in the slot's ring
    assert served.resumed.shape == served.got[3].shape == (27, 512)
    assert np.abs(served.resumed - served.got[3]).max() < F32_TOL
    assert np.abs(served.again - served.got[3]).max() < F32_TOL


def test_the_engine_serves_it_with_two_programs_and_says_all_three_caches(toy, monkeypatch):
    """``init_inference`` -> ``serve`` with a pool too small for its rows, so
    that rows are preempted and admitted again: two compiled programs; the
    latents, the indexer's keys and the rings of latents in the memory report
    and the ledger; the routed layers' assignments counted; and every served
    token is the arg-max of the reference's full forward, which knows no
    preemption."""
    cfg, lm, params, section, _ = toy
    monkeypatch.setattr(decode, "DENSE_TOKEN_TILE", 512)  # the wide window as a slab
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (37, 5, 20, 16, 50, 3)]
    budgets = [33, 65, 50, 54, 20, 67]  # every stream 70 tokens: one compilation of the reference
    eng = ds.init_inference(lm, dtype="fp32", paged_kv={"page_size": PAGE, "max_slots": SLOTS, "prefill_chunk": CHUNK, "max_seq_len": MAXLEN, "num_pages": 14})
    eng.set_params(params)
    outs = eng.serve(prompts, max_new_tokens=budgets)
    assert sorted(eng.compile_stats()) == ["paged_ragged_r4_w1", "paged_ragged_r4_w16"]
    stats = eng._paged_server.stats
    assert stats["preempted"] > 0
    assert stats["moe_routed_assignments"] % (8 * cfg.moe_top_k) == 0  # eight routed layers: the leading one is dense
    assert 0.1 < stats["moe_assignments"] / stats["moe_routed_assignments"] < 0.45  # 4 of 16 held
    pool = eng._paged_server.pool
    st = pool.states
    assert pool.cache.k_pages.shape[0] == 0 and st.window_k is None  # no layer keeps keys and values a head
    assert (st.latent.shape, st.index.shape, st.window_latent.shape) == ((3, 14, PAGE, 40), (3, 14, PAGE, 16), (6, 1 + SLOTS * RING, PAGE, 56))
    report = pool.memory_report()
    assert (report["paged_query_heads"], report["window_query_heads"], report["window_ring_pages"], report["window_keys"]) == (4, 2, RING, 9)
    assert (report["latent_bytes_per_token"], report["index_lanes"], report["window_latent_lanes"]) == (3 * (40 + 16) * 4, 16, 56)
    assert report["latent_total_bytes"] == st.latent.nbytes + st.index.nbytes and report["window_total_bytes"] == st.window_latent.nbytes
    entries = {b["name"]: b for b in eng.memory_report(enforce=False)["entries"]}
    assert entries["latent_kv"]["per_chip_bytes"] == st.latent_bytes() and entries["window_kv"]["per_chip_bytes"] == st.window_bytes()
    for p, o, lg in zip(prompts, outs, np.asarray(REFERENCE.logits(section, params, np.stack(outs)))):
        gap = lg[p.size - 1 : o.size - 1].max(-1) - np.take_along_axis(lg[p.size - 1 : o.size - 1], o[p.size :, None], -1)[:, 0]
        assert gap.max() < F32_TOL


@pytest.mark.parametrize(
    "max_seq_len, on_a_tpu, form", [(40, True, "walk"), (72, True, "walk"), (80, True, "gather"), (40, False, "gather")], ids=["walk", "boundary", "gather", "cpu"]
)
def test_the_engine_says_the_decode_rows_form_where_it_builds_the_server(toy, monkeypatch, max_seq_len, on_a_tpu, form):
    """``sparse_attend.form``, once a program (the narrow one's rows and the
    mixed one's one-token row group are the same call): the walk while the
    page table's positions are at most ``WALK_MAX_MULTIPLE x index_topk`` (8
    here: 72 positions), the gather a page past it and off a TPU;
    ``decode_form`` is the question the layer asks where the programs are
    traced. Nothing is compiled."""
    from deepspeed_tpu.ops.transformer import sparse_latent_attention as sla

    cfg, lm, params, _, _ = toy
    monkeypatch.setattr(sla, "on_tpu", lambda: on_a_tpu)
    assert sla.WALK_MAX_MULTIPLE * cfg.index_topk == 72
    eng = ds.init_inference(lm, dtype="fp32", paged_kv={"page_size": PAGE, "max_slots": SLOTS, "prefill_chunk": CHUNK, "max_seq_len": max_seq_len})
    eng.set_params(params)
    eng._build_paged_server()
    events = [s["attrs"] for s in eng.tracer.spans() if s["name"] == "sparse_attend.form"]
    assert events == [{"window": w, "form": form, "table_positions": max_seq_len, "index_topk": cfg.index_topk} for w in (1, CHUNK)]
    assert eng.compile_stats() == {}


@pytest.mark.parametrize("feature", ["prefix_cache", "spec_decode", "generate", "tensor_parallel"])
def test_what_assumes_a_rows_pages_hold_its_whole_past_is_refused(toy, feature):
    """Each raises where it is built, naming what the rings and the third and fourth arrays do not allow (or, for tensor
    parallelism, the kinds it has no rules for)."""
    cfg, lm, params, _, _ = toy
    tokens = np.arange(8, dtype=np.int32)[None]
    kw = dict(page_size=PAGE, max_slots=SLOTS, prefill_chunk=CHUNK, max_seq_len=MAXLEN)
    calls = {
        "prefix_cache": lambda: PagedServer(cfg, params, prefix_cache=True, **kw),
        "spec_decode": lambda: PagedServer(cfg, params, spec_decode={"enable": True}, **kw),
        "generate": lambda: decode.generate(cfg, params, tokens, 4),
        "tensor_parallel": lambda: decode.build_ragged_step(cfg, SLOTS, 1, PAGE, attn_impl="xla", tp=SimpleNamespace(degree=2, quantized_allreduce=False, quantized_weights=False, comm_chunks=2, cache_key=lambda: 2)),
    }
    with pytest.raises(NotImplementedError, match="window_latent|sparse_latent|latent"):
        calls[feature]()
