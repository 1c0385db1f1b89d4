"""A model with sliding-window layers through the paged server
(``inference/hybrid_decode.py``): full layers on KV pages, window layers with
sink biases on a per-slot page ring with a KV-head count of their own, keys
wider than values, rotary positions with a theta a kind, a leading dense layer
in front of the routed ones, one chip's share of the experts. Everything is
compared with the plain reference (``benchmark/reference/mimo_v2_decoder.py``:
float32, masks from positions, the sink an appended column, the experts a
loop) on seeded weights at a toy size (window 8, page 8, ring 3), LOGITS and
not tokens.

Tolerances. The toy model runs in float32 on the CPU, where the program and
the reference differ by the order of their sums alone: logits of standard
deviation ~0.17 agree to a few 1e-6 (measured 4e-6 at 7 layers); the limit is
5e-5. The bfloat16 run rounds every activation to 8 bits of significand: its
limit is 0.03, a fifth of the logits' spread (measured 0.008).
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
from deepspeed_tpu.inference.kv_pool import PagePool, key_lanes, window_ring_pages
from deepspeed_tpu.inference.scheduler import PagedServer
from deepspeed_tpu.models.hybrid_moe import HybridMoETransformerLM, mimo_v2_config
from tests.unit.inference.hybrid_toys import _clear_jax_caches, _compiled_programs_live_as_long_as_the_file, apply_logits, seeded  # noqa: F401 (the two fixtures are taken by their import)

REFERENCE = load_module("reference", "mimo_v2_decoder")
PAGE, SLOTS, CHUNK, MAXLEN = 8, 4, 16, 96
RING = window_ring_pages(8, PAGE, CHUNK)
F32_TOL = 5e-5


def _model(dtype="float32", **kw):
    cfg = mimo_v2_config("tiny", dtype=dtype, **kw)
    lm = HybridMoETransformerLM(cfg)
    params = seeded(lm)
    # trained-like sinks: of the size of a score, so that the column carries weight
    params["periods"]["window"]["sinks"] = jax.random.normal(jax.random.PRNGKey(1), params["periods"]["window"]["sinks"].shape)
    section = {"kwargs": {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}}
    return cfg, lm, params, section


_FORWARDS = {}  # (id of the config, token tile) -> (the config, kept alive for its id; its jitted forward)


class Driver:
    """Rows stepped by hand through ``hybrid_forward``: what the scheduler
    does, with the logits kept."""

    def __init__(self, cfg, params, dtype=jnp.float32):
        self.cfg, self.params = cfg, params
        maxp = MAXLEN // PAGE
        shapes = hybrid_decode.state_shapes(cfg, SLOTS)
        pool = PagePool(cfg, SLOTS * maxp + 1, PAGE, SLOTS, max_seq_len=MAXLEN, dtype=dtype, prefill_chunk=CHUNK)
        assert pool.window_ring == RING == 3
        self.pools = [pool.cache.k_pages, pool.cache.v_pages, jnp.zeros(shapes.state, jnp.float32), jnp.zeros(shapes.conv, dtype)]
        self.rings = (pool.states.window_k, pool.states.window_v)
        self.table = np.stack([1 + s * maxp + np.arange(maxp) for s in range(SLOTS)]).astype(np.int32)
        self.lengths = np.zeros(SLOTS, np.int32)
        key = (id(cfg), decode.token_tile(cfg))  # drivers of one model share its two compiled programs
        if key not in _FORWARDS:
            _FORWARDS[key] = (cfg, jax.jit(lambda p, *a, window: hybrid_decode.hybrid_forward(cfg, p, *a, attn_impl="xla", window=window)))
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
        logits, *self.pools, _, self.rings = self.forward(self.params, tokens, *self.pools, table, lengths, q_lens, slots, window=self.rings)
        out = {}
        for r, s in enumerate(order):
            out[s] = np.asarray(logits[r, : q_lens[r]], np.float32)
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


@pytest.fixture(scope="module")
def toy():
    return _model()


def _reference(section, params, seq):
    """The reference's logits [len, V] of one sequence, computed at MAXLEN (padded behind: the model is causal and
    routes token by token, so what follows a position does not move its logits): the reference compiles for ONE shape
    a model, not one a sequence (ten shapes in this file before)."""
    padded = np.zeros((1, MAXLEN), np.int32)
    padded[0, : seq.size] = seq
    return np.asarray(REFERENCE.logits(section, params, padded))[0, : seq.size]


def _reference_logits(section, params, seqs):
    return {s: _reference(section, params, seq) for s, seq in seqs.items()}


def test_a_leading_dense_layer_and_the_period_scan_are_the_unrolled_stack(toy):
    """``apply`` (the leading layer, then a scan over periods whose body holds
    six layers) against the reference, which walks the seven layers one by
    one: the stacking by kind and the prologue index the same weights."""
    cfg, lm, params, section = toy
    tokens = _sequences(7, lens=(50,))[0][None]
    assert cfg.period == ("window",) * 5 + ("softmax",) and cfg.num_periods == 1 and cfg.num_moe_layers == 6
    assert np.abs(apply_logits(lm, params, tokens) - np.asarray(REFERENCE.logits(section, params, tokens))).max() < F32_TOL
    # and two periods behind the leading layer
    cfg2, lm2, params2, section2 = _model(num_layers=13, layer_types=["softmax"] + (["window"] * 5 + ["softmax"]) * 2)
    assert cfg2.num_periods == 2
    assert np.abs(apply_logits(lm2, params2, tokens) - np.asarray(REFERENCE.logits(section2, params2, tokens))).max() < F32_TOL


@pytest.mark.parametrize("tiled", [False, True], ids=["slab", "token_tiles"])
def test_served_logits_match_the_reference(toy, tiled, monkeypatch):
    """Prefill in chunks beside decoding rows, then decode, through both paged
    caches, rows and slots in different orders, contexts of up to ten windows
    and more than three times round the ring of 24 positions: every position's
    logits are the reference's full forward's."""
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


def test_bf16_serving(monkeypatch):
    """The served type: bfloat16 weights, activations and pages. The
    reference reads the same rounded weights in float32."""
    cfg, _, params, section = _model("bfloat16")
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    seqs = _sequences(1, lens=(60, 9))
    got = Driver(cfg, params, jnp.bfloat16).run(seqs, decode_from={0: 41, 1: 4})
    want = _reference_logits(section, params, seqs)
    for s in seqs:
        assert np.abs(got[s] - want[s]).max() < 0.03, s


def test_a_readmitted_row_overwrites_what_its_ring_held(toy):
    """Preemption frees the slot and the row prefills again from position 0:
    what the slot's ring holds of its last tenant lies past the new row's
    length or outside its window, so the resumed row's logits are an
    undisturbed row's."""
    cfg, _, params, _ = toy
    seqs = _sequences(3, lens=(30, 75))
    undisturbed = Driver(cfg, params).run({1: seqs[0]}, decode_from={1: 22})[1]
    driver = Driver(cfg, params)
    driver.run({1: seqs[1]}, decode_from={1: 40})  # another request's keys are left in slot 1's ring
    assert float(jnp.abs(driver.rings[0][:, 1 + RING : 1 + 2 * RING]).max()) > 0
    driver.lengths[1] = 0  # the slot is freed and given to the resumed row
    resumed = driver.run({1: seqs[0]}, decode_from={1: 22})[1]
    assert np.abs(resumed - undisturbed).max() < F32_TOL


def test_the_ring_is_sized_by_the_chunk_grid():
    """Chunks start on the chunk grid, and a chunk of whole pages therefore on
    a page boundary: its walk spans the chunk's pages and those of the
    ``window - 1`` keys before it. A chunk that is no whole number of pages
    may start inside one, and the pool takes the one page more."""
    assert window_ring_pages(128, 64, 128) == 4
    assert window_ring_pages(8, 8, 16) == 3
    assert window_ring_pages(8, 8, 12) == 4
    assert key_lanes(192) == 256 and key_lanes(128) == 128 and key_lanes(24) == 24
    with pytest.raises(ValueError, match="prefill_chunk"):
        PagePool(mimo_v2_config("tiny"), 9, PAGE, SLOTS, max_seq_len=MAXLEN)


def _server(lm, params, **kw):
    eng = ds.init_inference(lm, dtype="fp32", paged_kv={"page_size": PAGE, "max_slots": SLOTS, "prefill_chunk": CHUNK, "max_seq_len": MAXLEN, **kw})
    eng.set_params(params)
    return eng


def test_the_engine_serves_it_with_two_programs_and_preemption_changes_nothing(toy):
    """``init_inference`` -> ``serve``: two compiled programs; the routed
    layers' assignments counted (six layers, not seven); the window layers'
    rings in the memory report, the same size whatever the rows' contexts; and
    with a pool too small for its rows (preempted and resumed rows) the
    streams are those of a pool that never preempts."""
    cfg, lm, params, section = toy
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (37, 5, 20, 16, 50, 3)]
    budgets = [40, 20, 9, 60, 7, 30]
    eng = _server(lm, params)
    before = eng.memory_report(enforce=False) if eng._paged_server is not None else None
    outs = eng.serve(prompts, max_new_tokens=budgets)
    assert sorted(eng.compile_stats()) == ["paged_ragged_r4_w1", "paged_ragged_r4_w16"]
    stats = eng._paged_server.stats
    assert stats["preempted"] == 0
    assert stats["moe_routed_assignments"] == (sum(p.size for p in prompts) + sum(budgets) - len(prompts)) * 6 * cfg.moe_top_k
    assert 0.1 < stats["moe_assignments"] / stats["moe_routed_assignments"] < 0.45  # 4 of 16 held
    pool = eng._paged_server.pool
    assert pool.cache.k_pages.shape == (2, SLOTS * (MAXLEN // PAGE) + 1, 2, PAGE, 24)
    assert pool.cache.v_pages.shape[-1] == 16
    assert pool.states.window_k.shape == (5, 1 + SLOTS * RING, 4, PAGE, 24) and pool.states.window_v.shape[-1] == 16
    report = eng.memory_report(enforce=False)
    ring = next(b for b in report["entries"] if b["name"] == "window_kv")
    assert ring["per_chip_bytes"] == pool.states.window_bytes() == 5 * (1 + SLOTS * RING) * 4 * PAGE * (24 + 16) * 4
    # a row's share of it is ring pages a slot, whatever its context
    assert ring["detail"]["window_bytes_per_slot"] == 5 * RING * 4 * PAGE * (24 + 16) * 4
    assert before is None or next(b for b in before["entries"] if b["name"] == "window_kv")["per_chip_bytes"] == ring["per_chip_bytes"]
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
    spans = tight.observability(analysis=False)["timeline"]["phases"]
    assert "serve.pack" in spans


@pytest.mark.parametrize("feature", ["prefix_cache", "spec_decode", "generate", "beam_generate", "rollback", "attach_prefix", "train", "tensor_parallel"])
def test_what_assumes_a_rows_pages_hold_its_whole_past_is_refused(toy, feature):
    """Each raises where it is built, naming what the ring does not keep."""
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
    with pytest.raises(NotImplementedError, match="window|not supported"):
        calls[feature]()


def test_the_other_hybrid_models_program_has_no_ring():
    """A config without window layers: the store's ring fields are ``None``,
    which are no parameters of its program, and nothing is sized for them."""
    from deepspeed_tpu.models.hybrid_moe import solar_open2_config

    cfg = solar_open2_config("tiny", dtype="float32")
    pool = PagePool(cfg, 9, PAGE, SLOTS, max_seq_len=MAXLEN)
    assert pool.states.window_k is None and pool.window_ring == 0
    assert len(jax.tree_util.tree_leaves(pool.states)) == 2
    assert "window_total_bytes" not in pool.memory_report()
