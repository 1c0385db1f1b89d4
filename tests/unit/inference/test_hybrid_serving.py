"""A model with layers of more than one kind through the paged server
(``inference/hybrid_decode.py``): softmax layers on KV pages, gated delta-rule
layers on the per-slot state store, a routed FFN that holds a share of its
router's experts plus a shared expert. Everything is compared with the plain
reference (``benchmark/reference/solar_open2_decoder.py``: float32, the
recurrence a plain scan, the experts a loop) on seeded weights at a toy size,
LOGITS and not tokens.

Tolerances. The toy model runs in float32 on the CPU, where the program and
the reference differ by the order of their sums alone: logits of standard
deviation 0.16 agree to ~2e-6 (measured, 8 layers); the limit is 5e-5. The
bfloat16 run keeps float32 state but rounds every activation to 8 bits of
significand: its limit is 0.03, 20% of the logits' spread (measured 0.006).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.files import load_module
from deepspeed_tpu.inference import decode, hybrid_decode
from deepspeed_tpu.inference.scheduler import PagedServer
from deepspeed_tpu.models.hybrid_moe import HybridMoETransformerLM, solar_open2_config
from tests.unit.inference.hybrid_toys import _clear_jax_caches, _compiled_programs_live_as_long_as_the_file, apply_logits, seeded  # noqa: F401 (the two fixtures are taken by their import)

REFERENCE = load_module("reference", "solar_open2_decoder")
PAGE, SLOTS, CHUNK, MAXLEN = 8, 4, 16, 96
F32_TOL = 5e-5


def _model(dtype="float32", **kw):
    cfg = solar_open2_config("tiny", num_layers=8, dtype=dtype, **kw)
    lm = HybridMoETransformerLM(cfg)
    params = seeded(lm)
    section = {"kwargs": {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}}
    return cfg, lm, params, section


_FORWARDS = {}  # (id of the config, token tile) -> (the config, kept alive for its id; its jitted forward)


class Driver:
    """Rows stepped by hand through ``hybrid_forward``: what the scheduler
    does, with the logits kept."""

    def __init__(self, cfg, params, dtype=jnp.float32):
        self.cfg, self.params = cfg, params
        maxp = MAXLEN // PAGE
        n_pages = SLOTS * maxp + 1
        shapes = hybrid_decode.state_shapes(cfg, SLOTS)
        pages = (cfg.layers_of("softmax"), n_pages, cfg.num_kv_heads, PAGE, cfg.head_dim)
        self.pools = [jnp.zeros(pages, dtype), jnp.zeros(pages, dtype), jnp.zeros(shapes.state, jnp.float32), jnp.zeros(shapes.conv, dtype)]
        self.table = np.stack([1 + s * maxp + np.arange(maxp) for s in range(SLOTS)]).astype(np.int32)
        self.lengths = np.zeros(SLOTS, np.int32)
        key = (id(cfg), decode.token_tile(cfg))  # drivers of one model share its two compiled programs
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


def _sequences(seed=0, lens=(37, 5, 52, 20)):
    rng = np.random.default_rng(seed)
    return {s: rng.integers(0, 512, n).astype(np.int32) for s, n in enumerate(lens)}


@pytest.fixture(scope="module")
def toy():
    return _model()


def _reference_logits(section, params, seqs):
    """At MAXLEN whatever the sequences' lengths (padded behind: the model is causal and routes token by token, so
    what follows a position does not move its logits): the reference compiles for ONE shape a file, not one a test."""
    tokens = np.zeros((len(seqs), MAXLEN), np.int32)
    for i, seq in enumerate(seqs.values()):
        tokens[i, : len(seq)] = seq
    lg = np.asarray(REFERENCE.logits(section, params, tokens))
    return {s: lg[i, : len(seq)] for i, (s, seq) in enumerate(seqs.items())}


@pytest.mark.parametrize("tiled", [False, True], ids=["slab", "token_tiles"])
def test_served_logits_match_the_reference(toy, tiled, monkeypatch):
    """Prefill in chunks beside decoding rows, then decode, through both
    pools, rows and slots in different orders: every position's logits are
    the reference's full forward's. ``token_tiles``: the wide window packed
    and computed in tiles of 16 tokens (the production path of a 64 x 128
    window), a tile's tail dead."""
    cfg, _, params, section = toy
    if tiled:
        monkeypatch.setattr(decode, "DENSE_TOKEN_TILE", 16)
        assert decode.token_tile(cfg) == 16 < SLOTS * CHUNK
    seqs = _sequences()
    got = Driver(cfg, params).run(seqs, decode_from={0: 30, 1: 3, 2: 41, 3: 20})
    want = _reference_logits(section, params, seqs)
    for s in seqs:
        assert got[s].shape == want[s].shape
        assert np.abs(got[s] - want[s]).max() < F32_TOL, s


def test_bf16_serving_keeps_float32_state(monkeypatch):
    """The served type: bfloat16 weights and activations, float32 state. The
    reference reads the same rounded weights in float32."""
    cfg, _, params, section = _model("bfloat16")
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    seqs = _sequences(1, lens=(40, 9))
    driver = Driver(cfg, params, jnp.bfloat16)
    got = driver.run(seqs, decode_from={0: 33, 1: 4})
    assert driver.pools[2].dtype == jnp.float32
    want = _reference_logits(section, params, seqs)
    for s in seqs:
        assert np.abs(got[s] - want[s]).max() < 0.03, s


def test_a_decode_row_in_a_wide_window_leaves_the_narrow_programs_state(toy):
    """A row that decodes while another prefills rides in the wide window:
    its state and its logits are those of the narrow program."""
    cfg, _, params, _ = toy
    seqs = _sequences(2, lens=(24, 40))
    alone = Driver(cfg, params)
    a = alone.run({0: seqs[0]}, decode_from={0: 16})[0]
    both = Driver(cfg, params)
    # slot 1 arrives when slot 0 is decoding: its chunks make slot 0's steps wide
    first = both.run({0: seqs[0][:18]}, decode_from={0: 16})[0]
    rest = both.run({0: seqs[0][18:], 1: seqs[1]}, decode_from={0: 0, 1: 36})
    assert np.abs(np.concatenate([first, rest[0]]) - a).max() < F32_TOL
    assert np.abs(np.asarray(both.pools[2][:, 0]) - np.asarray(alone.pools[2][:, 0])).max() < 1e-5


def test_a_readmitted_row_starts_from_zero_state_inside_the_program(toy):
    """Preemption frees the slot and the row prefills again from position 0:
    whatever the slot's state and tail held is not read, so the resumed row's
    logits are an undisturbed row's."""
    cfg, _, params, _ = toy
    seqs = _sequences(3, lens=(30, 45))
    undisturbed = Driver(cfg, params).run({1: seqs[0]}, decode_from={1: 22})[1]
    driver = Driver(cfg, params)
    driver.run({1: seqs[1]}, decode_from={1: 40})  # another request's state is left in slot 1
    assert float(jnp.abs(driver.pools[2][:, 1]).max()) > 0
    driver.lengths[1] = 0  # the slot is freed and given to the resumed row
    resumed = driver.run({1: seqs[0]}, decode_from={1: 22})[1]
    assert np.abs(resumed - undisturbed).max() < F32_TOL


def test_a_dead_row_leaves_every_state_alone(toy):
    cfg, _, params, _ = toy
    driver = Driver(cfg, params)
    driver.run(_sequences(4, lens=(20, 17, 9)), decode_from={0: 16, 1: 10, 2: 5})
    before = [np.asarray(p) for p in driver.pools]
    driver.step({3: np.arange(5)}, CHUNK)
    driver.step({3: np.arange(1)}, 1)
    for b, a in zip(before[2:], driver.pools[2:]):
        assert np.array_equal(b[:, :3], np.asarray(a)[:, :3])


def _server(lm, params, **kw):
    eng = ds.init_inference(lm, dtype="fp32", paged_kv={"page_size": PAGE, "max_slots": SLOTS, "prefill_chunk": CHUNK, "max_seq_len": MAXLEN, **kw})
    eng.set_params(params)
    return eng


@pytest.mark.parametrize("on_a_tpu, paths", [(True, ["kernel", "kernel"]), (False, ["sorted", "sorted"])], ids=["tpu", "cpu"])
def test_the_engine_records_the_route_plans_form_where_it_builds_the_server(toy, monkeypatch, on_a_tpu, paths):
    """``moe.route_plan``, once a shape: the narrow program routes its 64
    rows in one call, the mixed one (64 x 16 slots, above a token tile) a
    tile of 512 at a time; the path is ``plan_path``'s, the question
    ``route_plan`` asks where the programs are traced. Nothing is compiled."""
    from deepspeed_tpu.moe import route_plan

    cfg, lm, params, _ = toy
    monkeypatch.setattr(route_plan, "on_tpu", lambda: on_a_tpu)
    eng = _server(lm, params, max_slots=64)
    eng._build_paged_server()
    events = [s["attrs"] for s in eng.tracer.spans() if s["name"] == "moe.route_plan"]
    combine = "live_rows" if on_a_tpu else "gather"  # the two ways between token order and expert order go with the plan
    assert events == [
        {"path": paths[0], "S": 64, "E": cfg.moe_router_experts, "k": cfg.moe_top_k, "blocks": int(on_a_tpu), "combine": combine},
        {"path": paths[1], "S": 512, "E": cfg.moe_router_experts, "k": cfg.moe_top_k, "blocks": int(on_a_tpu), "combine": combine},
    ]
    assert decode.routed_rows(cfg, 64, 16) == decode.token_tile(cfg) == 512 and decode.routed_rows(cfg, 4, 16) == 64
    assert eng.compile_stats() == {}


def test_the_engine_serves_it_with_two_programs_and_preemption_changes_nothing(toy):
    """``init_inference`` -> ``serve``: two compiled programs, the held and
    all routed assignments counted, the state store in the memory report;
    and with a pool too small for its rows (preempted and resumed rows) the
    streams are those of a pool that never preempts."""
    cfg, lm, params, section = toy
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (37, 5, 20, 16, 50, 3)]
    budgets = [12, 20, 9, 15, 7, 30]
    eng = _server(lm, params)
    outs = eng.serve(prompts, max_new_tokens=budgets)
    assert sorted(eng.compile_stats()) == ["paged_ragged_r4_w1", "paged_ragged_r4_w16"]
    stats = eng._paged_server.stats
    assert stats["preempted"] == 0
    assert stats["moe_routed_assignments"] == (sum(p.size for p in prompts) + sum(budgets) - len(prompts)) * cfg.num_layers * cfg.moe_top_k
    assert 0.3 < stats["moe_assignments"] / stats["moe_routed_assignments"] < 0.7  # 4 of 8 held
    pool = eng._paged_server.pool
    assert pool.cache.k_pages.shape[0] == cfg.layers_of("softmax") == 2
    assert pool.states.state.shape == (6, SLOTS + 1, 8, 16, 16) and pool.states.state.dtype == jnp.float32
    report = eng.memory_report(enforce=False)
    assert any(b["name"] == "recurrent_state" and b["per_chip_bytes"] == pool.states.hbm_bytes() for b in report["entries"])
    # every served token is the reference's arg-max at its position (float32, no near-tie at this size)
    lg = _reference_logits(section, params, dict(enumerate(outs)))
    for i, (p, o) in enumerate(zip(prompts, outs)):
        gap = lg[i][p.size - 1 : o.size - 1].max(-1) - np.take_along_axis(lg[i][p.size - 1 : o.size - 1], o[p.size :, None], -1)[:, 0]
        assert gap.max() < F32_TOL, i
    tight = _server(lm, params, num_pages=14)
    squeezed = tight.serve(prompts, max_new_tokens=budgets)
    assert tight._paged_server.stats["preempted"] > 0
    for a, b in zip(outs, squeezed):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("feature", ["prefix_cache", "spec_decode", "generate", "beam_generate", "rollback", "attach_prefix", "train"])
def test_what_assumes_keys_and_values_are_the_only_state_is_refused(toy, feature):
    """Each raises where it is built, naming the missing state snapshot."""
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
    }
    with pytest.raises(NotImplementedError, match="state|not supported"):
        calls[feature]()


def test_a_uniform_models_step_is_built_where_it_always_was():
    """The branch is taken when the program is built, on the config alone: a
    config without ``layer_types`` never imports or enters the hybrid step."""
    from deepspeed_tpu.models.moe_transformer import olmoe_config

    cfg = olmoe_config("tiny")
    assert getattr(cfg, "layer_types", None) is None
    step = decode.build_ragged_step(cfg, 2, 1, 8, attn_impl="xla")
    assert step is decode.build_ragged_step(cfg, 2, 1, 8, attn_impl="xla")


def test_a_share_of_the_experts_takes_the_dense_token_tile():
    """Of a tile's assignments only the held share arrives: no tile in range
    brings a held expert a whole row tile, so the tile is the dense one (the
    uncut layer's ``128 E / k`` would be 5,120 tokens, 25 times a steady mixed
    step's live tokens: measured 1,746 against 2,707 tokens/s). The uncut
    layer keeps the routed rule."""
    share = solar_open2_config("tiny", num_experts=40, moe_router_experts=320, moe_expert_share=(0, 8), moe_top_k=8)
    whole = solar_open2_config("tiny", num_experts=320, moe_router_experts=320, moe_expert_share=(0, 1), moe_top_k=8)
    assert decode.token_tile(share) == decode.DENSE_TOKEN_TILE == 512
    assert decode.token_tile(whole) == 5120


def test_a_hybrid_config_without_linear_layers_serves_with_an_empty_state_store():
    """``layer_types`` all ``softmax``: the same step, a store of no layers,
    and nothing refused for a state that does not exist."""
    cfg = solar_open2_config("tiny", num_layers=2, layer_types=["softmax", "softmax"], dtype="float32")
    lm = HybridMoETransformerLM(cfg)
    params = seeded(lm)
    eng = _server(lm, params)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (20, 5)]
    outs = eng.serve(prompts, max_new_tokens=[6, 6])
    assert eng._paged_server.pool.states.state.shape[0] == 0
    tokens = np.zeros((2, 32), np.int32)
    for i, o in enumerate(outs):
        tokens[i, : o.size] = o
    lg = apply_logits(lm, params, tokens)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        assert [int(lg[i, p.size - 1 + j].argmax()) for j in range(6)] == [int(t) for t in o[p.size :]]
    assert eng._paged_server.pool.rollback(0, 0) == 0


# --- what a wide window's token buffers hold past the live tiles ----------------
def _one_kind(kind):
    """The smallest toy with a routed layer of ``kind``."""
    from deepspeed_tpu.models.hybrid_moe import glm4_moe_lite_config, mimo_v2_config

    if kind in ("softmax", "linear"):  # Solar-Open2's: a gated softmax layer, a delta-rule layer
        return solar_open2_config("tiny", num_layers=1, layer_types=[kind], dtype="float32")
    if kind == "window":  # MiMo's, with sinks, behind a leading dense layer of full attention
        return mimo_v2_config("tiny", num_layers=2, layer_types=["softmax", "window"], dtype="float32")
    return glm4_moe_lite_config("tiny", num_layers=2, dtype="float32")  # a leading dense layer, a routed one


# (tokens in the row's window, tokens it holds already) of each row
WINDOWS = {
    # 13 live tokens of the 16-token tile, the chunk row last: the tile's dead tail counts as that row's
    "a_dead_tail_in_the_live_tile": ((1, 9), (0, 0), (1, 3), (11, 16)),
    # the one tile full: the dead row's first packed token and the chunk's last two window slots lie PAST the live tiles
    "a_full_tile": ((1, 9), (14, 16), (1, 3), (0, 0)),
    # two chunk rows over two tiles, the second tile partly dead
    "two_chunks_two_tiles": ((16, 0), (1, 20), (7, 32), (0, 0)),
}


@pytest.mark.parametrize("kind", ["softmax", "window", "linear", "latent"])
def test_what_lies_past_the_live_tiles_reaches_nothing(kind, monkeypatch):
    """A wide window's per-layer token buffers are not filled
    (``hybrid_decode.unfilled``: on the chip whatever the allocator hands
    over; XLA's CPU lowering writes zeros). With NaN in every row nothing
    writes, the step's greedy tokens, routing counts and EVERY pool, trash
    page and spare slot included, are bit for bit those of zero-filled
    buffers, and all finite: each read of such a buffer is a live tile's slice
    or stands behind a select on the token being real. One chunk row, one-token
    rows and a dead row; a dead tail inside the live tile, a full tile, two."""
    from deepspeed_tpu.inference.kv_pool import StateStore, key_lanes, window_ring_pages

    cfg = _one_kind(kind)
    params = seeded(HybridMoETransformerLM(cfg))
    monkeypatch.setattr(decode, "DENSE_TOKEN_TILE", 16)
    assert decode.token_tile(cfg) == 16 < SLOTS * CHUNK and kind in cfg.period
    maxp = MAXLEN // PAGE
    n_pages = SLOTS * maxp + 1
    rng = np.random.default_rng(7)

    def pool(shape):  # what earlier steps left: anything finite
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    pages = (cfg.layers_of("softmax"), n_pages, cfg.num_kv_heads, PAGE)
    shapes = hybrid_decode.state_shapes(cfg, SLOTS)
    rings, latent = (None, None), None
    if kind == "window":
        ring = window_ring_pages(cfg.window, PAGE, CHUNK)
        rings = tuple(pool(shape) for shape in hybrid_decode.window_shapes(cfg, SLOTS, PAGE, ring))
    if kind == "latent":
        latent = pool((cfg.layers_of("latent"), n_pages, PAGE, key_lanes(cfg.latent_width)))
    pools = (pool(pages + (key_lanes(cfg.head_dim),)), pool(pages + (cfg.v_head_dim,)),
             StateStore(pool(shapes.state), pool(shapes.conv), *rings, latent))
    own_pages = np.stack([1 + s * maxp + np.arange(maxp) for s in range(SLOTS)]).astype(np.int32)

    def program(fill):
        monkeypatch.setattr(decode, "_paged_program_cache", {})
        monkeypatch.setattr(hybrid_decode, "unfilled", lambda shape, dtype: jnp.full(shape, fill, dtype))
        return decode.build_ragged_step(cfg, SLOTS, CHUNK, PAGE, attn_impl="xla")

    zeros, garbage = program(0), program(np.nan)
    for name, windows in WINDOWS.items():
        q_lens, lengths = (np.asarray(a, np.int32) for a in zip(*windows))
        tokens = rng.integers(0, 512, (SLOTS, CHUNK)).astype(np.int32)
        slots = np.where(q_lens > 0, (np.arange(SLOTS) + 1) % SLOTS, SLOTS).astype(np.int32)  # row and slot differ
        table = np.where((q_lens > 0)[:, None], own_pages[slots % SLOTS], -1).astype(np.int32)
        # the step donates its pools: each run takes copies
        want, got = (
            jax.tree_util.tree_leaves(step(params, tokens, *jax.tree_util.tree_map(jnp.copy, pools), table, lengths, q_lens, slots))
            for step in (zeros, garbage)
        )
        assert np.asarray(want[0])[SLOTS, 0] > 0, name  # the routing counts ride in the result: live assignments
        for a, b in zip(want, got):
            assert np.isfinite(np.asarray(b, np.float32)).all(), name
            assert np.array_equal(np.asarray(a), np.asarray(b)), name
