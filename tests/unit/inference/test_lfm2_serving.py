"""A model whose mixers are gated short convolutions (all a row carries is a
tail of two gated products: ``kv_pool.StateStore`` with a tail and NO state)
beside rotary GQA layers with an RMSNorm a head on q and k, two leading dense
layers of the conv kind, a routed FFN that holds a share of a sigmoid router's
experts, and a layer list that ends in a PARTIAL period (``[a c c c] x 2 + [a
c]`` behind two leading ``c``: two scanned periods and two trailing layers
with leaves of their own), through the paged server
(``inference/hybrid_decode.py``). Everything is compared with the plain
reference (``benchmark/reference/lfm2_moe_decoder.py``: float32, the
convolution a sum of three shifted arrays, full causal attention, every held
expert over all tokens behind a mask) on seeded weights at the ``tiny`` size,
LOGITS and not tokens.

Tolerances. The toy model runs in float32 on the CPU, where the program and
the reference differ by the order of their sums, by the sorted rows of the
routed FFN and by the ``1e-6`` in the gates' denominator that the reference
writes and the program's router does not (below 1e-6 relative): logits of
standard deviation ~0.25 agree to ~1e-6 (measured); the limit is 2e-5, and
every wrong block below moves them by more than a hundred times that. One
server, one driver's forward and one set of weights are built a module and
shared by the cases.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.files import load_module
from deepspeed_tpu.inference import decode, hybrid_decode
from deepspeed_tpu.inference.kv_pool import PagePool
from deepspeed_tpu.inference.scheduler import PagedServer
from deepspeed_tpu.models import hybrid_moe as hm
from deepspeed_tpu.models.config import TransformerConfig
from deepspeed_tpu.models.hybrid_moe import HybridMoETransformerLM, lfm2_moe_config
from tests.unit.inference.hybrid_toys import _clear_jax_caches, _compiled_programs_live_as_long_as_the_file, apply_logits, seeded  # noqa: F401 (the two fixtures are taken by their import)

REFERENCE = load_module("reference", "lfm2_moe_decoder")
PAGE, SLOTS, CHUNK, MAXLEN = 8, 4, 16, 96
F32_TOL = 2e-5
A, C = "softmax", "conv"


def section_of(cfg):
    return {"kwargs": {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}}


def toy_model(**kw):
    """(config, model, parameters, the reference's ``model`` section): one
    jitted ``init``, then trained-like scales: init's depth-scaled output
    projections leave the tied table's own row the winner of every arg-max,
    its router scores all 0.5 and its selection bias too small to move a
    choice; and the head norms' scales are drawn off one, so that a norm left
    out, or over the wrong features, shows."""
    cfg = lfm2_moe_config("tiny", dtype="float32", **kw)
    lm = HybridMoETransformerLM(cfg)
    rng = np.random.default_rng(3)
    factor = {"wo": 10.0, "w_out": 10.0, "wq": 4.0, "wk": 4.0, "wg": 20.0, "bias": 10.0}

    def trained_like(path, leaf):
        name = getattr(path[-1], "key", None)
        if name in ("q_norm_scale", "k_norm_scale"):
            return leaf * jnp.asarray(rng.uniform(0.5, 2.0, leaf.shape), jnp.float32)
        return leaf * factor.get(name, 1.0)

    params = jax.tree_util.tree_map_with_path(trained_like, seeded(lm))
    return cfg, lm, params, section_of(cfg)


@pytest.fixture(scope="module")
def toy():
    return toy_model()


_FORWARDS = {}  # id of the config -> (the config, kept alive for its id; its jitted forward)


class Driver:
    """Rows stepped by hand through ``hybrid_forward``: what the scheduler
    does, with the logits and the routing counts kept."""

    def __init__(self, cfg, params):
        self.cfg, self.params = cfg, params
        maxp = MAXLEN // PAGE
        pool = PagePool(cfg, SLOTS * maxp + 1, PAGE, SLOTS, max_seq_len=MAXLEN, dtype=jnp.float32)
        assert pool.states.state is None  # a tail and no state: no array, no parameter of the program
        self.pools = [pool.cache.k_pages, pool.cache.v_pages, None, pool.states.conv]
        self.table = np.stack([1 + s * maxp + np.arange(maxp) for s in range(SLOTS)]).astype(np.int32)
        self.lengths = np.zeros(SLOTS, np.int32)
        self.counts = np.zeros((cfg.num_moe_layers, cfg.num_experts), np.int64)
        if id(cfg) not in _FORWARDS:
            _FORWARDS[id(cfg)] = (cfg, jax.jit(lambda p, *a: hybrid_decode.hybrid_forward(cfg, p, *a, attn_impl="xla")))
        self.forward = _FORWARDS[id(cfg)][1]

    def step(self, windows, width):
        order = sorted(windows, key=lambda s: (s * 7) % 5)  # row and slot differ
        tokens = np.zeros((SLOTS, width), np.int32)
        q_lens = np.zeros(SLOTS, np.int32)
        slots = np.full(SLOTS, SLOTS, np.int32)
        table = np.full_like(self.table, -1)
        lengths = np.zeros(SLOTS, np.int32)
        for r, s in enumerate(order):
            w = np.asarray(windows[s], np.int32)
            tokens[r, : w.size], q_lens[r], slots[r], table[r], lengths[r] = w, w.size, s, self.table[s], self.lengths[s]
        logits, *self.pools, counts = self.forward(self.params, tokens, *self.pools, table, lengths, q_lens, slots)
        self.counts += np.asarray(counts)
        out = {}
        for r, s in enumerate(order):
            out[s] = np.asarray(logits[r, : q_lens[r]], np.float32)
            self.lengths[s] += q_lens[r]
        return out

    def run(self, seqs, decode_from):
        """Each slot's sequence: prefill ``[: decode_from[s]]`` in chunks of
        CHUNK beside whatever else is running, then one token a step."""
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


def padded(seq):
    out = np.zeros((1, MAXLEN), np.int32)
    out[0, : seq.size] = seq
    return out


def reference_logits(section, params, seq):
    """The reference's logits [len, V] of one sequence, computed at MAXLEN
    (padded behind: the model is causal), so that its jitted parts compile
    for one length."""
    return np.asarray(REFERENCE.logits(section, params, padded(seq)))[0, : seq.size]


# --- the configuration ---------------------------------------------------------------


def test_the_preset_is_the_published_model_and_its_list_ends_in_a_partial_period():
    cfg = lfm2_moe_config()
    assert (cfg.num_layers, cfg.hidden_size, cfg.vocab_size, cfg.tie_embeddings, cfg.activation, cfg.norm_eps) == (40, 2048, 65536, True, "swiglu", 1e-5)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.qk_norm, cfg.position, cfg.rope_theta, cfg.rope_dim) == (32, 8, 64, "head", "rope", 1e6, None)
    assert (cfg.conv_kernel, cfg.leading_dense_layers, cfg.intermediate_size, cfg.expert_intermediate_size) == (3, 2, 11776, 1536)
    assert (cfg.num_experts, cfg.moe_router_experts, cfg.moe_top_k, cfg.moe_shared_experts) == (64, 64, 4, 0)
    assert (cfg.moe_scoring, cfg.moe_select_bias, cfg.moe_norm_topk_prob, cfg.moe_routed_scaling) == ("sigmoid", True, True, 1.0)
    assert [i for i, t in enumerate(cfg.layer_types) if t == A] == list(range(2, 40, 4)) and cfg.layer_types[-1] == C
    # behind the two leading layers: [a c c c] x 9 + [a c]: nine scanned periods and a remainder of two, NOT one period of 38
    assert cfg.period == (A, C, C, C) and cfg.num_periods == 9 and cfg.remainder == (A, C)
    assert (cfg.layers_of(C), cfg.layers_of(A), cfg.num_moe_layers, cfg.state_kind) == (30, 10, 38, "conv")
    assert (cfg.leading_of(C), cfg.leading_of(A), cfg.trailing_of(C), cfg.trailing_of(A)) == (2, 0, 29, 9)
    shapes = jax.eval_shape(lambda: HybridMoETransformerLM(cfg).init(jax.random.PRNGKey(0), None))
    count = lambda tree: sum(a.size for a in jax.tree_util.tree_leaves(tree))
    periods = shapes["periods"]
    assert set(periods) == {A, C, "moe"} and len(shapes["leading"]) == len(shapes["trailing"]) == 2 and "lm_head" not in shapes
    assert periods[C]["w_in"].shape == (9, 3, 2048, 6144) and periods[C]["conv_w"].shape == (9, 3, 3, 2048) and periods[C]["wo"].shape == (9, 3, 2048, 2048)
    assert periods[A]["q_norm_scale"].shape == periods[A]["k_norm_scale"].shape == (9, 1, 64)
    assert [set(t) for t in shapes["trailing"]] == [{"mixer", "moe"}] * 2 and [set(t) for t in shapes["leading"]] == [{"mixer", "ffn"}] * 2
    assert shapes["trailing"][0]["mixer"]["wq"].shape == (2048, 2048) and shapes["trailing"][1]["mixer"]["w_in"].shape == (2048, 6144)
    assert shapes["trailing"][1]["moe"]["experts"]["w_gate"].shape == (64, 2048, 1536) and shapes["trailing"][1]["moe"]["gate"]["bias"].shape == (64,)
    # the issue's count: conv 16.78M, attention 10.49M, a dense FFN 72.35M, a routed FFN 604.11M, the tied table 134.2M: 23.84B
    assert (count(periods[C]) // 27, count(periods[A]) // 9, count(periods["moe"]) // 36) == (16_785_408, 10_487_936, 604_112_960)
    assert count(shapes["leading"][0]["ffn"]) == 72_353_792 and round(count(shapes) / 1e9, 2) == 23.84


PERIODS = {
    # the list behind the leading layers -> (the period, whole periods, the remainder)
    "two_periods_and_a_part": ([A, C, C, C] * 2 + [A, C], (A, C, C, C), 2, (A, C)),
    "whole_periods": ([A, C, C, C] * 2, (A, C, C, C), 2, ()),
    "a_part_behind_one_period_repeats_nothing": ([A, C, C, C, A, C], (A, C, C, C, A, C), 1, ()),
    "one_kind": ([C] * 5, (C,), 5, ()),
    "a_break_in_the_middle": ([A, C, C, A, C, C, C], (A, C, C, A, C, C, C), 1, ()),
}


@pytest.mark.parametrize("name", PERIODS)
def test_the_period_is_the_shortest_prefix_repeated_whole_and_then_in_part(name):
    body, period, whole, remainder = PERIODS[name]
    cfg = lfm2_moe_config("tiny", num_layers=2 + len(body), layer_types=[C, C] + body)
    assert (cfg.period, cfg.num_periods, cfg.remainder) == (period, whole, remainder)
    assert cfg.leading_dense_layers + cfg.num_periods * len(cfg.period) + len(cfg.remainder) == cfg.num_layers
    shapes = jax.eval_shape(lambda: HybridMoETransformerLM(cfg).init(jax.random.PRNGKey(0), None))
    assert ("trailing" in shapes) == bool(remainder)  # an empty remainder puts nothing into the tree
    assert shapes["periods"]["moe"]["gate"]["wg"].shape[:2] == (whole, len(period))


def test_a_list_that_names_ffn_blocks_keeps_its_one_period():
    """The accepted one-sublayer configuration's sixteen blocks repeat seven of them twice and then two more: its period
    stays the sixteen blocks (its parameter tree and its programs are what they were)."""
    cfg = hm.nemotron_h_config("tiny")
    assert cfg.single_sublayer and len(cfg.period) == 16 and cfg.num_periods == 1 and cfg.remainder == ()


REFUSALS = {
    "conv_and_state_space": (NotImplementedError, "ONE kind", lambda: lfm2_moe_config("tiny", layer_types=[C, C] + [A, C, "ssm", C] * 2 + [A, C], ssm_num_heads=2, ssm_head_dim=64, ssm_state=128)),
    "conv_and_linear": (NotImplementedError, "ONE kind", lambda: lfm2_moe_config("tiny", layer_types=[C, C] + [A, C, "linear", C] * 2 + [A, C])),
    "channels_in_no_whole_lane_tiles": (ValueError, "whole lane tiles of 128", lambda: lfm2_moe_config("tiny", hidden_size=64, head_dim=16)),
    "one_tap": (ValueError, "conv_kernel >= 2", lambda: lfm2_moe_config("tiny", conv_kernel=1)),
    "the_uniform_familys_norm_here": (NotImplementedError, "qk_norm='head'", lambda: lfm2_moe_config("tiny", qk_norm="projection")),
    "the_head_norm_in_the_uniform_family": (NotImplementedError, "multi-kind family", lambda: TransformerConfig(vocab_size=64, hidden_size=32, num_layers=1, num_heads=2, qk_norm="head")),
    "an_unknown_norm": (ValueError, "expected None, 'projection' or 'head'", lambda: lfm2_moe_config("tiny", qk_norm="heads")),
    "an_unknown_kind": (ValueError, "layer_types must name", lambda: lfm2_moe_config("tiny", layer_types=["convolution"] * 12)),
}


@pytest.mark.parametrize("name", REFUSALS)
def test_what_the_config_refuses_and_how_it_says_so(name):
    error, message, build = REFUSALS[name]
    with pytest.raises(error, match=message):
        build()


@pytest.mark.parametrize("feature", ["attach_prefix", "rollback", "train", "generate"])
def test_what_a_tail_only_store_cannot_do_yet_is_refused_with_the_missing_piece_named(toy, feature):
    """A tail cannot be shared, forked or rolled back a page at a time any more than a state can: the same refusals."""
    cfg, lm, params, _ = toy
    tokens = np.arange(1, 17, dtype=np.int32)[None]
    kw = dict(num_pages=SLOTS * (MAXLEN // PAGE) + 1, page_size=PAGE, max_slots=SLOTS, max_seq_len=MAXLEN, prefill_chunk=CHUNK)
    calls = {
        "attach_prefix": lambda: PagedServer(cfg, params, **kw).pool.alloc_slot(8, prefix_tokens=tokens[0]),
        "rollback": lambda: PagedServer(cfg, params, **kw).pool.rollback(0, 1),
        "train": lambda: lm.apply(params, (tokens, tokens), train=True),
        "generate": lambda: decode.generate(cfg, params, tokens, 4),
    }
    with pytest.raises(NotImplementedError, match="convolution's tail|convolution tail|not supported"):
        calls[feature]()


# --- apply ------------------------------------------------------------------------------


def test_apply_is_the_reference(toy):
    """``apply`` (two leading layers, a scan of two periods, two trailing layers; the sorted rows of the routed FFN)
    against the reference, which walks the twelve layers one by one."""
    cfg, lm, params, section = toy
    tokens = sequences(7, lens=(50,))[0][None]
    assert cfg.period == (A, C, C, C) and cfg.num_periods == 2 and cfg.remainder == (A, C) and cfg.num_moe_layers == 10
    want = reference_logits(section, params, tokens[0])
    assert want.std() > 0.1 and np.abs(apply_logits(lm, params, tokens)[0] - want).max() < F32_TOL


def test_the_head_norm_is_over_a_heads_features_before_the_rotation(toy):
    """``attn_heads`` under ``qk_norm="head"`` with non-unit scales, by hand: each head's ``D`` features of q and of k
    over their own root mean square, times the layer's scale ``[D]``, THEN the rotation; v untouched."""
    cfg, _, params, _ = toy
    p = jax.tree_util.tree_map(lambda a: a[1, 0], params["periods"][A])
    rng = np.random.default_rng(2)
    NH, NKV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = (jnp.asarray(rng.standard_normal((1, 3, n * D)) * 3.0, jnp.float32) for n in (NH, NKV, NKV))
    positions = jnp.asarray([[40, 41, 7]], jnp.int32)
    got_q, got_k, got_v = hm.attn_heads(cfg, A, q, k, v, positions, p)

    def by_hand(x, n, scale):
        x = np.asarray(x, np.float64).reshape(1, 3, n, D)
        x = x / np.sqrt((x * x).mean(-1, keepdims=True) + cfg.norm_eps) * np.asarray(scale, np.float64)
        half = D // 2
        angle = np.asarray(positions, np.float64)[..., None] * cfg.rope_theta ** (-np.arange(half) / half)
        cos, sin = np.cos(angle)[:, :, None], np.sin(angle)[:, :, None]
        return np.concatenate([x[..., :half] * cos - x[..., half:] * sin, x[..., :half] * sin + x[..., half:] * cos], -1)

    assert np.abs(np.asarray(p["q_norm_scale"]) - 1).max() > 0.3  # non-unit scales
    assert np.abs(got_q - by_hand(q, NH, p["q_norm_scale"])).max() < 1e-5 and np.abs(got_k - by_hand(k, NKV, p["k_norm_scale"])).max() < 1e-5
    assert np.array_equal(np.asarray(got_v), np.asarray(v).reshape(1, 3, NKV, D))
    plain = hm.attn_heads(dataclasses.replace(cfg, qk_norm=None), A, q, k, v, positions)  # no norm: no scales asked for
    assert np.abs(plain[0] - got_q).max() > 1.0


WRONG = ["b_and_c_swapped", "silu_behind_the_convolution", "the_taps_reversed", "head_norm_left_out", "norm_behind_the_rotation",
         "trailing_layers_left_out", "the_final_norm_on_the_embedding", "no_selection_bias"]


@pytest.mark.parametrize("wrong", WRONG)
def test_a_wrong_block_is_far_outside_the_tolerance(toy, wrong, monkeypatch):
    """What the tolerance is worth: each of these moves the logits by more than a hundred times ``F32_TOL``."""
    cfg, lm, params, section = toy
    tokens = sequences(7, lens=(50,))[0][None]
    want = reference_logits(section, params, tokens[0])[None]
    if wrong == "b_and_c_swapped":

        def swapped(p, h):
            b, c, x = jnp.split(hm.qmatmul(h, p["w_in"]), 3, axis=-1)
            return c * x, b

        monkeypatch.setattr(hm, "conv_inputs", swapped)
    elif wrong == "silu_behind_the_convolution":  # what the state-space kinds' convolution has and this one has not
        conv = hm.gated_conv
        monkeypatch.setattr(hm, "gated_conv", lambda p, tails, u: jax.nn.silu(conv(p, tails, u)))
    elif wrong == "the_taps_reversed":  # the newest tap on the oldest product
        conv = hm.gated_conv
        monkeypatch.setattr(hm, "gated_conv", lambda p, tails, u: conv({"conv_w": p["conv_w"][::-1]}, tails, u))
    elif wrong == "head_norm_left_out":
        lm = HybridMoETransformerLM(dataclasses.replace(cfg, qk_norm=None))
    elif wrong == "norm_behind_the_rotation":  # the norm commutes with the rotation only where its scale is one number
        heads = hm.attn_heads

        def rotated_first(cfg, kind, q, k, v, positions, p=None):
            q, k, v = heads(dataclasses.replace(cfg, qk_norm=None), kind, q, k, v, positions)
            return hm._norm(q, p["q_norm_scale"], None, "rmsnorm", cfg.norm_eps), hm._norm(k, p["k_norm_scale"], None, "rmsnorm", cfg.norm_eps), v

        monkeypatch.setattr(hm, "attn_heads", rotated_first)
    elif wrong == "trailing_layers_left_out":
        lm = HybridMoETransformerLM(dataclasses.replace(cfg, num_layers=10, layer_types=cfg.layer_types[:10]))
        assert lm.config.remainder == () and lm.config.num_periods == 2
    elif wrong == "the_final_norm_on_the_embedding":  # the family calls it embedding_norm; it is over the LAST hidden state
        table = params["embed"]["tokens"]
        normed = hm._norm(table, params["final_norm_scale"], None, "rmsnorm", cfg.norm_eps)
        lm, tied = HybridMoETransformerLM(dataclasses.replace(cfg, tie_embeddings=False)), table
        params = {**params, "embed": {"tokens": normed}, "lm_head": tied.T, "final_norm_scale": jnp.ones_like(params["final_norm_scale"])}
    elif wrong == "no_selection_bias":
        zero = lambda moe: {**moe, "gate": {**moe["gate"], "bias": jnp.zeros_like(moe["gate"]["bias"])}}
        params = {**params, "periods": {**params["periods"], "moe": zero(params["periods"]["moe"])},
                  "trailing": [{**t, "moe": zero(t["moe"])} for t in params["trailing"]]}
    assert np.abs(apply_logits(lm, params, tokens) - want).max() > 100 * F32_TOL


# --- the served step ----------------------------------------------------------------------


def test_served_logits_match_the_reference_with_a_one_token_chunk_and_a_row_restarted_in_a_used_slot(toy):
    """Prefill in chunks beside decoding rows, then decode through the tail
    store and the pages, rows and slots in different orders, a row finishing
    while the others run: every position's logits are the reference's full
    forward's. Slot 0's prompt is 2 chunks and NINE tokens (no multiple of the
    chunk), slot 2's is two chunks and ONE token: a chunk of one real token,
    whose tail keeps one OLD entry (``tail_after_chunk``); slot 1 prefills 3
    tokens, a chunk shorter than the tail itself. The tail store then holds
    each row's last two gated products, layer by layer, the trailing layer's
    in the LAST entry. Then a row is stopped in the middle of its decode, its
    slot is taken by another request (whose tails and pages are left there)
    and the row starts again from position 0 in the used slot: its logits are
    again the reference's. The routing counts cover all ten routed layers,
    the two trailing ones last."""
    cfg, _, params, section = toy
    seqs = sequences()
    driver = Driver(cfg, params)
    got = driver.run(seqs, decode_from={0: 41, 1: 3, 2: 33, 3: 27})
    want = {s: reference_logits(section, params, seq) for s, seq in seqs.items()}
    for s in seqs:
        assert got[s].shape == want[s].shape
        assert np.abs(got[s] - want[s]).max() < F32_TOL, s
    # the tail store after a row's tokens, by hand: u = B * x~ of the row's last two tokens in the first conv layer (leading layer 0)
    lead = params["leading"][0]["mixer"]
    x = params["embed"]["tokens"][seqs[2][-2:]]
    h = hm._norm(x, lead["attn_norm_scale"], None, "rmsnorm", cfg.norm_eps)
    b, _, xt = np.split(np.asarray(h @ lead["w_in"]), 3, axis=-1)
    tails = np.asarray(driver.pools[3])
    assert tails.shape == (9, SLOTS + 1, 2, 16, 128)  # nine conv layers; 128 channels: one lane tile of a sublane tile's sixteen rows
    assert np.abs(tails[0, 2, :, 0] - b * xt).max() < 1e-5 and not tails[:, :, :, 1:].any()  # the rows past the channels stay zeros
    assert np.abs(tails[8, 2]).max() > 0  # the trailing conv layer's entry: the last, behind the scanned layers'
    # the counts by hand: of every live token's four choices in each of the ten routed layers, the ones sent to experts 0 and 1
    by_hand = np.zeros((10, cfg.num_experts), np.int64)
    for s, seq in seqs.items():
        for layer, weights in enumerate(REFERENCE.router_weights(section, params, padded(seq)[0])):
            by_hand[layer] += (np.asarray(weights)[: seq.size, : cfg.num_experts] > 0).sum(axis=0)
    assert np.array_equal(driver.counts, by_hand) and by_hand[8:].sum() > 0 and 0 < by_hand.sum() < sum(len(q) for q in seqs.values()) * 10 * cfg.moe_top_k
    # a row stopped after 30 of its 61 tokens, another request in its slot, and the row again from position 0
    driver = Driver(cfg, params)
    driver.run({0: seqs[0][:30]}, decode_from={0: 24})
    driver.lengths[0] = 0
    driver.run({0: seqs[3]}, decode_from={0: 17})
    driver.lengths[0] = 0
    again = driver.run({0: seqs[0]}, decode_from={0: 41})[0]
    assert np.abs(again - want[0]).max() < F32_TOL


@pytest.mark.parametrize("wrong", ["tail_not_shifted", "the_chunks_tail_is_its_last_slots"])
def test_a_wrong_tail_is_far_outside_the_tolerance(toy, wrong, monkeypatch):
    """The tail's two hand-overs, each broken: a decode row that keeps its OLDEST product, and a chunk that leaves
    the window's last two SLOTS where its last two REAL tokens belong (right only for a full chunk)."""
    cfg, _, params, section = toy
    seq = sequences(9, lens=(45,))[0]
    want = reference_logits(section, params, seq)
    if wrong == "tail_not_shifted":
        monkeypatch.setattr(hm, "shifted_tail", lambda tail, u: jnp.concatenate([tail[..., :-1, :], u[..., None, :]], axis=-2))
        driver = Driver(dataclasses.replace(cfg), params)  # a config of its own: a forward traced with the stand-in
        got = driver.run({1: seq}, decode_from={1: 32})[1][37:]
    else:
        # the prompt's 2 chunks and 5 tokens served as THREE FULL windows, the last with 11 padding tokens counted real, then
        # the row's length set back to 37: the pages past it are masked, and the tail is the padding's, which is what a
        # hand-over of the window's last slots would have left
        driver = Driver(cfg, params)
        driver.run({1: np.concatenate([seq[:37], np.zeros(11, np.int32)])}, decode_from={1: 48})
        driver.lengths[1] = 37
        got = driver.run({1: seq[37:]}, decode_from={1: 0})[1]
    assert np.abs(got - want[37:]).max() > 100 * F32_TOL


def test_the_engine_serves_it_and_counts_all_ten_routed_layers(toy):
    """``init_inference`` -> ``serve``: the same ``PagedServer``, two compiled
    programs, six requests on four slots; the ``moe_`` counters over the TEN
    routed layers, the two trailing ones among them, held against routed; the
    tail store and the pages at the layers' own counts, no state array, and
    the pool's "state" bytes the tails'; every served token the reference's
    arg-max; and with a pool too small for its rows (rows preempted and
    re-admitted from position 0 in used slots) the same streams."""
    cfg, lm, params, section = toy
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (37, 5, 20, 17, 50, 3)]
    budgets = [40, 20, 9, 46, 7, 30]
    paged = {"page_size": PAGE, "max_slots": SLOTS, "prefill_chunk": CHUNK, "max_seq_len": MAXLEN}
    eng = ds.init_inference(lm, dtype="fp32", paged_kv=paged)
    eng.set_params(params)
    outs = eng.serve(prompts, max_new_tokens=budgets)
    assert sorted(eng.compile_stats()) == ["paged_ragged_r4_w1", "paged_ragged_r4_w16"]
    stats = eng._paged_server.stats
    live_tokens = sum(p.size for p in prompts) + sum(budgets) - len(prompts)
    assert stats["preempted"] == 0 and stats["finished"] == 6
    assert stats["moe_routed_assignments"] == live_tokens * 10 * cfg.moe_top_k  # ten routed layers: the two leading ones route nothing
    by_hand = 0
    for o in outs:  # a stream's last token was never an input
        for weights in REFERENCE.router_weights(section, params, padded(o)[0]):
            by_hand += int((np.asarray(weights)[: o.size - 1, : cfg.num_experts] > 0).sum())
    assert stats["moe_assignments"] == by_hand
    assert 0 < stats["moe_experts_hit"] <= stats["ragged_steps"] * 10 * cfg.num_experts and stats["moe_max_expert_load"] >= 1
    pool = eng._paged_server.pool
    assert pool.states.state is None and pool.states.conv.shape == (9, SLOTS + 1, 2, 16, 128) and pool.state_kind == "conv"
    assert pool.cache.k_pages.shape[0] == 3  # the three attention layers' pages
    report = pool.memory_report()
    tails = 9 * (SLOTS + 1) * 2 * 16 * 128 * 4
    assert (report["state_kind"], report["state_shape"], report["tail_shape"], report["state_layers"]) == ("conv", [], [2, 16, 128], 9)
    assert report["state_total_bytes"] == pool.states.hbm_bytes() == tails and pool.state_bytes_per_slot == tails // (SLOTS + 1)
    assert pool.cache_bytes() == {"state_bytes_in_use": 0, "latent_bytes_in_use": 0}  # nothing is live after the last request
    for i, (p, o) in enumerate(zip(prompts, outs)):
        lg = reference_logits(section, params, o)
        gap = lg[p.size - 1 : o.size - 1].max(-1) - np.take_along_axis(lg[p.size - 1 : o.size - 1], o[p.size :, None], -1)[:, 0]
        assert gap.max() < F32_TOL, i
    tight = ds.init_inference(lm, dtype="fp32", paged_kv={**paged, "num_pages": 14})
    tight.set_params(params)
    squeezed = tight.serve(prompts, max_new_tokens=budgets)
    assert tight._paged_server.stats["preempted"] > 0
    for a, b in zip(outs, squeezed):
        assert np.array_equal(a, b)


def test_the_eight_shares_add_up_to_the_uncut_layer(toy):
    """THE SHARE TEST. One routed FFN of the toy at the router's whole width
    (16 experts, 4 a token): the eight chips' addends, each its own two
    experts' terms (there is no shared expert to count once; the mixers and
    the dense layers are every chip's alike and are not part of the sum), add
    up to the uncut reference's layer; in the program (``hm.moe_ffn`` with
    ``held``) and in the reference alike."""
    cfg, _, params, _ = toy
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((1, 24, cfg.hidden_size)), jnp.float32)
    whole_cfg = lfm2_moe_config("tiny", dtype="float32", num_experts=16, moe_expert_share=(0, 1))
    moe = params["periods"]["moe"]
    layer = jax.tree_util.tree_map(lambda a: a[0, 1], {k: v for k, v in moe.items() if k != "experts"})
    # sixteen experts out of the stacks' own: the eight routed layers' two each stand for experts 0-15
    sixteen = jax.tree_util.tree_map(lambda a: a.reshape((16,) + a.shape[3:]), moe["experts"])
    whole = {**layer, "experts": sixteen}
    h = hm._norm(x, layer["mlp_norm_scale"], None, "rmsnorm", cfg.norm_eps)
    uncut = REFERENCE.routed_ffn(section_of(whole_cfg), whole, x[0])
    parts_ref, parts_prog, routed = [], [], 0
    for i in range(8):
        share_cfg = lfm2_moe_config("tiny", dtype="float32", moe_expert_share=(i, 8))
        p = {**layer, "experts": jax.tree_util.tree_map(lambda a: a[2 * i : 2 * i + 2], sixteen)}
        parts_ref.append(REFERENCE.routed_ffn(section_of(share_cfg), p, x[0]))
        out, counts = hm.moe_ffn(share_cfg, p, h)
        parts_prog.append(out[0])
        routed += int(counts.sum())
    assert routed == 24 * 4 and float(jnp.abs(uncut).max()) > 0.01  # every assignment is some chip's, once
    for parts in (parts_ref, parts_prog):
        assert float(jnp.abs(sum(parts) - uncut).max()) < F32_TOL
    whole_prog, counts = hm.moe_ffn(whole_cfg, whole, h)
    assert float(jnp.abs(whole_prog[0] - uncut).max()) < F32_TOL and int(counts.sum()) == 24 * 4


def test_the_mixer_is_under_its_scope_and_the_trailing_layers_ffn_under_mlp():
    """In the narrow program's text the conv kind's ops lie under ``conv_mixer`` (what the benchmark's readers find its
    device time by), apart from ``attention`` and ``mlp``, and the routed scopes inside ``mlp``."""
    import re

    cfg = lfm2_moe_config("tiny", dtype="float32")
    params = jax.eval_shape(lambda: HybridMoETransformerLM(cfg).init(jax.random.PRNGKey(0), None))
    pool = PagePool(cfg, SLOTS * (MAXLEN // PAGE) + 1, PAGE, SLOTS, max_seq_len=MAXLEN, dtype=jnp.float32)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    step = decode.build_ragged_step(cfg, SLOTS, 1, PAGE, attn_impl="xla")
    lowered = step.lower(params, i32(SLOTS, 1), pool.cache.k_pages, pool.cache.v_pages, pool.states, i32(SLOTS, MAXLEN // PAGE), i32(SLOTS), i32(SLOTS), i32(SLOTS))
    decode._paged_program_cache.clear()
    text = lowered.as_text(debug_info=True)
    scopes = set(re.findall(r'= loc\("([^"]*)"', text))
    paths = {tuple(part for part in path.split("/") if part in ("conv_mixer", "attention", "mlp", "moe_route", "moe_experts", "head_sample")) for path in scopes}
    assert {("conv_mixer",), ("attention",), ("mlp",), ("mlp", "moe_route"), ("mlp", "moe_experts")} <= paths
    assert not any(len(set(path) & {"conv_mixer", "attention", "mlp"}) > 1 for path in paths)
    # a tail and no state: the program's parameters hold ONE per-slot array, the tails, and no float32 store
    arguments = lowered.as_text().split("func.func public @main(", 1)[1].split(") -> (", 1)[0]
    assert arguments.count("tensor<9x5x2x16x128xf32>") == 1 and arguments.count("x5x") == 1 and "tensor<0x" not in arguments
