"""A model whose blocks are ONE sublayer each (a Mamba-2 mixer with GROUPS of
``B`` and ``C`` and a grouped gated norm, a NoPE GQA mixer, or an expert FFN of
two-matrix ``relu2`` experts behind a sigmoid router, alone) through the paged
server (``inference/hybrid_decode.py``): a mixer block is norm, mixer, add; an
FFN block norm, router, held experts and shared expert, add; each kind counts
its own index (states, pages, expert stacks). Everything is compared with the
plain reference (``benchmark/reference/nemotron_h_decoder.py``: float32, the
recurrence token by token with a head's own group, full causal attention,
every held expert over all tokens behind a mask) on seeded weights at the
``tiny`` size, the published leading 16 blocks ``MEMEM*EMEMEM*EME``, LOGITS and
not tokens.

Tolerances. The toy model runs in float32 on the CPU, where the program and
the reference differ by the order of their sums, by the chunk form of the
recurrence and by the sorted rows of the routed FFN: logits of standard
deviation ~0.4 agree to ~2e-6 (measured); the limit is 2e-5, and every wrong
block below moves them by more than a hundred times that.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.files import load_module
from deepspeed_tpu.inference import decode, hybrid_decode
from deepspeed_tpu.inference.kv_pool import PagePool
from deepspeed_tpu.models import hybrid_moe as hm
from deepspeed_tpu.models.hybrid_moe import HybridMoETransformerLM, nemotron_h_config
from deepspeed_tpu.moe import experts as moe_experts
from deepspeed_tpu.ops.transformer import state_space
from tests.unit.inference.hybrid_toys import _clear_jax_caches, _compiled_programs_live_as_long_as_the_file, apply_logits, seeded  # noqa: F401 (the two fixtures are taken by their import)

REFERENCE = load_module("reference", "nemotron_h_decoder")
PAGE, SLOTS, CHUNK, MAXLEN = 8, 4, 16, 96
F32_TOL = 2e-5
PATTERN = "MEMEM*EMEMEM*EME"


def section_of(cfg):
    return {"kwargs": {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}}


def toy_model(dtype="float32", **kw):
    """(config, model, parameters, the reference's ``model`` section): one
    jitted ``init``, then trained-like scales: init's 0.02 makes the softmax
    flat, the state a hundredth of ``D x``, every router score 0.5 and the
    selection bias too small to move a choice; and the relu-squared of a
    projection at 0.02 is a thousandth of the residual stream."""
    cfg = nemotron_h_config("tiny", dtype=dtype, **kw)
    lm = HybridMoETransformerLM(cfg)
    params = seeded(lm)
    periods = params["periods"]
    periods["softmax"]["wq"] = periods["softmax"]["wq"] * 40.0
    periods["softmax"]["wk"] = periods["softmax"]["wk"] * 8.0
    periods["ssm"]["w_xbc"] = periods["ssm"]["w_xbc"] * 8.0
    periods["moe"]["gate"]["wg"] = periods["moe"]["gate"]["wg"] * 20.0
    periods["moe"]["gate"]["bias"] = periods["moe"]["gate"]["bias"] * 10.0
    for tree, w_in in ((periods["moe"]["experts"], "w_in_t"), (periods["moe"]["shared"], "w_in")):
        tree[w_in], tree["w_out"] = tree[w_in] * 8.0, tree["w_out"] * 30.0
    return cfg, lm, params, section_of(cfg)


_FORWARDS = {}  # (id of the config, ssd_decode's form) -> (the config, kept alive for its id; its jitted forward)


class Driver:
    """Rows stepped by hand through ``hybrid_forward``: what the scheduler
    does, with the logits and the routing counts kept."""

    def __init__(self, cfg, params, dtype=jnp.float32, impl="xla"):
        self.cfg, self.params = cfg, params
        maxp = MAXLEN // PAGE
        pool = PagePool(cfg, SLOTS * maxp + 1, PAGE, SLOTS, max_seq_len=MAXLEN, dtype=dtype)
        self.pools = [pool.cache.k_pages, pool.cache.v_pages, pool.states.state, pool.states.conv]
        self.table = np.stack([1 + s * maxp + np.arange(maxp) for s in range(SLOTS)]).astype(np.int32)
        self.lengths = np.zeros(SLOTS, np.int32)
        self.counts = np.zeros((cfg.num_moe_layers, cfg.num_experts), np.int64)
        key = (id(cfg), impl)
        if key not in _FORWARDS:
            _FORWARDS[key] = (cfg, jax.jit(lambda p, *a: hybrid_decode.hybrid_forward(cfg, p, *a, attn_impl="xla")))
        self.forward = _FORWARDS[key][1]

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


@pytest.fixture(scope="module")
def toy():
    return toy_model()


def padded(seq):
    out = np.zeros((1, MAXLEN), np.int32)
    out[0, : seq.size] = seq
    return out


def reference_logits(section, params, seq):
    """The reference's logits [len, V] of one sequence, computed at MAXLEN
    (padded behind: the model is causal), so that its jitted parts compile
    for one length."""
    return np.asarray(REFERENCE.logits(section, params, padded(seq)))[0, : seq.size]


def test_the_preset_is_the_published_model():
    cfg = nemotron_h_config()
    assert (cfg.num_layers, cfg.hidden_size, cfg.vocab_size, cfg.tie_embeddings, cfg.activation) == (52, 2688, 131072, False, "relu2")
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.attn_softmax_scale, cfg.position) == (32, 2, 128, None, "none")
    assert (cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_conv_kernel) == (64, 64, 128, 8, 4)
    assert (cfg.ssm_inner, cfg.ssm_conv_channels) == (4096, 6144)  # d_inner off the head keys, NOT expand x 2,688 = 5,376
    assert (cfg.num_experts, cfg.moe_router_experts, cfg.moe_top_k, cfg.expert_intermediate_size, cfg.moe_shared_experts) == (128, 128, 6, 1856, 2)
    assert (cfg.moe_scoring, cfg.moe_select_bias, cfg.moe_norm_topk_prob, cfg.moe_routed_scaling) == ("sigmoid", True, True, 2.5)
    letters = {"ssm": "M", "softmax": "*", "ffn": "E"}
    assert "".join(letters[t] for t in cfg.layer_types) == "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    assert [i for i, t in enumerate(cfg.layer_types) if t == "softmax"] == [5, 12, 19, 26, 33, 42]
    assert (cfg.layers_of("ssm"), cfg.layers_of("ffn"), cfg.layers_of("softmax"), cfg.num_moe_layers) == (23, 23, 6, 23)
    assert cfg.single_sublayer and cfg.num_periods == 1 and len(cfg.period) == 52 and cfg.state_kind == "ssm"  # the list repeats nothing
    shapes = jax.eval_shape(lambda: HybridMoETransformerLM(cfg).init(jax.random.PRNGKey(0), None))
    count = lambda tree: sum(a.size for a in jax.tree_util.tree_leaves(tree))
    periods = shapes["periods"]
    assert set(periods) == {"ssm", "softmax", "moe"} and "leading" not in shapes
    # TWO matrices an expert, no gate; a routed expert's input matrix by its output rows, as the published up_proj lies
    assert set(periods["moe"]["experts"]) == {"w_in_t", "w_out"} and set(periods["moe"]["shared"]) == {"w_in", "w_out"}
    assert periods["moe"]["experts"]["w_in_t"].shape == periods["moe"]["experts"]["w_out"].shape == (1, 23, 128, 1856, 2688)
    assert periods["moe"]["shared"]["w_in"].shape == (1, 23, 2688, 3712)
    assert periods["ssm"]["w_xbc"].shape == (1, 23, 2688, 6144) and periods["ssm"]["o_norm_scale"].shape == (1, 23, 4096)
    # the issue's count: M 38.74M, * 23.40M, E 1,297.5M, embedding and head 352.3M each: 31.58B
    assert (count(periods["ssm"]) // 23, count(periods["softmax"]) // 6, count(periods["moe"]) // 23) == (38_744_896, 23_399_040, 1_297_468_160)
    assert round(count(shapes) / 1e9, 2) == 31.58


def test_apply_is_the_reference(toy):
    """``apply`` (one trip of a scan whose body is the 16 blocks, the chunk
    form of the grouped recurrence, the sorted rows of the routed FFN)
    against the reference, which walks the blocks one by one."""
    cfg, lm, params, section = toy
    tokens = sequences(7, lens=(50,))[0][None]
    assert "".join({"ssm": "M", "softmax": "*", "ffn": "E"}[t] for t in cfg.period) == PATTERN and cfg.num_periods == 1
    assert (cfg.ssm_groups, cfg.num_experts, cfg.moe_router_experts, cfg.num_moe_layers) == (2, 4, 8, 7)
    assert np.abs(apply_logits(lm, params, tokens)[0] - reference_logits(section, params, tokens[0])).max() < F32_TOL


WRONG = ["group_0_for_every_head", "one_norm_over_all_features", "gate_behind_the_norm", "relu_for_relu2", "a_swiglu_shaped_expert",
         "no_routed_scaling", "no_selection_bias", "shared_expert_dropped", "rotary", "state_dropped", "an_ffn_behind_every_mixer"]


@pytest.mark.parametrize("wrong", WRONG)
def test_a_wrong_block_is_far_outside_the_tolerance(toy, wrong, monkeypatch):
    """What the tolerance is worth: each of these moves the logits by more than a hundred times ``F32_TOL``."""
    cfg, lm, params, section = toy
    tokens = sequences(7, lens=(50,))[0][None]
    want = reference_logits(section, params, tokens[0])[None]
    moe = params["periods"]["moe"]
    with_moe = lambda **leaves: {**params, "periods": {**params["periods"], "moe": {**moe, **leaves}}}
    if wrong == "group_0_for_every_head":
        split = hm.ssm_split

        def first_group(cfg, y):
            x, B, C = split(cfg, y)
            return x, B[..., 0, :], C[..., 0, :]

        monkeypatch.setattr(hm, "ssm_split", first_group)
    elif wrong == "one_norm_over_all_features":

        def one_group(cfg, p, z, y):
            gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
            return hm.qmatmul(hm._norm(gated, p["o_norm_scale"], None, "rmsnorm", cfg.norm_eps).astype(z.dtype), p["wo"])

        monkeypatch.setattr(hm, "ssm_output", one_group)
    elif wrong == "gate_behind_the_norm":

        def gate_behind(cfg, p, z, y):
            by_group = (cfg.ssm_groups, cfg.ssm_inner // cfg.ssm_groups)
            normed = hm._norm(y.astype(jnp.float32).reshape(y.shape[:-1] + by_group), p["o_norm_scale"].reshape(by_group), None, "rmsnorm", cfg.norm_eps)
            return hm.qmatmul((normed.reshape(y.shape) * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype), p["wo"])

        monkeypatch.setattr(hm, "ssm_output", gate_behind)
    elif wrong == "relu_for_relu2":
        lm = HybridMoETransformerLM(dataclasses.replace(cfg, activation="relu"))
    elif wrong == "a_swiglu_shaped_expert":  # (silu(h W) * (h W)) W_out, the gate a copy of the up projection: silu(u) u for relu(u)^2
        monkeypatch.setattr(moe_experts, "_pointwise_activation", lambda u, activation: jax.nn.silu(u) * u)
    elif wrong == "no_routed_scaling":
        lm = HybridMoETransformerLM(dataclasses.replace(cfg, moe_routed_scaling=1.0))
    elif wrong == "no_selection_bias":
        params = with_moe(gate={**moe["gate"], "bias": jnp.zeros_like(moe["gate"]["bias"])})
    elif wrong == "shared_expert_dropped":
        params = with_moe(shared={**moe["shared"], "w_out": jnp.zeros_like(moe["shared"]["w_out"])})
    elif wrong == "rotary":
        lm = HybridMoETransformerLM(dataclasses.replace(cfg, position="rope"))
    elif wrong == "state_dropped":  # y = D x alone: what a state that is not carried reads
        chunked = state_space.ssd_chunked
        monkeypatch.setattr(state_space, "ssd_chunked", lambda x, B, C, dt, A, D, S, **kw: chunked(x, jnp.zeros_like(B), C, dt, A, D, S, **kw))
    elif wrong == "an_ffn_behind_every_mixer":  # the pairing this family does not have: the same nine mixers, each with an FFN
        types = [t for t in cfg.layer_types if t != "ffn"]
        paired = dataclasses.replace(cfg, layer_types=types, num_layers=len(types))
        assert not paired.single_sublayer and paired.num_moe_layers == 9
        lm = HybridMoETransformerLM(paired)
        nine = lambda a: jnp.concatenate([a, a[:, :2]], axis=1)  # nine FFNs out of the seven
        params = with_moe(**jax.tree_util.tree_map(nine, moe))
    assert np.abs(apply_logits(lm, params, tokens) - want).max() > 100 * F32_TOL


def test_served_logits_match_the_reference_with_a_row_preempted_and_readmitted(toy):
    """Prefill in chunks beside decoding rows (a prompt of 2.5 chunks, its
    state and tail carried chunk to chunk), then decode through the state
    store and the pages, rows and slots in different orders, a row finishing
    while the others run: every position's logits are the reference's full
    forward's. Then a row is PREEMPTED in the middle of its decode (its slot
    taken by another request, whose state, tails and pages are left there) and
    RE-ADMITTED from position 0: its logits are again the reference's."""
    cfg, _, params, section = toy
    seqs = sequences()
    driver = Driver(cfg, params)
    got = driver.run(seqs, decode_from={0: 40, 1: 3, 2: 33, 3: 27})
    want = {s: reference_logits(section, params, seq) for s, seq in seqs.items()}
    for s in seqs:
        assert got[s].shape == want[s].shape
        assert np.abs(got[s] - want[s]).max() < F32_TOL, s
    # the state store after a row's tokens: the reference's final states in BLOCK order, at the row's slot
    final = REFERENCE.final_states(section, params, seqs[2][None])
    assert len(final) == cfg.layers_of("ssm") == 7
    for layer, S in enumerate(final):
        assert np.abs(np.asarray(driver.pools[2][layer, 2]) - np.asarray(S[0])).max() < 1e-5, layer
    # the counts by hand: of every live token's three choices in each of the seven FFN blocks, the ones sent to experts 0-3
    by_hand = np.zeros((7, cfg.num_experts), np.int64)
    for s, seq in seqs.items():
        for block, weights in enumerate(REFERENCE.router_weights(section, params, padded(seq))):
            by_hand[block] += (np.asarray(weights)[0, : seq.size, : cfg.num_experts] > 0).sum(axis=0)
    assert np.array_equal(driver.counts, by_hand) and 0 < by_hand.sum() < sum(len(q) for q in seqs.values()) * 7 * cfg.moe_top_k
    # preemption: slot 0's row stops after 30 of its 61 tokens, another request runs in its slot, and it starts again
    driver = Driver(cfg, params)
    driver.run({0: seqs[0][:30]}, decode_from={0: 24})
    driver.lengths[0] = 0
    driver.run({0: seqs[3]}, decode_from={0: 20})
    driver.lengths[0] = 0
    again = driver.run({0: seqs[0]}, decode_from={0: 40})[0]
    assert np.abs(again - want[0]).max() < F32_TOL


def test_the_engine_serves_it_and_counts_the_seven_expert_blocks(toy):
    """``init_inference`` -> ``serve``: the same ``PagedServer``, two compiled
    programs, six requests on four slots; the ``moe_`` counters over the SEVEN
    expert blocks (not the sixteen blocks), held against routed; the state
    store and the pages at the blocks' own counts; every served token the
    reference's arg-max; and with a pool too small for its rows (rows
    preempted and re-admitted from position 0) the same streams."""
    cfg, lm, params, section = toy
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (37, 5, 20, 16, 50, 3)]
    budgets = [40, 20, 9, 46, 7, 30]
    paged = {"page_size": PAGE, "max_slots": SLOTS, "prefill_chunk": CHUNK, "max_seq_len": MAXLEN}
    eng = ds.init_inference(lm, dtype="fp32", paged_kv=paged)
    eng.set_params(params)
    outs = eng.serve(prompts, max_new_tokens=budgets)
    assert sorted(eng.compile_stats()) == ["paged_ragged_r4_w1", "paged_ragged_r4_w16"]
    stats = eng._paged_server.stats
    live_tokens = sum(p.size for p in prompts) + sum(budgets) - len(prompts)
    assert stats["preempted"] == 0 and stats["finished"] == 6
    assert stats["moe_routed_assignments"] == live_tokens * 7 * cfg.moe_top_k  # seven expert blocks, not sixteen
    by_hand = 0
    for o in outs:  # a stream's last token was never an input
        for weights in REFERENCE.router_weights(section, params, padded(o)):
            by_hand += int((np.asarray(weights)[0, : o.size - 1, : cfg.num_experts] > 0).sum())
    assert stats["moe_assignments"] == by_hand
    assert 0 < stats["moe_experts_hit"] <= stats["ragged_steps"] * 7 * cfg.num_experts and stats["moe_max_expert_load"] >= 1
    pool = eng._paged_server.pool
    assert pool.states.state.shape == (7, SLOTS + 1, 4, 64, 128) and pool.states.conv.shape == (7, SLOTS + 1, 3, 16, 128)
    assert pool.cache.k_pages.shape[0] == 2  # the two attention blocks' pages
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


def test_the_two_shares_add_up_to_the_uncut_block(toy):
    """THE SHARE TEST. One FFN block of the toy at the router's whole width
    (8 experts, 3 a token): the two chips' addends, each its own four experts'
    terms plus the shared expert, with the shared expert counted ONCE, add up
    to the uncut reference's block; in the program (``hm.moe_ffn`` with
    ``held``) and in the reference alike."""
    cfg, lm, params, _ = toy
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((1, 24, cfg.hidden_size)), jnp.float32)
    whole_cfg = nemotron_h_config("tiny", dtype="float32", num_experts=8, moe_expert_share=(0, 1))
    block = jax.tree_util.tree_map(lambda a: a[0, 3], params["periods"]["moe"])  # the fourth FFN block's leaves
    more = jax.tree_util.tree_map(lambda a: a[0, 4], params["periods"]["moe"]["experts"])  # another block's four stand for experts 4-7
    whole = {**block, "experts": jax.tree_util.tree_map(lambda a, b: jnp.concatenate([a, b]), block["experts"], more)}
    shares = [{**block, "experts": jax.tree_util.tree_map(lambda a: a[4 * i : 4 * i + 4], whole["experts"])} for i in range(2)]
    h = hm._norm(x, block["mlp_norm_scale"], None, "rmsnorm", cfg.norm_eps)
    shared = moe_experts.apply_dense_ffn(block["shared"], h, cfg.activation)
    uncut = REFERENCE.ffn_block(section_of(whole_cfg), whole, x[0])
    parts_ref, parts_prog = [], []
    for i, p in enumerate(shares):
        share_cfg = nemotron_h_config("tiny", dtype="float32", moe_expert_share=(i, 2))
        parts_ref.append(REFERENCE.ffn_block(section_of(share_cfg), p, x[0]))
        out, counts = hm.moe_ffn(share_cfg, p, h)
        parts_prog.append(out[0])
        assert counts.shape == (4,) and int(counts.sum()) > 0
    assert float(jnp.abs(shared).max()) > 0.01  # counted twice, the sum would be off by this
    for parts in (parts_ref, parts_prog):
        assert float(jnp.abs(parts[0] + parts[1] - shared[0] - uncut).max()) < F32_TOL
    whole_prog, counts = hm.moe_ffn(whole_cfg, whole, h)
    assert float(jnp.abs(whole_prog[0] - uncut).max()) < F32_TOL and int(counts.sum()) == 24 * 3


def test_the_kernel_serves_what_the_xla_form_serves(toy, monkeypatch):
    """``ssd_decode``'s Pallas kernel (interpreted) with two groups inside the step: the logits of the XLA form."""
    cfg, _, params, _ = toy
    seqs = sequences(4, lens=(21, 9))
    b = Driver(cfg, params).run(seqs, decode_from={0: 18, 1: 0})
    monkeypatch.setattr(hybrid_decode, "ssd_decode", functools.partial(state_space.ssd_decode, impl="pallas_interpret"))
    a = Driver(cfg, params, impl="pallas_interpret").run(seqs, decode_from={0: 18, 1: 0})
    for s in seqs:
        assert np.abs(a[s] - b[s]).max() < F32_TOL, s


def test_the_scopes_are_a_blocks_own():
    """A mixer block emits no ``mlp`` scope and an FFN block no mixer scope:
    in the narrow program's text every ``mlp`` op lies outside the mixers'
    scopes, ``ssd_recurrence`` inside ``ssm_mixer``, and the three routed
    scopes inside ``mlp``."""
    cfg = nemotron_h_config("tiny", dtype="float32", num_layers=3, layer_types=["ssm", "ffn", "softmax"])
    lm = HybridMoETransformerLM(cfg)
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), None))
    pool = PagePool(cfg, SLOTS * (MAXLEN // PAGE) + 1, PAGE, SLOTS, max_seq_len=MAXLEN, dtype=jnp.float32)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    step = decode.build_ragged_step(cfg, SLOTS, 1, PAGE, attn_impl="xla")
    text = step.lower(params, i32(SLOTS, 1), pool.cache.k_pages, pool.cache.v_pages, pool.states, i32(SLOTS, MAXLEN // PAGE), i32(SLOTS), i32(SLOTS), i32(SLOTS)).as_text(debug_info=True)
    decode._paged_program_cache.clear()
    import re

    scopes = set(re.findall(r'= loc\("([^"]*)"', text))
    paths = {tuple(part for part in path.split("/") if part in ("ssm_mixer", "ssd_recurrence", "attention", "mlp", "moe_route", "moe_experts", "moe_shared", "head_sample"))
             for path in scopes}
    assert {("ssm_mixer",), ("ssm_mixer", "ssd_recurrence"), ("attention",), ("mlp",), ("mlp", "moe_route"), ("mlp", "moe_experts"), ("mlp", "moe_shared")} <= paths
    assert not any("mlp" in path and set(path) & {"ssm_mixer", "attention"} for path in paths)
