"""A looped model (``num_loops`` passes over one stack of layers, a KV cache of
its own for every pass, sandwich norms, an exit gate) through
``TransformerLM``, the page pool and the paged server: everything is compared
with the plain reference (``benchmark/reference/ouro_decoder.py``: float32,
two Python loops, no cache) on seeded weights at a toy size (3 layers run 4
times: 12 cache layers), LOGITS and not tokens.

Tolerances. The toy runs in float32 on the CPU, where program and reference
differ by the order of their sums: logits of standard deviation 0.16 agree to
5e-7 (measured); the limit is 1e-5 (``TOL``), and each of the reference's wrong
blocks moves them by more than five hundred times that
(``WRONG_BY``).

The last tests hold what a model WITHOUT a loop keeps: its step's jaxpr (one
scan over ``num_layers``, no enclosing loop, ``2 L + 1`` norms) and a
Mistral-shaped toy's logits, equal to the ones the parent commit of PR 56
produced (``data/mistral_toy_golden.npz``, recorded there by
``record_golden`` below).
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.files import load_module
from deepspeed_tpu.inference import decode
from deepspeed_tpu.inference.kv_pool import PagePool, init_paged_cache
from deepspeed_tpu.inference.scheduler import PagedServer
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.config import TransformerConfig, cache_layers
from tests.unit.inference.hybrid_toys import _clear_jax_caches, _compiled_programs_live_as_long_as_the_file, seeded  # noqa: F401 (the two fixtures are taken by their import)

REFERENCE = load_module("reference", "ouro_decoder")
PAGE, SLOTS, CHUNK, MAXLEN = 8, 4, 16, 96
TOL = 1e-5
# the smallest mean absolute logit difference a wrong block must show, as a multiple of TOL (measured: 990 for float8 weights, 5,100 for a pass too few, 8,400-17,600 for the others)
WRONG_BY = {"three_passes": 2500, "shared_cache": 5000, "no_pass_norm": 5000, "no_post_norm": 5000, "weights_fp8": 500}
GOLDEN = os.path.join(os.path.dirname(__file__), "data", "mistral_toy_golden.npz")

LOOPED = dict(vocab_size=512, hidden_size=64, intermediate_size=96, num_layers=3, num_heads=4, head_dim=16, max_seq_len=256,
              norm="rmsnorm", norm_eps=1e-6, position="rope", rope_theta=1e6, activation="swiglu", use_bias=False, tie_embeddings=False,
              num_loops=4, post_sublayer_norm=True, exit_gate=True, dtype="float32", flash_attention=False)
MISTRAL_TOY = dict(vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                   max_seq_len=256, norm="rmsnorm", norm_eps=1e-5, position="rope", rope_theta=1e6, activation="swiglu", use_bias=False,
                   tie_embeddings=False, dtype="float32", flash_attention=False)


@pytest.fixture(scope="module")
def toy():
    """(config, model, parameters, the reference's ``model`` section)."""
    cfg = TransformerConfig(**LOOPED)
    lm = TransformerLM(cfg)
    return cfg, lm, seeded(lm), {"kwargs": dict(LOOPED, num_kv_heads=4)}


_FORWARDS = {}  # (id of the config, token tile) -> (the config, kept alive for its id; its jitted forward)


class Driver:
    """Rows stepped by hand through ``decode._paged_forward``: what the
    scheduler does, with the logits kept. Slot ``s`` owns pages that are not
    in walk order beside its neighbours'."""

    def __init__(self, cfg, params):
        self.cfg, self.params = cfg, params
        maxp = MAXLEN // PAGE
        cache = init_paged_cache(cfg, SLOTS * maxp + 1, PAGE, dtype=jnp.float32)
        self.pools = [cache.k_pages, cache.v_pages]
        self.table = np.stack([1 + s + SLOTS * np.arange(maxp) for s in range(SLOTS)]).astype(np.int32)
        self.lengths = np.zeros(SLOTS, np.int32)
        key = (id(cfg), decode.token_tile(cfg))
        if key not in _FORWARDS:
            def forward(p, tokens, kp, vp, table, lengths, q_lens):
                positions = lengths[:, None] + jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
                kv_lens = jnp.where(q_lens > 0, lengths + q_lens, 0)
                return decode._paged_forward(cfg, p, tokens, kp, vp, table, positions, None, "xla", prefill_kv_lens=kv_lens, ragged_q_lens=q_lens)[:3]

            _FORWARDS[key] = (cfg, jax.jit(forward, donate_argnums=(2, 3)))
        self.forward = _FORWARDS[key][1]

    def free(self, slot):
        self.lengths[slot] = 0  # its pages are written again from position 0 by the next row that takes it

    def step(self, windows, width):
        """``windows``: {slot: tokens}; rows in a shuffled order, so that row and slot differ. Returns {slot: logits [n, V]}."""
        order = sorted(windows, key=lambda s: (s * 7) % 5)
        tokens, q_lens = np.zeros((SLOTS, width), np.int32), np.zeros(SLOTS, np.int32)
        table, lengths = np.full_like(self.table, -1), np.zeros(SLOTS, np.int32)
        for r, s in enumerate(order):
            w = np.asarray(windows[s], np.int32)
            tokens[r, : w.size], q_lens[r], table[r], lengths[r] = w, w.size, self.table[s], self.lengths[s]
        logits, *self.pools = self.forward(self.params, tokens, *self.pools, table, lengths, q_lens)
        out = {}
        for r, s in enumerate(order):
            out[s] = np.asarray(logits[r, : q_lens[r]], np.float32)
            self.lengths[s] += q_lens[r]
        return out

    def run(self, seqs, decode_from):
        """Each slot's sequence: prefill ``[: decode_from[s]]`` in chunks of CHUNK beside whatever else is running (mixed
        steps), then one token a step; a row that has finished leaves the others running. Returns {slot: logits [len, V]}."""
        got, done = {s: [] for s in seqs}, {s: 0 for s in seqs}
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


def reference_logits(section, params, seq, wrong=None):
    """The reference's logits [len, V] of one sequence, computed at MAXLEN (padded behind: the model is causal), so
    that its jitted parts compile for one length."""
    padded = np.zeros((1, MAXLEN), np.int32)
    padded[0, : seq.size] = seq
    return np.asarray(REFERENCE.logits(section, params, padded, wrong=wrong))[0, : seq.size]


def sequences(seed=0, lens=(61, 5, 40, 27), vocab=512):
    rng = np.random.default_rng(seed)
    return {s: rng.integers(0, vocab, n).astype(np.int32) for s, n in enumerate(lens)}


# --- (a) the model against the reference -----------------------------------------


def test_apply_is_the_references_forward_and_exit_distribution(toy):
    cfg, lm, params, section = toy
    assert set(params["layers"]) >= {"attn_norm_scale", "attn_post_norm_scale", "mlp_norm_scale", "mlp_post_norm_scale"}
    assert params["exit_gate"]["w"].shape == (64,) and params["exit_gate"]["b"].shape == ()
    tokens = np.stack([np.resize(s, MAXLEN) for s in sequences(3).values()][:2])
    logits, shares = jax.jit(lambda p, t: lm.apply(p, t, train=False, exit_distribution=True))(params, tokens)
    ref = np.asarray(REFERENCE.logits(section, params, tokens))
    assert ref.std() > 0.1 and np.abs(np.asarray(logits) - ref).max() < TOL
    ref_shares = np.asarray(REFERENCE.exit_distribution(section, params, tokens))
    assert shares.shape == ref_shares.shape == (2, MAXLEN, 4) and np.abs(np.asarray(shares) - ref_shares).max() < TOL
    assert np.allclose(ref_shares.sum(-1), 1.0, atol=1e-6) and (ref_shares > 0.01).all()  # a distribution no pass is missing from
    # the plain call is the same program without the gate, and the loss differentiates through all four passes
    assert np.array_equal(np.asarray(jax.jit(lambda p, t: lm.apply(p, t, train=False))(params, tokens)), np.asarray(logits))
    grads = jax.jit(jax.grad(lambda p: lm.apply(p, (tokens[:, :-1], tokens[:, 1:]), train=True)))(params)
    assert float(jnp.abs(grads["layers"]["attn_post_norm_scale"]).sum()) > 0 and float(jnp.abs(grads["exit_gate"]["w"]).sum()) == 0.0


def test_unrolled_layers_and_generate_run_every_pass(toy):
    """``scan_layers=False`` and the dense workspace (``generate``: ``_forward_with_cache`` over ``cache_layers`` layers)."""
    cfg, lm, params, section = toy
    tokens = np.stack([np.resize(s, 40) for s in sequences(5).values()][:2])
    scanned = np.asarray(jax.jit(lambda p, t: lm.apply(p, t, train=False))(params, tokens))
    unrolled = TransformerLM(dataclasses.replace(cfg, scan_layers=False))
    assert np.abs(np.asarray(jax.jit(lambda p, t: unrolled.apply(p, t, train=False))(params, tokens)) - scanned).max() < TOL
    assert decode.init_cache(cfg, 2, 48).k.shape == (12, 2, 48, 4, 16)
    out = np.asarray(decode.generate(cfg, params, tokens[:, :32], max_new_tokens=6))
    full = np.asarray(jax.jit(lambda p, t: lm.apply(p, t, train=False))(params, out))
    gold = np.take_along_axis(full[:, 31:-1], out[:, 32:, None], axis=-1)[..., 0]
    assert out.shape == (2, 38) and (full[:, 31:-1].max(-1) - gold).max() < TOL  # every generated token the full forward's arg-max


# --- (b) the paged path against the reference's full forward ----------------------


@pytest.mark.parametrize("tile", [512, 16], ids=["slab", "token_tiles"])
def test_prefill_then_decode_through_every_pass_cache_is_the_full_forward(toy, monkeypatch, tile):
    """Prompts longer and shorter than a chunk, mixed steps, rows of unequal length, a slot freed and taken again; the
    wide step as the slab it is and, at a token tile of 16, over its packed live tokens (the form the chip's 8 x 128 takes)."""
    monkeypatch.setattr(decode, "DENSE_TOKEN_TILE", tile)
    cfg, lm, params, section = toy
    seqs = sequences(0)
    driver = Driver(cfg, params)
    got = driver.run(seqs, decode_from={0: 37, 1: 3, 2: 40, 3: 16})
    for s, seq in seqs.items():
        assert np.abs(got[s] - reference_logits(section, params, seq)).max() < TOL, s
    # slot 1 (5 tokens) is freed and taken by a new row while 0 and 2 go on from where they are
    driver.free(1)
    again = sequences(9, lens=(4, 33, 6, 1))
    ext = {0: again[0], 1: again[1], 2: again[2]}
    more = driver.run(ext, decode_from={0: 0, 1: 20, 2: 0})
    assert np.abs(more[1] - reference_logits(section, params, again[1])).max() < TOL
    for s in (0, 2):
        whole = np.concatenate([seqs[s], ext[s]])
        assert np.abs(more[s] - reference_logits(section, params, whole)[seqs[s].size :]).max() < TOL, s


def test_paged_server_serves_the_references_argmax(toy):
    """Through ``PagedServer`` itself: more requests than slots (slots are freed and taken again), prompts longer and
    shorter than a chunk, unequal budgets, the prefix cache on and a step in flight. Every served token is the
    reference's arg-max for its position, teacher-forced, to TOL (logits, not a comparison of tokens)."""
    cfg, lm, params, section = toy
    server = PagedServer(cfg, params, page_size=PAGE, max_slots=SLOTS, max_seq_len=MAXLEN, prefill_chunk=CHUNK, attn_impl="xla",
                         dtype=jnp.float32, prefix_cache=True)
    rng = np.random.default_rng(4)
    shared = rng.integers(0, 512, 24).astype(np.int32)  # three whole pages two prompts share: attached, then copied on write
    prompts = [np.concatenate([shared, rng.integers(0, 512, n).astype(np.int32)]) if i in (1, 4) else rng.integers(0, 512, n).astype(np.int32)
               for i, n in enumerate((40, 3, 17, 9, 5, 30))]
    budgets = [5, 9, 3, 8, 6, 24]
    uids = [server.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    while server.has_work():
        server.step()
    stats = server.serve_stats()
    assert (stats["loop_passes"], stats["cache_layers"]) == (4, 12)
    assert stats["run_ahead_share"] > 0.8 and server.pool.stats["prefix_hit_pages"] > 0, (stats["run_ahead_share"], stats["drain_reasons"], server.pool.stats)
    for uid, p, n in zip(uids, prompts, budgets):
        out = np.asarray(server.take_result(uid))
        assert out.shape == (p.size + n,) and np.array_equal(out[: p.size], p)
        ref = reference_logits(section, params, out)
        regret = ref[p.size - 1 : -1].max(-1) - np.take_along_axis(ref[p.size - 1 : -1], out[p.size :, None], -1)[:, 0]
        assert regret.max() < TOL, (uid, regret)


def test_verify_rows_of_a_drafter_roll_back_through_every_cache_layer(toy):
    """Speculative decode goes through the same ``_paged_layers`` (a verify width) and the pool's page-level rollback:
    a drafter that is right half the time leaves the stream what plain decode makes it."""
    from deepspeed_tpu.inference.spec_decode import Drafter

    cfg, lm, params, section = toy
    prompt = sequences(2)[2][:20]

    def serve(drafter):
        server = PagedServer(cfg, params, page_size=PAGE, max_slots=SLOTS, max_seq_len=MAXLEN, prefill_chunk=CHUNK, attn_impl="xla",
                             dtype=jnp.float32, drafter=drafter, spec_decode={"max_draft": 2})
        uid = server.submit(prompt, max_new_tokens=10)
        while server.has_work():
            server.step()
        return np.asarray(server.take_result(uid)), server.serve_stats()

    plain, _ = serve(None)

    class HalfRight(Drafter):
        def propose(self, uid, context, k):
            n = context.size
            nxt = plain[n : n + k].copy()
            if nxt.size == k and n % 2:
                nxt[-1] = (nxt[-1] + 1) % 512  # a wrong last draft: rejected and rolled back
            return nxt

    drafted, stats = serve(HalfRight())
    assert np.array_equal(drafted, plain) and 0 < stats["spec_accepted"] < stats["spec_drafted"]


# --- (c) the reference's wrong blocks ---------------------------------------------


@pytest.mark.parametrize("wrong", REFERENCE.WRONG)
def test_each_wrong_block_is_far_from_the_program(toy, wrong):
    cfg, lm, params, section = toy
    seq = sequences(0)[0]
    ours = np.asarray(jax.jit(lambda p, t: lm.apply(p, t, train=False))(params, np.resize(seq, MAXLEN)[None]))[0, : seq.size]
    assert np.abs(ours - reference_logits(section, params, seq)).max() < TOL
    assert np.abs(ours - reference_logits(section, params, seq, wrong=wrong)).mean() > WRONG_BY[wrong] * TOL


# --- (d) the pool ------------------------------------------------------------------


def test_the_pool_counts_cache_layers_in_one_place(toy):
    cfg, *_ = toy
    assert cache_layers(cfg) == 12 and cache_layers(dataclasses.replace(cfg, num_loops=1, exit_gate=False)) == 3
    cache = init_paged_cache(cfg, 5, PAGE, dtype=jnp.float32)
    assert cache.k_pages.shape == cache.v_pages.shape == (12, 5, 4, PAGE, 16)  # [loops x layers, pages, heads, page, head]
    assert cache.bytes_per_token == 4 * 3 * 2 * 4 * 16 * 4
    published = TransformerConfig(vocab_size=49152, hidden_size=2048, intermediate_size=5632, num_layers=48, num_heads=16, head_dim=128, num_loops=4)
    shapes = jax.eval_shape(lambda: init_paged_cache(published, 73, 64, dtype=jnp.bfloat16))
    assert shapes.k_pages.shape == (192, 73, 16, 64, 128) and 192 * 16 * 2 * 128 * 2 == 1_572_864
    # auto-sized pages: every slot at max length plus the trash page, whatever the loops (a page id holds a token in every cache layer)
    server = PagedServer(cfg, None, page_size=PAGE, max_slots=SLOTS, max_seq_len=MAXLEN, prefill_chunk=CHUNK, attn_impl="xla", dtype=jnp.float32)
    pool = server.pool
    report = pool.memory_report()
    assert pool.num_pages == SLOTS * (MAXLEN // PAGE) + 1 == 49
    assert (report["cache_layers"], report["weight_layers"], report["kv_bytes_per_token"]) == (12, 3, cache.bytes_per_token)
    assert report["kv_total_bytes"] == 49 * PAGE * cache.bytes_per_token == pool.cache.hbm_bytes()
    plain = PagePool(dataclasses.replace(cfg, num_loops=1, exit_gate=False), 5, PAGE, 2, max_seq_len=MAXLEN, dtype=jnp.float32).memory_report()
    assert (plain["cache_layers"], plain["weight_layers"]) == (3, 3)


# --- (f) refusals --------------------------------------------------------------------


def test_what_runs_the_stack_once_refuses_a_looped_model_by_name(toy):
    from deepspeed_tpu.inference.tp import TPServing, serving_mesh
    from deepspeed_tpu.models.moe_transformer import MoETransformerConfig
    from deepspeed_tpu.runtime.zero.overlap import OverlapPlan, overlap_scope

    cfg, lm, params, _ = toy
    with pytest.raises(NotImplementedError, match="early_exit_threshold=0.9 < 1 lets rows of one step leave the loop after different passes"):
        TransformerConfig(**{**LOOPED, "early_exit_threshold": 0.9})
    with pytest.raises(ValueError, match="exit_gate is a looped model's"):
        TransformerConfig(**{**LOOPED, "num_loops": 1})
    with pytest.raises(ValueError, match="prenorm=True and parallel_residual=False"):
        TransformerConfig(**{**LOOPED, "parallel_residual": True})
    with pytest.raises(NotImplementedError, match="are the dense TransformerLM's"):
        MoETransformerConfig(num_loops=2)
    with pytest.raises(NotImplementedError, match=r"tensor-parallel serving does not support a looped model \(num_loops=4\)"):
        TPServing(mesh=serving_mesh(2)).validate_cfg(cfg)
    with pytest.raises(NotImplementedError, match="layer streaming .* does not support a looped model"):
        lm.stream_fns()
    tokens = np.zeros((2, 8), np.int32)
    with pytest.raises(NotImplementedError, match="progressive layer drop / random-LTD does not support a looped model"):
        lm.apply(params, (tokens, tokens), train=True, pld_theta=0.5, rngs={"dropout": jax.random.PRNGKey(0)})
    with pytest.raises(ValueError, match="exit_distribution needs a model with an exit gate"):
        TransformerLM(TransformerConfig(**MISTRAL_TOY)).apply(None, tokens, exit_distribution=True)
    plan = OverlapPlan.__new__(OverlapPlan)  # a plan that asks for the layer pipeline: its gathers are never reached
    plan.prefetch_enabled, plan.reduce_grads = True, lambda per_layer: per_layer
    with overlap_scope(plan), pytest.raises(NotImplementedError, match=r"the ZeRO-3 layer pipeline \(_pipelined_layer_scan\) does not support a looped model"):
        lm.apply(params, (tokens, tokens), train=True)


# --- (e) a model without a loop is what it was ------------------------------------------


def _step_jaxpr(cfg, params, width):
    step = decode.build_ragged_step(cfg, SLOTS, width, PAGE, attn_impl="xla")
    cache = jax.eval_shape(lambda: init_paged_cache(cfg, 9, PAGE, dtype=jnp.float32))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    jaxpr = jax.make_jaxpr(step)(params, i32(SLOTS, width), cache.k_pages, cache.v_pages, i32(SLOTS, 2), i32(SLOTS), i32(SLOTS))
    decode._paged_program_cache.clear()
    return jaxpr.jaxpr.eqns[0].params["jaxpr"].jaxpr  # inside the step's pjit


def _count(jaxpr, primitive):
    """Equations of a primitive in a jaxpr and everything it calls."""
    total = 0
    for eqn in jaxpr.eqns:
        total += eqn.primitive.name == primitive
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    total += _count(inner, primitive)
    return total


@pytest.mark.parametrize("width,tile", [(1, 512), (CHUNK, 16)], ids=["narrow", "wide_token_tiles"])
def test_one_pass_traces_one_scan_over_the_layers_and_2L_plus_1_norms(toy, monkeypatch, width, tile):
    monkeypatch.setattr(decode, "DENSE_TOKEN_TILE", tile)
    plain_cfg = TransformerConfig(**MISTRAL_TOY)
    plain = _step_jaxpr(plain_cfg, jax.eval_shape(lambda: TransformerLM(plain_cfg).init(jax.random.PRNGKey(0), None)), width)
    scans = [e for e in plain.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == plain_cfg.num_layers  # no enclosing loop: the scan is the step's own equation
    # a norm is one sqrt: two in the layers' body, run L times, and the final one
    assert _count(scans[0].params["jaxpr"].jaxpr, "sqrt") == 2 and _count(plain, "sqrt") == 3
    assert "loop_pass" not in str(plain) and "pass_norm" not in str(plain)
    cfg, lm, params, _ = toy
    looped = _step_jaxpr(cfg, jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), None)), width)
    scans = [e for e in looped.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [cfg.num_layers] * 4  # the same stacks scanned a pass, no pool sliced between
    assert all(_count(e.params["jaxpr"].jaxpr, "sqrt") == 4 for e in scans) and _count(looped, "sqrt") == 4 * 4 + 3 + 1
    pools = [v for e in scans for v in e.invars if getattr(v.aval, "shape", ()) == (12, 9, 4, PAGE, 16)]
    assert len(pools) == 8  # each pass's scan takes the whole [loops x layers, ...] pools, K and V


def _golden_run():
    """The Mistral-shaped toy through the driver: a wide step of two chunks and a short prompt, then decode."""
    cfg = TransformerConfig(**MISTRAL_TOY)
    params = seeded(TransformerLM(cfg))
    seqs = sequences(1, lens=(21, 4, 18, 9), vocab=128)
    got = Driver(cfg, params).run(seqs, decode_from={0: 17, 1: 2, 2: 18, 3: 5})
    return np.concatenate([got[s] for s in sorted(got)])


def record_golden():
    """Run at the parent commit of PR 56: ``python3 -c 'from tests.unit.inference.test_looped_serving import record_golden as r; r()'``."""
    os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
    np.savez_compressed(GOLDEN, logits=_golden_run())


def test_a_mistral_shaped_toy_serves_the_parents_logits():
    """The logits the parent commit's ``_paged_forward`` gave for the same toy, weights and schedule, narrow and wide
    steps: bit for bit on the machine that recorded them (XLA's CPU code is the same program there); a CPU of another
    vector width sums in another order, so the limit is 2e-6 where the arrays are not equal, a hundredth of what a
    norm, a scale or a pass too many would move."""
    golden = np.load(GOLDEN)["logits"]
    ours = _golden_run()
    assert ours.shape == golden.shape == (52, 128)
    assert np.array_equal(ours, golden) or np.abs(ours - golden).max() < 2e-6
