"""The host works while the device does (``scheduler.py``): a call of
``step()`` enqueues the step the call before it packed (its decode tokens
gathered on the device from the unsettled result), settles the step before
that one behind the enqueue, packs the next and ends with the wait for the
device.

The oracle is the same server drained every step: ``settle()`` after each
``step()`` (a draining entry point, not a flag) settles the step just
dispatched and drops what was packed behind it, so the next one is packed
from settled state as a synchronous server packs it. On the CPU in float32
every stream must be byte-identical between the two under every feature, and
the counters must say which of the two a run was.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import decode
from deepspeed_tpu.inference.journal import JournaledRequest, RequestJournal
from deepspeed_tpu.inference.scheduler import PagedServer
from deepspeed_tpu.inference.spec_decode import Drafter
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.config import TransformerConfig
from deepspeed_tpu.models import hybrid_moe
from deepspeed_tpu.models.hybrid_moe import HybridMoETransformerLM
from deepspeed_tpu.models.moe_transformer import MoETransformerLM, olmoe_config
from deepspeed_tpu.profiling.tracer import Tracer
from deepspeed_tpu.utils import chaos
from tests.unit.inference.hybrid_toys import _clear_jax_caches, _compiled_programs_live_as_long_as_the_file, seeded  # noqa: F401 (the two fixtures are taken by their import)

CFG = dict(
    vocab_size=128, hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=96,
    norm="rmsnorm", position="rope", activation="swiglu", use_bias=False, tie_embeddings=False,
    flash_attention=False, dtype="float32",
)


@pytest.fixture(scope="module")
def dense():
    cfg = TransformerConfig(**CFG)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return cfg, params


@pytest.fixture(autouse=True)
def _disarm_chaos():
    yield
    chaos.uninstall()


def _prompts(n, seed=0, lo=3, hi=20, vocab=128):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, vocab, (int(rs.randint(lo, hi)),)).astype(np.int32) for _ in range(n)]


def _generate(cfg, params, prompt, n, eos=None):
    out = np.asarray(decode.generate(cfg, params, prompt[None], n, eos_token_id=eos))[0]
    if eos is not None:  # generate pads a finished row with its EOS; a stream ends at it
        made = out[prompt.size :]
        hit = np.flatnonzero(made == eos)
        if hit.size:
            out = out[: prompt.size + hit[0] + 1]
    return out


def _server(cfg, params, **kw):
    kw = {"page_size": 8, "max_slots": 4, "prefill_chunk": 8, "attn_impl": "xla", "dtype": jnp.float32, **kw}
    return PagedServer(cfg, params, **kw)


def _run(server, drained: bool):
    """``run()``, or the same loop with the step in flight settled after
    every call: the synchronous server."""
    while server.has_work():
        server.step()
        if drained:
            server.settle()
    return server._results


def _both(cfg, params, requests, **kw):
    """The requests through a server that runs ahead and through one drained
    every step; returns (ahead server, drained server, streams, streams)."""
    servers, streams = [], []
    for drained in (False, True):
        server = _server(cfg, params, **kw)
        uids = [server.submit(p, max_new_tokens=n, eos_token_id=eos) for p, n, eos in requests]
        results = _run(server, drained)
        servers.append(server)
        streams.append([results[u] for u in uids])
    return servers[0], servers[1], streams[0], streams[1]


def _assert_same(ahead_streams, drained_streams):
    assert len(ahead_streams) == len(drained_streams)
    for a, b in zip(ahead_streams, drained_streams):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def _drained_to_zero(server):
    """Nothing left behind: no step in flight, every slot and page back."""
    assert server._in_flight is None and not server.has_work()
    assert server.pool.used_pages() == 0 and len(server.pool._free_slots) == server.pool.max_slots
    server.pool.integrity_check()


# --- the dense model, feature by feature ---------------------------------------
def _eos_requests(cfg, params, prompts, budgets):
    """Each request's EOS is a token its own greedy stream produces part-way
    (first occurrence at a different depth a request), so every one ends early."""
    out = []
    for i, (p, n) in enumerate(zip(prompts, budgets)):
        made = _generate(cfg, params, p, n)[p.size :]
        out.append((p, n, int(made[min(2 + 3 * i, n - 2)])))
    return out


SCENARIOS = {
    # one chunk each, all admitted at once: decode alone after the first step
    "plain_decode": dict(prompts=dict(n=4, seed=1, lo=3, hi=8), budgets=[12, 9, 15, 11]),
    # prompts of several chunks and more requests than slots: chunks ride with decode rows, admissions mid-stream
    "chunked_prefill_rides_with_decode": dict(prompts=dict(n=7, seed=2, lo=6, hi=40), budgets=[10, 14, 6, 9, 12, 7, 11]),
    # budgets that end at the first token, the second, and later: a row whose budget ends is not packed again
    "budget_end": dict(prompts=dict(n=6, seed=3, lo=3, hi=20), budgets=[1, 2, 3, 1, 8, 2]),
    "eos_mid_run": dict(prompts=dict(n=5, seed=4, lo=3, hi=20), budgets=[16, 18, 20, 17, 19], eos=True),
    "prefix_cache": dict(prompts=dict(n=6, seed=5, lo=4, hi=12), budgets=[9, 6, 11, 8, 7, 10], shared_prefix=24, kw=dict(prefix_cache=True)),
    "prefix_cache_eos": dict(prompts=dict(n=5, seed=6, lo=4, hi=12), budgets=[14, 16, 15, 17, 13], shared_prefix=16, eos=True, kw=dict(prefix_cache=True)),
    # prompt + budget == max_seq_len: the step packed behind a row's last one must not carry the row (a position past the cap is refused)
    "budget_ends_at_the_seq_cap": dict(prompts=dict(n=4, seed=21, lo=3, hi=14), budgets="to_the_cap", kw=dict(max_seq_len=40)),
    # a row's last written position fills its last page, in a pool of exactly the rows' pages: one position more would have to preempt
    "finish_on_a_page_edge": dict(prompts=dict(n=4, seed=22, lo=3, hi=14), budgets="to_a_page_edge"),
}


@pytest.mark.parametrize("name", list(SCENARIOS))
def test_streams_are_those_of_a_server_drained_every_step(dense, name):
    cfg, params = dense
    sc = SCENARIOS[name]
    prompts = _prompts(**sc["prompts"])
    if sc.get("shared_prefix"):
        system = np.random.RandomState(99).randint(0, 128, sc["shared_prefix"]).astype(np.int32)
        prompts = [np.concatenate([system, p]) for p in prompts]
    budgets = sc["budgets"]
    kw = dict(sc.get("kw", {}))
    if budgets == "to_the_cap":
        budgets = [40 - p.size for p in prompts]
    elif budgets == "to_a_page_edge":  # 8 m + 1 tokens in all: 8 m positions written, m whole pages (and the trash page)
        pages = [-(-p.size // 8) + 1 + i % 2 for i, p in enumerate(prompts)]
        budgets = [8 * m + 1 - p.size for m, p in zip(pages, prompts)]
        kw["num_pages"] = sum(pages) + 1
    requests = _eos_requests(cfg, params, prompts, budgets) if sc.get("eos") else [(p, n, None) for p, n in zip(prompts, budgets)]
    ahead, drained, got, want = _both(cfg, params, requests, **kw)
    _assert_same(got, want)
    for (p, n, eos), stream in zip(requests, got):
        assert np.array_equal(stream, _generate(cfg, params, p, n, eos))
    if isinstance(sc["budgets"], str):
        assert all(s.size == p.size + n for (p, n, _), s in zip(requests, got)) and ahead.stats["preempted"] == drained.stats["preempted"] == 0
    # which of the two each was
    assert ahead.stats["run_ahead_steps"] >= ahead.stats["ragged_steps"] - 2 > 0
    assert set(ahead.stats["drain_reasons"]) <= {"idle"}
    assert drained.stats["run_ahead_steps"] == 0 and drained.stats["drain_reasons"] == {"settle": drained.stats["ragged_steps"]}
    assert drained.stats["overshoot_rows"] == 0
    if sc.get("eos"):
        # an EOS is seen a step late: the row rode in one more step, whose result for it was discarded
        assert ahead.stats["overshoot_rows"] >= 1
        assert all(stream[-1] == eos and (stream[p.size : -1] != eos).all() for (p, _, eos), stream in zip(requests, got))
    else:
        assert ahead.stats["overshoot_rows"] == 0
    assert ahead.stats["emitted_tokens"] == drained.stats["emitted_tokens"] == sum(s.size - p.size for (p, _, _), s in zip(requests, got))
    assert ahead.stats["prefill_chunks"] == drained.stats["prefill_chunks"]
    for server in (ahead, drained):
        _drained_to_zero(server)


@pytest.mark.parametrize("num_pages", [9, 11])
def test_a_pool_that_has_to_preempt_settles_first(dense, num_pages):
    """A reservation that would have to preempt is made on settled state: the
    in-flight step is fetched first (``drain_reasons['preempt']``), the victim
    holds no unsettled token, and the streams are ``generate``'s."""
    cfg, params = dense
    prompts = _prompts(5, seed=7, lo=10, hi=22)
    budgets = [20, 24, 18, 22, 16]
    requests = [(p, n, None) for p, n in zip(prompts, budgets)]
    ahead, drained, got, want = _both(cfg, params, requests, num_pages=num_pages)
    _assert_same(got, want)
    for (p, n, _), stream in zip(requests, got):
        assert np.array_equal(stream, _generate(cfg, params, p, n))
    assert ahead.stats["preempted"] > 0 and ahead.stats["drain_reasons"]["preempt"] > 0
    assert ahead.stats["run_ahead_steps"] > 0
    _drained_to_zero(ahead)


class _SilentDrafter(Drafter):
    """Armed, and proposes nothing: the server has to be the synchronous one."""

    def propose(self, uid, context, k):
        return np.zeros(0, np.int32)


class _FutureDrafter(Drafter):
    """Proposes the request's own greedy future, the last of every proposal wrong."""

    def __init__(self, futures):
        self.futures = futures

    def propose(self, uid, context, k):
        cont = self.futures[uid][context.size : context.size + k].copy()
        if cont.size:
            cont[-1] = (cont[-1] + 1) % 128
        return cont.astype(np.int32)


@pytest.mark.parametrize("armed", ["silent_drafter", "future_drafter", "ngram_config"])
def test_an_armed_drafter_makes_the_server_synchronous(dense, armed):
    """Drafts are proposed from settled contexts: the step is settled in the
    call that dispatched it, nothing ever runs ahead, and the streams are the
    plain server's."""
    cfg, params = dense
    prompts = _prompts(5, seed=8, lo=4, hi=18)
    budgets = [14, 17, 9, 13, 12]
    futures = {i: _generate(cfg, params, p, n) for i, (p, n) in enumerate(zip(prompts, budgets))}
    kw = {
        "silent_drafter": dict(drafter=_SilentDrafter()),
        "future_drafter": dict(drafter=_FutureDrafter(futures)),
        "ngram_config": dict(spec_decode={"enable": True, "max_draft": 3}),
    }[armed]
    requests = [(p, n, None) for p, n in zip(prompts, budgets)]
    ahead, drained, got, want = _both(cfg, params, requests, **kw)
    _assert_same(got, want)
    for i, stream in enumerate(got):
        assert np.array_equal(stream, futures[i])
    assert ahead.stats["run_ahead_steps"] == 0 and ahead.stats["overshoot_rows"] == 0
    assert ahead.stats["drain_reasons"] == {"draft": ahead.stats["ragged_steps"]}
    assert ahead.serve_stats()["run_ahead_share"] == 0.0
    if armed == "future_drafter":
        assert ahead.stats["spec_accepted"] > 0
    _drained_to_zero(ahead)


# --- the models with layers of more than one kind, and the routed one ----------
LOOPED = dict(vocab_size=512, hidden_size=64, intermediate_size=96, num_layers=3, num_heads=4, head_dim=16, max_seq_len=256,
              norm="rmsnorm", norm_eps=1e-6, position="rope", rope_theta=1e6, activation="swiglu", use_bias=False, tie_embeddings=False,
              num_loops=4, post_sublayer_norm=True, exit_gate=True, dtype="float32", flash_attention=False)
HYBRIDS = {"solar": "solar_open2_config", "mimo": "mimo_v2_config", "glm": "glm4_moe_lite_config", "laguna": "laguna_config",
           "kimi": "kimi_linear_config", "granite": "granite_hybrid_config", "nemotron": "nemotron_h_config"}
# init's 0.02 gives a nearly flat softmax and a recurrent state a hundredth of its input, in which a stale state,
# ring or latent page would hide: trained-like scales on the scores and on the state-space layers' x, B and C
TRAINED_LIKE = {"wq": 40.0, "wq_b": 40.0, "wk": 8.0, "w_xbc": 8.0}


def _trained_like(tree):
    if isinstance(tree, list):
        return [_trained_like(t) for t in tree]
    return {k: _trained_like(v) if isinstance(v, (dict, list)) else v * TRAINED_LIKE.get(k, 1.0) for k, v in tree.items()}


def _hybrid(kind):
    if kind == "olmoe":
        cfg = olmoe_config("tiny", dtype="float32", flash_attention=False, remat=False)
        return cfg, MoETransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    if kind == "ouro":  # the looped stack: a KV cache of its own for every pass
        cfg = TransformerConfig(**LOOPED)
        return cfg, seeded(TransformerLM(cfg))
    cfg = getattr(hybrid_moe, HYBRIDS[kind])("tiny", dtype="float32", **({"num_layers": 8} if kind == "solar" else {}))
    return cfg, _trained_like(seeded(HybridMoETransformerLM(cfg)))


@pytest.mark.parametrize("kind", ["solar", "mimo", "olmoe", "glm", "laguna", "kimi", "granite", "nemotron", "ouro"])
def test_the_state_store_the_window_rings_and_the_routing_counts_follow(kind):
    """The state store and the window rings are threaded through the steps
    as the pools are, and an MoE model's routing counts ride past the token
    rows of the result the gather reads: the streams and the counts are the
    drained server's, EOS overshoot included (a stray write into a slot's
    state, ring, latent page or, in a looped stack, a pass's cache is
    harmless to its next owner: the program restarts a row that begins at
    position 0). The families whose every cell runs ahead: Solar (linear
    state), MiMo (window rings), OLMoE, GLM (latent pages), Laguna (gated
    window + full layers), Kimi (KDA state + latent), granite (SSD state, a
    dense FFN), Nemotron (blocks of one sublayer), Ouro (four passes)."""
    cfg, params = _hybrid(kind)
    rs = np.random.RandomState(11)
    prompts = [rs.randint(0, min(cfg.vocab_size, 512), n).astype(np.int32) for n in (37, 5, 20, 16, 50, 3, 9)]
    budgets = [12, 20, 9, 15, 7, 30, 11]
    kw = dict(page_size=8, max_slots=4, prefill_chunk=16, max_seq_len=96)
    plain = _server(cfg, params, **kw)
    uids = [plain.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    futures = [_run(plain, drained=True)[u] for u in uids]
    # every other request ends at a token of its own stream
    requests = [(p, n, int(f[p.size + 3]) if i % 2 else None) for i, (p, n, f) in enumerate(zip(prompts, budgets, futures))]
    ahead, drained, got, want = _both(cfg, params, requests, **kw)
    _assert_same(got, want)
    assert ahead.stats["overshoot_rows"] >= 1 and ahead.stats["run_ahead_steps"] > 0.8 * ahead.stats["ragged_steps"]
    # a discarded row-step still routed: the overshoot rows are the only difference
    extra = ahead.stats["overshoot_rows"] * getattr(cfg, "num_moe_layers", cfg.num_layers) * getattr(cfg, "moe_top_k", 0)
    if "moe_routed_assignments" in ahead.stats:
        assert ahead.stats["moe_routed_assignments"] == drained.stats["moe_routed_assignments"] + extra
    elif "moe_assignments" in ahead.stats:
        assert ahead.stats["moe_assignments"] == drained.stats["moe_assignments"] + extra
    else:
        assert kind in ("granite", "ouro")  # no expert: nothing is counted
    # a slot freed by an overshoot row was taken again: seven requests over four slots
    assert ahead.stats["admitted"] == 7 > ahead.pool.max_slots
    _drained_to_zero(ahead)


# --- what a step in flight means for the caller -------------------------------
def test_an_eos_row_is_released_once_and_only_after_the_step_that_writes_it(dense):
    cfg, params = dense
    prompt = _prompts(1, seed=12, lo=6, hi=7)[0]
    future = _generate(cfg, params, prompt, 12)
    eos = int(future[prompt.size + 4])
    first = prompt.size + int(np.flatnonzero(future[prompt.size :] == eos)[0])
    server = _server(cfg, params)
    freed = []
    free_slot = server.pool.free_slot
    server.pool.free_slot = lambda slot: (freed.append(slot), free_slot(slot))[1]
    uid = server.submit(prompt, max_new_tokens=12, eos_token_id=eos)
    while server.result(uid) is None:
        server.step()
    # the stream is whole the moment its EOS settles: the EOS included, nothing after it
    assert np.array_equal(server.result(uid), future[: first + 1])
    # ... while the step packed before the EOS was seen still writes the row's pages
    step = server._in_flight
    assert step is not None and [r.uid for r in step.rows] == [uid]
    slot = step.rows[0].slot
    assert freed == [] and slot not in server.pool._free_slots and server.pool._owned[slot] > 0
    assert server.has_work() and not server._active and not server._queue  # a step in flight is work
    assert server.stats["overshoot_rows"] == 0 and server.stats["finished"] == 1
    server.step()  # nothing to pack: the call only settles, and discards the row's result
    assert freed == [slot] and server.stats["overshoot_rows"] == 1
    assert server.stats["drain_reasons"] == {"idle": 1}
    assert np.array_equal(server.take_result(uid), future[: first + 1])
    assert server.stats["emitted_tokens"] == first + 1 - prompt.size
    _drained_to_zero(server)
    server.step()  # and an idle server's step is a no-op
    assert freed == [slot] and server.stats["drain_reasons"] == {"idle": 1}


def test_a_call_returns_the_tokens_of_the_step_dispatched_one_call_earlier(dense):
    cfg, params = dense
    server = _server(cfg, params)
    uid = server.submit(_prompts(1, seed=13, lo=5, hi=8)[0], max_new_tokens=3)
    emitted = []
    for _ in range(4):
        server.step()
        emitted.append(server.stats["emitted_tokens"])
    # chunk | decode (token on the device) + first token | decode + second | settle only: third
    assert emitted == [0, 1, 2, 3]
    assert server.stats["ragged_steps"] == 3 and server.stats["run_ahead_steps"] == 2
    assert server.stats["prefill_chunks"] == 1 and server.stats["decode_steps"] == 2
    assert server.take_result(uid).size == server.stats["emitted_tokens"] + _prompts(1, seed=13, lo=5, hi=8)[0].size
    assert not server.has_work()


def test_a_request_that_comes_between_two_calls_rides_in_the_next_step(dense):
    """The step packed before it came is packed again with it: its wait for
    its first chunk is a synchronous server's, and nothing is drained."""
    cfg, params = dense
    server = _server(cfg, params)
    first, second = _prompts(2, seed=20, lo=5, hi=8)
    a = server.submit(first, max_new_tokens=20)
    for _ in range(4):
        server.step()
    assert [r.uid for r in server._packed.rows] == [a]  # packed while the device ran, without the newcomer
    b = server.submit(second, max_new_tokens=6)
    chunks, steps = server.stats["prefill_chunks"], server.stats["ragged_steps"]
    server.step()
    assert server.stats["admitted"] == 2 and server.stats["prefill_chunks"] == chunks + 1
    assert server.stats["ragged_steps"] == steps + 1 and [r.uid for r in server._in_flight.rows] == [a, b]
    assert server.stats["drain_reasons"] == {} and server.stats["run_ahead_steps"] == server.stats["ragged_steps"] - 1
    results = server.run()
    assert np.array_equal(results[a], _generate(cfg, params, first, 20))
    assert np.array_equal(results[b], _generate(cfg, params, second, 6))
    _drained_to_zero(server)


def test_run_ahead_share_of_a_steady_run(dense):
    cfg, params = dense
    server = _server(cfg, params)
    prompts = _prompts(4, seed=14, lo=4, hi=8)
    outs = server.serve(prompts, max_new_tokens=60)
    stats = server.serve_stats()
    assert stats["run_ahead_share"] > 0.9 and stats["run_ahead_share"] == stats["run_ahead_steps"] / stats["ragged_steps"]
    assert stats["drain_reasons"] == {"idle": 1}  # the run's end
    for p, o in zip(prompts, outs):
        assert np.array_equal(o, _generate(cfg, params, p, 60))


def test_extract_and_restore_with_a_step_in_flight(dense):
    """The entry points that read or move a request settle first: what they
    hand over holds every token the device was asked for."""
    cfg, params = dense
    prompts = _prompts(3, seed=15, lo=5, hi=14)
    source, target = _server(cfg, params), _server(cfg, params)
    uids = [source.submit(p, max_new_tokens=14) for p in prompts]
    for _ in range(5):
        source.step()
    assert source._in_flight is not None
    before = source.stats["emitted_tokens"]
    state = source.extract_request(uids[1])
    assert source._in_flight is None and source.stats["drain_reasons"] == {"extract_request": 1}
    assert source.stats["emitted_tokens"] > before and len(state.generated) >= 3
    assert np.array_equal(state.generated, _generate(cfg, params, prompts[1], 14)[prompts[1].size :][: len(state.generated)])
    moved = source.extract_request(uids[2])
    source.restore_request(moved)  # nowhere to go: back on the source
    assert source.stats["migrated_out"] == 1 and source.stats["migrated_in"] == 0
    target.recover({state.uid: state}, migrated_in=True)
    source.run(), target.run()
    for u, p in zip(uids, prompts):
        got = target.result(u) if u == uids[1] else source.result(u)
        assert np.array_equal(got, _generate(cfg, params, p, 14))
    _drained_to_zero(source), _drained_to_zero(target)


def test_recover_lands_on_a_server_with_a_step_in_flight(dense):
    cfg, params = dense
    prompts = _prompts(3, seed=16, lo=5, hi=14)
    server = _server(cfg, params)
    uids = [server.submit(p, max_new_tokens=10) for p in prompts[:2]]
    for _ in range(4):
        server.step()
    assert server._in_flight is not None
    future = _generate(cfg, params, prompts[2], 10)
    state = JournaledRequest(uid=7, prompt=prompts[2], max_new_tokens=10, eos_token_id=None, tenant="default",
                             generated=[int(t) for t in future[prompts[2].size :][:4]])
    assert server.recover({7: state}, next_uid=8, migrated_in=True) == 1
    assert server._in_flight is None and server.stats["drain_reasons"] == {"recover": 1}
    results = server.run()
    for u, p in zip(uids + [7], prompts):
        assert np.array_equal(results[u], _generate(cfg, params, p, 10))


@pytest.mark.parametrize("kill_step", [1, 2, 4, 7])
def test_a_journal_replays_after_a_kill_with_a_step_in_flight(dense, tmp_path, kill_step):
    """``serve.mid_step`` fires after a call's settle and before its journal
    flush, with the step the call dispatched still in flight: that step dies
    unseen, the unsynced tokens are re-derived, and every stream is whole."""
    cfg, params = dense
    prompts = _prompts(4, seed=17, lo=5, hi=20)
    budgets = [9, 12, 7, 10]
    server = _server(cfg, params, journal=RequestJournal(str(tmp_path)))
    uids = [server.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    chaos.install(chaos.ChaosSchedule([chaos.ChaosRule("serve.mid_step", hit=kill_step)]))
    with pytest.raises(chaos.ChaosKilled):
        server.run()
    chaos.uninstall()
    assert server._in_flight is not None
    states, next_uid = RequestJournal.replay(str(tmp_path))
    # the journal holds what was flushed: the tokens of the calls before the kill
    assert sum(len(st.generated) for st in states.values()) <= server.stats["emitted_tokens"]
    restarted = _server(cfg, params, journal=RequestJournal(str(tmp_path)))
    assert restarted.recover(states, next_uid) == len(prompts)
    results = restarted.run()
    for u, p, n in zip(uids, prompts, budgets):
        assert np.array_equal(results[u], _generate(cfg, params, p, n))
    _drained_to_zero(restarted)


def test_a_compaction_and_a_finalized_migration_settle_first(dense, tmp_path):
    cfg, params = dense
    prompts = _prompts(3, seed=18, lo=5, hi=14)
    server = _server(cfg, params, journal=RequestJournal(str(tmp_path)))
    uids = [server.submit(p, max_new_tokens=12) for p in prompts]
    for _ in range(4):
        server.step()
    server.compact_journal()
    assert server.stats["drain_reasons"] == {"compact_journal": 1}
    states, _ = RequestJournal.replay(str(tmp_path))
    # the rewritten segment holds every token the device had been asked for
    assert sum(len(st.generated) for st in states.values()) == server.stats["emitted_tokens"]
    server.step()
    state = server.extract_request(uids[0])
    server.step()
    server.finalize_migration(uids[0])
    assert server.stats["drain_reasons"] == {"compact_journal": 1, "extract_request": 1, "finalize_migration": 1}
    results = server.run()
    for u, p in zip(uids[1:], prompts[1:]):
        assert np.array_equal(results[u], _generate(cfg, params, p, 12))
    assert len(state.generated) >= 3


# --- the order that is the mechanism ------------------------------------------
def test_inside_one_step_the_dispatch_closes_before_the_fetch_opens(dense):
    """From the tracer's ring buffer: a call enqueues the step packed by the
    call before (``serve.dispatch``, ``ahead=1``), settles the step before
    that one, admits, packs the next step and only then waits for the device
    (``serve.fetch``); the first call packs its own step first and the last
    one only settles."""
    cfg, params = dense
    tracer = Tracer()
    server = _server(cfg, params, tracer=tracer)
    server.serve(_prompts(3, seed=19, lo=4, hi=30), max_new_tokens=10)
    spans = [r for r in tracer.spans() if r["ph"] == "X"]
    steps = [r for r in spans if r["name"] == "serve.step"]

    def inside(outer, name):
        return [r for r in spans if r["name"] == name and outer["t0"] <= r["t0"] and r["t1"] <= outer["t1"]]

    assert len(steps) >= 12
    for k, step in enumerate(steps):
        dispatch, fetch, settle, packs = (inside(step, "serve." + n) for n in ("dispatch", "fetch", "settle", "pack"))
        if k == len(steps) - 1:  # the run's end: nothing packed, nothing to wait for
            assert (len(dispatch), len(fetch), len(settle), len(packs)) == (0, 0, 1, 0)
            continue
        (dispatch,), (fetch,) = dispatch, fetch
        assert dispatch["attrs"]["ahead"] == int(k > 0)
        assert dispatch["t1"] <= fetch["t0"]  # the enqueue of step n+1 is over before anything waits for the device
        if k < len(steps) - 2:  # the next step, packed while the device runs (the last step has none behind it)
            assert packs and packs[-1]["t0"] >= dispatch["t1"] and packs[-1]["t1"] <= fetch["t0"]
        if k == 0:
            assert len(packs) == 2 and packs[0]["t1"] <= dispatch["t0"] and settle == []
        else:
            (settle,) = settle  # of step n, behind the enqueue of step n+1 and before the wait for it
            assert dispatch["t1"] <= settle["t0"] and settle["t1"] <= fetch["t0"]
            assert settle["attrs"]["seq"] == k - 1
        # the step's number ties what three calls do for it: call k enqueues and waits for step k (packed by call
        # k - 1, but for the first), settles step k - 1 and packs step k + 1; the jitted call is a span of its own
        (enqueue,) = inside(dispatch, "serve.enqueue")
        assert dispatch["attrs"]["seq"] == enqueue["attrs"]["seq"] == fetch["attrs"]["seq"] == step["attrs"]["seq_enqueued"] == k
        assert [p["attrs"]["seq"] for p in packs] == ([0, 1] if k == 0 else [k + 1] if packs else [])
    assert "seq_enqueued" not in steps[-1]["attrs"]
    assert server.stats["run_ahead_steps"] == len(steps) - 2 == server.stats["ragged_steps"] - 1


@pytest.mark.parametrize("name", ["chunked_prefill_rides_with_decode", "eos_mid_run"])
def test_streams_with_the_tracer_on_are_those_with_it_off(dense, name):
    """The spans, their ``seq`` and the turnaround stamps are host-side
    bookkeeping: the served tokens and the counters are the same with a live
    tracer as with the disabled one every other test of this file runs on."""
    cfg, params = dense
    sc = SCENARIOS[name]
    prompts, budgets = _prompts(**sc["prompts"]), sc["budgets"]
    requests = _eos_requests(cfg, params, prompts, budgets) if sc.get("eos") else [(p, n, None) for p, n in zip(prompts, budgets)]
    runs = []
    for tracer in (None, Tracer(max_spans=1 << 14)):
        server = _server(cfg, params, tracer=tracer)
        uids = [server.submit(p, max_new_tokens=n, eos_token_id=eos) for p, n, eos in requests]
        results = _run(server, drained=False)
        runs.append((server, [results[u] for u in uids]))
    (off, want), (on, got) = runs
    _assert_same(got, want)
    for (p, n, eos), stream in zip(requests, got):
        assert np.array_equal(stream, _generate(cfg, params, p, n, eos))
    counted = ("ragged_steps", "run_ahead_steps", "overshoot_rows", "emitted_tokens", "prefill_chunks", "drain_reasons")
    assert {k: on.stats[k] for k in counted} == {k: off.stats[k] for k in counted}
    assert off.tracer.spans() == [] and len([r for r in on.tracer.spans() if r["name"] == "serve.enqueue"]) == on.stats["ragged_steps"]
    # the histogram is the registry's and counts the same steps either way
    assert on.metrics.histogram("serve.turnaround_ms").snapshot()["count"] == off.metrics.histogram("serve.turnaround_ms").snapshot()["count"] == on.stats["run_ahead_steps"]


# --- the step's own record -----------------------------------------------------
def _decode_row_lens(text):
    """``serve.pack``'s ``row_lens`` back to (q_len, kv_len) pairs: a word a row, ``kv`` alone where q is 1."""
    return [tuple(int(x) for x in w.split(":")) if ":" in w else (1, int(w)) for w in text.split()]


@pytest.fixture(scope="module")
def recorded(dense):
    """One traced run that holds a prompt of three chunks beside a short one,
    a newcomer admitted behind a step in flight (the step packed before it
    came is packed again under its ``seq``) and one drain (``settle()``
    drops what was packed behind the step in flight: that ``seq`` is packed
    again too), with every ``_Packed`` that reached ``_dispatch`` kept as the
    device got it."""
    cfg, params = dense
    tracer = Tracer(max_spans=1 << 14)
    server = _server(cfg, params, tracer=tracer)
    dispatched = {}
    dispatch = server._dispatch

    def spy(packed):
        n = len(packed.rows)
        q = np.asarray(packed.q_lens)[:n]
        dispatched[packed.seq] = (packed.width, q.tolist(), (np.asarray(packed.operands[2])[:n] + q).tolist())
        return dispatch(packed)

    server._dispatch = spy
    long, short, newcomer = (np.arange(n, dtype=np.int32) % 128 for n in (20, 5, 6))
    server.submit(long, max_new_tokens=12)
    server.submit(short, max_new_tokens=14)
    for _ in range(4):
        server.step()
    repacked = [server._packed.seq]  # packed while the device ran, without the newcomer
    server.submit(newcomer, max_new_tokens=6)
    server.step()
    server.step()
    repacked.append(server._packed.seq)
    server.settle()  # the one drain: what was packed behind the step in flight is dropped
    server.run()
    assert server.stats["drain_reasons"] == {"settle": 1, "idle": 1} and server.stats["mixed_steps"] >= 4
    return server, tracer.spans(), dispatched, repacked


def _last_pack_before_each_enqueue(spans):
    """seq -> the attributes of the last ``serve.pack{seq}`` that ended before ``serve.enqueue{seq}`` began."""
    out = {}
    for enq in (r for r in spans if r["name"] == "serve.enqueue"):
        seq = enq["attrs"]["seq"]
        before = [r for r in spans if r["name"] == "serve.pack" and r["attrs"].get("seq") == seq and r["t1"] <= enq["t0"]]
        assert before, f"serve.enqueue seq {seq} has no serve.pack before it"
        out[seq] = max(before, key=lambda r: r["t1"])["attrs"]
    return out


STEP_RECORD = ["every_enqueue_has_its_pack", "row_lens_are_the_dispatched_rows", "mixed_is_the_width", "kv_tokens_is_their_sum",
               "a_repacked_seq_takes_its_last_pack", "json_dumps_as_the_flight_recorder"]


@pytest.mark.parametrize("case", STEP_RECORD)
def test_a_step_carries_its_own_record_under_its_seq(recorded, case):
    """``serve.pack`` holds what the kernel got: ``row_lens`` decode to the
    ``q_lens`` / ``lengths + q_lens`` of the ``_Packed`` that was dispatched,
    ``mixed`` says its width and ``kv_tokens`` their sum, under the ``seq``
    of the ``serve.enqueue`` that follows; a ``seq`` packed twice (a newcomer,
    a drain) is read from its LAST pack before the enqueue."""
    server, spans, dispatched, repacked = recorded
    record = _last_pack_before_each_enqueue(spans)
    if case == "every_enqueue_has_its_pack":
        assert sorted(record) == sorted(dispatched) == list(range(server.stats["ragged_steps"]))
        assert all({"mixed", "kv_tokens", "row_lens", "kv_pages", "live_tokens", "token_tiles"} <= set(a) for a in record.values())
        assert not any({"table_pages", "latent_tokens"} & set(a) for a in record.values())
    elif case == "row_lens_are_the_dispatched_rows":
        for seq, (_, q, kv) in dispatched.items():
            assert _decode_row_lens(record[seq]["row_lens"]) == list(zip(q, kv)), seq
            assert record[seq]["rows"] == len(q) and record[seq]["live_tokens"] == sum(q)
        # the long prompt's three chunks (8, 8, 4 of 20) and a decode row written ``kv`` alone
        assert [_decode_row_lens(record[s]["row_lens"])[0] for s in (0, 1, 2)] == [(8, 8), (8, 16), (4, 20)]
        assert record[3]["row_lens"] == "21 8"  # the short prompt: 5, then a token a step
    elif case == "mixed_is_the_width":
        for seq, (width, q, _) in dispatched.items():
            assert record[seq]["mixed"] == int(width == server._ragged_w_mixed) == int(record[seq]["width"] != 1), seq
        assert {a["mixed"] for a in record.values()} == {0, 1}
        assert sum(a["mixed"] for a in record.values()) == server.stats["mixed_steps"]
    elif case == "kv_tokens_is_their_sum":
        for seq, (_, _, kv) in dispatched.items():
            assert record[seq]["kv_tokens"] == sum(kv), seq
            assert record[seq]["kv_pages"] == sum(-(-n // server.pool.page_size) for n in kv)
    elif case == "a_repacked_seq_takes_its_last_pack":
        for seq in repacked:
            packs = [r["attrs"] for r in spans if r["name"] == "serve.pack" and r["attrs"].get("seq") == seq]
            assert len(packs) == 2 and record[seq] is packs[1] and _decode_row_lens(packs[1]["row_lens"]) == list(zip(*dispatched[seq][1:]))
        # the newcomer's first chunk rides in the pack that was enqueued, not in the one made before it came
        first, last = ([r["attrs"] for r in spans if r["name"] == "serve.pack" and r["attrs"].get("seq") == repacked[0]])
        assert (first["rows"], first["mixed"]) == (2, 0) and (last["rows"], last["mixed"]) == (3, 1) and last["row_lens"].endswith(" 6:6")
        # the pack a drain dropped was made from counts the settle did not overturn: the same rows, packed again
        first, last = ([r["attrs"] for r in spans if r["name"] == "serve.pack" and r["attrs"].get("seq") == repacked[1]])
        assert first == last and first is not last
    else:
        for r in spans:
            if r["name"] == "serve.pack":
                assert json.loads(json.dumps(r["attrs"])) == r["attrs"] and isinstance(r["attrs"]["row_lens"], str)


# --- what is left to count ------------------------------------------------------
DRAIN_REASONS = {"idle", "draft", "preempt", "settle", "recover", "extract_request", "restore_request", "finalize_migration", "compact_journal"}


def test_the_scheduler_names_nine_drain_reasons_and_a_run_counts_no_other(dense, tmp_path):
    """``_drain``'s call sites, read from the source, name nine reasons; and a
    run that takes a tight pool, an entry point between two calls and a
    compaction counts reasons of those nine alone."""
    import inspect
    import re

    assert set(re.findall(r'_drain\("(\w+)"\)', inspect.getsource(PagedServer))) == DRAIN_REASONS
    cfg, params = dense
    prompts = _prompts(5, seed=7, lo=10, hi=22)
    seen = set()
    for kw in (dict(num_pages=9), dict(drafter=_SilentDrafter()), dict(journal=RequestJournal(str(tmp_path)))):
        server = _server(cfg, params, **kw)
        uids = [server.submit(p, max_new_tokens=12) for p in prompts]
        for _ in range(4):
            server.step()
        if server.journal is not None:
            server.compact_journal()  # settles the step in flight first
            server.step()
        server.restore_request(server.extract_request(uids[0]))
        server.settle()
        results = server.run()
        for u, p in zip(uids, prompts):
            assert np.array_equal(results[u], _generate(cfg, params, p, 12))
        seen |= set(server.stats["drain_reasons"])
        _drained_to_zero(server)
    assert {"idle", "draft", "preempt", "extract_request", "compact_journal"} <= seen <= DRAIN_REASONS


def test_the_stats_hold_no_window_key_and_still_the_dispatches_a_token(dense):
    """``serve_stats()`` of one server and of a fleet of two: every counter
    is of the one path there is, and ``dispatches_per_token`` is still there:
    a server's dispatches over its tokens, fewer where drafts are accepted,
    and for a fleet the ratio of the sums."""
    from deepspeed_tpu.inference.fleet import FleetRouter, ReplicaHandle

    cfg, params = dense
    prompts = _prompts(4, seed=23, lo=4, hi=12)
    futures = {i: _generate(cfg, params, p, 12) for i, p in enumerate(prompts)}
    plain, drafted = _server(cfg, params), _server(cfg, params, drafter=_FutureDrafter(futures))
    for server in (plain, drafted):
        for i, o in enumerate(server.serve(prompts, max_new_tokens=12)):
            assert np.array_equal(o, futures[i])
    servers = [_server(cfg, params) for _ in range(2)]
    router = FleetRouter([ReplicaHandle(name=f"r{i}", server=s) for i, s in enumerate(servers)])
    uids = [router.submit(p, max_new_tokens=12) for p in prompts]
    results = router.run()
    for i, u in enumerate(uids):
        assert np.array_equal(results[u], futures[i])
    merged = router.serve_stats()
    for stats in (merged, plain.serve_stats(), drafted.serve_stats(), *merged["replicas"].values()):
        assert not [k for k in stats if "window" in k], sorted(stats)
        assert stats["dispatches"] == stats["ragged_steps"] > 0 and "dispatches_per_token" in stats
    assert merged["emitted_tokens"] == drafted.stats["emitted_tokens"] == 48
    assert drafted.serve_stats()["dispatches_per_token"] == drafted.stats["dispatches"] / 48 < plain.serve_stats()["dispatches_per_token"] == plain.stats["dispatches"] / 48
    assert merged["dispatches"] == sum(s.stats["dispatches"] for s in servers) and all(s.stats["dispatches"] for s in servers)
    assert merged["dispatches_per_token"] == merged["dispatches"] / 48
