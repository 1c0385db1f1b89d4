"""A serving step's identity (``scheduler.py``): since the host works while
the device does, one step's life is spread over three calls of ``step()``
(packed, enqueued and waited for, settled), and every span of it carries the
step's ``seq``: ``stats["dispatches"]`` as it stood at the pack. The jitted
call alone is ``serve.enqueue``, and the histogram ``serve.turnaround_ms``
times the host from one step's wait to the next step's enqueue wherever no
drain lies between.

Read from the tracer's ring buffer on a fake clock that ticks once a
reading, so that every span has a place in one total order and the
histogram's sum can be reckoned from the spans' own stamps.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.scheduler import PagedServer
from deepspeed_tpu.inference.spec_decode import Drafter
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.config import TransformerConfig
from deepspeed_tpu.profiling.tracer import MetricsRegistry, Tracer

CFG = dict(
    vocab_size=128, hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=96,
    norm="rmsnorm", position="rope", activation="swiglu", use_bias=False, tie_embeddings=False,
    flash_attention=False, dtype="float32",
)
LIFE = ("serve.pack", "serve.dispatch", "serve.enqueue", "serve.fetch", "serve.emit", "serve.settle")


@functools.lru_cache(maxsize=1)
def _dense():
    cfg = TransformerConfig(**CFG)
    return cfg, TransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def _prompts(n, seed, lo, hi):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 128, (int(rs.randint(lo, hi)),)).astype(np.int32) for _ in range(n)]


class _SilentDrafter(Drafter):
    """Armed, and proposes nothing: every step is drained (``draft``)."""

    def propose(self, uid, context, k):
        return np.zeros(0, np.int32)


def _late_arrivals(server, prompts, budgets):
    """Half the requests come between two calls, one at a time: the step
    packed before each came is packed again with it, under the same ``seq``."""
    half = len(prompts) // 2
    uids = [server.submit(p, max_new_tokens=n) for p, n in zip(prompts[:half], budgets)]
    late = list(zip(prompts[half:], budgets[half:]))
    while server.has_work() or late:
        server.step()
        if late and server.stats["ragged_steps"] % 3 == 0:
            p, n = late.pop(0)
            uids.append(server.submit(p, max_new_tokens=n))
    return uids


def _entry_point_drains(server, prompts, budgets):
    """``settle()`` every fifth call: an entry point's drain between two calls."""
    uids = [server.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    for k in itertools.count(1):
        if not server.has_work():
            return uids
        server.step()
        if k % 5 == 0:
            server.settle()


SCENARIOS = {
    # one chunk each, all admitted at once: a steady run ahead, one idle drain at its end
    "steady_decode": dict(prompts=dict(n=4, seed=1, lo=3, hi=8), budgets=[12, 9, 15, 11]),
    # prompts of several chunks, more requests than slots: admissions in the middle of the run
    "chunks_and_admissions": dict(prompts=dict(n=7, seed=2, lo=6, hi=40), budgets=[10, 14, 6, 9, 12, 7, 11]),
    "a_second_pack_for_a_newcomer": dict(prompts=dict(n=6, seed=3, lo=4, hi=12), budgets=[14, 9, 11, 8, 7, 10], drive=_late_arrivals),
    "idle_drains": dict(prompts=dict(n=4, seed=4, lo=3, hi=8), budgets=[3, 4, 3, 5], waves=3),
    "preempt_drains": dict(prompts=dict(n=5, seed=7, lo=10, hi=22), budgets=[20, 24, 18, 22, 16], kw=dict(num_pages=9)),
    "draft_drains": dict(prompts=dict(n=4, seed=8, lo=4, hi=18), budgets=[9, 12, 7, 10], kw=dict(drafter=_SilentDrafter())),
    # every request ends at a token of its own stream: the EOS is seen a step late, and the row-step packed behind it is discarded
    "an_eos_with_a_step_in_flight": dict(prompts=dict(n=4, seed=9, lo=4, hi=18), budgets=[14, 17, 9, 13], eos=True),
    "an_entry_point_between_calls": dict(prompts=dict(n=3, seed=10, lo=4, hi=10), budgets=[16, 13, 18], drive=_entry_point_drains),
}


def _serve(name, traced=True):
    """One scenario through a fresh server: (server, tracer, metrics, streams)."""
    cfg, params = _dense()
    sc = SCENARIOS[name]
    ticks = itertools.count()
    tracer = Tracer(max_spans=1 << 16, enabled=traced, clock=lambda: float(next(ticks)))
    metrics = MetricsRegistry()
    kw = {"page_size": 8, "max_slots": 4, "prefill_chunk": 8, "attn_impl": "xla", "dtype": jnp.float32, **sc.get("kw", {})}
    server = PagedServer(cfg, params, tracer=tracer, metrics=metrics, **kw)
    prompts, budgets = _prompts(**sc["prompts"]), sc["budgets"]
    if sc.get("eos"):
        eos = _eos_of_each(cfg, params, kw, prompts, budgets)
        submit = server.submit
        server.submit = lambda p, max_new_tokens: submit(p, max_new_tokens=max_new_tokens, eos_token_id=eos[p.tobytes()])
    streams = []
    for _ in range(sc.get("waves", 1)):  # a wave is served to its end before the next comes: the server empties between
        if "drive" in sc:
            uids = sc["drive"](server, prompts, budgets)
        else:
            uids = [server.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
            server.run()
        streams += [server.take_result(u) for u in uids]
    assert server._in_flight is None and not server.has_work()
    return server, tracer, metrics, streams


def _eos_of_each(cfg, params, kw, prompts, budgets):
    """prompt -> a token its own greedy stream holds part-way, at a different depth a request."""
    plain = PagedServer(cfg, params, **kw)
    futures = plain.serve(prompts, max_new_tokens=budgets)
    return {p.tobytes(): int(f[p.size + 2 + 2 * i]) for i, (p, f) in enumerate(zip(prompts, futures))}


@functools.lru_cache(maxsize=None)
def _traced(name):
    return _serve(name)


def _spans(tracer, name):
    return [r for r in tracer.spans() if r["ph"] == "X" and r["name"] == name]


@pytest.mark.parametrize("name", list(SCENARIOS))
def test_every_span_of_a_steps_life_carries_its_seq(name):
    server, tracer, _, _ = _traced(name)
    assert tracer.dropped() == 0
    by_seq = {}
    for kind in LIFE:
        for r in _spans(tracer, kind):
            assert r["attrs"] is not None and isinstance(r["attrs"].get("seq"), int), (kind, r["attrs"])
            by_seq.setdefault(r["attrs"]["seq"], {}).setdefault(kind, []).append(r)
    # consecutive over the enqueues, in time: an admission's second pack and a drain keep the count
    enqueues = _spans(tracer, "serve.enqueue")
    assert [r["attrs"]["seq"] for r in enqueues] == list(range(server.stats["dispatches"])) and len(enqueues) >= 6
    assert sorted(by_seq) == list(range(len(enqueues)))
    for seq, life in by_seq.items():
        (enqueue,), (dispatch,), (fetch,), (settle,), (emit,) = (life["serve." + k] for k in ("enqueue", "dispatch", "fetch", "settle", "emit"))
        # packed (perhaps twice), then enqueued inside its dispatch, then waited for, then settled inside its emit
        assert life["serve.pack"] and all(p["t1"] < enqueue["t0"] for p in life["serve.pack"])
        assert dispatch["t0"] < enqueue["t0"] and enqueue["t1"] < dispatch["t1"] and enqueue["attrs"]["program"] == dispatch["attrs"]["program"]
        assert enqueue["t1"] < fetch["t0"] and fetch["t1"] < settle["t0"]
        assert emit["t0"] < settle["t0"] and settle["t1"] < emit["t1"]
    # a call names the step it sent to the device, and only such a call does
    for step in _spans(tracer, "serve.step"):
        sent = [r["attrs"]["seq"] for r in enqueues if step["t0"] < r["t0"] and r["t1"] < step["t1"]]
        assert ([step["attrs"]["seq_enqueued"]] if "seq_enqueued" in step["attrs"] else []) == sent
    # a drain's settle says why, and of which step
    drains = [r["attrs"] for r in _spans(tracer, "serve.emit") if "drain" in r["attrs"]]
    reasons = {}
    for a in drains:
        reasons[a["drain"]] = reasons.get(a["drain"], 0) + 1
    assert reasons == server.stats["drain_reasons"]


EXPECTED_DRAINS = {
    "steady_decode": {"idle"}, "chunks_and_admissions": {"idle"}, "a_second_pack_for_a_newcomer": {"idle"}, "idle_drains": {"idle"},
    "preempt_drains": {"idle", "preempt"}, "draft_drains": {"draft"}, "an_eos_with_a_step_in_flight": {"idle"}, "an_entry_point_between_calls": {"settle"},
}


@pytest.mark.parametrize("name", list(SCENARIOS))
def test_the_scenario_is_the_one_its_name_says(name):
    server, tracer, _, _ = _traced(name)
    assert set(server.stats["drain_reasons"]) >= EXPECTED_DRAINS[name] and set(server.stats["drain_reasons"]) <= EXPECTED_DRAINS[name] | {"idle"}
    packs_of = {}
    for r in _spans(tracer, "serve.pack"):
        packs_of[r["attrs"]["seq"]] = packs_of.get(r["attrs"]["seq"], 0) + 1
    if name == "a_second_pack_for_a_newcomer":
        assert server.stats["admitted"] == 6 and sum(n == 2 for n in packs_of.values()) >= 2
    if name == "idle_drains":
        assert server.stats["drain_reasons"] == {"idle": 3}
    if name == "an_eos_with_a_step_in_flight":
        # each row rode in one more step after its EOS, whose spans are those of any step and whose result for it was dropped
        assert server.stats["overshoot_rows"] >= 1 and server.stats["finished"] == 4
        assert server.stats["emitted_tokens"] < sum(SCENARIOS[name]["budgets"])
    if name == "preempt_drains":
        assert server.stats["preempted"] > 0


@pytest.mark.parametrize("name", list(SCENARIOS))
def test_turnaround_is_observed_once_a_step_and_never_across_a_drain(name):
    server, tracer, metrics, _ = _traced(name)
    enqueue = {r["attrs"]["seq"]: r for r in _spans(tracer, "serve.enqueue")}
    fetch = {r["attrs"]["seq"]: r for r in _spans(tracer, "serve.fetch")}
    drained = {r["attrs"]["seq"] for r in _spans(tracer, "serve.emit") if "drain" in r["attrs"]}
    pairs = [n for n in enqueue if n + 1 in enqueue and n not in drained]
    hist = metrics.snapshot()["histograms"]["serve.turnaround_ms"]
    stats = server.stats
    assert hist["count"] == len(pairs) == stats["run_ahead_steps"]
    # every step but the first and those behind a drain that another step followed
    singles = sorted(enqueue)
    followed = sum(1 for a, b in zip(singles, singles[1:]) if a in drained or b != a + 1)
    assert len(pairs) == stats["ragged_steps"] - 1 - followed and len(singles) == stats["ragged_steps"]
    if name in ("steady_decode", "chunks_and_admissions", "a_second_pack_for_a_newcomer"):
        assert len(pairs) == stats["ragged_steps"] - 1 > 0  # the one drain is the run's end
    if name == "draft_drains":
        assert hist == {"count": 0} and server.serve_stats()["turnaround_ms_p50"] == 0.0
        return
    # the stamps are the tick after the wait's return and the tick after the enqueue's start
    want_ms = [1e3 * (enqueue[n + 1]["t0"] - fetch[n]["t0"]) for n in pairs]
    assert hist["sum"] == pytest.approx(sum(want_ms)) and hist["min"] == min(want_ms) and hist["max"] == max(want_ms)
    assert all(enqueue[n + 1]["t0"] > fetch[n]["t1"] for n in pairs)
    assert hist["min"] <= server.serve_stats()["turnaround_ms_p50"] <= hist["max"]


@pytest.mark.parametrize("name", ["steady_decode", "preempt_drains", "an_eos_with_a_step_in_flight"])
def test_with_tracing_off_the_tokens_are_the_same_and_no_span_is_recorded(name):
    _, _, metrics_on, want = _traced(name)
    server, tracer, metrics_off, got = _serve(name, traced=False)
    assert len(got) == len(want) and all(a.dtype == b.dtype and np.array_equal(a, b) for a, b in zip(got, want))
    assert tracer.spans() == [] and tracer.open_spans() == [] and tracer.phase_summary() == {}
    # the histogram is the registry's, as the SLA histograms are: it counts the same steps either way
    on, off = (m.snapshot()["histograms"]["serve.turnaround_ms"] for m in (metrics_on, metrics_off))
    assert on["count"] == off["count"] == server.stats["run_ahead_steps"]


def test_turnaround_reaches_the_operators_surfaces():
    """``observability()``, ``monitor_events`` and ``serve_stats()`` of an
    engine built the public way, on the real clock."""
    import deepspeed_tpu as ds

    cfg, params = _dense()
    engine = ds.init_inference(
        TransformerLM(cfg), dtype="fp32",
        paged_kv={"page_size": 8, "max_slots": 4, "prefill_chunk": 8, "attn_impl": "xla", "num_pages": 0, "max_seq_len": 96},
    )
    engine.set_params(params)
    engine.serve(_prompts(3, seed=21, lo=4, hi=10), max_new_tokens=12)
    stats = engine.serve_stats()
    hist = engine.observability()["metrics"]["histograms"]["serve.turnaround_ms"]
    assert hist["count"] == stats["run_ahead_steps"] > 8
    assert 0.0 < hist["min"] <= stats["turnaround_ms_p50"] <= hist["max"] and hist["p50"] == round(stats["turnaround_ms_p50"], 6)
    events = {name: value for name, value, _ in engine.observability_hub.monitor_events(step=1)}
    assert events["Metrics/serve.turnaround_ms/p50"] == hist["p50"] and events["Metrics/serve.turnaround_ms/p99"] == hist["p99"]
    assert "Trace/serve.enqueue/mean_ms" in events
    # the ring buffer carries the numbers as it carries every attribute: so do the flight recorder and the Chrome export
    enqueues = [r for r in engine.tracer.spans() if r["name"] == "serve.enqueue"]
    assert [r["attrs"]["seq"] for r in enqueues] == list(range(stats["dispatches"]))
