"""Serving-fleet tests: replicated engines, live migration, elastic
drain/join, fleet-scope chaos (ISSUE 12 acceptance).

Load-bearing checks:

* every output stream a fleet produces — across replica kills at every
  fleet chaos point, cooperative migrations mid-prefill and mid-decode,
  drains, circuit-breaker trips, and prefill/decode role splits — is
  **byte-identical** to an uninterrupted single-replica (dense oracle)
  run, and the acked prefix of a migrated request never diverges
  (``migrated_token_divergence`` stays 0);
* a drain empties its replica with zero dropped acked tokens and leaves
  its journal compacted (bounded segments);
* prefix-affinity consistent-hash routing beats random routing on the
  fleet-wide prefix hit rate;
* SLA tenancy and goodput survive a mid-trace replica kill under the
  loadgen's heavy-tailed multi-tenant replay, and the 3-replica fleet's
  goodput beats the single-replica baseline on the same trace;
* the real thing: a ``-m slow`` subprocess fleet dies by ``os._exit(137)``
  at the armed point and a fresh process adopts the journals and finishes
  every stream byte-identically.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.elasticity import FleetResizePolicy, valid_fleet_sizes
from deepspeed_tpu.inference import decode
from deepspeed_tpu.inference.fleet import (
    ConsistentHashRing,
    FleetRouter,
    ReplicaHandle,
    UID_STRIDE,
    prefix_chain_keys,
)
from deepspeed_tpu.inference.journal import RequestJournal
from deepspeed_tpu.inference.scheduler import PagedServer
from deepspeed_tpu.inference.traffic import MultiTenantServer, TenantSpec
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.config import TransformerConfig
from deepspeed_tpu.utils import chaos
from deepspeed_tpu.utils.loadgen import TenantLoad, VirtualClock, make_trace, replay
from tests.unit.inference.hybrid_toys import _clear_jax_caches, _compiled_programs_live_as_long_as_the_file  # noqa: F401 (the two fixtures are taken by their import)

CFG = dict(
    vocab_size=128,
    hidden_size=64,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    max_seq_len=64,
    norm="rmsnorm",
    position="rope",
    activation="swiglu",
    use_bias=False,
    tie_embeddings=False,
    flash_attention=False,
    dtype="float32",
)


@pytest.fixture(scope="module")
def model_and_params():
    cfg = TransformerConfig(**CFG)
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), toks)
    return cfg, model, params


def _dense(cfg, params, prompt, n, eos=None):
    return np.asarray(decode.generate(cfg, params, prompt[None], n, eos_token_id=eos))[0]


def _server(cfg, params, journal_dir=None, tenants=None, **kw):
    kw.setdefault("page_size", 8)
    kw.setdefault("max_slots", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("attn_impl", "xla")
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("prefix_cache", True)
    journal = RequestJournal(journal_dir) if journal_dir else None
    srv = PagedServer(cfg, params, journal=journal, **kw)
    if tenants:
        srv = MultiTenantServer(srv, tenants=tenants)
    return srv


def _fleet(cfg, params, n=3, tmp=None, names=None, tenants=None, **router_kw):
    handles = []
    for i in range(n):
        name = names[i] if names else f"r{i}"
        jdir = os.path.join(str(tmp), name) if tmp is not None else None
        handles.append(
            ReplicaHandle(
                name=name,
                server=_server(cfg, params, journal_dir=jdir, tenants=tenants),
                journal_dir=jdir,
            )
        )
    return FleetRouter(handles, **router_kw)


def _prompts(seed=7, n=6, shared_frac=2):
    rs = np.random.RandomState(seed)
    sysp = rs.randint(0, CFG["vocab_size"], (16,)).astype(np.int32)
    out = []
    for i in range(n):
        tail = rs.randint(0, CFG["vocab_size"], (int(rs.randint(3, 8)),)).astype(np.int32)
        out.append(np.concatenate([sysp, tail]) if i % shared_frac == 0 else tail)
    return out


def _assert_oracle(router, cfg, params, prompts, budgets, uids):
    for p, n, u in zip(prompts, budgets, uids):
        if u is None:
            continue
        out = router.take_result(u)
        assert out is not None, f"request {u} never finished"
        np.testing.assert_array_equal(out, _dense(cfg, params, p, n))


# ---------------------------------------------------------------------------
# host-side units: chain keys, the ring, uid strides
# ---------------------------------------------------------------------------
def test_chain_keys_and_ring_units():
    rs = np.random.RandomState(0)
    sysp = rs.randint(0, 128, (16,)).astype(np.int32)
    a = np.concatenate([sysp, rs.randint(0, 128, (5,)).astype(np.int32)])
    b = np.concatenate([sysp, rs.randint(0, 128, (5,)).astype(np.int32)])
    ka, kb = prefix_chain_keys(a, 8), prefix_chain_keys(b, 8)
    # the shared 16-token system prompt = 2 full pages: identical chain
    assert ka[:2] == kb[:2] and len(ka) == 2
    # the final partial block never keys (it cannot be a cached full page)
    assert prefix_chain_keys(sysp[:9], 8) == prefix_chain_keys(sysp[:15], 8)
    # a one-token-longer prompt crossing the boundary adds a key
    assert len(prefix_chain_keys(sysp, 8)) == 1  # 16 tokens: cap leaves 1 block
    assert prefix_chain_keys(np.asarray([1, 2], np.int32), 8) == []

    ring = ConsistentHashRing(vnodes=16)
    for n in ("a", "b", "c"):
        ring.add(n)
    keys = list(range(0, 2**32, 2**26))
    before = {k: ring.lookup(k, lambda n: True) for k in keys}
    assert set(before.values()) == {"a", "b", "c"}  # all nodes own arcs
    ring.remove("b")
    after = {k: ring.lookup(k, lambda n: True) for k in keys}
    for k in keys:
        # consistent hashing: only the removed node's arcs moved
        if before[k] != "b":
            assert after[k] == before[k]
        else:
            assert after[k] in ("a", "c")
    # exclusion predicate: a key whose owner is unacceptable walks on
    assert ring.lookup(keys[0], lambda n: n == "c") == "c"
    assert ring.lookup(keys[0], lambda n: False) is None


def test_uid_strides_and_geometry_guard(model_and_params):
    cfg, _, params = model_and_params
    router = _fleet(cfg, params, n=3)
    bases = sorted(h.uid_base for h in router.replicas.values())
    assert bases == [0, UID_STRIDE, 2 * UID_STRIDE]
    uids = [router.submit(p, max_new_tokens=2) for p in _prompts(n=6)]
    assert len(set(uids)) == 6  # fleet-wide unique
    router.run()
    # mixed pool geometry is rejected up front (it would retrace programs)
    with pytest.raises(ValueError, match="pool geometry"):
        FleetRouter([
            ReplicaHandle(name="x", server=_server(cfg, params)),
            ReplicaHandle(name="y", server=_server(cfg, params, page_size=4)),
        ])
    with pytest.raises(ValueError, match="pool geometry"):
        router.join(_server(cfg, params, max_slots=2))


# ---------------------------------------------------------------------------
# acceptance: byte-identical streams, healthy fleet
# ---------------------------------------------------------------------------
def test_fleet_streams_byte_identical_and_spread(model_and_params):
    """A healthy 3-replica fleet serves a shared-prefix mix byte-identically
    to the dense oracle, spreads distinct prompts across replicas, and the
    merged stats reconcile."""
    cfg, _, params = model_and_params
    router = _fleet(cfg, params, n=3)
    prompts = _prompts(n=8)
    budgets = [8, 5, 10, 6, 7, 9, 4, 8]
    uids = [router.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    router.run()
    _assert_oracle(router, cfg, params, prompts, budgets, uids)
    served = {
        n: h.inner.stats["finished"] for n, h in router.replicas.items()
    }
    assert sum(served.values()) == 8
    assert sum(1 for v in served.values() if v > 0) >= 2, served
    merged = router.serve_stats()
    assert merged["finished"] == 8
    assert merged["ttft_ms"]["count"] == 8
    assert merged["fleet"]["routed"] == 8
    assert merged["fleet"]["migrated_token_divergence"] == 0


# ---------------------------------------------------------------------------
# acceptance: replica kill at every fleet chaos point
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("hit", [2, 5, 9])
def test_replica_kill_chaos_byte_identical(model_and_params, tmp_path, hit):
    """An in-process chaos kill of one replica at a deterministic step
    arrival: its live requests re-route onto the survivors from its
    journal and EVERY stream finishes byte-identical to an uninterrupted
    single-replica run — the acked prefix never diverges."""
    cfg, _, params = model_and_params
    router = _fleet(cfg, params, n=3, tmp=tmp_path)
    prompts = _prompts(n=6)
    budgets = [10, 7, 12, 8, 9, 11]
    uids = [router.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    chaos.install(chaos.ChaosSchedule([chaos.ChaosRule("fleet.replica_kill", hit=hit)]))
    try:
        router.run()
    finally:
        chaos.uninstall()
    fs = router.fleet_stats()
    assert fs["replica_kills"] == 1
    assert fs["n_active"] == 2
    assert fs["migrated_token_divergence"] == 0
    _assert_oracle(router, cfg, params, prompts, budgets, uids)
    # survivors' pools stayed internally consistent through the adoption
    for h in router.replicas.values():
        if h.state != "dead":
            h.inner.pool.integrity_check()


def test_replica_kill_without_journal_shadow_fallback(model_and_params):
    """Journal-less replicas fall back to the router's shadow submissions:
    the dead replica's streams recompute from scratch — still
    byte-identical under greedy."""
    cfg, _, params = model_and_params
    router = _fleet(cfg, params, n=2)  # no tmp: no journals
    prompts = _prompts(seed=11, n=4)
    budgets = [9, 6, 8, 7]
    uids = [router.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    for _ in range(3):
        router.step()
    victim = next(
        n for n, h in router.replicas.items() if h.inner.has_work()
    )
    router.kill_replica(victim)
    router.run()
    assert router.fleet_stats()["replica_kills"] == 1
    _assert_oracle(router, cfg, params, prompts, budgets, uids)


def test_mid_migration_crash_no_loss_no_duplicates(model_and_params, tmp_path):
    """A kill in the mid-migration window (state off the source scheduler,
    target not yet seeded) is the source dying: failing it replays the
    source journal — the request is neither lost nor duplicated, and its
    acked tokens survive verbatim."""
    cfg, _, params = model_and_params
    router = _fleet(cfg, params, n=2, tmp=tmp_path)
    prompts = _prompts(seed=13, n=4)
    budgets = [10, 8, 9, 7]
    uids = [router.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    for _ in range(5):
        router.step()
    live_uid = next(u for u in uids if u in router._where)
    src = router._where[live_uid]
    chaos.install(chaos.ChaosSchedule([chaos.ChaosRule("fleet.mid_migration", hit=1)]))
    try:
        with pytest.raises(chaos.ChaosKilled):
            router.migrate(live_uid)
    finally:
        chaos.uninstall()
    # the supervisor's move: the source died mid-migration
    router.fail_replica(src, reason="died mid-migration")
    router.run()
    fs = router.fleet_stats()
    assert fs["migrated_token_divergence"] == 0
    assert len(router._results) == 4  # no duplicates, nothing lost
    _assert_oracle(router, cfg, params, prompts, budgets, uids)


def test_mid_drain_kill_recovers(model_and_params, tmp_path):
    """The draining replica dies between two drain migrations: the
    remainder re-routes from its journal with zero acked tokens
    dropped."""
    cfg, _, params = model_and_params
    router = _fleet(cfg, params, n=2, tmp=tmp_path)
    prompts = _prompts(seed=17, n=5)
    budgets = [9, 8, 10, 7, 9]
    uids = [router.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    for _ in range(4):
        router.step()
    victim = next(n for n, h in router.replicas.items() if h.inner.has_work())
    chaos.install(chaos.ChaosSchedule([chaos.ChaosRule("fleet.mid_drain", hit=2)]))
    try:
        router.drain(victim)  # the router catches the kill internally
    finally:
        chaos.uninstall()
    assert router.replicas[victim].state == "dead"
    router.run()
    assert router.fleet_stats()["migrated_token_divergence"] == 0
    _assert_oracle(router, cfg, params, prompts, budgets, uids)


# ---------------------------------------------------------------------------
# live migration: mid-decode, mid-prefill, drain
# ---------------------------------------------------------------------------
def test_migration_mid_decode_byte_identical(model_and_params, tmp_path):
    cfg, _, params = model_and_params
    router = _fleet(cfg, params, n=2, tmp=tmp_path)
    prompts = _prompts(seed=19, n=4)
    budgets = [12, 9, 11, 10]
    uids = [router.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    # step until some request is mid-stream (>= 2 tokens emitted, not done)
    mid = None
    for _ in range(30):
        router.step()
        for h in router.replicas.values():
            for r in h.inner._active:
                if len(r.generated) >= 2 and not r.done:
                    mid = r.uid
                    break
            if mid:
                break
        if mid:
            break
    assert mid is not None, "no request reached mid-stream decode"
    src = router._where[mid]
    acked_before = list(
        next(
            r
            for r in router.replicas[src].inner._active
            if r.uid == mid
        ).generated
    )
    assert router.migrate(mid)
    tgt = router._where[mid]
    assert tgt != src
    # the post-migration pool assert ran inside migrate; re-check both
    for name in (src, tgt):
        router.replicas[name].inner.pool.integrity_check()
    router.run()
    fs = router.fleet_stats()
    assert fs["migrations"] >= 1
    assert fs["migrated_token_divergence"] == 0
    out = router.result(mid)
    idx = uids.index(mid)
    p = prompts[idx]
    # the acked prefix rode the migration verbatim
    np.testing.assert_array_equal(
        out[p.size : p.size + len(acked_before)], np.asarray(acked_before)
    )
    _assert_oracle(router, cfg, params, prompts, budgets, uids)


def test_migration_mid_prefill_byte_identical(model_and_params, tmp_path):
    cfg, _, params = model_and_params
    router = _fleet(cfg, params, n=2, tmp=tmp_path)
    rs = np.random.RandomState(23)
    # multi-chunk prompts (prefill_chunk=8): migration lands mid-prefill
    prompts = [rs.randint(0, 128, (28,)).astype(np.int32) for _ in range(2)]
    budgets = [8, 6]
    uids = [router.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    router.step()
    mid = None
    for h in router.replicas.values():
        for r in h.inner._active:
            if r.pending is None and 0 < r.consumed < r.prompt.size:
                mid = r.uid
                break
        if mid:
            break
    assert mid is not None, "no request caught mid-prefill"
    assert router.migrate(mid)
    router.run()
    assert router.fleet_stats()["migrated_token_divergence"] == 0
    _assert_oracle(router, cfg, params, prompts, budgets, uids)


def test_drain_empties_replica_zero_dropped_and_compacts(model_and_params, tmp_path):
    """Elastic scale-down: the drain migrates every queued + live request
    off (zero dropped acked tokens), leaves the replica empty and out of
    the ring, and its journal compacted to a bounded segment count."""
    cfg, _, params = model_and_params
    router = _fleet(cfg, params, n=2, tmp=tmp_path)
    prompts = _prompts(seed=29, n=6)
    budgets = [9, 7, 11, 8, 10, 6]
    uids = [router.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    for _ in range(4):
        router.step()
    victim = next(n for n, h in router.replicas.items() if h.inner.has_work())
    inner = router.replicas[victim].inner
    outstanding = inner.queued_count() + inner.live_count()
    assert outstanding >= 1
    moved = router.drain(victim)
    assert moved == outstanding
    assert not inner.has_work()
    assert inner.stats["migrated_out"] == moved
    assert router.replicas[victim].state == "drained"
    assert victim not in router._ring.nodes()
    # journal growth bounded: the drain's final migration (live count 0 <
    # migrated-out garbage) triggers the compaction — and with nothing
    # left on the replica, nothing remains to replay
    jdir = router.replicas[victim].journal_dir
    assert len(RequestJournal.segments(jdir)) <= 1
    states, _ = RequestJournal.replay(jdir)
    assert not any(not st.done for st in states.values())
    # a fresh submit can no longer land on the drained replica
    extra = router.submit(prompts[0], max_new_tokens=3)
    assert router._where[extra] != victim
    router.run()
    assert router.fleet_stats()["migrated_token_divergence"] == 0
    _assert_oracle(router, cfg, params, prompts, budgets, uids)
    np.testing.assert_array_equal(
        router.take_result(extra), _dense(cfg, params, prompts[0], 3)
    )


def test_migrate_without_target_restores_request(model_and_params, tmp_path):
    """A migration that cannot find a target (single-replica fleet) must
    not strand the request: the state goes back on the source scheduler
    and the stream finishes there byte-identically. A failed drain
    likewise returns the replica to service."""
    cfg, _, params = model_and_params
    router = _fleet(cfg, params, n=1, tmp=tmp_path)
    prompts = _prompts(seed=31, n=2)
    budgets = [8, 6]
    uids = [router.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    for _ in range(3):
        router.step()
    inner = router.replicas["r0"].inner
    live = next(r.uid for r in inner._active if not r.done)
    with pytest.raises(RuntimeError):
        router.migrate(live)
    # the request is back on the source, not lost off every scheduler —
    # and the failed move left no phantom migration accounting
    assert router._where[live] == "r0"
    assert any(
        r.uid == live for r in list(inner._queue) + list(inner._active)
    )
    assert inner.stats["migrated_out"] == 0
    assert inner.stats["migrated_in"] == 0
    # a drain with nowhere to move also fails CLEAN: replica back in service
    with pytest.raises(RuntimeError):
        router.drain("r0")
    assert router.replicas["r0"].state == "active"
    assert "r0" in router._ring.nodes()
    router.run()
    assert router.fleet_stats()["migrated_token_divergence"] == 0
    _assert_oracle(router, cfg, params, prompts, budgets, uids)


def test_adopt_journal_raises_uid_floor_no_collision(model_and_params, tmp_path):
    """Adopted uids come from a previous fleet's stride space: a fresh
    fleet on the same strides must allocate PAST them, or a new submit
    reuses a uid the fleet already tracks and the global maps clobber."""
    cfg, _, params = model_and_params
    old_dir = os.path.join(str(tmp_path), "old-r0")
    old = _fleet(cfg, params, n=1, tmp=tmp_path, names=["old-r0"])
    prompts = _prompts(seed=37, n=3)
    budgets = [8, 7, 6]
    old_uids = [old.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    old.step()  # some progress journaled; then the whole process "dies"
    del old
    fresh = _fleet(cfg, params, n=1, tmp=tmp_path, names=["n0"])  # stride 0 again
    adopted = fresh.adopt_journal(old_dir)
    assert adopted == len(old_uids)
    # the fresh replica's allocator must clear every adopted uid
    new_uid = fresh.submit(prompts[0], max_new_tokens=4)
    assert new_uid not in old_uids
    # a LATER join on a stride the old fleet used is floored too
    jdir = os.path.join(str(tmp_path), "n1")
    h1 = fresh.join(_server(cfg, params, journal_dir=jdir), name="n1", journal_dir=jdir)
    assert h1.inner._next_uid >= h1.uid_base
    fresh.run()
    assert fresh.fleet_stats()["migrated_token_divergence"] == 0
    _assert_oracle(fresh, cfg, params, prompts, budgets, old_uids)
    np.testing.assert_array_equal(
        fresh.take_result(new_uid), _dense(cfg, params, prompts[0], 4)
    )


def test_single_migration_appends_without_full_compaction(model_and_params, tmp_path):
    """One rebalancing move off a busy replica costs an appended
    migrated-out record + sync, NOT a full-state journal rewrite — the
    compaction only fires when migrated-out garbage outweighs live state
    (which a drain's tail always reaches: the ≤1-segment drain guarantee
    is covered by the drain test)."""
    cfg, _, params = model_and_params
    router = _fleet(cfg, params, n=2, tmp=tmp_path)
    prompts = _prompts(seed=41, n=6)
    budgets = [9, 8, 10, 7, 9, 8]
    uids = [router.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    for _ in range(3):
        router.step()
    src = next(
        n for n, h in router.replicas.items()
        if h.inner.queued_count() + h.inner.live_count() >= 2
    )
    inner = router.replicas[src].inner
    victim = next(r.uid for r in list(inner._active) + list(inner._queue))
    assert router.migrate(victim)
    # garbage (1 migrated-out) <= live remaining: append-only, no rewrite
    assert inner.stats["journal_compactions"] == 0
    # ... but the migrated-out record IS durable: a replay of the source
    # journal no longer claims the request
    states, _ = RequestJournal.replay(router.replicas[src].journal_dir)
    assert victim not in states
    router.run()
    assert router.fleet_stats()["migrated_token_divergence"] == 0
    _assert_oracle(router, cfg, params, prompts, budgets, uids)


def test_migration_to_journal_less_target_keeps_source_claim(
    model_and_params, tmp_path
):
    """The target-journal-FIRST durability contract requires the target
    to HAVE a journal: migrating onto a journal-less replica must leave
    the source journal claiming the request (no migrated-out record), or
    a crash after the move finds the state in neither journal and acked
    tokens are lost. The double-claim this keeps is what adoption
    dedupes."""
    cfg, _, params = model_and_params
    jdir = os.path.join(str(tmp_path), "src")
    handles = [
        ReplicaHandle(name="src", server=_server(cfg, params, journal_dir=jdir),
                      journal_dir=jdir),
        ReplicaHandle(name="bare", server=_server(cfg, params)),  # no journal
    ]
    router = FleetRouter(handles)
    rs = np.random.RandomState(47)
    prompts, budgets, uids = [], [], []
    # keep submitting distinct prompts until one routes to the journaled
    # replica (consistent hashing spreads unseen keys — a handful suffices)
    for _ in range(24):
        p = rs.randint(0, 128, (int(rs.randint(6, 20)),)).astype(np.int32)
        u = router.submit(p, max_new_tokens=7)
        prompts.append(p), budgets.append(7), uids.append(u)
        if router._where.get(u) == "src" and len(uids) >= 3:
            break
    assert any(router._where.get(u) == "src" for u in uids)
    # budgets of 7 cannot finish in 3 steps: the victim is still live
    for _ in range(3):
        router.step()
    inner = router.replicas["src"].inner
    victim = next(
        (r.uid for r in list(inner._active) + list(inner._queue)), None
    )
    assert victim is not None
    acked = list(
        next(
            (r.generated for r in inner._active if r.uid == victim), []
        )
    )
    assert router.migrate(victim, target="bare")
    # no "m" disclaim: the source journal still replays the request —
    # with every acked token — because the target holds it only in memory
    states, _ = RequestJournal.replay(jdir)
    assert victim in states and not states[victim].done
    assert list(states[victim].generated)[: len(acked)] == acked
    # the claim survives a full compaction of the source journal
    inner.compact_journal()
    states, _ = RequestJournal.replay(jdir)
    assert victim in states and not states[victim].done
    router.run()
    assert router.fleet_stats()["migrated_token_divergence"] == 0
    # ...and is disclaimed once the output was delivered: a later replay
    # cannot resurrect the finished request
    states, _ = RequestJournal.replay(jdir)
    assert victim not in states or states[victim].done
    _assert_oracle(router, cfg, params, prompts, budgets, uids)


def test_inbound_recover_preserves_compaction_garbage_counter(
    model_and_params, tmp_path
):
    """``recover()`` on a LIVE migration target re-seeds one request — it
    is NOT a compaction (the writer's retirement boundary is unchanged) —
    so it must not zero the migrated-out garbage counter, or a replica
    that both sends and receives migrations never triggers the rewrite
    and its journal grows without bound."""
    cfg, _, params = model_and_params
    router = _fleet(cfg, params, n=2, tmp=tmp_path)
    prompts = _prompts(seed=53, n=6)
    uids = [router.submit(p, max_new_tokens=6) for p in prompts]
    for _ in range(3):
        router.step()
    a, b = router.replicas["r0"].inner, router.replicas["r1"].inner
    if a.queued_count() + a.live_count() < 2:
        a, b = b, a
    out_uid = next(r.uid for r in list(a._active) + list(a._queue))
    assert router.migrate(out_uid)
    assert a._migrated_since_compact == 1
    # an INBOUND migration (recover on the live server) keeps the count
    in_uid = next(
        (r.uid for r in list(b._active) + list(b._queue)), None
    )
    if in_uid is not None:
        router.migrate(in_uid, target=_name_of(router, a))
        assert a._migrated_since_compact == 1
    router.run()
    assert router.fleet_stats()["migrated_token_divergence"] == 0
    _assert_oracle(router, cfg, params, prompts, [6] * 6, uids)


def _name_of(router, inner):
    return next(n for n, h in router.replicas.items() if h.inner is inner)


# ---------------------------------------------------------------------------
# routing quality + failure detection
# ---------------------------------------------------------------------------
def test_prefix_affinity_beats_random_on_hit_rate(model_and_params):
    """Consistent-hash affinity pins each shared system prompt to one
    replica (its prefix cache pays the prefill once); random spread pays
    the cold miss once per replica — measurably lower hit rate."""
    cfg, _, params = model_and_params

    def run(affinity):
        router = _fleet(
            cfg, params, n=2, names=["a0", "a1"], affinity=affinity
        )
        rs = np.random.RandomState(3)
        sysps = [rs.randint(0, 128, (16,)).astype(np.int32) for _ in range(3)]
        for _wave in range(3):
            ps = [
                np.concatenate(
                    [sysps[i % 3], rs.randint(0, 128, (4,)).astype(np.int32)]
                )
                for i in range(6)
            ]
            router.serve(ps, max_new_tokens=4)
        return router.serve_stats()["prefix"]["prefix_hit_rate"]

    hit_affinity = run(True)
    hit_random = run(False)
    assert hit_affinity > hit_random, (hit_affinity, hit_random)


def test_circuit_breaker_trips_on_flaky_replica(model_and_params, tmp_path):
    """Ordinary exceptions (not chaos kills) trip the per-replica circuit
    breaker after ``breaker_threshold`` consecutive failures; the dead
    replica's streams finish on the survivor byte-identically."""
    cfg, _, params = model_and_params
    router = _fleet(cfg, params, n=2, tmp=tmp_path, breaker_threshold=3)
    prompts = _prompts(seed=31, n=4)
    budgets = [8, 9, 7, 10]
    uids = [router.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    for _ in range(2):
        router.step()
    victim = next(n for n, h in router.replicas.items() if h.inner.has_work())

    def boom():
        raise RuntimeError("wedged backend")

    router.replicas[victim].server.step = boom
    router.run()
    h = router.replicas[victim]
    assert h.state == "dead"
    assert router.fleet_stats()["replica_kills"] == 1
    _assert_oracle(router, cfg, params, prompts, budgets, uids)


def test_health_probe_circuit_breaker(model_and_params):
    cfg, _, params = model_and_params
    router = _fleet(cfg, params, n=2, breaker_threshold=2)
    name = next(iter(router.replicas))
    router.replicas[name].health_fn = lambda srv: False
    assert router.probe()[name] is False
    assert router.replicas[name].state == "active"  # one strike
    router.probe()  # second strike: breaker opens
    assert router.replicas[name].state == "dead"


# ---------------------------------------------------------------------------
# SLA + goodput across a mid-trace kill (loadgen fleet scope)
# ---------------------------------------------------------------------------
def test_sla_and_goodput_across_mid_trace_kill(model_and_params, tmp_path):
    """The acceptance replay: a heavy-tailed two-tenant trace across 3
    SLA-scheduled replicas with a replica killed mid-trace. Every stream
    stays byte-identical to the oracle, no tenant starves, p99 TTFT stays
    bounded, and fleet goodput beats the single-replica baseline on the
    SAME trace (virtual clock: each replica is its own service lane)."""
    cfg, _, params = model_and_params
    tenants = [
        TenantSpec(name="gold", weight=3.0, priority=1, ttft_target_ms=4000),
        TenantSpec(name="free", weight=1.0),
    ]
    trace = make_trace(
        [
            TenantLoad(name="gold", rate=60, prompt_len=(6, 14),
                       max_new_tokens=(3, 7)),
            TenantLoad(name="free", rate=60, prompt_len=(6, 14),
                       max_new_tokens=(3, 7)),
        ],
        horizon_s=1.0,
        vocab_size=CFG["vocab_size"],
        seed=5,
    )
    router = _fleet(cfg, params, n=3, tmp=tmp_path, tenants=tenants)
    rep = replay(
        router,
        trace,
        clock=VirtualClock(step_cost_s=0.02),
        events=[(0.3, lambda srv: srv.kill_replica(next(
            n for n, h in srv.replicas.items() if h.inner.has_work()
        )))],
    )
    fs = router.fleet_stats()
    assert rep["events_fired"] == 1 and fs["replica_kills"] == 1
    assert fs["rerouted"] >= 1, fs  # the kill landed on a busy replica
    assert fs["migrated_token_divergence"] == 0
    assert rep["starved_tenants"] == []
    assert rep["ttft_ms"]["count"] > 0 and np.isfinite(rep["ttft_ms"]["p99"])
    # byte-identical outputs for every finished request, kill included
    for idx, out in rep["outputs"].items():
        if out is None:
            continue
        r = trace[idx]
        np.testing.assert_array_equal(
            out, _dense(cfg, params, r.prompt, r.max_new_tokens)
        )
    # goodput: 3 replicas (one killed mid-trace) still beat 1 replica
    single = _fleet(cfg, params, n=1, tenants=tenants)
    rep1 = replay(single, trace, clock=VirtualClock(step_cost_s=0.02))
    assert rep["goodput_tokens_per_s"] > rep1["goodput_tokens_per_s"], (
        rep["goodput_tokens_per_s"], rep1["goodput_tokens_per_s"]
    )


# ---------------------------------------------------------------------------
# prefill/decode role split
# ---------------------------------------------------------------------------
def test_role_split_migration_at_first_decode(model_and_params):
    """Disaggregation: prefill-role replicas admit, and the step the first
    decode token exists the request hands off to the decode replica (KV
    handoff = migration). Streams stay byte-identical; the prefill
    replica never runs a plain decode dispatch."""
    cfg, _, params = model_and_params
    router = FleetRouter([
        ReplicaHandle(name="pf", server=_server(cfg, params), role="prefill"),
        ReplicaHandle(name="dc", server=_server(cfg, params), role="decode"),
    ])
    rs = np.random.RandomState(37)
    prompts = [rs.randint(0, 128, (int(rs.randint(10, 20)),)).astype(np.int32)
               for _ in range(4)]
    budgets = [6, 9, 4, 8]
    outs = router.serve(prompts, max_new_tokens=budgets)
    for o, p, n in zip(outs, prompts, budgets):
        np.testing.assert_array_equal(o, _dense(cfg, params, p, n))
    fs = router.fleet_stats()
    assert fs["role_migrations"] == 4  # one handoff per request
    pf = router.replicas["pf"].inner.stats
    dc = router.replicas["dc"].inner.stats
    assert pf["decode_steps"] == 0  # the prefill tier never plain-decodes
    assert dc["decode_steps"] > 0
    # each request emitted exactly its first token on the prefill tier
    assert pf["emitted_tokens"] == 4
    assert dc["emitted_tokens"] == sum(budgets) - 4
    assert fs["migrated_token_divergence"] == 0


# ---------------------------------------------------------------------------
# elasticity: resize policy + journal-catch-up join
# ---------------------------------------------------------------------------
def test_resize_policy_watermarks_hysteresis_and_quantization():
    # the valid-count quantization reuses the elastic batch math: 4-slot
    # replicas under a 32-slot fleet budget resize through {1, 2, 4, 8}
    assert valid_fleet_sizes(32, 4) == [1, 2, 4, 8]
    pol = FleetResizePolicy(
        min_replicas=1, max_replicas=8, target_backlog_per_replica=4.0,
        cooldown_steps=5, valid_counts=valid_fleet_sizes(32, 4),
    )
    # heavy backlog: 40 requests over 2 replicas -> wants 10 -> snaps to 8
    assert pol.decide(backlog=40, n_active=2, step=0) == 8
    # inside the cooldown nothing moves, however loud the signal
    assert pol.decide(backlog=40, n_active=4, step=2) == 4
    # idle fleet far past the cooldown shrinks (snapped downward)
    assert pol.decide(backlog=1, n_active=4, step=20) == 1
    # the hysteresis band holds steady
    assert pol.decide(backlog=16, n_active=4, step=40) == 4
    with pytest.raises(ValueError, match="scale_down_at"):
        FleetResizePolicy(scale_up_at=0.2, scale_down_at=0.5)
    with pytest.raises(ValueError, match="min_replicas"):
        FleetResizePolicy(min_replicas=3, max_replicas=2)


def test_autoscale_grows_and_drains(model_and_params):
    cfg, _, params = model_and_params
    router = _fleet(cfg, params, n=1)
    rs = np.random.RandomState(41)
    uids = [
        router.submit(rs.randint(0, 128, (8,)).astype(np.int32), max_new_tokens=4)
        for _ in range(12)
    ]
    pol = FleetResizePolicy(
        min_replicas=1, max_replicas=4, target_backlog_per_replica=3.0,
        cooldown_steps=0,
    )
    grew = router.autoscale_step(pol, spawn=lambda: _server(cfg, params), step=0)
    assert grew == 3
    assert router.fleet_stats()["n_active"] == 4
    assert router.fleet_stats()["joins"] == 3
    router.run()
    for u in uids:
        assert router.take_result(u) is not None
    shrank = router.autoscale_step(pol, spawn=lambda: _server(cfg, params), step=10)
    assert shrank == -3
    assert router.fleet_stats()["n_active"] == 1


def test_journal_catchup_join_and_adoption(model_and_params, tmp_path):
    """Scale-up by journal catch-up: a dead replica's orphaned journal is
    adopted by a joining replica (the new capacity arrives already
    carrying the dead one's load), byte-identically."""
    cfg, _, params = model_and_params
    router = _fleet(cfg, params, n=2, tmp=tmp_path)
    prompts = _prompts(seed=43, n=4)
    budgets = [10, 8, 9, 11]
    uids = [router.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    for _ in range(4):
        router.step()
    victim = next(n for n, h in router.replicas.items() if h.inner.has_work())
    # the replica vanishes without the router re-routing (simulates an
    # operator-level removal): detach its requests from router tracking
    h = router.replicas[victim]
    h.state = "dead"
    router._ring.remove(victim)
    dead_uids = [u for u, n in router._where.items() if n == victim]
    for u in dead_uids:
        del router._where[u]
    # journal-catch-up join: fresh replica + adopt the orphaned journal
    jdir = os.path.join(str(tmp_path), "joiner")
    router.join(
        _server(cfg, params, journal_dir=jdir),
        name="joiner",
        journal_dir=jdir,
        catchup_dir=h.journal_dir,
    )
    assert router.fleet_stats()["adopted"] >= len(dead_uids)
    router.run()
    assert router.fleet_stats()["migrated_token_divergence"] == 0
    _assert_oracle(router, cfg, params, prompts, budgets, uids)


# ---------------------------------------------------------------------------
# merged observability
# ---------------------------------------------------------------------------
def test_fleet_serve_stats_and_observability_merge(model_and_params):
    cfg, _, params = model_and_params
    from deepspeed_tpu.profiling.tracer import (
        MetricsRegistry,
        ObservabilityHub,
        Tracer,
    )

    tracer = Tracer(max_spans=4096)
    metrics = MetricsRegistry()
    router = _fleet(cfg, params, n=2, tracer=tracer, metrics=metrics)
    hub = ObservabilityHub(tracer, metrics)
    router.attach_observability(hub)
    prompts = _prompts(seed=47, n=4)
    router.serve(prompts, max_new_tokens=[5, 6, 4, 7])
    merged = router.serve_stats()
    per = merged["replicas"]
    assert len(per) == 2
    for key in ("finished", "emitted_tokens", "dispatches", "admitted"):
        assert merged[key] == sum(rep[key] for rep in per.values()), key
    assert merged["dispatches_per_token"] == pytest.approx(
        merged["dispatches"] / merged["emitted_tokens"]
    )
    assert merged["tenants"]["default"]["finished"] == 4
    assert merged["tenants"]["default"]["ttft_ms"]["count"] == 4
    assert 0.0 <= merged["prefix"]["prefix_hit_rate"] <= 1.0
    assert merged["fleet"]["n_active"] == 2
    # the hub's merged report carries the fleet source + router spans
    report = hub.report()
    assert report["fleet"]["fleet"]["routed"] == 4
    names = {s["name"] for s in tracer.spans()}
    assert "fleet.step" in names and "fleet.replica_step" in names
    assert "fleet.route" in names


# ---------------------------------------------------------------------------
# the real thing: kill -9 a fleet process, adopt the journals, finish
# ---------------------------------------------------------------------------
REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)

_FLEET_CHILD_PRELUDE = """
import os, sys, json
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.environ["DS_TEST_REPO"])
import numpy as np
import jax
import jax.numpy as jnp
from deepspeed_tpu.inference.fleet import FleetRouter, ReplicaHandle
from deepspeed_tpu.inference.journal import RequestJournal
from deepspeed_tpu.inference.scheduler import PagedServer
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.config import TransformerConfig
from deepspeed_tpu.utils import chaos

WORKDIR = os.environ["DS_TEST_DIR"]
cfg = TransformerConfig(
    vocab_size=128, hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
    max_seq_len=64, norm="rmsnorm", position="rope", activation="swiglu",
    use_bias=False, tie_embeddings=False, flash_attention=False, dtype="float32")
model = TransformerLM(cfg)
toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
params = model.init(jax.random.PRNGKey(0), toks)

def server(jdir):
    return PagedServer(cfg, params, page_size=8, max_slots=4, prefill_chunk=8,
                       attn_impl="xla", dtype=jnp.float32, prefix_cache=True,
                       journal=RequestJournal(jdir))

rs = np.random.RandomState(7)
sysp = rs.randint(0, 128, (16,)).astype(np.int32)
prompts = []
for i in range(6):
    tail = rs.randint(0, 128, (int(rs.randint(3, 8)),)).astype(np.int32)
    prompts.append(np.concatenate([sysp, tail]) if i % 2 == 0 else tail)
budgets = [10, 7, 12, 8, 9, 11]
"""

_FLEET_KILL_CHILD = _FLEET_CHILD_PRELUDE + """
dirs = [os.path.join(WORKDIR, f"r{i}") for i in range(3)]
router = FleetRouter([
    ReplicaHandle(name=f"r{i}", server=server(d), journal_dir=d)
    for i, d in enumerate(dirs)
])
for p, n in zip(prompts, budgets):
    router.submit(p, max_new_tokens=n)
# a REAL kill -9 of the whole fleet process at a replica's step arrival
chaos.install(chaos.ChaosSchedule(
    [chaos.ChaosRule("fleet.replica_kill", hit=int(os.environ["DS_TEST_HIT"]),
                     action="exit")]))
router.run()
print("NOCRASH")
"""

_FLEET_RECOVER_CHILD = _FLEET_CHILD_PRELUDE + """
# the restart: FRESH replicas on FRESH journals; every pre-crash journal is
# adopted (journal-catch-up), outstanding requests re-distributed, finished
# results restored — then the fleet runs everything to completion
dirs = [os.path.join(WORKDIR, f"n{i}") for i in range(2)]
router = FleetRouter([
    ReplicaHandle(name=f"n{i}", server=server(d), journal_dir=d)
    for i, d in enumerate(dirs)
])
for i in range(3):
    router.adopt_journal(os.path.join(WORKDIR, f"r{i}"))
router.run()
outs = sorted(out.tolist() for out in router._results.values())
assert router.fleet_stats()["migrated_token_divergence"] == 0
print("RESULTS " + json.dumps(outs))
"""


@pytest.mark.slow
@pytest.mark.parametrize("hit", [3, 7])
def test_fleet_kill9_restart_adopts_journals_byte_identical(
    model_and_params, tmp_path, hit
):
    """The maximum-fidelity case: the whole fleet process dies by a real
    ``os._exit(137)`` at a deterministic replica-step arrival; a fresh
    process adopts every journal and finishes all six streams
    byte-identically to the dense oracle."""
    cfg, _, params = model_and_params
    env = dict(os.environ)
    env.update({
        "DS_TEST_REPO": REPO,
        "DS_TEST_DIR": str(tmp_path),
        "DS_TEST_HIT": str(hit),
    })
    proc = subprocess.run(
        [sys.executable, "-c", _FLEET_KILL_CHILD], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=420,
    )
    assert proc.returncode == 137, (
        f"kill did not fire (rc={proc.returncode}):\n{proc.stdout[-2000:]}"
        f"\n{proc.stderr[-2000:]}"
    )
    assert "NOCRASH" not in proc.stdout

    proc2 = subprocess.run(
        [sys.executable, "-c", _FLEET_RECOVER_CHILD], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=420,
    )
    assert proc2.returncode == 0, proc2.stdout[-2000:] + proc2.stderr[-2000:]
    line = next(
        l for l in proc2.stdout.splitlines() if l.startswith("RESULTS ")
    )
    outs = json.loads(line[len("RESULTS "):])
    # the oracle, in-process: same prompts, uninterrupted dense decode
    rs = np.random.RandomState(7)
    sysp = rs.randint(0, 128, (16,)).astype(np.int32)
    prompts = []
    for i in range(6):
        tail = rs.randint(0, 128, (int(rs.randint(3, 8)),)).astype(np.int32)
        prompts.append(np.concatenate([sysp, tail]) if i % 2 == 0 else tail)
    budgets = [10, 7, 12, 8, 9, 11]
    want = sorted(
        _dense(cfg, params, p, n).tolist() for p, n in zip(prompts, budgets)
    )
    assert outs == want
