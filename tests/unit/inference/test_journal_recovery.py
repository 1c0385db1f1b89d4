"""Serving crash recovery: the request journal + PagedServer replay.

Guarantees under test: a crash at any serving instant (mid-step before the
journal flush, torn tail mid-append) loses NOTHING a restart cannot
re-derive — the rebuilt server replays the journal and every stream resumes
**byte-identically** from its last emitted token (the preemption-recompute
machinery driven from disk). Corruption a crash cannot explain (a bad
record inside a sealed segment, valid records after a broken one) raises
``JournalCorruptError`` — red tests."""

import os

import numpy as np
import pytest

import deepspeed_tpu as ds
import deepspeed_tpu.parallel.mesh as mesh_mod
from deepspeed_tpu.inference.journal import (
    JournalCorruptError,
    RequestJournal,
)
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.config import TransformerConfig
from deepspeed_tpu.utils import chaos

CFG = TransformerConfig(
    vocab_size=256, hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
    max_seq_len=96, norm="rmsnorm", position="rope", activation="swiglu",
    use_bias=False, tie_embeddings=False, flash_attention=False,
)
PAGED = {"page_size": 8, "max_slots": 4, "prefill_chunk": 8}

rs = np.random.RandomState(0)
PROMPTS = [rs.randint(0, CFG.vocab_size, (12,)).astype(np.int32) for _ in range(4)]
# a shared system prompt for the prefix-cache recovery case
SHARED = rs.randint(0, CFG.vocab_size, (16,)).astype(np.int32)
SHARED_PROMPTS = [
    np.concatenate([SHARED, rs.randint(0, CFG.vocab_size, (6 + i,)).astype(np.int32)])
    for i in range(3)
]


@pytest.fixture(autouse=True)
def _disarm_chaos():
    yield
    chaos.uninstall()


def _engine(journal_dir=None, **paged_over):
    mesh_mod.reset_topology()
    kw = dict(dtype="bf16", paged_kv={**PAGED, **paged_over})
    if journal_dir is not None:
        kw["journal"] = {"enabled": True, "dir": str(journal_dir)}
    eng = ds.init_inference(TransformerLM(CFG), **kw)
    eng.init_params(np.stack(PROMPTS))
    eng._ds_config = CFG
    eng._paged_server = eng._build_paged_server()
    return eng


# ---------------------------------------------------------------------------
# journal units
# ---------------------------------------------------------------------------
class TestJournalUnits:
    def test_roundtrip_submit_emit_finish(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        j.append_submit(0, np.asarray([1, 2, 3], np.int32), 8, None, "default")
        j.append_emit(0, 7)
        j.append_emit(0, 9)
        j.append_submit(1, np.asarray([4], np.int32), 4, 3, "tenantB")
        j.append_finish(0)
        j.sync()
        states, next_uid = RequestJournal.replay(str(tmp_path))
        assert next_uid == 2
        assert states[0].finished and states[0].generated == [7, 9]
        np.testing.assert_array_equal(states[0].prompt, [1, 2, 3])
        assert not states[1].finished and states[1].eos_token_id == 3
        assert states[1].tenant == "tenantB"

    def test_replay_preserves_timing_stamps(self, tmp_path):
        """The submit record's ts and the one-shot first-token record keep
        TTFT honest across a live-fleet re-route: replay returns the
        original stamps (absent records replay as None — a fresh process
        must restamp against its own clock)."""
        j = RequestJournal(str(tmp_path))
        j.append_submit(0, np.asarray([1, 2], np.int32), 8, None, "default",
                        t_submit=2.5)
        j.append_first_token(0, 3.25)
        j.append_emit(0, 7)
        j.append_submit(1, np.asarray([4], np.int32), 4, None, "default")
        j.sync()
        states, _ = RequestJournal.replay(str(tmp_path))
        assert states[0].t_submit == 2.5 and states[0].t_first == 3.25
        assert states[1].t_submit is None and states[1].t_first is None

    def test_seeded_resubmit_replaces_state(self, tmp_path):
        """Recovery compaction: a later submit record with pre-seeded
        emissions resets the uid's state (old segments stay replayable)."""
        j = RequestJournal(str(tmp_path))
        j.append_submit(0, np.asarray([1], np.int32), 8, None, "default")
        j.append_emit(0, 5)
        j.append_submit(0, np.asarray([1], np.int32), 8, None, "default",
                        generated=[5])
        j.append_emit(0, 6)
        j.sync()
        states, _ = RequestJournal.replay(str(tmp_path))
        assert states[0].generated == [5, 6]

    def test_implicit_done_budget_and_eos(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        j.append_submit(0, np.asarray([1], np.int32), 2, None, "default")
        j.append_emit(0, 5)
        j.append_emit(0, 6)  # budget hit; crash ate the finish record
        j.append_submit(1, np.asarray([1], np.int32), 8, 3, "default")
        j.append_emit(1, 3)  # EOS
        j.sync()
        states, _ = RequestJournal.replay(str(tmp_path))
        assert states[0].done and states[1].done

    def test_segment_rotation_and_cross_segment_replay(self, tmp_path):
        j = RequestJournal(str(tmp_path), segment_bytes=128)
        j.append_submit(0, np.arange(8, dtype=np.int32), 64, None, "default")
        j.sync()
        for t in range(20):
            j.append_emit(0, t)
            j.sync()  # rotates whenever the active segment passes 128B
        assert j.segments_sealed >= 2
        names = sorted(os.listdir(tmp_path))
        assert any(n.endswith(".jrnl") for n in names)
        states, _ = RequestJournal.replay(str(tmp_path))
        assert states[0].generated == list(range(20))

    def test_torn_tail_of_active_segment_is_dropped(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        j.append_submit(0, np.asarray([1], np.int32), 8, None, "default")
        for t in range(4):
            j.append_emit(0, t)
        j.sync()
        seg = [n for n in os.listdir(tmp_path) if n.endswith(".open")][0]
        path = os.path.join(tmp_path, seg)
        with open(path, "r+b") as f:  # tear mid-record, like a real crash
            f.truncate(os.path.getsize(path) - 7)
        states, _ = RequestJournal.replay(str(tmp_path))
        assert states[0].generated == [0, 1, 2]  # the torn emit is gone

    def test_double_crash_torn_tails_stay_tolerable(self, tmp_path):
        """Crash 1 tears seg_000000.open; recovery opens seg_000001. A
        second crash (torn or not) must still replay — an old .open torn
        tail is a crash artifact forever, not corruption."""
        j1 = RequestJournal(str(tmp_path))
        j1.append_submit(0, np.asarray([1], np.int32), 8, None, "default")
        j1.append_emit(0, 4)
        j1.sync()
        seg0 = os.path.join(tmp_path, "seg_000000.open")
        with open(seg0, "r+b") as f:  # crash 1 tears the tail
            f.truncate(os.path.getsize(seg0) - 5)
        states, next_uid = RequestJournal.replay(str(tmp_path))
        assert states[0].generated == []
        j2 = RequestJournal(str(tmp_path))  # recovery writer: seg_000001
        j2.append_submit(0, np.asarray([1], np.int32), 8, None, "default",
                         generated=[])
        j2.append_emit(0, 4)
        j2.sync()
        # crash 2, then a THIRD replay over both torn/partial segments
        states, _ = RequestJournal.replay(str(tmp_path))
        assert states[0].generated == [4]
        assert len(RequestJournal.segments(str(tmp_path))) == 2

    def test_corrupt_sealed_segment_raises(self, tmp_path):
        j = RequestJournal(str(tmp_path), segment_bytes=1)  # seal every sync
        j.append_submit(0, np.asarray([1], np.int32), 8, None, "default")
        j.sync()
        assert j.segments_sealed == 1
        sealed = [n for n in os.listdir(tmp_path) if n.endswith(".jrnl")][0]
        path = os.path.join(tmp_path, sealed)
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) - 5)
        with pytest.raises(JournalCorruptError, match="sealed"):
            RequestJournal.replay(str(tmp_path))

    def test_valid_records_after_a_bad_one_raise(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        j.append_submit(0, np.asarray([1], np.int32), 8, None, "default")
        j.append_emit(0, 1)
        j.append_emit(0, 2)
        j.sync()
        seg = [n for n in os.listdir(tmp_path) if n.endswith(".open")][0]
        path = os.path.join(tmp_path, seg)
        with open(path, "rb") as f:
            lines = f.readlines()
        lines[1] = b"deadbeef corrupted-not-torn\n"  # mid-file damage
        with open(path, "wb") as f:  # noqa: DS-R008 — test writes damage in place
            f.writelines(lines)
        with pytest.raises(JournalCorruptError, match="valid records after"):
            RequestJournal.replay(str(tmp_path))

    def test_chaos_truncate_at_append_is_survivable(self, tmp_path):
        """The journal.append injection point + truncate action: the torn
        tail is dropped at replay, everything fsynced earlier survives."""
        j = RequestJournal(str(tmp_path))
        j.append_submit(0, np.asarray([1], np.int32), 8, None, "default")
        j.sync()
        j.append_emit(0, 1)
        chaos.install(chaos.ChaosSchedule(
            [chaos.ChaosRule("journal.append", action="truncate", nbytes=5)]
        ))
        with pytest.raises(chaos.ChaosKilled):
            j.sync()
        chaos.uninstall()
        states, _ = RequestJournal.replay(str(tmp_path))
        assert states[0].generated == []  # the torn emit never happened
        np.testing.assert_array_equal(states[0].prompt, [1])


# ---------------------------------------------------------------------------
# crash-restart through the serving engine
# ---------------------------------------------------------------------------
class TestServeRecovery:
    def _reference(self, prompts, max_new):
        eng = _engine()
        return eng.serve(prompts, max_new_tokens=max_new)

    @pytest.mark.parametrize("kill_step", [1, 3])
    def test_mid_step_crash_streams_resume_byte_identical(
        self, tmp_path, eight_devices, kill_step
    ):
        ref = self._reference(PROMPTS, 16)

        eng = _engine(tmp_path)
        srv = eng._paged_server
        uids = [srv.submit(p, max_new_tokens=16) for p in PROMPTS]
        chaos.install(chaos.ChaosSchedule(
            [chaos.ChaosRule("serve.mid_step", hit=kill_step)]
        ))
        with pytest.raises(chaos.ChaosKilled):
            srv.run()
        chaos.uninstall()

        # restart: a fresh engine over the same journal dir replays it
        eng2 = _engine(tmp_path)
        srv2 = eng2._paged_server
        assert srv2.stats["recovered"] == len(PROMPTS)
        srv2.run()
        outs = [srv2.take_result(u) for u in uids]
        for got, want in zip(outs, ref):
            np.testing.assert_array_equal(got, want)
        srv2.pool.integrity_check()

    def test_recovery_with_prefix_cache_shared_prompts(self, tmp_path, eight_devices):
        """Re-prefill of recovered requests rides the prefix cache: shared
        system prompts attach instead of recomputing, and the streams stay
        byte-identical."""
        ref = self._reference(SHARED_PROMPTS, 12)

        eng = _engine(tmp_path, prefix_cache=True)
        srv = eng._paged_server
        uids = [srv.submit(p, max_new_tokens=12) for p in SHARED_PROMPTS]
        chaos.install(chaos.ChaosSchedule([chaos.ChaosRule("serve.mid_step", hit=4)]))
        with pytest.raises(chaos.ChaosKilled):
            srv.run()
        chaos.uninstall()

        eng2 = _engine(tmp_path, prefix_cache=True)
        srv2 = eng2._paged_server
        srv2.run()
        for uid, want in zip(uids, ref):
            np.testing.assert_array_equal(srv2.take_result(uid), want)

    def test_finished_results_survive_restart(self, tmp_path, eight_devices):
        eng = _engine(tmp_path)
        srv = eng._paged_server
        uids = [srv.submit(p, max_new_tokens=6) for p in PROMPTS]
        srv.run()
        done = {u: srv.result(u) for u in uids}
        assert all(v is not None for v in done.values())

        # crash AFTER completion, before anyone fetched the results
        eng2 = _engine(tmp_path)
        srv2 = eng2._paged_server
        assert srv2.stats["recovered"] == 0  # nothing live to re-run
        for u in uids:
            np.testing.assert_array_equal(srv2.take_result(u), done[u])

    def test_new_submissions_after_recovery_get_fresh_uids(self, tmp_path, eight_devices):
        eng = _engine(tmp_path)
        srv = eng._paged_server
        uids = [srv.submit(p, max_new_tokens=4) for p in PROMPTS[:2]]
        chaos.install(chaos.ChaosSchedule([chaos.ChaosRule("serve.mid_step", hit=1)]))
        with pytest.raises(chaos.ChaosKilled):
            srv.run()
        chaos.uninstall()

        eng2 = _engine(tmp_path)
        srv2 = eng2._paged_server
        new_uid = srv2.submit(PROMPTS[2], max_new_tokens=4)
        assert new_uid not in uids  # the journal advanced the uid counter
        srv2.run()
        assert srv2.take_result(new_uid) is not None
        srv2.pool.integrity_check()

    def test_recovery_compacts_and_retires_old_segments(self, tmp_path, eight_devices):
        """Repeated crash/recover cycles must not grow the journal: each
        recovery re-journals the full state (live + finished) into one
        fresh segment and retires everything it supersedes."""
        eng = _engine(tmp_path)
        srv = eng._paged_server
        uids = [srv.submit(p, max_new_tokens=6) for p in PROMPTS]
        srv.run()
        done = {u: srv.result(u) for u in uids}
        for _ in range(3):
            eng2 = _engine(tmp_path)  # restart: replay + compact + retire
            srv2 = eng2._paged_server
            assert len(RequestJournal.segments(str(tmp_path))) == 1
            for u in uids:
                np.testing.assert_array_equal(srv2.result(u), done[u])

    def test_journal_disabled_leaves_no_files(self, tmp_path, eight_devices):
        eng = _engine()  # no journal config
        eng.serve(PROMPTS[:2], max_new_tokens=4)
        assert eng._paged_server.journal is None
        assert list(tmp_path.iterdir()) == []

    def test_spec_decode_streams_survive_crash(self, tmp_path, eight_devices):
        """Speculative serving journals through the same _emit path: a
        crash mid-round recovers byte-identically (drafts are host-side
        scratch — only accepted tokens are journaled)."""
        def eng_spec(jd=None):
            mesh_mod.reset_topology()
            kw = dict(
                dtype="bf16", paged_kv={**PAGED, "attn_impl": "xla"},
                spec_decode={"enable": True, "max_draft": 3},
            )
            if jd is not None:
                kw["journal"] = {"enabled": True, "dir": str(jd)}
            e = ds.init_inference(TransformerLM(CFG), **kw)
            e.init_params(np.stack(PROMPTS))
            e._ds_config = CFG
            e._paged_server = e._build_paged_server()
            return e

        ref = eng_spec().serve(PROMPTS, max_new_tokens=12)
        eng = eng_spec(tmp_path)
        srv = eng._paged_server
        uids = [srv.submit(p, max_new_tokens=12) for p in PROMPTS]
        chaos.install(chaos.ChaosSchedule([chaos.ChaosRule("serve.mid_step", hit=2)]))
        with pytest.raises(chaos.ChaosKilled):
            srv.run()
        chaos.uninstall()
        eng2 = eng_spec(tmp_path)
        srv2 = eng2._paged_server
        srv2.run()
        for uid, want in zip(uids, ref):
            np.testing.assert_array_equal(srv2.take_result(uid), want)

    def test_journal_syncs_once_a_step(self, tmp_path, eight_devices):
        """One durability point a ``step()`` call, with a step in flight: the
        tokens a call emits (those of the step dispatched one call earlier)
        land in that call's one ``journal.sync``, and the call that only
        settles the last step syncs too."""
        eng = _engine(tmp_path)
        srv = eng._paged_server
        outs = eng.serve(PROMPTS, max_new_tokens=13)
        for got, want in zip(outs, _engine().serve(PROMPTS, max_new_tokens=13)):
            np.testing.assert_array_equal(got, want)
        assert srv.stats["run_ahead_steps"] >= 10
        spans = eng.tracer.spans()
        steps = [s for s in spans if s["name"] == "serve.step"]
        syncs = [s for s in spans if s["name"] == "serve.journal_sync"]
        assert len(syncs) == len(steps) > srv.stats["ragged_steps"]  # the last call enqueues nothing
        for step, sync in zip(steps, syncs):  # each inside its call, behind everything else the call did
            assert step["t0"] <= sync["t0"] and sync["t1"] <= step["t1"]
        states, _ = RequestJournal.replay(str(tmp_path))
        assert all(st.finished for st in states.values()) and len(states) == len(PROMPTS)
