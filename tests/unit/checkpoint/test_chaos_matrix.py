"""The FULL crash-restart matrix (``-m slow``): a REAL ``os._exit(137)`` —
no atexit, no flushing, the honest ``kill -9`` — injected at every named
fault point, for both engines, followed by an in-process restart with
``auto_resume=True`` / journal replay.

Assertions, per ISSUE 9's acceptance bar:

* training losses after resume are **bit-identical** to an uninterrupted
  run from the same seed;
* serving streams are **byte-identical** to an uninterrupted serve;
* no injection point can make ``latest``/``find_latest_valid`` resolve to
  a torn checkpoint.

Each kill runs in its own subprocess (the in-process fast subset lives in
``test_fault_tolerance.py`` / ``test_journal_recovery.py``); this matrix is
the expensive, maximum-fidelity sweep.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)

_CHILD_PRELUDE = """
import os, sys, json
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
sys.path.insert(0, os.environ["DS_TEST_REPO"])
import numpy as np
import jax
import deepspeed_tpu as ds
from deepspeed_tpu.utils import chaos

POINT = os.environ["DS_TEST_POINT"]
HIT = int(os.environ["DS_TEST_HIT"])
ACTION = os.environ.get("DS_TEST_ACTION", "exit")
WORKDIR = os.environ["DS_TEST_DIR"]
chaos.install(chaos.ChaosSchedule([chaos.ChaosRule(POINT, hit=HIT, action=ACTION)]))
"""

_TRAIN_CHILD = _CHILD_PRELUDE + """
from tests.unit.simple_model import SimpleModel

def batch_for(step):
    rs = np.random.RandomState(1000 + step)
    return (rs.randn(8, 16).astype(np.float32), rs.randn(8, 16).astype(np.float32))

engine, *_ = ds.initialize(model=SimpleModel(), config={
    "train_micro_batch_size_per_gpu": 1,
    "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
    "bf16": {"enabled": True},
    "zero_optimization": {"stage": 1},
    "scheduler": {"type": "WarmupLR", "params": {
        "warmup_min_lr": 0.0, "warmup_max_lr": 1e-2, "warmup_num_steps": 10}},
    "checkpoint": {"interval_steps": 1, "save_dir": WORKDIR,
                   "async_snapshot": os.environ.get("DS_TEST_ASYNC") == "1"},
})
engine.init_params(batch_for(0))
for _ in range(6):
    loss = engine(batch_for(engine.global_steps))
    engine.backward(loss)
    engine.step()
engine.wait_pending_checkpoint()
print("NOCRASH")  # the parent asserts the kill actually fired (rc 137)
"""

_SERVE_CHILD = _CHILD_PRELUDE + """
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.config import TransformerConfig

mcfg = TransformerConfig(
    vocab_size=256, hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
    max_seq_len=96, norm="rmsnorm", position="rope", activation="swiglu",
    use_bias=False, tie_embeddings=False, flash_attention=False)
rs = np.random.RandomState(0)
prompts = [rs.randint(0, 256, (12,)).astype(np.int32) for _ in range(4)]
eng = ds.init_inference(
    TransformerLM(mcfg), dtype="bf16",
    paged_kv={"page_size": 8, "max_slots": 4, "prefill_chunk": 8},
    journal={"enabled": True, "dir": WORKDIR})
eng.init_params(np.stack(prompts))
eng._ds_config = mcfg
eng._paged_server = eng._build_paged_server()
srv = eng._paged_server
try:
    # submit() syncs the journal too (admissions are durable at submit),
    # so the kill can land there as well as in the step loop
    uids = [srv.submit(p, max_new_tokens=16) for p in prompts]
    srv.run()
except BaseException:
    # a truncate-action ChaosKilled reaches here: die ABRUPTLY (os._exit,
    # no flushing) so the on-disk state is exactly what the kill left
    os._exit(137)
print("NOCRASH")
"""


def _run_child(code, env_over, timeout=420):
    env = dict(os.environ)
    env["DS_TEST_REPO"] = REPO
    env.update(env_over)
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=timeout,
    )
    return proc


def _batch(step):
    rs = np.random.RandomState(1000 + step)
    return (rs.randn(8, 16).astype(np.float32), rs.randn(8, 16).astype(np.float32))


def _fresh_train_engine():
    import deepspeed_tpu as ds
    import deepspeed_tpu.parallel.mesh as mesh_mod
    from tests.unit.simple_model import SimpleModel

    mesh_mod.reset_topology()
    engine, *_ = ds.initialize(model=SimpleModel(), config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "scheduler": {"type": "WarmupLR", "params": {
            "warmup_min_lr": 0.0, "warmup_max_lr": 1e-2, "warmup_num_steps": 10}},
    })
    engine.init_params(_batch(0))
    return engine


def _train_losses(engine, n):
    import jax

    out = []
    for _ in range(n):
        loss = engine(_batch(engine.global_steps))
        engine.backward(loss)
        engine.step()
        out.append(float(jax.device_get(loss)))
    return out


class TestTrainingKillMatrix:
    @pytest.mark.parametrize("async_snapshot", [False, True])
    @pytest.mark.parametrize(
        "point,hit",
        [
            ("ckpt.mid_array_write", 2),
            ("ckpt.pre_commit", 2),
            ("ckpt.post_commit", 2),
        ],
    )
    def test_kill_then_auto_resume_bit_identical(
        self, tmp_path, eight_devices, point, hit, async_snapshot
    ):
        from deepspeed_tpu.runtime.checkpoint_engine.atomic import (
            find_latest_valid,
            is_complete_checkpoint,
        )

        proc = _run_child(_TRAIN_CHILD, {
            "DS_TEST_POINT": point, "DS_TEST_HIT": str(hit),
            "DS_TEST_DIR": str(tmp_path),
            "DS_TEST_ASYNC": "1" if async_snapshot else "0",
        })
        assert proc.returncode == 137, (
            f"kill did not fire (rc={proc.returncode}):\n{proc.stdout[-2000:]}"
            f"\n{proc.stderr[-2000:]}"
        )
        assert "NOCRASH" not in proc.stdout

        tag = find_latest_valid(str(tmp_path))
        assert tag is not None, "at least one committed checkpoint must survive"
        assert is_complete_checkpoint(os.path.join(tmp_path, tag))

        ref = _fresh_train_engine()
        ref_losses = _train_losses(ref, 6)

        resumed = _fresh_train_engine()
        path, _ = resumed.load_checkpoint(str(tmp_path), auto_resume=True)
        assert path is not None
        start = resumed.global_steps
        assert 1 <= start <= 6
        tail = _train_losses(resumed, 6 - start)
        assert tail == ref_losses[start:], (
            f"resume from step {start} after kill at {point} diverged:"
            f"\n{tail}\nvs\n{ref_losses[start:]}"
        )


class TestServingKillMatrix:
    @pytest.mark.parametrize(
        "point,hit,action",
        [
            ("serve.mid_step", 2, "exit"),
            ("serve.mid_step", 5, "exit"),
            # journal.append hits 1-4 are the per-submit admission syncs;
            # 3 tears an admission record, 7 tears mid-stream emissions
            ("journal.append", 3, "truncate"),
            ("journal.append", 7, "truncate"),
        ],
    )
    def test_kill_then_replay_byte_identical(
        self, tmp_path, eight_devices, point, hit, action
    ):
        import deepspeed_tpu as ds
        import deepspeed_tpu.parallel.mesh as mesh_mod
        from deepspeed_tpu.models import TransformerLM
        from deepspeed_tpu.models.config import TransformerConfig

        proc = _run_child(_SERVE_CHILD, {
            "DS_TEST_POINT": point, "DS_TEST_HIT": str(hit),
            "DS_TEST_ACTION": action, "DS_TEST_DIR": str(tmp_path),
        })
        assert proc.returncode == 137, (
            f"kill did not fire (rc={proc.returncode}):\n{proc.stdout[-2000:]}"
            f"\n{proc.stderr[-2000:]}"
        )

        mcfg = TransformerConfig(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, max_seq_len=96, norm="rmsnorm", position="rope",
            activation="swiglu", use_bias=False, tie_embeddings=False,
            flash_attention=False,
        )
        rs = np.random.RandomState(0)
        prompts = [rs.randint(0, 256, (12,)).astype(np.int32) for _ in range(4)]

        def build(journal):
            mesh_mod.reset_topology()
            kw = dict(dtype="bf16",
                      paged_kv={"page_size": 8, "max_slots": 4, "prefill_chunk": 8})
            if journal:
                kw["journal"] = {"enabled": True, "dir": str(tmp_path)}
            eng = ds.init_inference(TransformerLM(mcfg), **kw)
            eng.init_params(np.stack(prompts))
            eng._ds_config = mcfg
            eng._paged_server = eng._build_paged_server()
            return eng

        ref = build(False).serve(prompts, max_new_tokens=16)
        srv = build(True)._paged_server
        srv.run()
        survived = 0
        for uid, want in enumerate(ref):
            got = srv.take_result(uid)
            if got is None:
                # a stream can be missing only when the crash predates its
                # durable admission — the torn submit record itself, or
                # submits that never ran because the process was already
                # dead; either way the client never got an ack for it
                assert action == "truncate", f"acked stream {uid} lost"
                continue
            survived += 1
            np.testing.assert_array_equal(got, want)
        if action == "exit":
            assert survived == len(ref)  # every acked stream resumes
        else:
            assert survived >= 1  # everything durably admitted resumes
        srv.pool.integrity_check()
