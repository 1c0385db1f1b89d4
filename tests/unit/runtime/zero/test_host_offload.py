"""ZeRO-Infinity streamed host offload (ISSUE 16): fp32 master + Adam
moments live in pinned host buffers and stream device-ward bucket by
bucket through a depth-2 double-buffered pipeline, hidden behind compute.

The bit-identity contract pinned here:

* sequential streamed training bit-matches the on-device path — losses,
  master tree, moments, fp16 scale trajectory — across
  zero{1,3} x {fp32,bf16,fp16} x gas{1,2};
* a checkpoint roundtrip and a ``train.mid_offload_stream`` chaos kill
  both resume bit-identically — torn host buffers are never trusted,
  they are rebuilt from the last committed checkpoint.

Plus the stream accounting (declared schedule == measured bytes, zero
exposed ms with both pipeline knobs on, red when a knob is off), the
bucket splitter edges, the config-hygiene red tests, and the bench
bisection-probe helper.
"""

from __future__ import annotations

import numpy as np
import pytest

import deepspeed_tpu as ds
import deepspeed_tpu.parallel.mesh as mesh_mod
from deepspeed_tpu.runtime.zero.host_offload import split_offload_buckets
from deepspeed_tpu.utils import chaos
from tests.unit.simple_model import (
    SimpleModel,
    assert_same_master,
    master_snapshot,
    step_batch,
    train_steps_batch,
)

# 300-element buckets split SimpleModel's 512 params into 2 buckets, so
# every test exercises real bucket boundaries and the double-buffer depth
STREAM = {
    "device": "cpu",
    "pin_memory": True,
    "pipeline_read": True,
    "pipeline_write": True,
    "bucket_size": 300,
}


def _cfg(offload, gas=1, stage=1, prec="bf16", **over):
    base = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": stage},
        "gradient_clipping": 1.0,
    }
    if prec == "bf16":
        base["bf16"] = {"enabled": True}
    elif prec == "fp16":
        base["fp16"] = {"enabled": True, "initial_scale_power": 4, "hysteresis": 1}
    if gas > 1 and offload:
        base["compile"] = {"fuse_grad_accum": True}
    if offload:
        base["zero_optimization"]["offload_optimizer"] = dict(STREAM)
    base.update(over)
    return base


def _engine(offload, **kw):
    mesh_mod.reset_topology()
    engine, *_ = ds.initialize(model=SimpleModel(), config=_cfg(offload, **kw))
    return engine


def _batches(gas, steps, seed=0, bad_step=None):
    bad = set() if bad_step is None else {bad_step}
    rs = np.random.RandomState(seed)
    out = []
    for s in range(steps):
        for g in range(gas):
            x = rs.randn(8, 16).astype(np.float32)
            y = rs.randn(8, 16).astype(np.float32)
            if s in bad and g == 0:
                x = x.copy()
                x[0, 0] = np.inf
            out.append((x, y))
    return out


def _drive(engine, data, steps):
    it = iter(list(data))
    return [float(engine.train_batch(data_iter=it)) for _ in range(steps)]


# ---------------------------------------------------------------------------
# bucket splitter unit edges
# ---------------------------------------------------------------------------
def test_split_buckets_groups_whole_leaves_in_order():
    assert split_offload_buckets([100, 100, 100], 200) == [[0, 1], [2]]
    assert split_offload_buckets([100, 100, 100], 300) == [[0, 1, 2]]
    assert split_offload_buckets([100, 100], 1) == [[0], [1]]


def test_split_buckets_oversized_leaf_gets_own_bucket():
    # a leaf bigger than bucket_size never splits (whole-leaf streaming);
    # it closes the open bucket and rides alone
    assert split_offload_buckets([50, 500, 50], 100) == [[0], [1], [2]]
    assert split_offload_buckets([500], 100) == [[0]]


def test_split_buckets_exact_fit_and_empty():
    assert split_offload_buckets([100, 100, 100, 100], 200) == [[0, 1], [2, 3]]
    assert split_offload_buckets([], 100) == []


# ---------------------------------------------------------------------------
# bit-identity: sequential streamed vs on-device
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("stage", [1, 3])
@pytest.mark.parametrize("prec", ["fp32", "bf16", "fp16"])
@pytest.mark.parametrize("gas", [1, 2])
def test_streamed_bit_identical_to_on_device(eight_devices, stage, prec, gas):
    """Losses AND fp32 master bit-match the on-device engine over 3 steps
    for every zero-stage x precision x gas combination. The streamed step
    (fwd_bwd + offload_stats + per-bucket donated updates) mirrors the
    on-device update math op for op; at gas>1 the on-device arm runs the
    unfused micro path — the program family the streamed grads share."""
    batch = step_batch(batch_size=8 * gas, seed=0)
    ref = _engine(False, gas=gas, stage=stage, prec=prec)
    ref_losses = train_steps_batch(ref, batch, 3)
    ref_master = master_snapshot(ref)
    off = _engine(True, gas=gas, stage=stage, prec=prec)
    off_losses = train_steps_batch(off, batch, 3)
    assert off._streamed_offload, "streamed engine not selected"
    assert off._host_offload.num_buckets >= 2  # real bucket boundaries
    np.testing.assert_array_equal(np.asarray(off_losses), np.asarray(ref_losses))
    assert_same_master(master_snapshot(off), ref_master)


def test_fp16_overflow_reverts_bitwise_and_tracks_scale(eight_devices):
    """An overflow micro-batch must leave the offloaded master bitwise
    untouched (the donated bucket programs revert via jnp.where, the host
    discards the staged buckets) and walk the loss scale exactly like the
    on-device engine."""
    batch = step_batch(batch_size=8, seed=0)
    x, y = batch
    xbad = x.copy()
    xbad[0, 0] = np.inf
    for offload in (False, True):
        engine = _engine(offload, prec="fp16")
        train_steps_batch(engine, batch, 1)
        before = master_snapshot(engine)
        engine.train_batch(batch=(xbad, y))
        assert engine.skipped_steps == 1, f"offload={offload}"
        assert engine.loss_scale == 8.0, f"offload={offload}"
        assert_same_master(master_snapshot(engine), before)


# ---------------------------------------------------------------------------
# stream accounting: declared schedule vs measured transfers
# ---------------------------------------------------------------------------
def test_stream_schedule_matches_measured_bytes(eight_devices):
    engine = _engine(True)
    batch = step_batch(batch_size=8, seed=0)
    train_steps_batch(engine, batch, 3)
    ho = engine._host_offload
    sched = ho.stream_schedule()
    assert sched["anchor"] == "offload_stats"
    declared_h2d = sum(t["bytes"] for t in sched["transfers"] if t["direction"] == "h2d")
    declared_d2h = sum(t["bytes"] for t in sched["transfers"] if t["direction"] == "d2h")
    compute = set(sched["compute_programs"])
    assert all(t["hide_behind"] in compute for t in sched["transfers"])
    stats = engine.offload_stream_stats()
    assert stats["steps"] == 3
    assert stats["h2d_bytes"] == 3 * declared_h2d
    assert stats["d2h_bytes"] == 3 * declared_d2h
    # both pipeline knobs on: every copy is issued async and lands behind
    # compute — zero blocking wait on the stream
    assert stats["exposed_ms"] == 0.0


def test_stream_exposed_when_pipeline_write_off(eight_devices):
    """pipeline_write=False is the red arm of the overlap story: writes
    block at the end of each bucket (measured exposed_ms > 0 once timing
    is observable) and the DECLARED schedule stops claiming a hiding
    program, which the overlap pass turns into exposed stream bytes."""
    over = dict(STREAM)
    over["pipeline_write"] = False
    engine = _engine(True, **{"zero_optimization": {
        "stage": 1, "offload_optimizer": over}})
    batch = step_batch(batch_size=8, seed=0)
    train_steps_batch(engine, batch, 2)
    assert engine._streamed_offload
    sched = engine._host_offload.stream_schedule()
    d2h = [t for t in sched["transfers"] if t["direction"] == "d2h"]
    assert d2h and all(t["hide_behind"] is None for t in d2h)
    rep = engine.analysis_report(programs=["offload_stats"], passes=["overlap"])
    t = rep["totals"]
    assert t["stream_verified"] is False
    assert t["exposed_stream_bytes"] == sum(x["bytes"] for x in d2h)


# ---------------------------------------------------------------------------
# checkpoints: host-resident snapshot, roundtrip, format guards
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip_bit_identical(eight_devices, tmp_path):
    steps = 4
    data = _batches(1, steps)
    ref = _engine(True, prec="fp16")
    ref_losses = _drive(ref, data, steps)
    ref_master = master_snapshot(ref)

    engine = _engine(True, prec="fp16")
    _drive(engine, data[:2], 2)
    engine.save_checkpoint(str(tmp_path), tag="mid")
    engine.wait_pending_checkpoint()

    resumed = _engine(True, prec="fp16")
    resumed.init_params(data[0])
    path, _ = resumed.load_checkpoint(str(tmp_path), tag="mid")
    assert path is not None
    out = _drive(resumed, data[2:], steps - 2)
    assert out == ref_losses[2:]
    assert_same_master(master_snapshot(resumed), ref_master)


def test_state_dict_is_host_resident_numpy(eight_devices):
    """The checkpoint snapshot must come straight from the pinned host
    buffers — plain numpy, no device round-trip for the async writer to
    stall on — and must drain the in-flight write fence first."""
    engine = _engine(True)
    batch = step_batch(batch_size=8, seed=0)
    train_steps_batch(engine, batch, 2)
    state = engine._host_offload.state_dict()
    assert state["format"] == "streamed"
    assert state["step"] == 2
    for rec in state["leaves"]:
        for key in ("master", "exp_avg", "exp_avg_sq"):
            assert type(rec[key]) is np.ndarray, key
    # copies, not views of the live buffers: training must not mutate a
    # snapshot the async writer is still draining
    engine._host_offload._master[0][...] = 0.0
    assert np.any(state["leaves"][0]["master"] != 0.0)


def test_streamed_rejects_legacy_checkpoint_and_vice_versa(eight_devices):
    batch = step_batch(batch_size=8, seed=0)
    streamed = _engine(True)
    train_steps_batch(streamed, batch, 1)
    streamed_state = streamed._host_offload.state_dict()

    legacy_cfg = dict(STREAM)
    legacy_cfg["pipeline_read"] = legacy_cfg["pipeline_write"] = False
    legacy = _engine(True, **{"zero_optimization": {
        "stage": 1, "offload_optimizer": legacy_cfg}})
    train_steps_batch(legacy, batch, 1)
    assert not legacy._streamed_offload  # the legacy host-Adam engine
    legacy_state = legacy._host_offload.state_dict()

    with pytest.raises(ValueError, match="(?i)streamed"):
        legacy._host_offload.load_state_dict(streamed_state)
    with pytest.raises(ValueError, match="legacy"):
        streamed._host_offload.load_state_dict(legacy_state)


# ---------------------------------------------------------------------------
# chaos: kill mid-stream, resume from the last committed checkpoint
# ---------------------------------------------------------------------------
def test_mid_stream_chaos_kill_resumes_bit_identical(eight_devices, tmp_path):
    """``train.mid_offload_stream`` fires between bucket dispatches: the
    kill lands with staged H2D buckets live, in-flight D2H writes pending,
    and the host buffers torn mid-step. The resumed engine never trusts
    them — it rebuilds from the last interval autosave — and the continued
    run is bit-identical to an uninterrupted one. fp16: scale state rides
    the checkpoint too."""
    steps = 6
    data = _batches(1, steps, seed=7)

    def build():
        return _engine(True, prec="fp16", **{
            "checkpoint": {"interval_steps": 2, "save_dir": str(tmp_path)},
        })

    ref = build()
    ref_losses = _drive(ref, data, steps)
    ref_master = master_snapshot(ref)
    import shutil

    shutil.rmtree(str(tmp_path))
    tmp_path.mkdir()

    engine = build()
    it = iter(list(data))
    committed = []
    # 2 buckets -> the point fires twice per step; hit=5 kills step 3
    # (0-indexed step 2) on its FIRST bucket — a genuinely torn stream
    chaos.install(chaos.ChaosSchedule([
        chaos.ChaosRule("train.mid_offload_stream", hit=5),
    ]))
    try:
        for _ in range(steps):
            committed.append(float(engine.train_batch(data_iter=it)))
        raise AssertionError("chaos never fired")
    except chaos.ChaosKilled:
        pass
    finally:
        chaos.uninstall()
    assert committed == ref_losses[: len(committed)]

    resumed = build()
    resumed.init_params(data[0])
    path, _ = resumed.load_checkpoint(str(tmp_path), auto_resume=True)
    assert path is not None
    start = resumed.global_steps
    assert start % 2 == 0 and start >= len(committed) - 1
    it2 = iter(list(data[start:]))
    out = [float(resumed.train_batch(data_iter=it2)) for _ in range(steps - start)]
    assert out == ref_losses[start:]
    assert_same_master(master_snapshot(resumed), ref_master)


# ---------------------------------------------------------------------------
# config hygiene (red tests)
# ---------------------------------------------------------------------------
def test_config_red_orphan_pin_memory_knob():
    """The silently-popped knob: cpu_offload_use_pin_memory without any
    offloaded optimizer used to parse and then vanish. Now it's a clear
    error."""
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    with pytest.raises(Exception, match="cpu_offload_use_pin_memory"):
        DeepSpeedConfig({
            "train_batch_size": 8,
            "zero_optimization": {"stage": 1, "cpu_offload_use_pin_memory": True},
        })


def test_config_legacy_cpu_offload_routes():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    cfg = DeepSpeedConfig({
        "train_batch_size": 8,
        "zero_optimization": {
            "stage": 1,
            "cpu_offload": True,
            "cpu_offload_use_pin_memory": True,
            "cpu_offload_param": True,
        },
    })
    off = cfg.zero_config.offload_optimizer
    assert off is not None and str(off.device.value) == "cpu"
    assert off.pin_memory is True
    assert cfg.zero_config.offload_param is not None
    assert str(cfg.zero_config.offload_param.device.value) == "cpu"


def test_config_red_streamed_buffer_count_too_small(eight_devices):
    over = dict(STREAM)
    over["buffer_count"] = 1
    engine = _engine(True, **{"zero_optimization": {
        "stage": 1, "offload_optimizer": over}})
    with pytest.raises(ValueError, match="buffer_count"):
        engine.train_batch(batch=step_batch(batch_size=8, seed=0))


def test_config_red_streamed_partial_ratio(eight_devices):
    over = dict(STREAM)
    over["ratio"] = 0.5
    engine = _engine(True, **{"zero_optimization": {
        "stage": 1, "offload_optimizer": over}})
    with pytest.raises(ValueError, match="ratio"):
        engine.train_batch(batch=step_batch(batch_size=8, seed=0))
