"""Green sweep (ISSUE 3 acceptance): the real engine programs pass every
analysis pass clean — donation verified on each step flavor (fused gas=1,
fused-accum gas>1, unfused fwd_bwd+step, fp16 and bf16) and on the paged
serving programs; zero host transfers in any hot-loop program; zero f32
upcast-compute sites; collective schedule extracted with nonzero traffic on
the 8-device training mesh. Plus the ``analysis.verify`` knob contract:
``warn``/``raise`` run at first compile without breaking a clean engine,
and ``raise`` actually raises on a violating program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
import deepspeed_tpu.parallel.mesh as mesh_mod
from deepspeed_tpu.analysis import AnalysisError, run_program_passes
from deepspeed_tpu.profiling.compile_telemetry import CompileTelemetry
from tests.unit.simple_model import (
    SimpleModel,
    step_batch,
    train_steps_batch,
    train_steps_micro,
)


def _engine(**over):
    mesh_mod.reset_topology()
    base = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
    }
    base.update(over)
    engine, *_ = ds.initialize(model=SimpleModel(), config=base)
    return engine


def _assert_clean(report, expect_programs):
    assert set(expect_programs) <= set(report["programs"]), report["programs"].keys()
    t = report["totals"]
    assert t["analysis_failures"] == 0, report
    assert t["violations"] == 0, [
        v
        for e in report["programs"].values()
        for p in e.get("passes", {}).values()
        for v in p["violations"]
    ]
    assert t["donation_verified"] is True
    for name in expect_programs:
        passes = report["programs"][name]["passes"]
        assert passes["host_transfer"]["ok"]
        assert passes["dtype_promotion"]["ok"]
        assert passes["donation"]["ok"]


def test_green_fused_step_bf16(eight_devices):
    """gas=1 bf16: the fused forward+optimizer program verifies clean and
    its dp collective schedule is nonempty (grad reduction exists)."""
    engine = _engine()
    train_steps_batch(engine, step_batch(batch_size=8), 2)
    rep = engine.analysis_report()
    _assert_clean(rep, ["fused_step"])
    assert rep["totals"]["collective_count"] >= 1
    assert rep["totals"]["collective_bytes"] > 0


def test_green_fused_accum_step(eight_devices):
    """gas=4 fused scan program: donation of the full state tuple verified
    statically (what test_fused_grad_accum asserted via is_deleted)."""
    engine = _engine(
        gradient_accumulation_steps=4, compile={"fuse_grad_accum": True}
    )
    train_steps_batch(engine, step_batch(batch_size=32), 2)
    rep = engine.analysis_report()
    _assert_clean(rep, ["fused_accum_step"])
    don = rep["programs"]["fused_accum_step"]["passes"]["donation"]["summary"]
    assert don["declared_donations"] >= 4  # params+master+opt+scale_state leaves
    assert don.get("unhonored", 0) == 0


def test_green_unfused_fp16_step(eight_devices):
    """fp16 gas=2 per-microbatch protocol: fwd_bwd (accumulator donation)
    and the full-state step program both verify clean."""
    engine = _engine(
        gradient_accumulation_steps=2,
        bf16={"enabled": False},
        fp16={"enabled": True, "initial_scale_power": 4},
    )
    train_steps_micro(engine, step_batch(batch_size=16), 2)
    rep = engine.analysis_report()
    _assert_clean(rep, ["fwd_bwd", "step"])


def test_green_fp32_single_buffer_step(eight_devices):
    """fp32 (params IS master): the single-buffer donation contract."""
    engine = _engine(bf16={"enabled": False})
    train_steps_batch(engine, step_batch(batch_size=8), 1)
    rep = engine.analysis_report()
    _assert_clean(rep, ["fused_step"])


def test_green_paged_serving_programs():
    """The serving programs (the ragged step at its narrow decode width and
    at the mixed width that carries the prefill chunks) verify clean: donated
    page buffers aliased, no host callback, no upcast compute."""
    from deepspeed_tpu.inference.scheduler import PagedServer
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, max_seq_len=64, norm="rmsnorm", position="rope",
        activation="swiglu", use_bias=False, tie_embeddings=False,
        flash_attention=False, dtype="float32",
    )
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), toks)
    tel = CompileTelemetry()
    server = PagedServer(
        cfg, params, page_size=8, max_slots=4, prefill_chunk=8,
        attn_impl="xla", dtype=jnp.float32, telemetry=tel,
    )
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 128, (7,)).astype(np.int32) for _ in range(3)]
    server.serve(prompts, max_new_tokens=4)
    rep = run_program_passes(tel)
    names = set(rep["programs"])
    assert names == {"paged_ragged_r4_w1", "paged_ragged_r4_w8"}, names
    _assert_clean(rep, sorted(names))


def test_verify_warn_and_raise_clean_engine(eight_devices):
    """analysis.verify on a clean engine: first compile runs the passes
    (visible as extra traces, not extra counted compiles) and training
    proceeds normally under both modes."""
    for mode in ("warn", "raise"):
        engine = _engine(analysis={"verify": mode})
        losses = train_steps_batch(engine, step_batch(batch_size=8), 2)
        assert np.isfinite(losses).all()
        stats = engine.compile_stats()["fused_step"]
        assert stats["compiles"] == 1 and stats["dispatches"] == 2, stats


def test_verify_raise_trips_on_violation():
    """verify=raise must fail fast when a program violates a pass — driven
    through the same telemetry hook the engines install."""
    from deepspeed_tpu.analysis import raise_or_warn

    tel = CompileTelemetry()

    def on_compile(name):
        report = run_program_passes(tel, programs=[name], passes=["host_transfer"])
        raise_or_warn(report, "raise")

    tel.on_compile = on_compile

    def bad(x):
        return jax.pure_callback(
            lambda a: np.asarray(a) + 1.0, jax.ShapeDtypeStruct((4,), jnp.float32), x
        )

    f = tel.instrument("bad", bad)
    with pytest.raises(AnalysisError):
        f(jnp.ones((4,)))


def test_invalid_verify_mode_rejected():
    with pytest.raises(Exception):
        _engine(analysis={"verify": "everything"})
