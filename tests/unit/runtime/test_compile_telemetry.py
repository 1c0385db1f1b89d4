"""Compile telemetry + full-state donation.

Donation is verified through the analysis layer (the ``donation`` pass
checks every declared donated arg is aliased in the compiled module —
``engine.analysis_report()``), with ONE legacy functional cross-check kept:
the step consumes its input buffers, observed via ``is_deleted`` (if the
pass and the runtime ever disagree, the pass is wrong). The retrace guard
asserts ≤1 compile of the step programs across a 5-step loop via the
counters, and the ``invalidate_compiled_step`` test pins the
executable-release fix for the PERF.md mid-suite wedge.
"""

import os
import subprocess
import sys

import jax
import numpy as np

import deepspeed_tpu as ds
import deepspeed_tpu.parallel.mesh as mesh_mod
from deepspeed_tpu.profiling import use_compile_cache
from tests.unit.simple_model import SimpleModel, step_batch, train_steps_micro


def _cfg(**over):
    base = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
    }
    base.update(over)
    return base


def _engine(**over):
    mesh_mod.reset_topology()
    engine, *_ = ds.initialize(model=SimpleModel(), config=_cfg(**over))
    return engine


def test_step_consumes_donated_state(eight_devices):
    """LEGACY functional cross-check for the ``donation`` analysis pass:
    after an optimizer step, every pre-step state buffer (params, master,
    opt_state, grad_acc, scale_state) is deleted — XLA reused it in place
    instead of double-buffering the training state. Kept deliberately
    runtime-observed (is_deleted) so a bug in the static pass cannot
    silently blind both checks."""
    engine = _engine(gradient_accumulation_steps=2)
    batch = step_batch(batch_size=16)
    train_steps_micro(engine, batch, 1)  # init + first window
    old = {
        "params": jax.tree_util.tree_leaves(engine._params)[0],
        "master": jax.tree_util.tree_leaves(engine._master)[0],
        "opt_state": jax.tree_util.tree_leaves(engine._opt_state)[0],
        "grad_acc": jax.tree_util.tree_leaves(engine._grad_acc)[0],
        "scale": engine._scale_state.scale,
    }
    train_steps_micro(engine, batch, 1)
    for name, buf in old.items():
        assert buf.is_deleted(), f"{name} buffer survived the step (not donated)"


def test_fused_step_donation_verified_by_analysis(eight_devices):
    """The gas=1 fused forward+step program's donation contract, checked
    by the ``donation`` analysis pass (replaces the old is_deleted probe:
    the pass reads the compiled module's alias table instead of poking
    runtime buffer state)."""
    engine = _engine()
    train_steps_micro(engine, step_batch(batch_size=8), 1)
    rep = engine.analysis_report(programs=["fused_step"], passes=["donation"])
    don = rep["programs"]["fused_step"]["passes"]["donation"]
    assert don["ok"], don["violations"]
    assert don["summary"]["declared_donations"] >= 4  # params+master+opt+scale
    assert don["summary"].get("unhonored", 0) == 0
    assert rep["totals"]["donation_verified"] is True


def test_step_program_aliases_donated_inputs(eight_devices):
    """Structural check on the compiled unfused step, via the donation
    pass (replaces the hand-rolled lower().compile() + as_text() grep):
    every declared donated arg is aliased, zero bytes double-buffered."""
    engine = _engine(gradient_accumulation_steps=2)
    train_steps_micro(engine, step_batch(batch_size=16), 1)
    rep = engine.analysis_report(programs=["step"], passes=["donation"])
    don = rep["programs"]["step"]["passes"]["donation"]
    assert don["ok"], don["violations"]
    assert don["summary"]["declared_donated_bytes"] > 0
    assert don["summary"].get("double_buffered_bytes", 0) == 0


def test_retrace_guard_unfused_five_steps(eight_devices):
    """≤1 compile of each hot-loop program across a 5-step train loop: the
    step programs trace exactly once and every later dispatch is warm."""
    engine = _engine(gradient_accumulation_steps=2)
    train_steps_micro(engine, step_batch(batch_size=16), 5)
    stats = engine.compile_stats()
    assert stats["fwd_bwd"]["compiles"] == 1, stats
    assert stats["fwd_bwd"]["dispatches"] == 10, stats  # gas × steps
    assert stats["step"]["compiles"] == 1, stats
    assert stats["step"]["dispatches"] == 5, stats


def test_compile_stats_surface(eight_devices):
    """compile_stats() exposes every instrumented program with the counter
    fields the monitor consumes."""
    engine = _engine()
    train_steps_micro(engine, step_batch(batch_size=8), 1)
    stats = engine.compile_stats()
    assert {"fwd_bwd", "step", "fused_step", "eval_fwd"} <= set(stats)
    for rec in stats.values():
        assert {"traces", "compiles", "dispatches", "compile_seconds", "invalidations"} <= set(rec)
    totals = engine._telemetry.totals()
    assert totals["compiles"] >= 1 and totals["dispatches"] >= 1


def test_invalidate_releases_stale_executables(eight_devices):
    """invalidate_compiled_step must actually release the old executables
    (the PERF.md wedge: rebinding attributes left them alive in jit's
    cache), then rebuild working programs."""
    engine = _engine()  # gas=1 → fused_step is the hot program
    batch = step_batch(batch_size=8)
    train_steps_micro(engine, batch, 2)
    old = engine._jit_fused_step
    assert old.cache_size() >= 1
    engine.invalidate_compiled_step()
    assert engine._jit_fused_step is not old
    assert old.cache_size() == 0, "stale executable still cached after invalidate"
    stats = engine.compile_stats()["fused_step"]
    assert stats["invalidations"] >= 1
    # the rebuilt program works and its recompile is visible in the counters
    train_steps_micro(engine, batch, 1)
    stats = engine.compile_stats()["fused_step"]
    assert stats["compiles"] == 2 and stats["dispatches"] == 3, stats


def test_micro_batch_resize_bounded_executables(eight_devices):
    """The micro-batch resize loop that reproduced the mid-suite wedge:
    shape changes retrace (expected), and invalidate_compiled_step drops
    the accumulated executables so they cannot pile up."""
    engine = _engine()
    for micro, rows in ((1, 8), (2, 16), (1, 8), (2, 16)):
        engine.set_train_micro_batch_size(micro)
        train_steps_micro(engine, step_batch(batch_size=rows), 1)
    assert engine._jit_fused_step.cache_size() >= 2  # one executable per shape
    engine.invalidate_compiled_step()
    assert engine._jit_fused_step.cache_size() == 0


_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def test_compile_cache_yields_to_environment(monkeypatch, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, whoever runs the program
    places the cache: the helper sets nothing in code."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed_outside"))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path / "placed_outside")
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_path(monkeypatch):
    """Unset, the cache is <checkout>/.jax_cache: the same path on every
    call and in every process, or the next run never finds this one's
    programs."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expected = os.path.join(_REPO, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == expected
        assert use_compile_cache() == expected
        assert jax.config.jax_compilation_cache_dir == expected
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    child = subprocess.run(
        [
            sys.executable,
            "-c",
            "import jax\n"
            "from deepspeed_tpu.profiling import use_compile_cache\n"
            "use_compile_cache()\n"
            "print(jax.config.jax_compilation_cache_dir)",
        ],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=120, check=True,
    )
    assert child.stdout.strip().splitlines()[-1] == expected
def test_monitor_receives_compile_counters(eight_devices, tmp_path):
    """The monitor stream carries the compile counters (wired through
    _write_monitor)."""
    engine = _engine(
        steps_per_print=1,
        csv_monitor={"enabled": True, "output_path": str(tmp_path) + "/", "job_name": "t"},
    )
    train_steps_micro(engine, step_batch(batch_size=8), 1)
    import glob

    files = glob.glob(str(tmp_path) + "/t/*compile_count*.csv")
    assert files, "no compile_count csv written by the monitor"
    body = open(files[0]).read()
    assert body.strip(), body
