"""Benchmarks for the BASELINE target configs, one JSON line each.

Process architecture:

- The PARENT process never imports jax (nor the package, which does). A
  chip belongs to one process at a time, so no process touches it before
  the child that measures: each config runs in its own subprocess with a
  hard per-config timeout, one after the other.
- A full-size run measures the TPU and nothing else: a child that finds no
  TPU, or raises, exits non-zero, prints no metric line, and the parent
  exits non-zero with it. ``DS_BENCH_TINY=1`` shrinks every config to
  smoke-test the plumbing on the CPU; every record names the ``platform``,
  ``device_kind`` and ``device_count`` it ran on, and a CPU record carries
  ``null`` where a device peak would be needed.
- Every result line is printed the instant it exists AND appended to
  ``bench_partial.jsonl`` — a killed run still leaves everything it measured.
- Children place JAX's persistent compilation cache through
  ``use_compile_cache`` (``JAX_COMPILATION_CACHE_DIR`` if set, else
  ``.jax_cache/``), so a repeated config skips its XLA compile.
- A total wall-clock budget (DS_BENCH_BUDGET_S, default 22 min) gates each
  launch; a config that does not fit is reported on stderr and the run
  exits non-zero.

Printed order (the driver parses the LAST line as the headline; each metric
is emitted EXACTLY once — the headline is MEASURED first, while the budget
is freshest, but its line prints last):

  2. llama-style ZeRO-3 fused training    (config 2, sized to one chip's HBM)
  3. ZeRO-Infinity max trainable params   (config 3, layer-streamed offload)
  4. 32k-sequence training                (config 4, flash attention + remat)
  5. MoE inference vs dense               (config 5, expert dispatch overhead)
  6. Paged-KV continuous-batching serving (config 6, decode tokens/s/chip)
  6b. Tensor-parallel sharded serving     (config 6b, tokens/s/chip at tp∈{1,2,4},
                                           scaling efficiency + quantized comm bytes)
  7. Serving fleet under replica kill     (config 7, goodput vs single replica)
  1. GPT-2 125M ZeRO-1 training           (config 1, tokens/s/chip — headline, LAST)

``vs_baseline`` semantics per line: training configs report measured MFU
over the 0.40 north star (BASELINE.json; ``null`` on a CPU smoke run); the Infinity line reports trained
params over the ~1B in-HBM ceiling of this chip; the MoE line reports MoE
throughput over an active-param-matched dense model; the fleet line
reports 3-replica goodput UNDER a mid-trace replica kill over the
single-replica replay of the same trace (>1 = the fleet beats one replica
even while losing a member).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import traceback

import numpy as np

SEED = 0
NORTH_STAR_MFU = 0.40
# DS_BENCH_TINY=1: shrink every config so the whole bench smoke-tests on CPU
TINY = os.environ.get("DS_BENCH_TINY") == "1"
# Tiny mode (or an explicit JAX_PLATFORMS=cpu) means CPU-only: children get
# JAX_PLATFORMS=cpu, and the multi-device configs force virtual CPU devices.
CPU_ONLY = TINY or os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
REPO = os.path.dirname(os.path.abspath(__file__))

# Canonical metric name per config — single source of truth for the return
# dicts below.
METRICS = {
    "gpt2_zero1": "gpt2_125m_zero1_tokens_per_sec_per_chip",
    "llama_zero3": "llama_0p8b_zero3_tokens_per_sec_per_chip",
    "infinity": "zero_infinity_trainable_params_per_chip",
    "long_seq": "seq32k_flash_tokens_per_sec_per_chip",
    "moe_inference": "moe8x_top1_prefill_tokens_per_sec",
    "moe_train": "moe_ep_train_tokens_per_sec",
    "decode_serving": "decode_tokens_per_sec_per_chip",
    "decode_serving_tp": "tp_decode_tokens_per_sec_per_chip",
    "fleet_serving": "fleet_goodput_tokens_per_sec",
}


# Per-chip bf16 peak FLOP/s, keyed by ``device_kind`` exactly as JAX reports
# it. Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16). A
# device that is not in the table is an error, not a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def _peak_flops_bf16() -> float:
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"no bf16 peak on record for device_kind {kind!r}; add it to "
            "PEAK_BF16_FLOPS with its source"
        )
    return PEAK_BF16_FLOPS[kind]


def _block(engine):
    """Every queued step has finished once the params it produced are ready."""
    import jax

    jax.block_until_ready(engine.get_params())


def _train_engine(model, config):
    import deepspeed_tpu as ds
    import deepspeed_tpu.parallel.mesh as mesh_mod

    mesh_mod.reset_topology()
    engine, _, _, _ = ds.initialize(model=model, config=config, dist_init_required=False)
    return engine


def _compile_fields(engine):
    """Compile telemetry for the result record: total compiles + wall time,
    and the step program's dispatch count. Makes dispatch/recompile
    regressions visible in the BENCH files (a healthy steady-state run
    compiles each program once; the timed window adds zero compiles)."""
    try:
        stats = engine.compile_stats()
    except Exception:
        return {}
    step = (
        stats.get("fused_accum_step")
        or stats.get("fused_step")
        or stats.get("step")
        or {}
    )
    if not step:
        # inference serving engines: the steady-state program is the ragged
        # step (≤2 programs, one dispatch per scheduler step) — the fused
        # multi-step window when armed — or, on the bucketed oracle path,
        # the paged decode step per slot bucket
        paged = [rec for name, rec in sorted(stats.items())
                 if name.startswith(
                     ("paged_ragged_", "paged_multistep_", "paged_decode_"))]
        if paged:
            step = {"dispatches": sum(rec["dispatches"] for rec in paged)}
    return {
        "compiles": int(sum(rec["compiles"] for rec in stats.values())),
        "compile_s": round(sum(rec["compile_seconds"] for rec in stats.values()), 1),
        "step_dispatches": int(step.get("dispatches", 0)),
    }


def _analysis_fields(engine):
    """Static-analysis summary for the result record: the per-config comms
    budget (collective op count + per-device payload bytes, summed over the
    dispatched hot programs) and the donation-verified flag, derived from
    the compiled HLO by ``engine.analysis_report()``. BENCH_r*.json then
    tracks the communication schedule alongside throughput — a perf PR
    that silently adds an all-gather or drops a buffer alias shows up in
    the record even when the wall clock is too noisy to catch it. Runs
    after the timed window (it re-traces + re-compiles each program once)."""
    try:
        rep = engine.analysis_report(
            passes=["donation", "collectives", "host_transfer", "overlap"]
        )
        t = rep["totals"]
        return {
            "static_collective_ops": int(t.get("collective_count", 0)),
            "static_collective_bytes": int(t.get("collective_bytes", 0)),
            "donation_verified": bool(t.get("donation_verified", False)),
            "analysis_violations": int(t.get("violations", 0)),
            # comm/compute overlap verifier (ISSUE 5): True only when no
            # loop-body collective is exposed on the critical path; the byte
            # split says how much of the schedule's collective traffic has
            # real compute to hide behind vs how much is serialized.
            "overlap_verified": t.get("overlap_verified"),
            "hidden_collective_bytes": int(t.get("hidden_collective_bytes", 0)),
            "exposed_collective_bytes": int(t.get("exposed_collective_bytes", 0)),
        }
    except Exception as e:
        # never fail a bench record over analysis, but never vanish
        # silently either: the missing-fields case must be distinguishable
        # from "analysis ran clean" in the BENCH files
        traceback.print_exc()
        return {"analysis_error": f"{type(e).__name__}: {e}"[:200]}


def _memory_fields(engine):
    """Static HBM-ledger summary for the result record (ISSUE 18): the
    engine's whole-run per-chip residency peak (persistent buffers + the
    largest program's transient footprint, from ``memory_report`` with
    the per-program estimates folded in), the bytes sitting fully
    replicated across the mesh, and the ``analysis.hbm_budget_bytes``
    verdict (None when no budget is configured). On the CPU bench backend
    the estimator's temp bytes are a lower bound (see PERF.md). Runs after
    the timed window — folding the programs re-traces each one once."""
    try:
        led = engine.memory_report(include_programs=True, enforce=False)
        return {
            "peak_hbm_bytes_per_chip": int(led["peak_hbm_bytes_per_chip"]),
            "replicated_bytes": int(led["replicated_bytes"]),
            "hbm_budget_verified": led["hbm_budget_verified"],
        }
    except Exception as e:
        # same contract as _analysis_fields: never fail the record, never
        # vanish silently
        traceback.print_exc()
        return {"memory_error": f"{type(e).__name__}: {e}"[:200]}


def _trace_fields(engine, name, timed_window=None, overhead_reps=8):
    """Unified-tracing fields for a result record (ISSUE 10):

    - ``step_phase_ms`` — mean ms of the top-4 leaf phases by total time
      over a FRESH traced window of the measured configuration (the ring
      is cleared first: by this point it holds every comparison pass the
      config ran — spec-on, bucketed oracle, dense baseline warmup — and a
      breakdown labeled "the measured server" must not mix them in). The
      outer ``train.step``/``serve.step`` aggregates are excluded: this is
      the WHERE-did-the-step-go breakdown, not the step time again;
    - ``trace_overhead_pct`` — the same window re-run with the tracer
      disabled vs enabled ((t_on - t_off)/t_off; the fast tier pins the
      deterministic per-span bound under 2%, this is the in-situ
      wall-clock cross-check and rides informationally);
    - ``trace_file`` — a Perfetto/Chrome trace of that window's timeline,
      exported next to the other bench artifacts.

    Runs AFTER the headline timed window; the re-runs add no compiles
    (tracing is host-side only — the telemetry-free tests gate that
    globally)."""
    try:
        if timed_window is not None:
            # min-of-2 windows per arm: the signal is sub-percent, so one
            # noisy window would swamp it. The ring holds exactly these
            # traced windows afterwards — the phase snapshot below reads
            # the measured configuration only.
            engine.tracer.clear()
            t_on = min(timed_window(overhead_reps) for _ in range(2))
        phases = engine.tracer.phase_summary()
        # step-loop phases only: the outer step aggregates repeat the step
        # time, and the async writer's ckpt.stage/commit run OFF the step
        # loop (ckpt.d2h_stall is the step-loop piece and stays in; it
        # only appears when the window itself checkpoints — the record's
        # ckpt_stall_ms field carries the measured stall regardless)
        leaf = {
            k: v
            for k, v in phases.items()
            if k.split(".", 1)[0] in ("train", "serve", "eval", "timer", "comm", "fleet")
            and k not in ("train.step", "serve.step", "fleet.step")
            or k == "ckpt.d2h_stall"
        }
        top = sorted(leaf.items(), key=lambda kv: kv[1]["total_ms"], reverse=True)[:4]
        fields = {"step_phase_ms": {k: v["mean_ms"] for k, v in top}}
        trace_path = os.path.join(REPO, f"bench_trace_{name}.json")
        engine.observability_hub.export_chrome_trace(trace_path)
        fields["trace_file"] = os.path.basename(trace_path)
        if timed_window is not None:
            engine.tracer.enabled = False
            try:
                t_off = min(timed_window(overhead_reps) for _ in range(2))
            finally:
                engine.tracer.enabled = True
            if t_off > 0:
                fields["trace_overhead_pct"] = round((t_on - t_off) / t_off * 100, 3)
        return fields
    except Exception as e:
        traceback.print_exc()
        return {"trace_error": f"{type(e).__name__}: {e}"[:160]}


def _multistep_fields(engine_factory, batch, tokens_per_step, horizon=None):
    """Multi-step TRAINING window A/B (ISSUE 14), same-seed: two fresh
    engines from ``engine_factory(multi_step_on, horizon)`` — identical
    config seed, identical repeated batch, both driven through
    ``train_batch(data_iter)`` so the measured loops pay the same data/h2d
    structure — one with ``compile.multi_step`` armed, one without.

    Records the windowed tokens/s (``multistep_value``), the A/B ratio
    (``multistep_vs_singlestep``: the per-dispatch host cost, not measured
    on this chip, amortizes to 1/N),
    ``dispatches_per_opt_step`` from the engine's window stats (telemetry-
    derived: the tentpole's 1/N target), and the tracer phase deltas the
    windows exist to crush — data_fetch / h2d / dispatch / loss_fetch mean
    ms as ``[single_step, windowed]`` pairs (the windowed loss_fetch is
    the deferred ``train.loss_drain``). Runs AFTER the headline window on
    its own engines; the headline record's compile counters are untouched."""
    import itertools

    try:
        H = int(horizon or (4 if TINY else 8))

        def run(ms_on):
            engine = engine_factory(ms_on, H)
            it = itertools.repeat(batch)
            # warmup to a window boundary: 1 sequential init step (compiles
            # the single-step program) + one full window (compiles the
            # window program); the single-step arm just compiles + settles
            for _ in range(1 + (H if ms_on else 1)):
                engine.train_batch(data_iter=it)
            if ms_on:
                engine.flush_loss_drain()
            _block(engine)
            engine.tracer.clear()
            steps = 2 * H
            t0 = time.perf_counter()
            for _ in range(steps):
                engine.train_batch(data_iter=it)
            if ms_on:
                engine.flush_loss_drain()
            _block(engine)
            dt = time.perf_counter() - t0
            return engine, steps, dt, engine.tracer.phase_summary()

        seq_engine, steps, seq_dt, seq_ph = run(False)
        seq_tps = steps * tokens_per_step / seq_dt if seq_dt > 0 else 0.0
        win_engine, steps, win_dt, win_ph = run(True)
        win_tps = steps * tokens_per_step / win_dt if win_dt > 0 else 0.0
        ws = win_engine.window_stats()

        def mean_ms(ph, key):
            v = ph.get(key)
            return round(v["mean_ms"], 3) if v else 0.0

        return {
            "multistep_horizon": H,
            "multistep_value": round(win_tps, 1),
            "multistep_vs_singlestep": round(win_tps / seq_tps, 4) if seq_tps else 0.0,
            "dispatches_per_opt_step": round(ws["dispatches_per_opt_step"], 4),
            "train_window_steps": ws["window_steps"],
            "train_window_break_reasons": {
                k: v for k, v in ws["window_break_reasons"].items() if v
            },
            "multistep_phase_ms": {
                k: [mean_ms(seq_ph, k), mean_ms(win_ph, k)]
                for k in (
                    "train.data_fetch", "train.h2d", "train.dispatch",
                    "train.loss_fetch", "train.loss_drain",
                )
            },
        }
    except Exception as e:
        traceback.print_exc()
        return {"multistep_error": f"{type(e).__name__}: {e}"[:160]}


def _ckpt_fields(engine):
    """Fault-tolerance telemetry for a training record (ISSUE 9), measured
    AFTER the timed window on a scratch dir:

    - ``ckpt_stall_ms`` — how long ``save_checkpoint(asynchronous=True)``
      blocks the step loop. By construction that is ONLY the device→host
      snapshot (the staged atomic write + commit + latest update run on the
      background writer while subsequent steps dispatch), so the target is
      ~0 relative to the step time; the acceptance bar is ≤5% of it.
    - ``ckpt_save_s`` — the full background persist (stage → fsync →
      rename), i.e. what a SYNCHRONOUS save would have stalled.
    - ``ckpt_restore_s`` — ``load_checkpoint(auto_resume=True)`` wall time
      (scan + validate + restore of the full replay state).

    The async path is jit-free — the no-new-programs guarantee is enforced
    by compile telemetry in tests/unit/checkpoint/test_fault_tolerance.py —
    so these fields ride AFTER _compile_fields/_analysis_fields and do not
    disturb the record's compile counters."""
    import shutil
    import tempfile

    ckpt_dir = tempfile.mkdtemp(prefix="dsbench_ckpt_")
    try:
        t0 = time.perf_counter()
        engine.save_checkpoint(ckpt_dir, asynchronous=True)
        stall_ms = (time.perf_counter() - t0) * 1e3
        engine.wait_pending_checkpoint()
        save_s = engine.checkpoint_stats()["last_save_s"]
        t0 = time.perf_counter()
        engine.load_checkpoint(ckpt_dir, auto_resume=True)
        restore_s = time.perf_counter() - t0
        return {
            "ckpt_stall_ms": round(stall_ms, 2),
            "ckpt_save_s": round(save_s, 3),
            "ckpt_restore_s": round(restore_s, 3),
        }
    except Exception as e:
        traceback.print_exc()
        return {"ckpt_error": f"{type(e).__name__}: {e}"[:160]}
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def _timed_steps(engine, batch, warmup=3, steps=20):
    """Place the batch once (a real input pipeline prefetches to device;
    re-uploading identical tokens every step would measure the host link,
    not the chip), run warmup + timed steps, external wall clock."""
    placed = engine._place_batch(batch)
    for _ in range(warmup):
        loss = engine(placed)
        engine.backward(loss)
        engine.step()
    _block(engine)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine(placed)
        engine.backward(loss)
        engine.step()
    _block(engine)
    return time.perf_counter() - t0, loss


def _mfu_vs_north_star(tokens_per_sec, n_params, num_layers, hidden, seq):
    """Model FLOP/s utilization over the north star, or None on the CPU: a
    smoke run has no device peak to divide by."""
    from deepspeed_tpu.accelerator import on_tpu

    if not on_tpu():
        return None
    # 6N per token (fwd+bwd) + attention 12*L*H*T
    flops_per_token = 6 * n_params + 12 * num_layers * hidden * seq
    mfu = tokens_per_sec * flops_per_token / _peak_flops_bf16()
    return round(mfu / NORTH_STAR_MFU, 4)


def _max_params_under_budget(fits, lo, hi):
    """Largest rung index in [lo, hi] whose model still fits, by bisection.

    ``fits`` must be monotone (a bigger model never fits when a smaller one
    didn't) — true for the HBM-residency predicate: model bytes grow with
    the rung, the budget is fixed. Pure so the unit suite can pin the
    bisection against synthetic predicates; returns ``lo - 1`` when even
    the smallest rung doesn't fit."""
    if not fits(lo):
        return lo - 1
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if fits(mid):
            lo = mid
        else:
            hi = mid - 1
    return lo


def _live_device_bytes():
    """Resident device bytes right now: what an HBM would be holding. On
    the CPU test backend this is the accounting stand-in for real HBM
    occupancy (the probe compares offload-on vs off under the SAME
    measure, so the stand-in cancels out of the ratio)."""
    import jax

    return int(sum(int(getattr(a, "nbytes", 0)) for a in jax.live_arrays()))


def _offload_stream_fields(engine_factory, batch, steps=4):
    """Streamed host-offload stream timings for a result record: build the
    offload variant of the scenario's engine, take a few optimizer steps,
    and report per-step H2D / D2H issue time plus the EXPOSED time (waits
    the depth-2 pipeline failed to hide — the number the overlap gate
    pins to ~0). Never fails the parent record."""
    try:
        engine = engine_factory()
        for _ in range(steps):
            engine.train_batch(batch=batch)
        stats = engine.offload_stream_stats()
        if not stats or not stats.get("steps"):
            return {"offload_stream_error": "streamed offload path not active"}
        n = stats["steps"]
        return {
            "offload_stream_h2d_ms": round(stats["h2d_ms"] / n, 3),
            "offload_stream_d2h_ms": round(stats["d2h_ms"] / n, 3),
            "offload_stream_exposed_ms": round(stats["exposed_ms"] / n, 3),
        }
    except Exception as e:
        traceback.print_exc()
        return {"offload_stream_error": f"{type(e).__name__}: {e}"[:160]}


# ---------------------------------------------------------------------------
def bench_gpt2_zero1():
    """Config 1: GPT-2 125M ZeRO-1, tokens/s/chip (the headline)."""
    from deepspeed_tpu.models import TransformerLM, gpt2_config

    seq, micro = (128, 2) if TINY else (1024, 8)
    micro = int(os.environ.get("DS_BENCH_MICRO", micro))
    mcfg = gpt2_config("tiny" if TINY else "125m", max_seq_len=seq, remat=False)
    engine = _train_engine(
        TransformerLM(mcfg),
        {
            "train_micro_batch_size_per_gpu": micro,
            "optimizer": {"type": "adam", "params": {"lr": 3e-4, "weight_decay": 0.01}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 1},
            "gradient_clipping": 1.0,
            "steps_per_print": 10_000,
        },
    )
    n_chips = max(engine.data_parallel_world_size(), 1)
    rs = np.random.RandomState(SEED)
    toks = rs.randint(0, mcfg.vocab_size, (micro * n_chips, seq + 1)).astype(np.int32)
    batch = {"input_ids": toks[:, :-1], "labels": toks[:, 1:]}
    dt, _ = _timed_steps(engine, batch, warmup=3, steps=20)
    tps_chip = 20 * micro * n_chips * seq / dt / n_chips
    vs_north_star = _mfu_vs_north_star(tps_chip, engine.num_parameters(), mcfg.num_layers, mcfg.hidden_size, seq)
    rec = {
        "metric": METRICS["gpt2_zero1"],
        "value": round(tps_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": vs_north_star,
    }
    rec.update(_compile_fields(engine))
    rec.update(_analysis_fields(engine))
    rec.update(_memory_fields(engine))
    rec.update(_ckpt_fields(engine))
    rec.update(
        _trace_fields(
            engine, "gpt2_zero1",
            timed_window=lambda n: _timed_steps(engine, batch, warmup=0, steps=n)[0],
        )
    )

    def _ms_engine(ms_on, horizon):
        return _train_engine(
            TransformerLM(mcfg),
            {
                "train_micro_batch_size_per_gpu": micro,
                "optimizer": {"type": "adam", "params": {"lr": 3e-4, "weight_decay": 0.01}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 1},
                "gradient_clipping": 1.0,
                "steps_per_print": 10_000,
                "compile": {"multi_step": {"enable": ms_on, "horizon": horizon}},
            },
        )

    rec.update(_multistep_fields(_ms_engine, batch, micro * n_chips * seq))

    def _offload_engine():
        return _train_engine(
            TransformerLM(mcfg),
            {
                "train_micro_batch_size_per_gpu": micro,
                "optimizer": {"type": "adam", "params": {"lr": 3e-4, "weight_decay": 0.01}},
                "bf16": {"enabled": True},
                "zero_optimization": {
                    "stage": 1,
                    "offload_optimizer": {
                        "device": "cpu",
                        "pin_memory": True,
                        "pipeline_read": True,
                        "pipeline_write": True,
                    },
                },
                "gradient_clipping": 1.0,
                "steps_per_print": 10_000,
            },
        )

    rec.update(_offload_stream_fields(_offload_engine, batch))
    return rec


def bench_llama_zero3():
    """Config 2 (scaled to one chip's HBM): llama-architecture ~0.8B,
    ZeRO-3 + fused Adam, bf16, remat."""
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig

    seq, micro = (256, 1) if TINY else (2048, 1)
    mcfg = TransformerConfig(
        vocab_size=1024 if TINY else 32000,
        hidden_size=256 if TINY else 2048,
        num_layers=2 if TINY else 16,
        num_heads=16,
        num_kv_heads=4,
        max_seq_len=seq,
        norm="rmsnorm",
        position="rope",
        activation="swiglu",
        use_bias=False,
        tie_embeddings=False,
        remat=True,
    )
    engine = _train_engine(
        TransformerLM(mcfg),
        {
            "train_micro_batch_size_per_gpu": micro,
            "optimizer": {"type": "adam", "params": {"lr": 3e-4}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 3},
            "gradient_clipping": 1.0,
            "steps_per_print": 10_000,
        },
    )
    rs = np.random.RandomState(SEED)
    toks = rs.randint(0, mcfg.vocab_size, (micro, seq + 1)).astype(np.int32)
    batch = {"input_ids": toks[:, :-1], "labels": toks[:, 1:]}
    steps = 8
    dt, _ = _timed_steps(engine, batch, warmup=2, steps=steps)
    tps = steps * micro * seq / dt
    vs_north_star = _mfu_vs_north_star(tps, engine.num_parameters(), mcfg.num_layers, mcfg.hidden_size, seq)
    # remat recomputes the forward in the backward: the chip does ~8N useful
    # FLOPs/token but MFU counts the 6N model FLOPs (standard accounting)
    rec = {
        "metric": METRICS["llama_zero3"],
        "value": round(tps, 1),
        "unit": "tokens/s/chip",
        "steps": steps,
        "vs_baseline": vs_north_star,
    }
    rec.update(_compile_fields(engine))
    rec.update(_analysis_fields(engine))
    rec.update(_memory_fields(engine))
    rec.update(_ckpt_fields(engine))

    def _ms_engine(ms_on, horizon):
        return _train_engine(
            TransformerLM(mcfg),
            {
                "train_micro_batch_size_per_gpu": micro,
                "optimizer": {"type": "adam", "params": {"lr": 3e-4}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 3},
                "gradient_clipping": 1.0,
                "steps_per_print": 10_000,
                "compile": {"multi_step": {"enable": ms_on, "horizon": horizon}},
            },
        )

    rec.update(
        _multistep_fields(
            _ms_engine, batch, micro * seq,
            horizon=4 if TINY else 8,
        )
    )

    def _offload_engine():
        return _train_engine(
            TransformerLM(mcfg),
            {
                "train_micro_batch_size_per_gpu": micro,
                "optimizer": {"type": "adam", "params": {"lr": 3e-4}},
                "bf16": {"enabled": True},
                "zero_optimization": {
                    "stage": 3,
                    "offload_optimizer": {
                        "device": "cpu",
                        "pin_memory": True,
                        "pipeline_read": True,
                        "pipeline_write": True,
                    },
                },
                "gradient_clipping": 1.0,
                "steps_per_print": 10_000,
            },
        )

    rec.update(_offload_stream_fields(_offload_engine, batch, steps=3))
    return rec


def bench_infinity_max_params():
    """Config 3: ZeRO-Infinity optimizer-state offload — the trainable-
    params ceiling probe. A ladder of transformer sizes is bisected twice
    under the SAME device-byte budget: once with the fp32 master +
    moments resident on device (offload off), once with them streamed
    from host DRAM (offload on). Value = largest param count that still
    trains offload-ON; vs_baseline = multiple of the offload-OFF ceiling
    (the headroom the host offload buys — Adam states are 12 bytes/param
    of the ~18 the on-device path keeps resident, so ~3x is the
    theoretical ceiling on this measure)."""
    import gc

    import jax

    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig

    seq, micro = (64, 1) if TINY else (256, 1)
    hidden = 128 if TINY else 512
    ladder = [2, 4, 8, 12, 16, 24, 32]  # num_layers rungs, sizes ascending

    def _mcfg(layers):
        return TransformerConfig(
            vocab_size=512 if TINY else 8192,
            hidden_size=hidden,
            num_layers=layers,
            num_heads=4,
            max_seq_len=seq,
            norm="rmsnorm",
            position="rope",
            activation="swiglu",
            use_bias=False,
            tie_embeddings=True,
            remat=False,
            dtype="bfloat16",
        )

    rs = np.random.RandomState(SEED)
    toks = rs.randint(0, 512 if TINY else 8192, (micro, seq + 1)).astype(np.int32)
    batch = {"input_ids": toks[:, :-1], "labels": toks[:, 1:]}

    def _probe(layers, offload):
        """(trained ok, resident device bytes, n_params, stream stats)."""
        zero = {"stage": 1}
        if offload:
            zero["offload_optimizer"] = {
                "device": "cpu",
                "pin_memory": True,
                "pipeline_read": True,
                "pipeline_write": True,
                # several buckets per model: the resident transient is one
                # bucket deep, not the whole Adam state
                "bucket_size": 500_000 if TINY else 2_000_000,
            }
        gc.collect()
        base = _live_device_bytes()
        engine = _train_engine(
            TransformerLM(_mcfg(layers)),
            {
                "train_micro_batch_size_per_gpu": micro,
                "optimizer": {"type": "adam", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True},
                "zero_optimization": zero,
                "steps_per_print": 10_000,
            },
        )
        try:
            loss = float(engine.train_batch(batch=batch))
            ok = np.isfinite(loss)
            if engine._host_offload is not None:
                # land the in-flight D2H writes: a kept-pending write pins
                # its device bucket, which is stream state, not residency
                engine._host_offload.drain_writes()
            used = _live_device_bytes() - base
            return ok, used, int(engine.num_parameters()), engine.offload_stream_stats()
        finally:
            del engine
            jax.clear_caches()
            gc.collect()

    # the budget is synthetic on the CPU test backend (no real HBM wall):
    # sized so the MIDDLE rung just fits with Adam state resident — both
    # probes then bisect against the same wall, and the record reports how
    # much further the streamed-offload run climbs
    _, mid_bytes, _, _ = _probe(ladder[2], offload=False)
    budget = int(mid_bytes * 1.05)

    t0 = time.perf_counter()
    results = {}
    stream_stats = {}

    def _fits(offload):
        def fits(idx):
            ok, used, n_params, stats = _probe(ladder[idx], offload)
            fit = ok and used <= budget
            if fit:
                results[(offload, idx)] = n_params
                if stats:
                    stream_stats.update(stats)
            return fit

        return fits

    top_off = _max_params_under_budget(_fits(False), 0, len(ladder) - 1)
    top_on = _max_params_under_budget(_fits(True), 0, len(ladder) - 1)
    probe_s = time.perf_counter() - t0
    params_off = results.get((False, top_off), 0)
    params_on = results.get((True, top_on), 0)
    assert params_on > 0, "offload-on probe fit nothing under the budget"
    assert params_on > params_off, (
        f"host offload bought no headroom: on={params_on} off={params_off}"
    )
    rec = {
        "metric": METRICS["infinity"],
        "value": int(params_on),
        "unit": f"params (bisection, {probe_s:.0f}s)",
        "vs_baseline": round(params_on / max(params_off, 1), 2),
        "offload_off_params": int(params_off),
        "budget_bytes": budget,
        "ladder_layers": [ladder[max(top_off, 0)], ladder[max(top_on, 0)]],
    }
    n = stream_stats.get("steps") or 1
    rec.update(
        {
            "offload_stream_h2d_ms": round(stream_stats.get("h2d_ms", 0.0) / n, 3),
            "offload_stream_d2h_ms": round(stream_stats.get("d2h_ms", 0.0) / n, 3),
            "offload_stream_exposed_ms": round(stream_stats.get("exposed_ms", 0.0) / n, 3),
        }
    )
    return rec


def bench_long_seq():
    """Config 4: long sequences. Full-size: 32k tokens via the Pallas flash
    kernel + remat on one chip. TINY: the 2k config instead trains through
    ``sequence/layer.py``'s Ulysses attention on a ``sequence=2`` mesh, so
    the recorded collectives budget carries the head-scatter/seq-gather
    all-to-alls (``ulysses_a2a_bytes`` — previously this bench ran single
    chip and the a2a metric read 0; full-size sequence-parallel training
    stays future work)."""
    ulysses = bool(TINY)
    if ulysses and "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""
    ):
        # the sequence axis needs a real mesh in this child (same pattern
        # as the tp serving arm)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        )
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig

    seq, micro = (2048, 1) if TINY else (32768, 1)
    mcfg = TransformerConfig(
        vocab_size=1024 if TINY else 32000,
        hidden_size=128 if TINY else 1024,
        num_layers=2 if TINY else 8,
        num_heads=2 if TINY else 8,
        max_seq_len=seq,
        norm="rmsnorm",
        position="rope",
        activation="swiglu",
        use_bias=False,
        tie_embeddings=True,
        # Ulysses: the a2a exchange owns the head/seq reshard; the
        # interpret-mode flash kernel can't run under it on CPU
        remat=not ulysses,
        flash_attention=not ulysses,
        sequence_parallel=ulysses,
        sequence_parallel_mode="ulysses",
    )
    config = {
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "adam", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "steps_per_print": 10_000,
    }
    if ulysses:
        config["mesh"] = {"sequence": 2, "data": 2}
    engine = _train_engine(TransformerLM(mcfg), config)
    dp = engine.data_parallel_world_size()
    rs = np.random.RandomState(SEED)
    toks = rs.randint(0, mcfg.vocab_size, (micro * dp, seq + 1)).astype(np.int32)
    batch = {"input_ids": toks[:, :-1], "labels": toks[:, 1:]}
    steps = 5
    dt, _ = _timed_steps(engine, batch, warmup=2, steps=steps)
    tps = steps * micro * dp * seq / dt
    vs_north_star = _mfu_vs_north_star(tps, engine.num_parameters(), mcfg.num_layers, mcfg.hidden_size, seq)
    rec = {
        "metric": METRICS["long_seq"],
        "value": round(tps, 1),
        "unit": "tokens/s/chip",
        "steps": steps,
        "vs_baseline": vs_north_star,
    }
    rec.update(_compile_fields(engine))
    rec.update(_analysis_fields(engine))
    if ulysses:
        a2a = _a2a_wire_summary(engine)
        rec["sequence_parallel"] = "ulysses"
        rec["ulysses_a2a_bytes"] = int(a2a["bytes"]) if a2a else 0
    return rec


def bench_moe_inference():
    """Config 5 (one chip): MoE prefill throughput vs a dense model with the
    same ACTIVE parameters — vs_baseline ≥ ~1 means the expert dispatch
    (gate + capacity einsums) adds no material overhead."""
    import jax

    import deepspeed_tpu as ds
    import deepspeed_tpu.parallel.mesh as mesh_mod
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig
    from deepspeed_tpu.models.moe_transformer import MoETransformerConfig, MoETransformerLM

    seq, B = (128, 2) if TINY else (1024, 8)
    base = dict(
        vocab_size=1024 if TINY else 32000,
        hidden_size=128 if TINY else 1024,
        num_layers=2 if TINY else 8,
        num_heads=2 if TINY else 8,
        max_seq_len=seq,
        norm="rmsnorm",
        position="rope",
        activation="swiglu",
        use_bias=False,
        tie_embeddings=True,
    )
    rs = np.random.RandomState(SEED)
    toks = rs.randint(0, base["vocab_size"], (B, seq)).astype(np.int32)

    def prefill_tps(model):
        mesh_mod.reset_topology()
        engine = ds.init_inference(model, dtype="bf16")
        engine.init_params(toks)
        out = engine(toks)
        jax.device_get(np.asarray(out[0, -1, :8]))  # compile + drain
        t0 = time.perf_counter()
        reps = 5
        for _ in range(reps):
            out = engine(toks)
        jax.device_get(np.asarray(out[0, -1, :8]))
        return reps * B * seq / (time.perf_counter() - t0), engine

    moe_tps, moe_engine = prefill_tps(
        MoETransformerLM(MoETransformerConfig(num_experts=8, moe_top_k=1, **base))
    )
    # the full structural snapshot from the MoE engine (the measured
    # object), before the dense baseline rebuilds the topology: compile
    # telemetry, the comms/donation/overlap budget, and the HBM ledger
    # (expert shards land in peak_hbm_bytes_per_chip via the PR-18
    # estimator)
    moe_fields = {}
    moe_fields.update(_compile_fields(moe_engine))
    moe_fields.update(_analysis_fields(moe_engine))
    moe_fields.update(_memory_fields(moe_engine))
    dense_tps, _ = prefill_tps(TransformerLM(TransformerConfig(**base)))
    rec = {
        "metric": METRICS["moe_inference"],
        "value": round(moe_tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(moe_tps / dense_tps, 4),
    }
    rec.update(moe_fields)
    return rec


def _a2a_wire_summary(engine):
    """The collectives-pass ``all-to-all`` pricing for the engine's step
    program: ``{count, bytes, wire_bytes, quantized{...}}`` or None when the
    schedule has no a2a (the analysis never fails the bench record)."""
    try:
        rep = engine.analysis_report(passes=["collectives"])
        for prog in rep["programs"].values():
            coll = prog.get("passes", {}).get("collectives")
            if not coll:
                continue
            a2a = coll.get("summary", {}).get("ops", {}).get("all-to-all")
            if a2a:
                return a2a
    except Exception:
        traceback.print_exc()
    return None


def bench_moe_train():
    """Config 5b (data×expert mesh): expert-parallel MoE training — the
    shard_map fast path with explicit dispatch/combine all-to-alls (ISSUE
    20). ``value`` is trained tokens/s on the fp-wire arm; the int8 arm
    re-prices the same schedule with the EQuARX-style wire format and
    ``vs_baseline`` is its fp-equivalent-over-wire byte ratio (4.0 when
    every a2a payload quantizes cleanly — the pure fp32/int8 dtype ratio).
    ``overlap_verified`` rides the standard analysis block: every dispatch/
    combine a2a must hide behind the PR-MoE residual / next-layer gating
    compute (exposed loop-collective bytes == 0 — the
    ``test_green_moe_programs`` training gate, recorded here per round)."""
    # the expert axis needs a real mesh: force the 8-device CPU host mesh
    # before this child initializes its backend (same pattern as the tp
    # serving arm)
    if CPU_ONLY and "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""
    ):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        )
    import jax

    from deepspeed_tpu.models.moe_transformer import MoETransformerConfig, MoETransformerLM

    n = len(jax.devices())
    if n < 4 or n % 2:
        raise RuntimeError(f"moe_train: expert mesh needs >=4 even devices, have {n}")
    mesh = {"data": n // 2, "expert": 2}
    seq, micro = (32, 8) if TINY or CPU_ONLY else (512, 8)

    def build(quantized):
        # mirrors the gate-green config: PR-MoE residual gives the overlap
        # pass real compute to hide the exchanges behind; fp32 keeps the
        # int8-vs-fp wire ratio an exact dtype ratio; flash/remat off is
        # the repo's CPU multi-device convention
        cfg = MoETransformerConfig(
            vocab_size=1024 if TINY or CPU_ONLY else 32000,
            hidden_size=128 if TINY or CPU_ONLY else 1024,
            num_layers=2 if TINY or CPU_ONLY else 8,
            num_heads=2 if TINY or CPU_ONLY else 8,
            max_seq_len=seq, norm="rmsnorm", position="rope",
            activation="swiglu", use_bias=False, tie_embeddings=True,
            num_experts=4 if TINY or CPU_ONLY else 8, moe_top_k=1,
            scan_layers=True, use_residual=True, dtype="float32",
            flash_attention=False, remat=False, moe_quantized_a2a=quantized,
        )
        engine = _train_engine(
            MoETransformerLM(cfg),
            {
                "train_micro_batch_size_per_gpu": micro,
                "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3, "overlap_comm": True},
                "mesh": mesh,
                "steps_per_print": 10_000,
            },
        )
        return cfg, engine

    mcfg, engine = build(quantized=False)
    rs = np.random.RandomState(SEED)
    toks = rs.randint(0, mcfg.vocab_size, (micro, seq + 1)).astype(np.int32)
    batch = {"input_ids": toks[:, :-1], "labels": toks[:, 1:]}
    steps = 5 if TINY or CPU_ONLY else 20
    dt, _ = _timed_steps(engine, batch, warmup=2, steps=steps)
    tps = steps * micro * seq / dt
    rec = {
        "metric": METRICS["moe_train"],
        "value": round(tps, 1),
        "unit": "tokens/s",
        "steps": steps,
        "mesh": mesh,
    }
    rec.update(_compile_fields(engine))
    rec.update(_analysis_fields(engine))
    rec.update(_memory_fields(engine))
    fp_a2a = _a2a_wire_summary(engine)
    rec["a2a_wire_bytes_fp"] = int(fp_a2a["wire_bytes"]) if fp_a2a else 0

    # int8 wire arm: same schedule, quantized dispatch/combine payloads —
    # priced statically by the collectives pass (one engine, one step)
    _qcfg, q_engine = build(quantized=True)
    q_engine.train_batch(batch=batch)
    q_a2a = _a2a_wire_summary(q_engine)
    quant = (q_a2a or {}).get("quantized") or {}
    rec["a2a_wire_bytes_int8"] = int(quant.get("wire_bytes", 0))
    fp_equiv = int(quant.get("fp_equiv_wire_bytes", 0))
    reduction = (
        round(fp_equiv / quant["wire_bytes"], 4) if quant.get("wire_bytes") else 0
    )
    rec["a2a_wire_reduction"] = reduction
    rec["vs_baseline"] = reduction
    return rec


def bench_decode_serving():
    """Config 6 (one chip): continuous-batching serving over the paged KV
    pool (``engine.serve()``) — generated tokens/s/chip on a ragged request
    mix, speculation OFF (``value``) and ON (``spec_on_value`` +
    ``spec_accept_rate``: n-gram drafting, one verify dispatch per round).
    The measured path is the RAGGED one-program dispatch (the default):
    mixed prefill+decode rows share every step, ``compiled_programs``
    (≤ 2 expected) and ``cold_start_compile_s`` record the collapsed
    compile matrix, and ``bucketed_value`` / ``ragged_vs_bucketed`` replay
    the same mixed traffic through the bucketed per-shape oracle for
    comparison. ``vs_baseline`` = paged serving throughput over the dense
    lockstep ``generate`` on the same prompts padded to one max-budget
    batch (≥ ~1 means request-level batching serves ragged traffic at
    least as fast as the fixed-shape batch that can't retire rows early);
    ``spec_vs_off`` = spec-on over spec-off (the drafter is model-free, so
    the ratio tracks how much repetitive structure the mix exposes ×
    acceptance — see PERF.md round 9 for the expected-speedup math)."""
    import time as _time

    import jax.numpy as jnp

    import deepspeed_tpu as ds
    import deepspeed_tpu.parallel.mesh as mesh_mod
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig

    if TINY:
        n_req, prompt_len, max_new = 6, 12, 24
        mcfg = TransformerConfig(
            vocab_size=1024, hidden_size=128, num_layers=2, num_heads=4,
            num_kv_heads=2, max_seq_len=128, norm="rmsnorm", position="rope",
            activation="swiglu", use_bias=False, tie_embeddings=False,
            flash_attention=False,
        )
        paged = {"page_size": 8, "max_slots": 4, "prefill_chunk": 8}
    else:
        n_req, prompt_len, max_new = 16, 128, 128
        mcfg = TransformerConfig(
            vocab_size=32000, hidden_size=1024, num_layers=8, num_heads=16,
            num_kv_heads=4, max_seq_len=1024, norm="rmsnorm", position="rope",
            activation="swiglu", use_bias=False, tie_embeddings=False,
        )
        paged = {"page_size": 64, "max_slots": 8, "prefill_chunk": 128}

    mesh_mod.reset_topology()
    engine = ds.init_inference(TransformerLM(mcfg), dtype="bf16", paged_kv=paged)
    rs = np.random.RandomState(SEED)
    # half of each prompt is a tiled motif: serving traffic (code, templated
    # text) has repetitive spans the n-gram drafter can exploit; the random
    # half keeps the prefix from being a degenerate single pattern
    def _prompt():
        m = max(2, prompt_len // 32)  # short enough to repeat in the tail
        motif = rs.randint(0, mcfg.vocab_size, (m,)).astype(np.int32)
        head = rs.randint(0, mcfg.vocab_size, (prompt_len // 2,)).astype(np.int32)
        tail = np.tile(motif, -(-(prompt_len - head.size) // m))[: prompt_len - head.size]
        return np.concatenate([head, tail])

    prompts = [_prompt() for _ in range(n_req)]
    toks = np.stack(prompts)
    engine.init_params(toks)
    # ragged budgets: early finishers make room for admissions mid-stream
    budgets = [max(1, max_new - (i * max_new) // (2 * n_req)) for i in range(n_req)]

    def timed_serve():
        t0 = _time.perf_counter()
        outs = engine.serve(prompts, max_new_tokens=budgets)
        gen = sum(len(o) - prompt_len for o in outs)
        return gen / (_time.perf_counter() - t0)

    timed_serve()  # cold start: compiles the (≤2) ragged serving programs
    # the collapsed compile matrix, measured at the cold boundary: program
    # count and the wall time the first serve spent compiling
    from deepspeed_tpu.inference.scheduler import compiled_serving_programs

    cold_stats = engine.compile_stats()
    compiled_programs = compiled_serving_programs(cold_stats)
    cold_start_compile_s = sum(
        rec["compile_seconds"] for name, rec in cold_stats.items()
        if name.startswith("paged_")
    )
    paged_tps = timed_serve()
    # serving SLOs + prefix-cache effectiveness of the measured (spec-off)
    # server: p50/p99 TTFT (submit -> first token, queue wait included) and
    # TPOT from serve_stats(), plus the pool's prefix hit rate — the warm
    # pass re-serves the same prompts, so shared full pages attach instead
    # of re-prefilling (the production shared-system-prompt pattern)
    base_stats = engine.serve_stats()
    # speculation ON through the same engine/telemetry: the server is
    # rebuilt from the flipped knob, verify programs compile once, and the
    # second pass is the measured one
    engine._config.spec_decode.enable = True
    engine._paged_server = None
    timed_serve()  # compile every (bucket, K) verify program
    pre = dict(engine._paged_server.stats)  # counters cover the warm-up too
    spec_tps = timed_serve()
    post = engine._paged_server.stats
    rounds = post["spec_rounds"] - pre["spec_rounds"]
    drafted = post["spec_drafted"] - pre["spec_drafted"]
    accepted = post["spec_accepted"] - pre["spec_accepted"]
    spec_stats = {  # deltas of the MEASURED pass only
        "spec_rounds": rounds,
        "spec_accept_rate": accepted / drafted if drafted else 0.0,
        "spec_mean_accepted_per_round": accepted / rounds if rounds else 0.0,
    }
    engine._config.spec_decode.enable = False
    engine._paged_server = None
    # multi-step windows through the same engine (ISSUE 11): N decode
    # rounds fuse into one dispatch whenever the running set is stable, so
    # the host gap/packing/journal amortize to 1/N — the A/B runs the SAME
    # trace with windows armed, and dispatches_per_token is measured from
    # the scheduler's own dispatch/token counters over the measured pass
    engine._config.paged_kv.multi_step.enable = True
    timed_serve()  # compile the window program (one per armed horizon)
    ms_srv = engine._paged_server
    # every reported window field is a MEASURED-pass delta (the warm-up
    # pass forms windows too — lifetime totals would overstate them
    # relative to the dispatches_per_token they explain)
    ms_pre = {
        k: ms_srv.stats[k]
        for k in ("dispatches", "emitted_tokens", "window_steps")
    }
    ms_breaks_pre = dict(ms_srv.stats["window_break_reasons"])
    ms_tps = timed_serve()
    ms_disp = ms_srv.stats["dispatches"] - ms_pre["dispatches"]
    ms_toks = ms_srv.stats["emitted_tokens"] - ms_pre["emitted_tokens"]
    ms_stats = {
        "multistep_horizon": int(engine._config.paged_kv.multi_step.horizon),
        "window_steps": int(ms_srv.stats["window_steps"] - ms_pre["window_steps"]),
        "dispatches_per_token": round(ms_disp / ms_toks, 4) if ms_toks else 0.0,
        "window_break_reasons": {
            k: int(v - ms_breaks_pre[k])
            for k, v in ms_srv.stats["window_break_reasons"].items()
        },
    }
    engine._config.paged_kv.multi_step.enable = False
    engine._paged_server = None
    # the same mixed prefill+decode traffic through the bucketed per-shape
    # oracle (slot-bucket × chunk programs, prefill steps stealing from
    # decode): the ragged_vs_bucketed ratio is the headline of ISSUE 8
    engine._config.paged_kv.ragged = False
    engine._paged_server = None
    timed_serve()  # compile the bucketed program matrix
    bucketed_tps = timed_serve()
    engine._config.paged_kv.ragged = True
    engine._paged_server = None
    # snapshot AFTER the bucketed comparison and BEFORE the dense baseline
    # runs: the record's compile/analysis fields describe every paged
    # serving program (ragged + the multi-step window + the bucketed
    # comparison set), not kv_decode_loop
    compile_fields = _compile_fields(engine)
    compile_fields.update(_analysis_fields(engine))
    compile_fields.update(_memory_fields(engine))
    # unified-tracing fields for the measured (ragged, spec-off) server:
    # phase breakdown + overhead A/B + the Perfetto trace artifact. The
    # timed window returns seconds-per-token (1/tps), so the on/off ratio
    # is the wall-clock overhead of tracing the serving loop.
    compile_fields.update(
        _trace_fields(engine, "decode_serving",
                      timed_window=lambda n: 1.0 / timed_serve())
    )

    def timed_dense():
        t0 = _time.perf_counter()
        out = engine.generate(jnp.asarray(toks), max_new_tokens=max_new)
        np.asarray(out[..., -1:])  # drain
        return n_req * max_new / (_time.perf_counter() - t0)

    timed_dense()  # compile
    dense_tps = timed_dense()
    ttft = base_stats.get("ttft_ms", {})
    tpot = base_stats.get("tpot_ms", {})
    prefix = base_stats.get("prefix", {})
    rec = {
        "metric": METRICS["decode_serving"],
        "value": round(paged_tps, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(paged_tps / dense_tps, 4),
        # the ragged one-program dispatch (ISSUE 8): collapsed compile
        # matrix + the same mixed traffic through the bucketed oracle
        "compiled_programs": int(compiled_programs),
        "cold_start_compile_s": round(cold_start_compile_s, 3),
        "bucketed_value": round(bucketed_tps, 1),
        "ragged_vs_bucketed": round(paged_tps / bucketed_tps, 4),
        # serving SLO percentiles (TTFT includes queue wait; the headline
        # for serving is latency distribution, not aggregate tokens/s —
        # arXiv 2605.25645's TTFT/TPOT framing)
        "ttft_p50_ms": round(ttft.get("p50", 0.0), 2),
        "ttft_p99_ms": round(ttft.get("p99", 0.0), 2),
        "tpot_p50_ms": round(tpot.get("p50", 0.0), 3),
        "tpot_p99_ms": round(tpot.get("p99", 0.0), 3),
        # prefix caching: fraction of looked-up prompt tokens attached from
        # the page index instead of re-prefilled, + CoW divergence copies
        "prefix_hit_rate": round(prefix.get("prefix_hit_rate", 0.0), 4),
        "prefix_cow_copies": int(prefix.get("cow_copies", 0)),
        # multi-step windows (ISSUE 11): same trace with N-round fused
        # dispatches armed — dispatches_per_token is the amortization the
        # tentpole buys (steady state → 1/horizon), multistep_vs_singlestep
        # the wall-clock win (≈ 1 + host_gap_fraction × (1 − 1/N); the
        # per-dispatch host cost is not measured on this chip)
        "multistep_value": round(ms_tps, 1),
        "multistep_vs_singlestep": round(ms_tps / paged_tps, 4),
        **ms_stats,
        # speculative serving: same metric with n-gram draft-and-verify on
        "spec_on_value": round(spec_tps, 1),
        "spec_vs_off": round(spec_tps / paged_tps, 4),
        "spec_accept_rate": round(spec_stats.get("spec_accept_rate", 0.0), 4),
        "spec_rounds": spec_stats.get("spec_rounds", 0),
        "spec_mean_accepted_per_round": round(
            spec_stats.get("spec_mean_accepted_per_round", 0.0), 3
        ),
    }
    rec.update(compile_fields)
    return rec


def bench_decode_serving_tp():
    """Config 6b (multi-chip): tensor-parallel sharded serving (ISSUE 13)
    — the same ragged continuous-batching trace served at tp ∈ {1, 2, 4}
    with the weights column/row-parallel and the paged KV pool sharded
    over the kv-head axis. ``value`` is generated tokens/s **per chip** at
    the widest tp arm (the number that must stay ~flat for linear
    scaling); ``scaling_efficiency`` is (tokens/s/chip at tp) over the
    tp=1 throughput per arm. On a CPU host every "chip" is a forced host
    device, so absolute numbers are smoke-scale and the per-chip ratio is
    dominated by the emulation — the structural fields
    (``compiled_programs`` ≤ 2 on the mesh, ``quantized_comm`` wire-byte
    accounting = fp/4) are the portable signal. ``quantized_value``
    re-serves the widest arm with the EQuARX int8 all-reduce armed."""
    # multi-device CPU smoke: the forced host-device count must land
    # before this child process first initializes its backend
    if CPU_ONLY and "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""
    ):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        )
    import time as _time

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.analysis import run_program_passes
    from deepspeed_tpu.inference.scheduler import PagedServer, compiled_serving_programs
    from deepspeed_tpu.inference.tp import TPServing, serving_mesh
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig
    from deepspeed_tpu.profiling.compile_telemetry import CompileTelemetry

    if TINY:
        n_req, prompt_len, max_new = 6, 12, 24
        mcfg = TransformerConfig(
            vocab_size=1024, hidden_size=128, num_layers=2, num_heads=8,
            num_kv_heads=4, max_seq_len=128, norm="rmsnorm", position="rope",
            activation="swiglu", use_bias=False, tie_embeddings=False,
            flash_attention=False, dtype="float32",
        )
        paged = {"page_size": 8, "max_slots": 4, "prefill_chunk": 8}
    else:
        n_req, prompt_len, max_new = 16, 128, 128
        mcfg = TransformerConfig(
            vocab_size=32000, hidden_size=1024, num_layers=8, num_heads=16,
            num_kv_heads=4, max_seq_len=1024, norm="rmsnorm", position="rope",
            activation="swiglu", use_bias=False, tie_embeddings=False,
        )
        paged = {"page_size": 64, "max_slots": 8, "prefill_chunk": 128}

    n_dev = len(jax.devices())
    arms = [t for t in (1, 2, 4) if t <= n_dev and mcfg.num_kv_heads % t == 0]
    dtype = jnp.float32 if TINY else jnp.bfloat16
    model = TransformerLM(mcfg)
    rs = np.random.RandomState(SEED)
    prompts = [
        rs.randint(0, mcfg.vocab_size, (prompt_len,)).astype(np.int32)
        for _ in range(n_req)
    ]
    params = model.init(
        jax.random.PRNGKey(SEED), np.stack(prompts)[:1]
    )
    budgets = [max(1, max_new - (i * max_new) // (2 * n_req)) for i in range(n_req)]

    def timed_serve(server):
        t0 = _time.perf_counter()
        outs = server.serve(prompts, max_new_tokens=budgets)
        gen = sum(len(o) - prompt_len for o in outs)
        return gen / (_time.perf_counter() - t0)

    def build(tp_degree, quantized=False):
        tel = CompileTelemetry()
        tp = (
            None
            if tp_degree == 1
            else TPServing(mesh=serving_mesh(tp_degree), quantized_allreduce=quantized)
        )
        server = PagedServer(
            mcfg, params, attn_impl="xla" if CPU_ONLY else "auto",
            dtype=dtype, telemetry=tel, tp=tp, **paged,
        )
        return tel, server

    arm_tps = {}
    compiled = {}
    for t in arms:
        tel, server = build(t)
        timed_serve(server)  # cold: compiles the (≤2) sharded programs
        arm_tps[t] = timed_serve(server)
        compiled[t] = compiled_serving_programs(tel.stats())
    widest = arms[-1]
    per_chip = arm_tps[widest] / widest
    # quantized all-reduce arm at the widest tp + its static comm account
    q_tel, q_server = build(widest, quantized=True)
    timed_serve(q_server)
    q_tps = timed_serve(q_server)
    q_wire = q_fp_equiv = 0
    if widest > 1:
        q_rep = run_program_passes(q_tel, passes=["collectives"])
        for prog in q_rep["programs"].values():
            qs = prog["passes"]["collectives"]["summary"]["quantized"]
            q_wire += qs["wire_bytes"]
            q_fp_equiv += qs["fp_equiv_wire_bytes"]
    # static HBM ledger fields (ISSUE 18). No engine wraps this server, so
    # the per-chip program peak / replicated bytes come from the memory
    # pass over the quantized arm's telemetry — audited against the tp
    # plan's declared sharding rules + comm schedule — and the KV-pool
    # residency straight from the pool (bytes/chip == total/tp with the
    # page tables host-side: the ledger gate's serving invariant).
    try:
        q_srv = getattr(q_server, "server", q_server)
        pool_rep = q_srv.pool.memory_report()
        mem_cfg = None
        if q_srv.tp is not None and q_srv.tp.degree > 1:
            mem_cfg = {
                "declared_collectives": q_srv.tp.declared_collectives(),
                "sharding_rules": q_srv.tp.sharding_rules(),
            }
        mem_tot = run_program_passes(q_tel, passes=["memory"], config=mem_cfg)[
            "totals"
        ]
        mem_fields = {
            "peak_hbm_bytes_per_chip": int(mem_tot["peak_hbm_bytes_per_chip"]),
            "replicated_bytes": int(mem_tot["replicated_bytes"]),
            # no analysis.hbm_budget_bytes configured for the bench arms
            "hbm_budget_verified": None,
            "kv_bytes_per_chip": int(pool_rep["kv_bytes_per_chip"]),
            "undeclared_collectives": int(mem_tot["undeclared_collectives"]),
        }
    except Exception as e:
        traceback.print_exc()
        mem_fields = {"memory_error": f"{type(e).__name__}: {e}"[:200]}
    return {
        **mem_fields,
        "metric": METRICS["decode_serving_tp"],
        "value": round(per_chip, 1),
        "unit": "tokens/s/chip",
        "tp_degree": int(widest),
        "tp_arms_tokens_per_sec": {str(t): round(v, 1) for t, v in arm_tps.items()},
        # (tokens/s/chip at tp) / (tokens/s at tp=1): 1.0 = linear scaling
        "scaling_efficiency": {
            str(t): round((arm_tps[t] / t) / arm_tps[1], 4) for t in arms
        },
        "vs_baseline": round((arm_tps[widest] / widest) / arm_tps[1], 4),
        "compiled_programs": int(compiled[widest]),
        "quantized_value": round(q_tps / widest, 1),
        # static per-scan-body wire bytes of the int8 exchanges, summed
        # over the compiled sharded programs, + the exact fp-equivalent
        # (= 4x: the EQuARX accounting identity the analysis gate asserts)
        "quantized_comm_wire_bytes": int(q_wire),
        "quantized_comm_fp_equiv_bytes": int(q_fp_equiv),
    }


def bench_fleet_serving():
    """Config 7: the serving fleet under a mid-trace replica kill
    (``inference/fleet.py``). Three SLA-scheduled replicas replay a
    deterministic heavy-tailed two-tenant trace (``utils/loadgen.py``) on
    the virtual clock — each replica is modeled as its own service lane,
    which is the fleet premise (a single host cannot physically host
    three chips, so the wall clock cannot measure fleet scaling; the
    virtual replay is the deterministic capacity model, and all byte-
    exactness claims are checked for real). One replica is chaos-killed
    at 40% of the trace and its live requests re-route onto the
    survivors from its journal.

    ``value`` = fleet goodput (SLA-meeting tokens per virtual second)
    WITH the kill; ``vs_baseline`` = that over the single-replica replay
    of the same trace (the acceptance bar is > 1 even while losing a
    replica mid-trace). ``p99_ttft_under_kill_ms`` vs ``p99_ttft_ms``
    (the same fleet, no kill) is the bounded-latency claim, and
    ``migrated_token_divergence`` MUST be 0 — every re-routed stream's
    acked prefix reproduced verbatim."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.fleet import FleetRouter, ReplicaHandle
    from deepspeed_tpu.inference.journal import RequestJournal
    from deepspeed_tpu.inference.scheduler import (
        PagedServer,
        compiled_serving_programs,
    )
    from deepspeed_tpu.inference.traffic import MultiTenantServer, TenantSpec
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig
    from deepspeed_tpu.profiling.compile_telemetry import CompileTelemetry
    from deepspeed_tpu.utils.loadgen import (
        TenantLoad,
        VirtualClock,
        make_trace,
        replay,
    )

    if TINY:
        mcfg = TransformerConfig(
            vocab_size=1024, hidden_size=128, num_layers=2, num_heads=4,
            num_kv_heads=2, max_seq_len=128, norm="rmsnorm", position="rope",
            activation="swiglu", use_bias=False, tie_embeddings=False,
            flash_attention=False,
        )
        paged = {"page_size": 8, "max_slots": 4, "prefill_chunk": 8}
        rate, horizon_s = 40.0, 1.0
    else:
        mcfg = TransformerConfig(
            vocab_size=32000, hidden_size=512, num_layers=4, num_heads=8,
            num_kv_heads=4, max_seq_len=256, norm="rmsnorm", position="rope",
            activation="swiglu", use_bias=False, tie_embeddings=False,
        )
        paged = {"page_size": 16, "max_slots": 8, "prefill_chunk": 16}
        rate, horizon_s = 60.0, 2.0

    model = TransformerLM(mcfg)
    rs = np.random.RandomState(SEED)
    toks = rs.randint(0, mcfg.vocab_size, (1, 16)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), toks)
    tel = CompileTelemetry()
    tenants = [
        TenantSpec(name="gold", weight=3.0, priority=1, ttft_target_ms=4000),
        TenantSpec(name="free", weight=1.0),
    ]
    import shutil
    import tempfile

    workdir = tempfile.mkdtemp(prefix="dsbench_fleet_")

    def replica(tag):
        jdir = os.path.join(workdir, tag)
        srv = PagedServer(
            mcfg, params, attn_impl="xla", dtype=jnp.bfloat16, telemetry=tel,
            prefix_cache=True, journal=RequestJournal(jdir), **paged,
        )
        return ReplicaHandle(
            name=tag, server=MultiTenantServer(srv, tenants=tenants),
            journal_dir=jdir,
        )

    trace = make_trace(
        [
            TenantLoad(name="gold", rate=rate, prompt_len=(8, 24),
                       max_new_tokens=(4, 10), prefix_len=paged["page_size"] * 2),
            TenantLoad(name="free", rate=rate, prompt_len=(8, 24),
                       max_new_tokens=(4, 10), prefix_len=paged["page_size"] * 2),
        ],
        horizon_s=horizon_s,
        vocab_size=mcfg.vocab_size,
        seed=SEED,
    )

    def kill_busy(router):
        victim = next(
            (n for n, h in router.replicas.items() if h.inner.has_work()),
            next(iter(router.replicas)),
        )
        router.kill_replica(victim)

    try:
        # fleet WITH the mid-trace kill (the measured configuration)
        fleet = FleetRouter([replica(f"kill_r{i}") for i in range(3)])
        rep_kill = replay(
            fleet, trace, clock=VirtualClock(step_cost_s=0.02),
            events=[(0.4 * horizon_s, kill_busy)], keep_outputs=False,
        )
        fs = fleet.fleet_stats()
        # the same fleet shape, uninterrupted (the p99-TTFT comparison arm)
        fleet_ok = FleetRouter([replica(f"ok_r{i}") for i in range(3)])
        rep_ok = replay(
            fleet_ok, trace, clock=VirtualClock(step_cost_s=0.02),
            keep_outputs=False,
        )
        # the single-replica baseline on the same trace
        single = FleetRouter([replica("solo")])
        rep_one = replay(
            single, trace, clock=VirtualClock(step_cost_s=0.02),
            keep_outputs=False,
        )
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    goodput = rep_kill["goodput_tokens_per_s"]
    baseline = max(rep_one["goodput_tokens_per_s"], 1e-9)
    rec = {
        "metric": METRICS["fleet_serving"],
        "value": round(goodput, 1),
        "unit": "tokens/s (3-replica virtual-clock replay, mid-trace kill)",
        "vs_baseline": round(goodput / baseline, 4),
        "replicas": 3,
        "clock": "virtual",
        "n_requests": rep_kill["n_requests"],
        # bounded-p99 claim: the kill arm vs the uninterrupted arm
        "p99_ttft_under_kill_ms": round(rep_kill["ttft_ms"].get("p99", 0.0), 1),
        "p99_ttft_ms": round(rep_ok["ttft_ms"].get("p99", 0.0), 1),
        "single_replica_goodput": round(rep_one["goodput_tokens_per_s"], 1),
        "replica_kills": fs["replica_kills"],
        # every cooperative + failure-driven move, and the audit that no
        # migrated stream's acked prefix ever diverged
        "migration_count": fs["migrations"] + fs["rerouted"],
        "migrated_token_divergence": fs["migrated_token_divergence"],
        "starved_tenants": rep_kill["starved_tenants"],
        "prefix_hit_rate": round(rep_kill.get("prefix_hit_rate", 0.0), 4),
        # the fleet adds no programs: all replicas share the ragged set
        "compiled_programs": int(compiled_serving_programs(tel.stats())),
    }
    return rec


# ---------------------------------------------------------------------------
# Orchestration. The parent never imports jax; every jax-touching activity
# runs in a subprocess the parent can kill, one at a time.

CONFIGS = {
    "gpt2_zero1": (bench_gpt2_zero1, 420),
    "llama_zero3": (bench_llama_zero3, 330),
    "infinity": (bench_infinity_max_params, 360),
    "long_seq": (bench_long_seq, 360),
    "moe_inference": (bench_moe_inference, 300),
    "moe_train": (bench_moe_train, 420),
    "decode_serving": (bench_decode_serving, 330),
    "decode_serving_tp": (bench_decode_serving_tp, 330),
    "fleet_serving": (bench_fleet_serving, 330),
}
HEADLINE = "gpt2_zero1"
PARTIAL_PATH = os.path.join(REPO, "bench_partial.jsonl")


def _atomic_write_json(path, obj, **dump_kwargs):
    """Write-to-temp → fsync → rename → fsync dir (DS-R008): the per-config
    child result files must never be readable half-written (the parent
    reads the child json while the child may be dying). A local copy of
    ``runtime/checkpoint_engine/atomic.py``'s pattern ON PURPOSE: the
    bench PARENT never imports the package (importing deepspeed_tpu pulls
    jax)."""
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(obj, f, **dump_kwargs)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    try:  # the rename is not durable until the directory entry is
        fd = os.open(os.path.dirname(os.path.abspath(path)), os.O_RDONLY)
        os.fsync(fd)
        os.close(fd)
    except OSError:
        pass


def _run_child(args, timeout_s, log_path):
    """Run ``python bench.py <args>`` in its own session; kill the whole
    process group on timeout (jax spawns threads that survive a plain kill).
    Returns (rc, timed_out)."""
    env = dict(os.environ)
    if CPU_ONLY:
        env["JAX_PLATFORMS"] = "cpu"
    with open(log_path, "ab") as log:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)] + args,
            stdout=log,
            stderr=subprocess.STDOUT,
            start_new_session=True,
            cwd=REPO,
            env=env,
        )
        try:
            return proc.wait(timeout=timeout_s), False
        except subprocess.TimeoutExpired:
            try:
                os.killpg(os.getpgid(proc.pid), 9)
            except (ProcessLookupError, PermissionError):
                proc.kill()
            proc.wait()
            return -9, True


def _child_run(name):
    """One config in this process, which is the only one on the chip. A
    full-size config measures the TPU or fails; whatever ``fn`` raises
    propagates, so the child exits non-zero and writes no record."""
    import jax

    from deepspeed_tpu.accelerator import on_tpu
    from deepspeed_tpu.profiling import use_compile_cache

    use_compile_cache()
    # TINY children may still force a virtual CPU device count inside fn(),
    # so only the full-size path initializes the backend up here
    if not TINY and not on_tpu():
        raise SystemExit(
            f"bench: full-size configs measure the TPU, found "
            f"{jax.default_backend()!r} (DS_BENCH_TINY=1 smoke-tests on the CPU)"
        )
    fn, _ = CONFIGS[name]
    rec = fn()
    devs = jax.devices()
    rec.update(
        platform=devs[0].platform,
        device_kind=devs[0].device_kind,
        device_count=len(devs),
    )
    _atomic_write_json(os.path.join(REPO, f".bench_{name}.json"), rec)


def main():
    # This parent stays off JAX: it imports neither jax nor deepspeed_tpu,
    # so the chip is free for each measuring child in turn.
    t_start = time.monotonic()
    budget = float(os.environ.get("DS_BENCH_BUDGET_S", "1320"))  # 22 min

    def budget_left():
        return budget - (time.monotonic() - t_start)

    open(PARTIAL_PATH, "w").close()
    failed = []

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        with open(PARTIAL_PATH, "a") as f:
            f.write(line + "\n")

    def run_config(name):
        """The config's record, or None (reported on stderr) if its child
        failed, timed out, or no longer fits the budget."""
        _, timeout_s = CONFIGS[name]
        out_path = os.path.join(REPO, f".bench_{name}.json")
        log_path = os.path.join(REPO, f"bench_child_{name}.log")
        left = budget_left()
        if left < 75:
            why = f"skipped: budget ({left:.0f}s left)"
        else:
            eff = min(timeout_s, left - 15)
            if os.path.exists(out_path):
                os.remove(out_path)
            rc, timed_out = _run_child(["--child-run", name], eff, log_path)
            if rc == 0 and os.path.exists(out_path):
                with open(out_path) as f:
                    return json.load(f)
            why = f"timeout after {eff:.0f}s" if timed_out else f"child rc={rc}"
            why += f" (see {os.path.basename(log_path)})"
        print(f"[bench] {name}: {why}", file=sys.stderr, flush=True)
        failed.append(name)
        return None

    # Headline MEASURED first, while the budget is freshest, but EMITTED
    # last and exactly once: the driver parses the last line as the
    # headline. If it cannot be measured (no chip, broken step) nothing
    # after it is worth the budget.
    headline = run_config(HEADLINE)
    if headline is None:
        sys.exit(1)
    for name in ("llama_zero3", "infinity", "long_seq", "moe_inference",
                 "decode_serving", "decode_serving_tp", "fleet_serving"):
        rec = run_config(name)
        if rec is not None:
            emit(rec)
    emit(headline)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    if "--child-run" in sys.argv:
        _child_run(sys.argv[sys.argv.index("--child-run") + 1])
    else:
        main()
