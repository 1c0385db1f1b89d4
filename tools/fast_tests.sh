#!/bin/sh
# Fast test tier — target <10 min on the 1-core harness box (the full
# 650+-test suite on the 8-device virtual CPU mesh runs for hours there).
# Covers the core surface: engine + config, the fused grad-accum path
# (single-dispatch parity + donation/retrace guards — catches dispatch and
# recompile regressions per commit), the whole ZeRO stack
# (1/2/3/offload/zero++), mesh/groups, collectives, op-builder registry,
# MoQ, and compression. Run the FULL suite (python -m pytest tests/ -q)
# before shipping cross-cutting changes; this tier is the per-commit loop.
# Measured 2026-07-31: ~5 min, 195 tests (+22 fused/telemetry 2026-08-03,
# +24 paged-KV serving 2026-08-03: pool allocator, paged attention parity,
# continuous-batching vs dense token-exactness + retrace/dispatch guards;
# +static-analysis gate 2026-08-03: tools/lint.sh runs the repo AST lint —
# errors in deepspeed_tpu/ fail the tier — and the analysis pass suite,
# red fixtures + green sweep over the real step/serving programs;
# +13 speculative-decoding tests 2026-08-03: drafter units, spec-on vs
# spec-off vs dense token-exactness incl. preemption/EOS/budget clamp,
# one-dispatch-per-round + compile-bound guards, rollback accounting;
# +18 comm-overlap tests 2026-08-03: pipelined-vs-unpipelined bit-identity
# across ZeRO-1/3 × gas × precision (remat incl.), overlap-pass green on
# the real ZeRO-3 step / red on a serialized schedule, PLD-disables-
# prefetch gating, DS-R006 lint. The old known-failure
# set (zero_stage_trains[0-3] + zeropp qwZ/qgZ "did not learn in 5 steps"
# rng flakes) is GONE: those tests now use deterministic learnable data +
# a relative loss-decrease criterion — expect 0 failures on this box).
# +production-traffic tests 2026-08-03 (test_traffic.py + extended
# test_kv_pool.py): prefix-cache token-exactness vs sharing-off incl.
# preemption, pages-allocated-once refcount accounting, CoW/invalidation,
# randomized pool partition invariant, SLA no-starvation replay smoke
# (2 tenants, shared prefix, flood-vs-trickle on a virtual clock),
# admission control, DS-R007 lint, traffic green sweep.
# +ragged serving 2026-08-04 (test_ragged_serving.py + extended
# test_paged_attention.py + analysis compile gate): ragged-vs-dense
# byte-identical streams across admission/preemption/prefix/spec-K-mix/
# EOS, ≤2-compiled-programs + 1-dispatch-per-step + 3-wave retrace
# guards, ragged attention kernel parity (XLA fallback + Pallas
# interpret), ragged program green sweep.
# +fault tolerance 2026-08-04 (test_fault_tolerance.py +
# test_journal_recovery.py + test_chaos.py): atomic staged-commit
# checkpoint layout, in-process chaos kills at every ckpt/serve injection
# point, auto_resume bit-identical losses (bf16 + fp16 dynamic scale),
# async-snapshot parity + zero-new-programs telemetry guard, torn-file /
# torn-journal red tests, byte-identical stream recovery, DS-R008 lint.
# The FULL subprocess kill -9 matrix is `pytest -m slow
# tests/unit/checkpoint/test_chaos_matrix.py` (excluded here and from
# tier-1).
# +observability 2026-08-04 (test_tracer.py + test_flight_recorder.py +
# test_telemetry_free.py + test_request_spans.py + monitor suite): unified
# tracing plane — span nesting/ring/percentiles/thread-safety-with-async-
# writer, serving request-lifecycle spans across admission/preemption/
# spec-decode, chaos-kill flight-recorder postmortems (subprocess exit
# case is `-m slow`), telemetry-is-free guard (0 new programs, host-
# transfer pass clean, <2% overhead bound), engine.observability() merged
# reports + Perfetto export, monitor block + JSONL backend + hub feed,
# DS-R009 lint.
# +serving fleet 2026-08-04 (test_fleet.py + fleet green gate + DS-R010
# lint): replicated engines behind the FleetRouter — byte-identical
# streams under replica kills at every fleet chaos point, live migration
# mid-prefill/mid-decode with the acked prefix audited, drain-to-empty +
# journal compaction, prefix-affinity-beats-random routing, SLA/goodput
# across a mid-trace kill on the loadgen replay, circuit breaker,
# prefill/decode role split, elasticity resize policy + journal-catch-up
# join, fleet-adds-0-programs compile gate. The real kill -9
# restart-and-adopt case is `-m slow`.
# +multi-chip TP serving 2026-08-04 (test_tp_serving.py + extended
# test_source_lint.py; the analysis gate test_passes.py::
# test_green_tp_serving rides the lint.sh analysis suite below):
# tensor-parallel sharded ragged serving on the virtual CPU mesh —
# byte-identical greedy streams at tp∈{1,2,4} vs the single-chip oracle
# across admission/preemption/prefix-attach/spec-K/multi-step windows,
# ≤2-compiled-programs + 1-dispatch-per-step + retrace guards ON the
# mesh, int8 weight roundtrip ≤ max|w_ch|/254 + logits-allclose bound,
# EQuARX quantized all-reduce allclose + wire-bytes = fp/4 accounting,
# DS-R005/DS-R007 TP-path lint extensions.
# +ZeRO-Infinity streamed host offload 2026-08-07 (test_host_offload.py
# rides the tests/unit/runtime/zero dir below; test_passes.py::
# test_green_infinity_offload_program rides the lint.sh analysis suite;
# DS-R008/DS-R009 Streamer-family lint extensions ride
# test_source_lint.py): fp32 master + Adam moments live in pinned host
# buffers and stream per-bucket through a depth-2 double-buffered async
# pipeline — streamed vs on-device BIT-identical losses/master across
# zero{1,3} × {fp32,bf16,fp16-forced-overflow} × gas{1,2}, declared
# stream schedule == measured bytes + 0 exposed ms with both pipeline
# knobs on / red overlap verdict with pipeline_write off, host-resident
# checkpoint snapshot roundtrip + streamed/legacy format guards,
# train.mid_offload_stream chaos kill → auto_resume bit-identical,
# legacy cpu_offload* config-routing red tests.
# +static HBM ledger 2026-08-07 (test_memory.py + test_passes.py::
# test_green_memory_ledger_{offload,tp_serving} ride the lint.sh analysis
# suite; DS-R011/DS-R012 lint + the --json/--rule CLI ride
# test_source_lint.py): per-program peak-HBM estimator (backend
# memory_analysis() + optimized-HLO walk fallback with donation-alias
# dedup), sharding auditor (replicated-leaf-vs-declared-rule +
# pjit-inserted-collective-vs-declared-schedule red/green), whole-run
# residency ledger behind engine.memory_report() gated by
# analysis.hbm_budget_bytes (off|warn|raise, over-budget raises with
# per-buffer attribution). The two green gates statically reproduce the
# runtime claims: streamed zero-3 offload holds ≤2 buckets on device with
# the fp32 master host-side, and tp=4 serving holds KV bytes/chip ==
# total/tp with page tables host-side + 0 undeclared reshard collectives.
# +expert-parallel MoE fast path 2026-08-07 (tests/unit/moe below;
# test_passes.py::test_green_moe_programs rides the lint.sh analysis
# suite; DS-R005/DS-R009 *Gate/*MoE/*MoELayer routing-path lint
# extensions ride test_source_lint.py): expert-sharded training with
# explicit overlapped dispatch/combine all-to-alls (moe/a2a.py) — top-1/
# top-2 gating parity vs the dense-dispatch reference, deterministic
# capacity-overflow drops, expert-sharded checkpoint roundtrip bit-
# identity, train.mid_step chaos resume on the MoE config; the green
# gate pins 1 dispatch/step + full donation + every a2a hidden (exposed
# loop-collective bytes == 0) + int8 a2a wire == fp/4, and MoE routing
# inside the ragged serving programs at ≤2 compiles with zero retraces
# over shifting expert mixes.
cd "$(dirname "$0")/.." || exit 1
sh tools/lint.sh || exit 1
exec python -m pytest -q \
  tests/unit/runtime/test_engine.py \
  tests/unit/runtime/test_fused_grad_accum.py \
  tests/unit/runtime/test_train_batch_loop.py \
  tests/unit/runtime/test_compile_telemetry.py \
  tests/unit/runtime/test_config.py \
  tests/unit/runtime/test_lr_schedules.py \
  tests/unit/runtime/test_loss_scaler.py \
  tests/unit/runtime/test_runtime_utils.py \
  tests/unit/runtime/test_moq.py \
  tests/unit/runtime/zero \
  tests/unit/checkpoint/test_fault_tolerance.py \
  tests/unit/inference/test_journal_recovery.py \
  tests/unit/utils/test_chaos.py \
  tests/unit/profiling/test_tracer.py \
  tests/unit/profiling/test_flight_recorder.py \
  tests/unit/profiling/test_telemetry_free.py \
  tests/unit/inference/test_request_spans.py \
  tests/unit/monitor/test_monitor.py \
  tests/unit/inference/test_kv_pool.py \
  tests/unit/inference/test_serving.py \
  tests/unit/inference/test_ragged_serving.py \
  tests/unit/inference/test_spec_decode.py \
  tests/unit/inference/test_tp_serving.py \
  tests/unit/inference/test_traffic.py \
  tests/unit/inference/test_fleet.py \
  tests/unit/ops/test_paged_attention.py \
  tests/unit/ops/test_op_builder.py \
  tests/unit/parallel/test_mesh.py \
  tests/unit/utils/test_groups.py \
  tests/unit/comm/test_collectives.py \
  tests/unit/compression/test_compression.py \
  tests/unit/moe \
  "$@"
