"""Traffic kind ``train_steps``: optimizer steps back to back for
``--seconds``, a different host batch every step, through the loop a user
writes: ``loss = engine(batch); engine.backward(loss); engine.step()``.

Set-up builds the engine with ``ds.initialize``, makes the batches on the
host from the seed, computes the plain reference's loss on the first batch's
two distinct sequences from the initial weights, and takes two warm-up steps
(the first compiles or loads from the cache). The window ends in
``block_until_ready`` on the last step's parameters. The host is kept at
most two steps ahead of the device by waiting for the loss of the step
before last: a loop that logs does the same, and a queue of dozens of
enqueued steps would only move the wait to the window's end.
"""

from __future__ import annotations

import time
from typing import Dict

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from benchmark import files, loadgen

ANNOTATIONS = ("data_next", "train_step")
RUN_AHEAD = 2


def as_batch(tokens: np.ndarray) -> Dict[str, np.ndarray]:
    return {"input_ids": tokens[:, :-1], "labels": tokens[:, 1:]}


def run(ctx) -> Dict:
    import deepspeed_tpu as ds

    cfg, mix = ctx.config, ctx.traffic
    model, shape = files.build_model(cfg)
    ds_config = cfg["engine"]["ds_config"]
    chips = ctx.chips
    rows = ds_config["train_micro_batch_size_per_gpu"] * chips
    if mix["seq_len"] > shape["max_seq_len"]:
        raise ValueError(f"seq_len {mix['seq_len']} exceeds the model's {shape['max_seq_len']} positions")
    pool = loadgen.train_batches(mix, rows, shape["vocab_size"], ctx.seed)

    engine, _, _, _ = ds.initialize(model=model, config=ds_config)
    engine.init_params(as_batch(pool[0]), rng=jax.random.PRNGKey(ctx.seed))
    n_params = engine.num_parameters()

    # the plain reference on the two distinct sequences of the first batch,
    # from the weights the first step will read (before any update)
    ref_loss = float(files.reference_of(cfg).loss(cfg["model"], engine.get_params(), pool[0][:2]))

    def step(tokens):
        loss = engine(as_batch(tokens))
        engine.backward(loss)
        engine.step()
        return loss

    first_loss = float(step(pool[0]))
    warm = [first_loss, float(step(pool[1 % len(pool)]))]
    jax.block_until_ready(engine.get_params())
    stats0 = engine.compile_stats()
    skipped0 = engine.skipped_steps

    stamps = []  # host clock after each wait: one step apart in steady state

    def loop(until_s: float, max_steps: int, start: int):
        """Steps from pool index ``start`` until the clock passes ``until_s``
        or ``max_steps`` ran; returns the losses (device arrays)."""
        losses = []
        stamps.clear()
        while time.perf_counter() < until_s and len(losses) < max_steps:
            with TraceAnnotation("data_next"):
                tokens = pool[(start + len(losses)) % len(pool)]
            with TraceAnnotation("train_step"):
                losses.append(step(tokens))
            if len(losses) > RUN_AHEAD:
                losses[-1 - RUN_AHEAD].block_until_ready()
                stamps.append(time.perf_counter())
        jax.block_until_ready(engine.get_params())
        return losses

    t0 = time.perf_counter()
    losses = loop(t0 + ctx.seconds, 10**9, start=2)
    window_s = time.perf_counter() - t0
    step_stamps = list(stamps)  # the traced slice below runs the loop again
    step_ms = np.diff(step_stamps) * 1e3
    stats1 = engine.compile_stats()
    steps = len(losses)

    if ctx.trace:
        ctx.start_trace()
        loop(time.perf_counter() + 3600, 2, start=2 + steps)  # settle after the profiler's start
        with TraceAnnotation("bench_slice"):
            loop(time.perf_counter() + mix["trace_seconds"], mix["trace_steps"], start=4 + steps)
        ctx.stop_trace()

    values = [float(x) for x in losses]
    dispatches = sum(r["dispatches"] for r in stats1.values()) - sum(r["dispatches"] for r in stats0.values())
    compiles = sum(r["compiles"] for r in stats1.values()) - sum(r["compiles"] for r in stats0.values())
    tol = cfg["engine"]["check"]["loss_atol"]
    finite = bool(np.all(np.isfinite(values + warm)))
    skipped = engine.skipped_steps - skipped0
    correct = finite and skipped == 0 and abs(first_loss - ref_loss) <= tol
    return {
        "t_window_start": t0,
        "attempted": steps,
        "failed": int(skipped) + int(np.sum(~np.isfinite(values))),
        "correct": bool(correct),
        "window": {
            "window_s": window_s,
            "steps": steps,
            "tokens": steps * rows * mix["seq_len"],
            "chips": chips,
            "step_stamps": step_stamps,
        },
        "counters": {
            "dispatches": dispatches,
            "compiles": compiles,
            "steps": steps,
            "tokens": steps * rows * mix["seq_len"],
            "window_s": window_s,
            "n_params": n_params,
            "model": shape,
            "seq_len": mix["seq_len"],
            "rows_per_chip": rows // chips,
        },
        "annotations": ANNOTATIONS,
        "sync_annotations": (),  # dispatch is asynchronous: no host span waits for its own step
        "info": {
            "first_loss": first_loss,
            "reference_loss": ref_loss,
            "loss_gap": abs(first_loss - ref_loss),
            "loss_atol": tol,
            "last_loss": values[-1] if values else None,
            # host-clock step times of the window: a stall shows as a max far above the median
            "step_ms_p50_p99_max": [float(np.percentile(step_ms, q)) for q in (50, 99, 100)] if step_ms.size else None,
            # what the metric was before it became a median over groups of steps
            "whole_window_tokens_per_s_per_chip": steps * rows * mix["seq_len"] / window_s / chips,
            "programs": {k: [v["compiles"], v["dispatches"]] for k, v in stats1.items() if v["dispatches"]},
        },
    }
