"""Traffic kind ``open_loop``: requests fall due on a seeded schedule at the
cell's fixed rate whatever the server does; independent users.

One thread: between ``server.step()`` calls every request that has fallen
due is submitted, and when the server has nothing to do the driver sleeps
until the next is due. A request that fell due during a long step is sent
late; its latency still counts from when it was **due**, and how late the
generator ran is printed on the info line (``layer_metrics/loadgen_late_p90_ms.py``
reads it in a cell that lists that metric).

The window is ``--seconds`` of arrivals. The first tenth of its requests
fill the batch and are not measured; the rest are, and are the same
multiset of lengths whatever the seed (``loadgen.open_loop_trace``). After the last arrival the server
is drained for at most ``drain_seconds``; what has not finished by then
counts as failed. With ``--trace 1`` arrivals go on for a settle second and
``trace_seconds`` more, and that last stretch is traced; the window before
it is the one ``--trace 0`` runs on the same seed.
"""

from __future__ import annotations

import time
from typing import Dict

from jax.profiler import TraceAnnotation

from benchmark import loadgen
from benchmark.serving import Served, ServeSession

ANNOTATIONS = ("submit", "server_step", "wait_for_arrival")
SETTLE_S = 1.0


def drive(session, trace, seconds: float, mix: Dict, tracer=None) -> Dict:
    """The loop itself, on anything with the session's surface (the tests
    drive a fake whose steps stall). ``tracer`` is the run's Context when a
    slice is to be traced."""
    t0 = time.perf_counter()
    pending = [Served(req=r, due=t0 + r.due_s) for r in trace]
    nxt = 0
    backlog = []  # (seconds into the window, requests waiting, requests running) per step

    def submit_due(now: float) -> None:
        nonlocal nxt
        while nxt < len(pending) and pending[nxt].due <= now:
            session.submit(pending[nxt])
            nxt += 1

    def loop(until: float) -> None:
        while True:
            now = time.perf_counter()
            if now >= until:
                return
            submit_due(now)
            if session.server.has_work():
                backlog.append((now - t0, session.server.queued_count(), len(session.live)))
                session.step()
            else:
                wake = min(until, pending[nxt].due if nxt < len(pending) else until)
                with TraceAnnotation("wait_for_arrival"):
                    time.sleep(max(0.0, wake - time.perf_counter()))

    loop(t0 + seconds)
    window_s = time.perf_counter() - t0
    counters1 = session.counters()
    rows_log = None
    if tracer is not None:
        rows_log = session.traced_slice(tracer, loop, SETTLE_S, mix["trace_seconds"])
    submit_due(time.perf_counter())
    session.drain(mix["drain_seconds"])
    measured = [r for r in session.records if r.req.measured]
    return {"t0": t0, "window_s": window_s, "seconds": seconds, "requests": measured, "backlog": backlog,
            "counters1": counters1, "rows_log": rows_log}


def run(ctx) -> Dict:
    mix = ctx.traffic
    session = ServeSession(ctx.config, ctx.seed)
    session.warm_up(ctx.seed)
    tail_s = (SETTLE_S + mix["trace_seconds"]) if ctx.trace else 0.0
    trace = loadgen.open_loop_trace(mix, ctx.seconds, session.shape["vocab_size"], ctx.seed, tail_s=tail_s)
    counters0 = session.counters()
    window = drive(session, trace, ctx.seconds, mix, tracer=ctx if ctx.trace else None)
    counters1, rows_log, measured, t0 = window.pop("counters1"), window.pop("rows_log"), window["requests"], window["t0"]

    check = session.check_streams(session.records, ctx.seed)
    ttft = [(r.stamps[0] - r.due) * 1e3 for r in measured if r.ok()]
    gaps = [(b - a) * 1e3 for r in measured if r.ok() for a, b in zip(r.stamps, r.stamps[1:])]
    prompt_tokens = sum(r.req.prompt.size for r in session.records if r.due < t0 + ctx.seconds)
    counters = session.window_counters(counters0, counters1, prompt_tokens, rows_log)
    counters["late_ms"] = [(r.submitted - r.due) * 1e3 for r in measured]
    return {
        "t_window_start": t0,
        "attempted": len(measured),
        "failed": sum(1 for r in measured if r.rejected or not r.ok()),
        "correct": check["correct"],
        "window": window,
        "counters": counters,
        "annotations": ANNOTATIONS,
        "sync_annotations": ("server_step",),
        "info": {
            **check,
            "requests_sent": len(session.records),
            "requests_measured": len(measured),
            "queued_at_end": session.server.queued_count(),
            # for the reader; the cell's metric is the median gap alone (TTFT swings with the seed's bursts)
            "ttft_ms_mean": sum(ttft) / max(1, len(ttft)),
            "ttft_ms_p50_p90_p99": [loadgen.percentile(ttft, q) for q in (50, 90, 99)],
            "loadgen_late_ms_p90": loadgen.percentile(counters["late_ms"], 90),
            "itl_ms_p50_p90_p99_mean": [loadgen.percentile(gaps, q) for q in (50, 90, 99)] + [sum(gaps) / max(1, len(gaps))],
            "ttft_ms_sorted": sorted(round(t, 1) for t in ttft),
            "serve_stats": {k: session.server.stats[k] for k in ("preempted", "ragged_steps", "prefill_chunks", "emitted_tokens")},
        },
    }
