"""Traffic kind ``closed_loop``: ``clients`` callers, each sending its next
request the moment its last one finished; batch generation. A slow server
receives less load, so the end-to-end metric is the tokens completed per
second, never a tail.

Requests come in rounds of ``clients``, every round the same stratified
multiset of lengths in an order that is the same for every seed (the seed
draws the token ids and the weights: ``loadgen.request_stream``). The window is
``--seconds`` from the first submissions; tokens stamped inside it count.
With ``--trace 1`` the loop goes on for a settle second and
``trace_seconds`` more, and that last stretch is traced. Then the run ends:
a request takes as long as a window at today's pace, so what is in flight
is not waited for. It counts as attempted, and as failed unless its stream so
far is consistent (prompt echoed, a stamp a token); the reference check reads
its first ``check.max_context`` tokens like anyone's.
"""

from __future__ import annotations

import time
from typing import Dict

from benchmark import loadgen
from benchmark.serving import Served, ServeSession

ANNOTATIONS = ("submit", "server_step")
SETTLE_S = 1.0


def run(ctx) -> Dict:
    mix = ctx.traffic
    session = ServeSession(ctx.config, ctx.seed)
    session.warm_up(ctx.seed)
    clients = session.paged["max_slots"] if mix["clients"] == "max_slots" else int(mix["clients"])
    supply = loadgen.request_stream(mix, clients, session.shape["vocab_size"], ctx.seed)
    counters0 = session.counters()

    def send_next() -> None:
        session.submit(Served(req=next(supply), due=time.perf_counter()))

    t0 = time.perf_counter()
    for _ in range(clients):
        send_next()

    def loop(until: float) -> None:
        while time.perf_counter() < until:
            done = session.done_count
            session.step()
            for _ in range(session.done_count - done):
                send_next()

    loop(t0 + ctx.seconds)
    t1 = time.perf_counter()
    counters1 = session.counters()

    rows_log = session.traced_slice(ctx, loop, SETTLE_S, mix["trace_seconds"]) if ctx.trace else None
    session.drain(0.0)  # no waiting: only keeps the streams so far

    check = session.check_streams(session.records, ctx.seed, in_flight_ok=True)
    prompt_tokens = sum(r.req.prompt.size for r in session.records if r.submitted < t1)
    counters = session.window_counters(counters0, counters1, prompt_tokens, rows_log)
    return {
        "t_window_start": t0,
        "attempted": len(session.records),
        "failed": check["failed"],
        "correct": check["correct"],
        "window": {"t0": t0, "window_s": t1 - t0, "seconds": ctx.seconds, "requests": session.records},
        "counters": counters,
        "annotations": ANNOTATIONS,
        "sync_annotations": ("server_step",),
        "info": {
            **check,
            "requests_sent": len(session.records),
            "requests_finished_in_window": sum(1 for r in session.records if r.finished is not None and r.finished < t1),
            "serve_stats": {k: session.server.stats[k] for k in ("preempted", "ragged_steps", "prefill_chunks", "emitted_tokens")},
        },
    }
