"""Find the knee of an open-loop cell once, on the chip, when the cell is
defined: the highest swept rate at which at least 90% of the requests sent
meet both limits (PERF.md section 2: TTFT <= 2000 ms, a request's mean gap
<= 150 ms) and the queue of waiting requests is no longer at the window's
end than at its first third. The cell then runs at 0.8 x that rate, written as a number in
``benchmark/cells/<cell>.json``.

    python3 benchmark/tools/knee_sweep.py --workload mistral7b_chat_steady \
        --rates 0.75,1,1.25 --seeds 3 --seconds 51

One process, one server build; every rate gets ``--seeds`` windows of
``--seconds`` plus the drain, each on a seed of its own and from a pool that
the last window left empty; the rates alternate, so that a drift of the
machine does not read as a rate's. A rate is sustained when every one of its
windows attains 90% and the waiting queue, averaged over its windows, did
not grow. Waiting and running requests are recorded apart. One JSON line per
window, one per rate, one for the knee.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

TTFT_LIMIT_MS = 2000.0
MEAN_GAP_LIMIT_MS = 150.0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=100)
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true", help="tiny sizes on the CPU: control flow only, no number means anything")
    args = ap.parse_args()

    from benchmark import loadgen
    from benchmark.drivers import open_loop
    from benchmark.files import load_cell, load_json, load_module
    from benchmark.serving import ServeSession
    from deepspeed_tpu.profiling import use_compile_cache

    use_compile_cache()
    cell = load_cell(load_json(ROOT, "BENCHMARK.json"), args.workload, rehearse=args.rehearse)
    config, base_mix = cell["config_file"], cell["traffic_file"]
    session = ServeSession(config, args.seed)
    session.warm_up(args.seed)
    vocab = session.shape["vocab_size"]

    def window(rate: float, seed: int) -> dict:
        mix = dict(base_mix, rate_rps=rate)
        session.drain(300.0)  # whatever the last window's own drain left
        if session.server.has_work():
            raise RuntimeError("the pool did not empty between two windows")
        session.records.clear()
        session.by_uid.clear()
        stats0 = dict(session.server.stats)
        win = open_loop.drive(session, loadgen.open_loop_trace(mix, args.seconds, vocab, seed), args.seconds, mix)
        reqs = win["requests"]
        ttft = load_module("end_to_end", "ttft_mean_ms").samples(win)
        mean_gap = [(r.stamps[-1] - r.stamps[0]) * 1e3 / max(1, len(r.stamps) - 1) if r.ok() else float("inf") for r in reqs]
        gaps = load_module("end_to_end", "itl_p50_ms").samples(win)
        met = sum(1 for t, g in zip(ttft, mean_gap) if t <= TTFT_LIMIT_MS and g <= MEAN_GAP_LIMIT_MS)
        third = [(q, n) for t, q, n in win["backlog"] if args.seconds / 3 - 2 <= t <= args.seconds / 3 + 2]
        end = [(q, n) for t, q, n in win["backlog"] if t >= args.seconds - 4]
        mean = lambda rows, i: sum(r[i] for r in rows) / max(1, len(rows))
        p = loadgen.percentile
        out = {
            "rate_rps": rate, "seed": seed, "measured": len(reqs), "attained_share": met / max(1, len(reqs)),
            "ttft_ms": {"mean": sum(ttft) / max(1, len(ttft)), "p50": p(ttft, 50), "p90": p(ttft, 90), "p99": p(ttft, 99)},
            "itl_ms": {"p50": p(gaps, 50), "p90": p(gaps, 90), "p99": p(gaps, 99)},
            "mean_gap_p90_ms": p(mean_gap, 90),
            "late_p90_ms": p([(r.submitted - r.due) * 1e3 for r in reqs], 90),
            "queue_first_third": mean(third, 0), "queue_end": mean(end, 0),
            "running_first_third": mean(third, 1), "running_end": mean(end, 1),
            "steps": session.server.stats["ragged_steps"] - stats0["ragged_steps"],
            "prefill_chunks": session.server.stats["prefill_chunks"] - stats0["prefill_chunks"],
            "failed": sum(1 for r in reqs if not r.ok()),
        }
        print(json.dumps(out), flush=True)
        return out

    rates = [float(r) for r in args.rates.split(",")]
    rows = [window(rate, args.seed + 10 * k + i) for k in range(args.seeds) for i, rate in enumerate(rates)]
    sustained = []
    for rate in rates:
        own = [r for r in rows if r["rate_rps"] == rate]
        mean = lambda key: sum(r[key] for r in own) / len(own)
        summary = {
            "rate_rps": rate, "windows": len(own), "attained_share_min": min(r["attained_share"] for r in own),
            "ttft_p90_ms": sorted(r["ttft_ms"]["p90"] for r in own), "ttft_p99_ms": sorted(r["ttft_ms"]["p99"] for r in own),
            "queue_first_third": mean("queue_first_third"), "queue_end": mean("queue_end"),
            "running_first_third": mean("running_first_third"), "running_end": mean("running_end"),
        }
        summary["sustained"] = summary["attained_share_min"] >= 0.9 and summary["queue_end"] <= summary["queue_first_third"] + 0.5
        if summary["sustained"]:
            sustained.append(rate)
        print(json.dumps(summary), flush=True)
    knee = max(sustained) if sustained else None
    print(json.dumps({"knee_rps": knee, "cell_rate_rps": None if knee is None else round(0.8 * knee, 3)}), flush=True)


if __name__ == "__main__":
    main()
