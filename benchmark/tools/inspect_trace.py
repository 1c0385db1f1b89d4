"""Look at one trace by hand: planes, lines, how many events each holds, the
most time-consuming event names of every line with a sample of their stats.

    python3 benchmark/tools/inspect_trace.py <trace dir or .xplane.pb> [--top 25]

This is how PERF.md's notes on how this runtime names the flash, ragged,
step and collective events were made; rerun it when the runtime changes and
correct ``benchmark/kernels/*.py`` ``EVENTS`` and ``trace_reduce.py``.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> None:
    from jax.profiler import ProfileData

    from benchmark.trace_reduce import find_xplane

    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args()
    path = args.path if args.path.endswith(".pb") else find_xplane(args.path)
    data = ProfileData.from_file(path)
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"\nPLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            lo = min(ev.start_ns for ev in events)
            hi = max(ev.start_ns + ev.duration_ns for ev in events)
            print(f"  LINE {line.name!r}: {len(events)} events, {lo} .. {hi} ns")
            agg = {}
            for ev in events:
                rec = agg.setdefault(ev.name, [0, 0.0, ev])
                rec[0] += 1
                rec[1] += ev.duration_ns
            for name, (n, ns, ev) in sorted(agg.items(), key=lambda kv: -kv[1][1])[: args.top]:
                stats = {k: (str(v)[:120]) for k, v in ev.stats}
                print(f"    {ns / 1e6:10.3f} ms  x{n:<6d} {name[:100]!r}  stats={stats}")


if __name__ == "__main__":
    main()
