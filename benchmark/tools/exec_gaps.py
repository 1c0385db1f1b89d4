"""One traced run's serving steps by their own numbers: where the gap between
two executions goes.

    python3 benchmark/tools/exec_gaps.py <trace dir or .xplane.pb> [--steps 40]

One line a step of the slice (``--steps`` of them from its middle): ``seq``,
the program, the execution's device milliseconds, the gap before it on the
device's line, the host's turnaround before its enqueue (``drain`` where a
drain settled the step before, ``sync`` where nothing was in flight), the
enqueue call, the wait for it, and the self times of its pack and its settle,
each marked ``in`` where the span lies inside an execution (the host worked
while the device did) and ``out`` where it does not. The first line says how
many steps were paired with their execution by order and not by time (0 in a
quiet slice; a host stall inside the jitted call otherwise, named with its
``seq`` and how late the execution started). Then the medians, which
are what the four readers of ``benchmark/step_seq.py`` report, the gap's
decomposition (gap = turnaround + enqueue call + the rest: the runtime's
launch after the enqueue and the completion's way back to the host, which
the two clocks cannot split) and the bounds the pairs put on the clock
offset: a device event cannot start before its enqueue does nor end after the
wait for it returns, so the correction to the aligned device clock lies in
[max(enqueue.start - execution.start), min(fetch.end - execution.end)], and
the width of that interval is how far any host-against-device attribution in
this trace (``in`` / ``out`` here, ``step_host_share``, ``idle_gaps``) can be
wrong. ``inspect_scopes.py`` finds a cell's trace the same way.
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def ms(seconds: float) -> str:
    return f"{1e3 * seconds:7.3f}"


def med(values) -> float:
    values = list(values)
    return statistics.median(values) if values else float("nan")


def main() -> int:
    from benchmark import program_spans, step_seq, trace_reduce

    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("--steps", type=int, default=40, help="how many steps to print, from the slice's middle")
    args = ap.parse_args()
    path = args.path if args.path.endswith(".pb") else trace_reduce.find_xplane(args.path)
    trace = trace_reduce.reduce_xplane(path, ("server_step",), ("server_step",))
    spans = [s for s in program_spans.load(path) if s.start >= trace.lo and s.end <= trace.hi]
    found = step_seq.steps(trace, spans)
    if not found:
        print("no serve.enqueue span in the slice: a program before PR 36, or no serving step")
        return 1
    runs = step_seq.executions(trace, {st.program for st in found})
    gaps = step_seq.exec_gaps(trace, found)
    gap_before = dict(zip(map(id, runs[1:]), gaps))
    turnaround = step_seq.turnarounds(found)
    busy = [(m.start, m.end) for m in runs]

    def own(name: str):
        """By ``seq``: the self time of the step's last span of that name, and whether it ran inside an execution."""
        named = [s for s in spans if s.name == name]
        return {s.attrs.get("seq"): (t, any(lo <= s.start and s.end <= hi for lo, hi in busy)) for s, t in zip(named, program_spans.self_seconds(spans, name))}

    pack_of, settle_of = own("serve.pack"), own("serve.settle")
    by_order = [st for st in found if st.by_order]
    print(f"slice {trace.window_s:.3f} s, {len(found)} steps enqueued, {len(runs)} whole executions, clock_shift {1e3 * trace.clock_shift:+.3f} ms; "
          f"{len(by_order)} steps paired by order (an execution more than {1e3 * step_seq.PAIR_REACH_S:.0f} ms from its enqueue: a host stall)"
          + "".join(f"; seq {st.seq} {1e3 * (st.execution.start - st.enqueue.start):+.1f} ms" for st in by_order[:8]))
    print("    seq program                       device     gap  turnar. enqueue    wait   pack        settle")
    first = max(0, (len(found) - args.steps) // 2)
    packs, settles = [], []
    for i, st in enumerate(found):
        pack, settle = pack_of.get(st.seq), settle_of.get(st.seq)
        if pack:
            packs.append(pack)
        if settle:
            settles.append(settle)
        if not first <= i < first + args.steps:
            continue
        ex = st.execution
        if st.seq in turnaround:
            before = ms(turnaround[st.seq])
        elif i and found[i - 1].drained:
            before = "  drain"
        else:
            before = "      -" if st.ahead else "   sync"
        cells = [
            f"{st.seq:7d}", f"{st.program:28s}", ms(ex.duration) if ex else "      -", ms(gap_before[id(ex)]) if ex and id(ex) in gap_before else "      -",
            before, ms(st.enqueue.duration), ms(st.fetch.duration) if st.fetch else "      -",
        ] + [f"{ms(v[0])} {'in ' if v[1] else 'out'}" if v else "      -    " for v in (pack, settle)]
        print(" ".join(cells))
    gap, turn, call = med(gaps), med(turnaround.values()), med(st.enqueue.duration for st in found)
    print(f"\nmedians: execution {ms(med(m.duration for m in runs))} ms, wait {ms(med(st.fetch.duration for st in found if st.fetch))} ms, "
          f"pack {ms(med(p[0] for p in packs))} ms ({sum(p[1] for p in packs)}/{len(packs)} inside an execution), "
          f"settle {ms(med(s[0] for s in settles))} ms ({sum(s[1] for s in settles)}/{len(settles)} inside)")
    print(f"exec_gap_ms {ms(gap)} = host_turnaround_ms {ms(turn)} + enqueue_call_ms {ms(call)} + the rest {ms(gap - turn - call)} "
          f"(launch after the enqueue, completion to the wait's return); run ahead {sum(st.ahead for st in found)}/{len(found)}, drains {sum(st.drained for st in found)}")
    pairs = [st for st in found if st.execution is not None]
    lows = sorted((st.enqueue.start - st.execution.start, st.seq) for st in pairs)
    highs = sorted((st.fetch.end - st.execution.end, st.seq) for st in pairs if st.fetch)
    (lower, at_lower), (upper, at_upper) = lows[-1], highs[0] if highs else (float("nan"), None)
    print(f"clock: the aligned device clock is off by between {1e3 * lower:+.3f} (seq {at_lower}) and {1e3 * upper:+.3f} ms (seq {at_upper}): {len(pairs)} pairs, width {1e3 * (upper - lower):.3f} ms; "
          f"as a shift of the raw device clock {1e3 * (trace.clock_shift + lower):+.3f} .. {1e3 * (trace.clock_shift + upper):+.3f} ms")
    if lower > upper:  # an execution that starts before its enqueue on every clock that keeps another's end before its wait's return
        worst = ", ".join(f"seq {seq} {1e3 * d:+.3f}" for d, seq in lows[-5:][::-1])
        print(f"       no constant offset fits every pair: the largest lower bounds are {worst} ms; the median pair gives {1e3 * med(d for d, _ in lows):+.3f} .. {1e3 * med(d for d, _ in highs):+.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
