"""Look at what the program's own names say about one trace: the jitted
programs on the module line, the Pallas kernels by ``name=``, device time by
named scope, and the program's host spans with their attributes.

    python3 benchmark/tools/inspect_scopes.py <trace dir or .xplane.pb> [--top 12]

``inspect_trace.py`` shows the trace as the runtime names it; this shows it as
the program does (``benchmark/op_scopes.py``, ``benchmark/program_spans.py``).
PERF.md's notes on where each name is found were made with it; rerun it when
the runtime changes.
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def scope_path(stack: str, op_scopes) -> str:
    """The named scopes of a name stack, outermost first, without the
    ``jit(...)`` wrappers, control flow and the primitive."""
    skip = {"while", "body", "cond", "closed_call", "checkpoint", "remat", "custom_vjp_call", "custom_jvp_call", "shard_map", "pallas_call"}
    parts = [op_scopes.bare(c) for c in op_scopes.components(stack)[:-1] if not c.startswith(("jit(", "pjit"))]
    return "/".join(p for p in parts if p not in skip) or "(no scope)"


def main() -> None:
    from benchmark import op_scopes, program_spans, trace_reduce

    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args()
    path = args.path if args.path.endswith(".pb") else trace_reduce.find_xplane(args.path)
    names = op_scopes.load(path)
    trace = trace_reduce.reduce_xplane(path, ())
    for dev in trace.devices:
        print(f"\nDEVICE {dev.ordinal}: busy {dev.busy_s():.4f} s of {trace.window_s:.4f} s")
        by_module = {}
        for m in dev.modules:
            by_module.setdefault(op_scopes.module_of(m.name)[0], []).append(m.duration)
        for name, times in sorted(by_module.items(), key=lambda kv: -sum(kv[1])):
            print(f"  module {name}: x{len(times)}, median {1e3 * statistics.median(times):.3f} ms")
        kernels, scopes, unnamed = {}, {}, 0.0
        for ev in dev.leaves:
            stack = names.stack(dev.ordinal, ev.name)
            kernel = op_scopes.kernel_of(stack)
            if "custom-call" in ev.name and "tpu_custom_call" in ev.name:
                kernels.setdefault(kernel or f"(unnamed) {stack}", []).append(ev.duration)
            scope = scope_path(stack, op_scopes) if stack else "(no name stack)"
            scopes[scope] = scopes.get(scope, 0.0) + ev.duration
        for kernel, times in sorted(kernels.items(), key=lambda kv: -sum(kv[1])):
            print(f"  kernel {kernel}: x{len(times)}, median {1e6 * statistics.median(times):.1f} us, {100 * sum(times) / dev.busy_s():.2f}% of busy")
        for scope, t in sorted(scopes.items(), key=lambda kv: -kv[1])[: args.top]:
            print(f"  scope {scope}: {1e3 * t:.3f} ms, {100 * t / dev.busy_s():.2f}% of busy")
        sample = next((ev for ev in dev.leaves if "tpu_custom_call" in ev.name), None)
        if sample is not None:
            print(f"  a kernel event's name: {sample.name[:160]!r}\n  its metadata: { {k: str(v)[:160] for k, v in names.stats(dev.ordinal, sample.name).items()} }")
    spans = [s for s in program_spans.load(path) if s.start >= trace.lo and s.end <= trace.hi]
    print(f"\nPROGRAM SPANS inside the slice: {len(spans)}")
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    for name, group in sorted(by_name.items()):
        self_s = program_spans.self_seconds(spans, name)
        print(f"  {name}: x{len(group)}, median {1e3 * statistics.median(s.duration for s in group):.4f} ms, self {1e3 * statistics.median(self_s):.4f} ms, attrs of the first: {group[0].attrs}")


if __name__ == "__main__":
    main()
