"""One traced run of a cell through ``run.py`` itself, then the cross-checks
between what the program's own names say and what the benchmark's wrappers and
signature readers say of the same trace (PERF.md section 5 reports them):

    python3 benchmark/tools/trace_consistency.py --workload <cell> --seed <n> --seconds <s>

Serving: per step, the four phases' self times plus ``serve.fetch`` against
the ``server_step`` annotation around them; the ragged kernel's time found by
name against the time found by signature; every execution of the mixed-width
program against the driver's log of which steps were mixed. Training: the
flash kernels found by name against those found by signature. Both: the module
names in the slice. Prints one JSON line after ``run.py``'s own.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def serving(trace, counters, cell, names, spans):
    from benchmark import op_scopes, program_spans, serve_steps, trace_reduce
    from benchmark.kernels import ragged_paged_attention as k

    steps = serve_steps.steps(trace, counters)
    phases = ("serve.admit", "serve.pack", "serve.dispatch", "serve.fetch", "serve.settle")
    ratios = []
    for ev, _, _ in steps:
        inside = [s for s in spans if ev.start <= s.start and s.end <= ev.end]
        if inside:
            ratios.append(sum(sum(program_spans.self_seconds(inside, p)) for p in phases) / ev.duration)
    dev = trace.devices[0]
    by_name = op_scopes.kernel_events(names, dev, ["ragged_paged_attention"])["ragged_paged_attention"]
    by_signature = dev.kernel_events(k.EVENTS["ragged"])
    mixed_width = f"_w{counters['width']}"

    def step_of(module):
        """The logged step a program execution ran in: the ``server_step`` it overlaps most."""
        return max(steps, key=lambda st: trace_reduce.overlap((module.start, module.end), (st[0].start, st[0].end)))

    misplaced = checked = 0
    for m in dev.whole_modules:
        if op_scopes.module_of(m.name)[0].endswith(mixed_width):
            checked += 1
            misplaced += not step_of(m)[1]
    narrow = [m for m in dev.whole_modules if not op_scopes.module_of(m.name)[0].endswith(mixed_width)]
    in_mixed = sum(1 for m in narrow if step_of(m)[1])
    return {
        "steps": len(steps),
        "phases_plus_fetch_over_server_step": {"median": statistics.median(ratios), "min": min(ratios), "max": max(ratios)} if ratios else None,
        "ragged_kernel_s": {"by_name": sum(e.duration for e in by_name), "by_signature": sum(e.duration for e in by_signature), "calls": [len(by_name), len(by_signature)]},
        "mixed_width_executions": checked,
        "mixed_width_executions_in_steps_logged_narrow": misplaced,
        "narrow_executions_in_steps_logged_mixed": in_mixed,
    }


def training(trace, counters, cell, names, spans):
    from benchmark import flash_names
    from benchmark.kernels import flash_attention as k

    found = flash_names.events(trace, counters, cell)
    out = {}
    for kind, name in flash_names.NAMES.items():
        by_signature = sum(ev.duration for dev in trace.devices for ev in dev.kernel_events(k.EVENTS[kind]))
        by_name = sum(ev.duration for f in (found or []) for ev in f[name])
        out[name] = {"by_name_s": by_name, "by_signature_s": by_signature}
    return {"flash": out, "train.dispatch": len([s for s in spans if s.name == "train.dispatch"])}


def main() -> int:
    from benchmark import run as bench

    kept = {}
    load_module = bench.load_module

    def keeping(kind, name):
        module = load_module(kind, name)
        if kind != "drivers":
            return module

        class Driver:
            @staticmethod
            def run(ctx):
                kept["ctx"], kept["result"] = ctx, module.run(ctx)
                return kept["result"]

        return Driver

    bench.load_module = keeping
    rc = bench.main(sys.argv[1:] + ["--trace", "1"])
    from benchmark import op_scopes, program_spans, trace_reduce

    ctx, result = kept["ctx"], kept["result"]
    path = trace_reduce.find_xplane(ctx.trace_dir)
    trace = trace_reduce.reduce_xplane(path, result["annotations"], result["sync_annotations"])
    cell = {"name": ctx.cell, "config": ctx.config}
    names, spans = op_scopes.of_cell(cell), program_spans.of_cell(trace, cell)
    check = (serving if ctx.config["engine"]["kind"] == "serve" else training)(trace, result["counters"], cell, names, spans)
    check["modules"] = sorted({op_scopes.module_of(m.name)[0] for dev in trace.devices for m in dev.modules})
    check["clock_shift_s"] = trace.clock_shift
    print(json.dumps({"consistency": check}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
