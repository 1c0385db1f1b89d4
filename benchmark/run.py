"""The benchmark's one command: runs one cell of ``BENCHMARK.json`` once, in
this one process, and prints the result as the last line of standard output.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data, found by name
(``benchmark/files.py`` lists where).

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics plus ``breakdown``. A metric whose reader
returns None is left out. No TPU, or fewer chips than the cell asks for:
exit code 3 and no result line. ``--rehearse`` runs the same control flow on
the CPU with each file's ``rehearse`` overrides (tiny sizes) and prints no
metric at all.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# as a script, sys.path starts with benchmark/ itself: put the checkout's root there
sys.path[:] = [ROOT] + [p for p in sys.path if os.path.abspath(p or ".") != os.path.join(ROOT, "benchmark")]

from benchmark.files import HERE, load_cell, load_json, load_module, metrics_of, reader_of  # noqa: E402

TRACE_ROOT = os.path.join(ROOT, ".benchmark_trace")  # listed in .gitignore


@dataclasses.dataclass
class Context:
    """What a driver gets."""

    cell: str
    chips: int
    config: Dict
    traffic: Dict
    seed: int
    seconds: float
    trace: bool
    trace_dir: str

    def start_trace(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir)
        # host TraceMe events (the benchmark's annotations) and the device
        # planes; not the Python call tracer, which slows the host loop
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)

    def stop_trace(self) -> None:
        import jax

        jax.profiler.stop_trace()


def device_report(jax, chips: int, rehearse: bool) -> Dict[str, Any]:
    devices = jax.devices()
    report = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    wanted = "cpu" if rehearse else "tpu"
    if report["platform"] != wanted or report["count"] != chips:
        print(f"benchmark: the cell needs exactly {chips} {wanted} device(s), JAX found {report}", file=sys.stderr)
        raise SystemExit(3)
    return report


def memory_peak(jax, chips: int) -> Optional[int]:
    """Peak bytes on the fullest chip. On this runtime ``peak_bytes_in_use``
    counts live arrays only; a program's temporaries are reserved apart
    (``peak_bytes_reserved``: 7.9 GB for the GPT-2 step against 8.2 GiB of
    compiled temp, PR 22), while its arguments stay in use, so the peak is
    the sum."""
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            return None
        peaks.append(stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0))
    return max(peaks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true", help="tiny sizes on the CPU; prints no metric")
    args = ap.parse_args(argv)

    spec = load_json(ROOT, "BENCHMARK.json")
    cell = load_cell(spec, args.workload, args.rehearse)
    chips = int(cell["chips"])
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"

    import jax

    # the repo's one cache path: $JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    from deepspeed_tpu.profiling import use_compile_cache

    cache_dir = use_compile_cache()
    device = device_report(jax, chips, args.rehearse)
    # every program goes to the cache, however quickly it compiled, so that a
    # second run in the same checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    ctx = Context(
        cell=args.workload,
        chips=chips,
        config=cell["config_file"],
        traffic=cell["traffic_file"],
        seed=args.seed,
        seconds=args.seconds,
        trace=bool(args.trace),
        trace_dir=os.path.join(TRACE_ROOT, args.workload),
    )
    driver = load_module("drivers", ctx.traffic["kind"])
    result = driver.run(ctx)
    setup_s = result["t_window_start"] - T_PROCESS_START

    peaks = load_json(HERE, "peaks.json")
    reader_cell = {
        "name": args.workload,
        "chips": chips,
        "config": ctx.config,
        "traffic": ctx.traffic,
        "peak": peaks.get(device["kind"]),
        "setup_s": setup_s,
    }

    def read(kind: str, wanted, *inputs) -> Dict[str, Dict]:
        """Every wanted metric whose reader has something to read."""
        file_of = reader_of if kind == "layer_metrics" else str
        values = {m["name"]: (load_module(kind, file_of(m["name"])).value(*inputs, reader_cell), m["unit"]) for m in wanted}
        return {name: {"value": v, "unit": unit} for name, (v, unit) in values.items() if v is not None}

    end_to_end = metrics_of(spec, "end_to_end", args.workload)
    breakdown = None
    if not args.trace:
        metrics = read("end_to_end", end_to_end, result["window"])
    else:
        if not args.rehearse and reader_cell["peak"] is None:
            raise KeyError(f"no peaks on record for device_kind {device['kind']!r}: add it to benchmark/peaks.json with its source")
        from benchmark import trace_reduce

        reduced = None
        if not args.rehearse:  # the CPU backend writes no device plane
            reduced = trace_reduce.reduce_xplane(trace_reduce.find_xplane(ctx.trace_dir), result["annotations"], result["sync_annotations"])
            device["busy_s"] = reduced.busy_s()
            device["window_s"] = reduced.window_s
            breakdown = {"device_ops": reduced.device_ops(), "idle_gaps": reduced.idle_gaps()}
        reported = {m["name"] for m in end_to_end}
        per_layer = [m for m in metrics_of(spec, "per_layer", args.workload) if m["moves"] in reported]
        metrics = read("layer_metrics", per_layer, reduced, result["counters"])

    device["memory_peak_bytes"] = memory_peak(jax, chips)
    if args.rehearse:
        # a CPU run has counts and control flow, no device number and nobody's
        # latency: its counts are printed, no time and no metric
        print(json.dumps({"info": {k: v for k, v in result["info"].items() if "_ms" not in k and "_per_s" not in k}}, default=str), flush=True)
        print(json.dumps({"rehearsal": "passed", "correct": result["correct"], "attempted": result["attempted"],
                          "failed": result["failed"], "metric_names": sorted(metrics), "device": device}), flush=True)
        return 0 if result["correct"] else 1
    memory_stats = {k: v for k, v in (jax.devices()[0].memory_stats() or {}).items() if isinstance(v, int)}
    print(json.dumps({"info": result["info"], "memory_stats_device0": memory_stats, "setup_s": setup_s, "compile_cache_dir": cache_dir, "window_s": result["window"]["window_s"]}, default=str), flush=True)
    out = {
        "correct": result["correct"],
        "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
