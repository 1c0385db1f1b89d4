"""Records the small ``.xplane.pb`` and the ``rows_log`` that
``tests/benchmark/test_bench_step_record.py`` reads: a short slice of a paged
server of the program itself, driven as the closed-loop driver drives a cell
(``benchmark/serving.py::ServeSession``: its stamping policy, its ``step()``
under the ``server_step`` annotation, its ``traced_slice`` with the driver's
own log of every call's rows), so that the trace holds what PR 54 put there
(``mixed``, ``kv_tokens`` and ``row_lens`` on every ``serve.pack``, under the
step's ``seq``) beside the outside log the five new readers are compared
with. The model is ``record_runahead_trace.py``'s: two layers of a wide dense
decoder, so that a step takes the device milliseconds and an execution is
paired with its enqueue without doubt. Four callers, each sending its next
request when its last one finished, with budgets of a few tokens: requests
end and newcomers are admitted inside the slice, so it holds narrow and mixed
steps, steps packed twice under one ``seq``, and a prompt longer than a
chunk. Run on the chip machine:

    python3 benchmark/tools/record_steprecord_trace.py chiprun_out/steprecord_trace

and copy ``steprecord_tpu.xplane.pb`` and ``steprecord_rows_log.json`` from
there to ``tests/benchmark/data/``. The loop counts calls, not seconds
(``SETTLE_CALLS`` before the slice, ``SLICE_CALLS`` inside it): the first
step the slice enqueues was packed before it began, so it has no record
inside the slice; the last call waits for the step it enqueued, so no
execution is cut. The file keeps what ``record_runahead_trace.py`` keeps.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

SETTLE_CALLS, SLICE_CALLS = 6, 30
PROMPTS = (150, 8, 40, 90, 200, 16, 70, 130)  # tokens, in turn; 150 and 200 are longer than a chunk of 128
BUDGETS = (14, 22, 10, 18, 26, 12)  # tokens a request, in turn: a request ends every few calls


def _runahead():
    spec = importlib.util.spec_from_file_location("record_runahead_trace", os.path.join(ROOT, "benchmark", "tools", "record_runahead_trace.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class _Profiler:
    """What ``ServeSession.traced_slice`` asks of the run's context."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir

    def start_trace(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)

    def stop_trace(self) -> None:
        import jax

        jax.profiler.stop_trace()


def main() -> None:
    import numpy as np

    from benchmark.loadgen import TrafficRequest
    from benchmark.serving import Served, ServeSession
    from benchmark.trace_reduce import find_xplane

    runahead = _runahead()
    out = sys.argv[1]
    config = {
        "model": {"adapter": "dense_transformer", "reference": "dense_decoder", "kwargs": runahead.MODEL},
        "engine": {"kind": "serve", "init_inference": {"dtype": "bf16", "paged_kv": runahead.PAGED}, "check": {}},
    }
    session = ServeSession(config, 0)
    session.warm_up(0)
    rng = np.random.default_rng(0)
    sent = 0

    def send_next() -> None:
        nonlocal sent
        prompt = rng.integers(0, runahead.MODEL["vocab_size"], PROMPTS[sent % len(PROMPTS)], dtype=np.int32)
        session.submit(Served(req=TrafficRequest(sent, prompt, BUDGETS[sent % len(BUDGETS)]), due=time.perf_counter()))
        sent += 1

    for _ in range(runahead.PAGED["max_slots"]):
        send_next()
    calls = iter((SETTLE_CALLS, SLICE_CALLS))

    def loop(until: float) -> None:  # so many calls, whatever the clock says
        for _ in range(next(calls)):
            done = session.done_count
            session.step()
            for _ in range(session.done_count - done):
                send_next()

    rows_log = session.traced_slice(_Profiler(out), loop, 0.0, 0.0)
    stats = session.server.serve_stats()
    print({k: stats[k] for k in ("dispatches", "ragged_steps", "mixed_steps", "run_ahead_steps", "drain_reasons", "admitted", "finished")}, flush=True)
    print("mixed calls in the slice", sum(e["mixed"] for e in rows_log), "of", len(rows_log), flush=True)
    path = os.path.join(out, "steprecord_tpu.xplane.pb")
    runahead.kept_planes_and_lines(find_xplane(out), runahead.KEEP, path)
    with open(os.path.join(out, "steprecord_rows_log.json"), "w") as f:
        json.dump([{"mixed": bool(e["mixed"]), "rows": [list(map(int, row)) for row in e["rows"]]} for e in rows_log], f)
    print(f"{path}: {os.path.getsize(path)} bytes", flush=True)


if __name__ == "__main__":
    main()
