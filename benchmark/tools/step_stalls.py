"""One run of a serving cell through ``run.py`` itself with every call of
``ServeSession.step`` timed, and for each long call WHO lost the time:

    python3 benchmark/tools/step_stalls.py --workload <cell> --seed <n> --seconds <s> --trace 0

Beside the run, a child process that never imports jax and a thread of this
process each sleep 5 ms at a time and log every sleep that took over 30 ms;
every garbage collection is timed (``gc.callbacks``); a long call keeps the
longest of the program's own spans inside it (``serve.admit`` / ``pack`` /
``enqueue`` / ``settle`` / ``fetch``). A long call is then one of (``blame``):

* ``machine``: the child saw a pause at the same instant. Nothing of this
  process ran in the child: the whole machine stood still.
* ``interpreter``: only this process's thread saw it: something held the
  interpreter's lock (a collection of the oldest generation is named).
* ``wait``: neither saw it: the main thread really waited, in the phase the
  longest span names (``serve.fetch``: for the device).

PR 45 wrote it to find the ~130 ms calls of ``laguna_s21_long_decode``
(``PERF.md`` section 6): all ``machine``. Prints ``run.py``'s lines, then one
JSON line ``{"step_stalls": ...}``. The wrapper costs two clock readings a
step; the result line's numbers are a plain run's.
"""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

LONG_S, PAUSE_S, NAP_S = 0.060, 0.030, 0.005
NAPPER = """
import time
while True:
    t = time.perf_counter(); time.sleep(%r); g = time.perf_counter() - t
    if g > %r:
        print(t, g, flush=True)
""" % (NAP_S, PAUSE_S)
PHASES = ("serve.admit", "serve.pack", "serve.enqueue", "serve.settle", "serve.fetch")


def overlapping(gaps, t0: float, t1: float) -> float:
    """Seconds of the ``(start, length)`` gaps that fall inside ``[t0, t1]``."""
    return sum(max(0.0, min(t1, t + g) - max(t0, t)) for t, g in gaps)


def blame(t0: float, t1: float, child_gaps, thread_gaps, collections) -> str:
    """Who lost most of a long call's time over a usual step's (above)."""
    if overlapping(child_gaps, t0, t1) > 0.5 * (t1 - t0):
        return "machine"
    if overlapping(thread_gaps, t0, t1) > 0.5 * (t1 - t0):
        full = overlapping([(t, d) for t, d, gen in collections if gen == 2], t0, t1)
        return "interpreter (a full collection)" if full > 0.5 * (t1 - t0) else "interpreter"
    return "wait"


def main(argv) -> int:
    from benchmark import run, serving

    # perf_counter is the machine's clock: the same in both processes. The pipe holds thousands of pauses
    child = subprocess.Popen([sys.executable, "-c", NAPPER], stdout=subprocess.PIPE, text=True)
    thread_gaps, collections, calls, long_calls, started = [], [], [], [], [0.0]

    def nap():
        while True:
            t = time.perf_counter()
            time.sleep(NAP_S)
            if time.perf_counter() - t > PAUSE_S:
                thread_gaps.append((t, time.perf_counter() - t))

    def on_gc(phase, info):
        if phase == "start":
            started[0] = time.perf_counter()
        else:
            collections.append((started[0], time.perf_counter() - started[0], info["generation"]))

    threading.Thread(target=nap, daemon=True).start()
    gc.callbacks.append(on_gc)
    plain_step = serving.ServeSession.step

    def step(self):
        t0 = time.perf_counter()
        plain_step(self)
        t1 = time.perf_counter()
        calls.append((t0, t1))
        if t1 - t0 > LONG_S:
            spans = [s for s in self.server.tracer.spans(last=64) if s["t1"] >= t0 and s["name"] in PHASES]
            long_calls.append((t0, t1, max(spans, key=lambda s: s["t1"] - s["t0"], default=None)))

    serving.ServeSession.step = step
    try:
        rc = run.main(argv)
    finally:
        child.terminate()
    child_gaps = [tuple(float(x) for x in line.split()) for line in child.communicate()[0].splitlines() if len(line.split()) == 2]
    if not calls:
        return rc
    start = calls[0][0]
    report = {
        "calls": len(calls),
        "long_calls": [
            {"at_s": round(t0 - start, 2), "ms": round(1e3 * (t1 - t0), 1), "blame": blame(t0, t1, child_gaps, thread_gaps, collections),
             "longest_phase": span and [span["name"], round(1e3 * (span["t1"] - span["t0"]), 1), (span["attrs"] or {}).get("program")]}
            for t0, t1, span in long_calls
        ],
        "machine_pauses": [(round(t - start, 2), round(1e3 * g, 1)) for t, g in child_gaps if t >= start],
        "full_collections": [(round(t - start, 2), round(1e3 * d, 1)) for t, d, gen in collections if gen == 2 and t >= start],
    }
    print(json.dumps({"step_stalls": report}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
