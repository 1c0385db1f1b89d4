"""Programs dispatched per optimizer step inside the window, from
``engine.compile_stats()``; exact."""


def value(trace, counters, cell):
    if "steps" not in counters or not counters["steps"]:
        return None
    return counters["dispatches"] / counters["steps"]
