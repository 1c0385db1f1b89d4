"""Mean of (first token visible to the host - the instant the request was
DUE) over the measured requests. A rejected, wrong or unfinished request
counts as +inf.

No cell lists it yet: in ``mistral7b_chat_steady`` a burst of arrivals crowds
the server in about one seed of four and every TTFT statistic then swings by
10-90% (PERF.md section 2), so the open-loop driver prints it on
its info line and the knee sweep reads ``samples``. A cell whose bursts do
not queue can list it: a mean and not a percentile, because a percentile of a
few dozen requests is one or two samples, each off by up to a step according
to where in a step the request fell due."""

import math


def samples(window):
    return [(r.stamps[0] - r.due) * 1e3 if r.ok() else float("inf") for r in window["requests"]]


def value(window, cell):
    if not window.get("requests"):
        return None
    return math.fsum(samples(window)) / len(window["requests"])
