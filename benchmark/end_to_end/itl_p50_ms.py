"""Median of every gap between consecutive tokens of one request as the host
sees them, over the measured requests. A dispatch that yields several tokens
shows as one long gap and several of zero: what a streaming user sees. A
failed request adds one +inf gap.

The median and not the 90th percentile: about a seventh of the gaps are a
mixed step and the rest a narrow one, three times shorter, so the 90th
percentile sits in the sparse foot of the mixed steps and swings with how the
arrivals overlap."""

from benchmark.loadgen import percentile


def samples(window):
    out = []
    for r in window["requests"]:
        if r.ok():
            out += [(b - a) * 1e3 for a, b in zip(r.stamps, r.stamps[1:])]
        else:
            out.append(float("inf"))
    return out


def value(window, cell):
    if "requests" not in window:
        return None
    return percentile(samples(window), 50)
