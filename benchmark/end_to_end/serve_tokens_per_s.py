"""Output tokens the host saw inside the window, over the window's seconds."""


def value(window, cell):
    if "requests" not in window:
        return None
    t0, t1 = window["t0"], window["t0"] + window["window_s"]
    return sum(1 for r in window["requests"] for t in r.stamps if t0 <= t < t1) / window["window_s"]
