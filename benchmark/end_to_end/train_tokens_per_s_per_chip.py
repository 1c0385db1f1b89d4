"""Tokens per second and chip of the optimizer steps inside the window: the
median over the window's ``GROUPS`` consecutive groups of steps of a group's
tokens over its seconds, on the host's clock as each step's loss arrives.

A median over the window and not its total over its length: a machine whose
host is shared stalls a run for a few tenths of a second now and then, which
is the machine's and not the program's, and one stall is 1% of a window. It
falls into one group and leaves the median where it was; what slows every
step, or more than half of the groups, moves it in full. The driver prints
the window's total over its length beside it (``whole_window_tokens_per_s_per_chip``)."""

import statistics

GROUPS = 24


def group_rates(stamps, tokens_per_step):
    """Tokens per second of each group of consecutive steps; ``stamps`` are
    the instants at which successive steps were seen complete."""
    n = len(stamps) - 1
    m = max(1, n // GROUPS)
    return [m * tokens_per_step / (stamps[i + m] - stamps[i]) for i in range(0, n - m + 1, m)]


def value(window, cell):
    if "tokens" not in window:
        return None
    rates = group_rates(window["step_stamps"], window["tokens"] / window["steps"])
    if not rates:  # fewer than two steps seen complete
        return window["tokens"] / window["window_s"] / cell["chips"]
    return statistics.median(rates) / cell["chips"]
