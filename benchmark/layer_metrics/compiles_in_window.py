"""Compilations the program counted (``compile_stats()``) between the
window's first and last instant. Must be 0: set-up warms every shape."""


def value(trace, counters, cell):
    return counters["compiles"]
