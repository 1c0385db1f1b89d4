"""Useful share of the mixed program's token slots: prompt tokens prefilled
in the window / (dispatches of the mixed program x rows x width). Counts
only; the program runs the whole model over every slot, live or not."""


def value(trace, counters, cell):
    if not counters.get("mixed_dispatches"):
        return None
    return 100.0 * counters["prompt_tokens"] / (counters["mixed_dispatches"] * counters["rows"] * counters["width"])
