"""Device time of a mixed serving step (width = the prefill chunk: at least
one row prefills), from the step's own execution: median over the slice's
steps whose ``serve.pack`` says ``mixed = 1`` of the length of the whole
execution paired with their ``serve.enqueue{seq}``. The inside twin of
``mixed_step_device_ms``; see ``narrow_exec_ms.py``. None where the slice
holds no mixed step (a chat slice of a few seconds may hold none)."""

from benchmark import files


def value(trace, counters, cell):
    return files.load_module("layer_metrics", "mixed_step_share").exec_ms(trace, cell, mixed=True)
