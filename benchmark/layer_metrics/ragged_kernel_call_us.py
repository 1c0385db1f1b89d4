"""Median device time of one call of the ragged paged-attention kernel, found
by the name the program gave it (``pallas_call(name="ragged_paged_attention")``,
read from the op's name stack: ``benchmark/op_scopes.py``). Every whole
execution of a ``jit_paged_ragged_*`` program in the slice must hold one call
a layer, or the reader raises."""

import statistics

from benchmark import op_scopes
from benchmark.kernels import ragged_paged_attention as k

KERNEL = "ragged_paged_attention"


def value(trace, counters, cell):
    if trace is None:
        return None
    calls = {KERNEL: k.calls_per_step(counters["model"]["num_layers"])["ragged"]}
    events = op_scopes.checked_kernel_events(op_scopes.of_cell(cell), trace.devices[0], calls, "jit_paged_ragged")
    return 1e6 * statistics.median(ev.duration for ev in events[KERNEL]) if events else None
