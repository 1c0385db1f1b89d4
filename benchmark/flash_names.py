"""Shared by the three ``flash_*_time_share`` readers: the flash-attention
kernels of a traced training step, found by the names the program gave its
``pallas_call``s."""

from typing import Dict, List, Optional

from benchmark import op_scopes
from benchmark.kernels import flash_attention as k

# the kinds of benchmark/kernels/flash_attention.py -> the program's names
NAMES = {"forward": "flash_fwd", "backward_dq": "flash_bwd_dq", "backward_dkv": "flash_bwd_dkv"}


def events(trace, counters, cell) -> Optional[List[Dict[str, List]]]:
    """Per chip, the events of each kernel by name; every whole step in the
    slice must hold the calls the model's layers need (one of each a layer,
    the forward twice under remat), or this raises. None where the trace
    names no such kernel."""
    model = counters["model"]
    calls = {NAMES[kind]: n for kind, n in k.calls_per_step(model["num_layers"], model["remat"]).items()}
    names = op_scopes.of_cell(cell)
    found = [op_scopes.checked_kernel_events(names, dev, calls) for dev in trace.devices]
    return None if any(f is None for f in found) else found


def time_share(trace, counters, cell, kind: str) -> Optional[float]:
    """Percent of device busy time inside the kernel ``kind``, mean over the chips."""
    if trace is None:
        return None
    found = events(trace, counters, cell)
    if found is None:
        return None
    shares = [sum(ev.duration for ev in f[NAMES[kind]]) / dev.busy_s() for f, dev in zip(found, trace.devices)]
    return 100.0 * sum(shares) / len(shares)
