"""Device time inside the flash-attention kernel ``flash_bwd_dkv`` over device
busy time; mean over the chips. The kernel is found by the name the program
gave its ``pallas_call`` (``benchmark/op_scopes.py``), after the count check
of ``flash_names.events``."""

from benchmark import flash_names


def value(trace, counters, cell):
    return flash_names.time_share(trace, counters, cell, "backward_dkv")
