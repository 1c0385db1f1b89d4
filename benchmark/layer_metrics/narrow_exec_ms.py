"""Device time of a narrow serving step (width 1: every row decodes), from
the step's own execution: median over the slice's steps whose ``serve.pack``
says ``mixed = 0`` of the length of the whole execution paired with their
``serve.enqueue{seq}`` (``end - start`` of its event on device 0's ``XLA
Modules`` line: the device's clock alone). The inside twin of
``decode_step_device_ms``, which sums the device's busy time inside the
``server_step`` annotation under an aligned clock: an execution's length
holds that busy time plus whatever the device idles inside the module, and
needs neither the wrapper nor the alignment, nor a call that waits for the
device. ``mixed_step_share.py`` has the pairing and the join."""

from benchmark import files


def value(trace, counters, cell):
    return files.load_module("layer_metrics", "mixed_step_share").exec_ms(trace, cell, mixed=False)
