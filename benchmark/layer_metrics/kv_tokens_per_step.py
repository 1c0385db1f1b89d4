"""The context a narrow serving step read: mean over the slice's steps with
``mixed = 0`` of ``kv_tokens`` on their ``serve.pack`` (the live rows' keys
once the step's own are written: what every attention and latent kernel
reads that step, a layer; a state kernel reads a state a row whatever this
says). The number two slices must share before their kernels' shares, their
rooflines or their step times are compared: a decode step's attention time
follows it. ``kv_pages`` (x the page's bytes) and ``live_tokens`` lie beside
it in the record for a whole-step roofline. ``mixed_step_share.py`` has the
pairing and the join."""

from benchmark import files


def value(trace, counters, cell):
    found = files.load_module("layer_metrics", "mixed_step_share").records(trace, cell)
    narrow = [r.kv_tokens for r in found or () if not r.mixed]
    return sum(narrow) / len(narrow) if narrow else None
