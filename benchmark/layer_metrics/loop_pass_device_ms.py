"""Device time of ONE pass over a looped model's layer stack: the ops traced
inside the ``loop_pass`` scope (``deepspeed_tpu/inference/decode.py``: a pass's
layers, the ragged kernel's calls among them; the norm between passes lies
outside, under ``pass_norm``) summed over the traced slice, over the slice's
steps times the model's passes. From the ops' name stacks
(``benchmark/op_scopes.py``). None for a model that runs its stack once, and
where no op names the scope (the parent)."""

from benchmark import op_scopes


def value(trace, counters, cell):
    passes = counters["model"].get("num_loops", 1)
    if trace is None or passes < 2 or not counters.get("rows_log"):
        return None
    spent = op_scopes.scope_self_time(op_scopes.of_cell(cell), trace.devices[0], "loop_pass")
    if not spent:
        return None
    return 1e3 * spent / (len(counters["rows_log"]) * passes)
