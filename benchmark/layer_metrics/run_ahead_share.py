"""How often a step was enqueued with the one before it unsettled: the
``serve.dispatch`` spans of the slice with ``ahead = 1`` over all of them, in
percent. Near 100 in steady serving; a silent fall back to the synchronous
path (a drafter armed by default, a drain every call) reads 0. None without
``serve.enqueue`` spans (a program before PR 36), so that every reader of
this family speaks of the same program."""

from benchmark import program_spans, step_seq


def value(trace, counters, cell):
    if trace is None:
        return None
    spans = program_spans.of_cell(trace, cell)
    dispatches = [s for s in spans if s.name == step_seq.DISPATCH]
    if not dispatches or not any(s.name == step_seq.ENQUEUE for s in spans):
        return None
    return 100.0 * sum(s.attrs.get("ahead") == 1 for s in dispatches) / len(dispatches)
