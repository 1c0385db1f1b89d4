"""How a test finds a cell's per-layer entries in ``BENCHMARK.json``: by the
cell's name in an entry's ``workloads``, never by a prefix, a count or a
position in the list. Since PR 44 an entry is one (reader, end-to-end metric
moved) and its list holds every cell that reports it, so the next PR's cell
lengthens lists and appends entries, and no test here may mind either."""

from benchmark.files import metrics_of, reader_of


def readers_of(spec, cell):
    """``{reader: entry}`` over the per-layer entries that list the cell. A
    cell reports one family of end-to-end metric, so no reader comes twice."""
    entries = metrics_of(spec, "per_layer", cell)
    found = {reader_of(m["name"]): m for m in entries}
    assert len(found) == len(entries), f"{cell}: a reader is listed twice"
    return found
