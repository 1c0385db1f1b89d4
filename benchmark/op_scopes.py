"""What the program called each device operation of a profiler trace: its
JAX name stack, and from that its Pallas kernel's ``name=``, the
``jax.named_scope``s around it and the jitted program it belongs to.

``jax.profiler.ProfileData`` gives an op event its name (the instruction's
HLO text), start and duration, and the per-event stats, which hold only
device offsets. The name stack is a stat of the event's *metadata*
(``tf_op``: ``jit(fused_step)/jit(main)/transpose(jvp(layers))/while/body/
attention/flash_bwd_dq/pallas_call:``), next to ``program_id`` (the
fingerprint in the module event's name, ``jit_fused_step(<id>)``),
``hlo_category`` and ``source``; ``ProfileData`` does not show it. So this
file reads the metadata tables itself, from the protobuf wire format (no
dependency: ``XSpace.planes[] > XPlane.event_metadata / stat_metadata``;
the lines, which are nearly all of the file, are skipped by their length),
and joins them to ``trace_reduce``'s events by event name.

The program's names (PR 23): the six ``pallas_call``s carry ``name=``
(``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``, ``decode_attention``,
``paged_decode_attention``, ``ragged_paged_attention``), the step programs
carry named scopes (training ``embed``, ``layers`` > ``attention`` / ``mlp``,
``head_loss``, ``optimizer``, ``grad_reduce``; serving ``attention``,
``kv_write``, ``mlp``, ``head_sample``), and a jitted program's module is
``jit_<its compile_stats() key>``. A trace of a program without them (the
parent of PR 23) has name stacks too, only without these components: every
lookup here then finds nothing, and the readers return None.
"""

from __future__ import annotations

import functools
import os
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from benchmark import files, trace_reduce

# XPlane / XEventMetadata / XStat field numbers (tsl/profiler/protobuf/xplane.proto)
_PLANE_NAME, _PLANE_EVENT_METADATA, _PLANE_STAT_METADATA = 2, 4, 5
_MAP_VALUE = 2
_META_ID, _META_NAME, _META_STATS = 1, 2, 5
_STAT_METADATA_ID, _STAT_UINT64, _STAT_INT64, _STAT_STR, _STAT_REF = 1, 3, 4, 5, 7
NAME_STACK = "tf_op"
_MODULE = re.compile(r"^(?P<name>.*)\((?P<id>\d+)\)$")


def wire_varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def wire_fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = wire_varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = wire_varint(buf, i)
        elif wire == 2:
            size, i = wire_varint(buf, i)
            value, i = buf[i : i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i : i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane message")
        yield tag >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _plane_metadata(plane) -> Tuple[str, Dict[str, List[Dict[str, object]]]]:
    """A plane's name and, per event name, the stats of every event
    metadata of that name (one a program, where two programs hold the same
    instruction text)."""
    name, stat_names, raw = "", {}, []
    for field, value in wire_fields(plane):
        if field == _PLANE_NAME:
            name = _text(value)
        elif field == _PLANE_STAT_METADATA:
            entry = dict(wire_fields(dict(wire_fields(value)).get(_MAP_VALUE, b"")))
            stat_names[entry.get(_META_ID, 0)] = _text(entry.get(_META_NAME, b""))
        elif field == _PLANE_EVENT_METADATA:
            raw.append(dict(wire_fields(value)).get(_MAP_VALUE, b""))
    by_name: Dict[str, List[Dict[str, object]]] = {}
    for meta in raw:
        event_name, stats = "", {}
        for field, value in wire_fields(meta):
            if field == _META_NAME:
                event_name = _text(value)
            elif field == _META_STATS:
                stat = dict(wire_fields(value))
                key = stat_names.get(stat.get(_STAT_METADATA_ID, 0), "")
                if _STAT_STR in stat:
                    stats[key] = _text(stat[_STAT_STR])
                elif _STAT_REF in stat:
                    stats[key] = stat_names.get(stat[_STAT_REF], "")
                elif _STAT_UINT64 in stat or _STAT_INT64 in stat:
                    stats[key] = stat.get(_STAT_UINT64, stat.get(_STAT_INT64))
        if stats:
            by_name.setdefault(event_name, []).append(stats)
    return name, by_name


class OpNames:
    """The event metadata of one ``.xplane.pb``, by plane and event name."""

    def __init__(self, planes: Dict[str, Dict[str, List[Dict[str, object]]]]):
        self.planes = planes

    def stats(self, ordinal: int, event_name: str) -> Dict[str, object]:
        """The metadata stats of a device's op event. Two programs that hold
        the same instruction text (the pool copies of both serving widths)
        have a metadata each; the first is returned: their kernels and
        scopes are the same, only the ``jit(<program>)`` in front differs,
        and which program an event ran in is told by the module line."""
        found = self.planes.get(f"/device:TPU:{ordinal}", {}).get(event_name, [])
        return found[0] if found else {}

    def stack(self, ordinal: int, event_name: str) -> str:
        """The op's JAX name stack, ``''`` where the trace has none."""
        return str(self.stats(ordinal, event_name).get(NAME_STACK, ""))


@functools.lru_cache(maxsize=4)
def load(path: str) -> OpNames:
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = {}
    for field, plane in wire_fields(space):
        if field == 1:
            name, by_name = _plane_metadata(plane)
            if by_name:
                planes[name] = by_name
    return OpNames(planes)


def xplane_of(cell: Dict) -> str:
    """The run's own trace: ``.benchmark_trace/<cell name>/``, where ``run.py`` wrote it."""
    return trace_reduce.find_xplane(os.path.join(files.ROOT, ".benchmark_trace", cell["name"]))


def of_cell(cell: Dict) -> OpNames:
    """The names of the run's own trace; parsed once per process."""
    return load(xplane_of(cell))


# ---------------------------------------------------------------------------
# reading a name stack


def components(stack: str) -> List[str]:
    """``jit(f)/transpose(jvp(layers))/while/body/mlp/dot_general:`` ->
    ``[jit(f), transpose(jvp(layers)), while, body, mlp, dot_general]``."""
    return [c for c in stack.rstrip(":").split("/") if c]


def bare(component: str) -> str:
    """A scope's own name inside the transformations JAX wraps around it:
    ``transpose(jvp(layers))`` -> ``layers``."""
    while "(" in component and component.endswith(")"):
        component = component[component.index("(") + 1 : -1]
    return component


def in_scope(stack: str, scope: str) -> bool:
    """Whether the op was traced inside ``jax.named_scope(scope)``, forward
    or backward. The last component is the primitive, not a scope."""
    return any(bare(c) == scope for c in components(stack)[:-1])


def kernel_of(stack: str) -> Optional[str]:
    """The ``name=`` of the ``pallas_call`` the op is: the component in front
    of ``pallas_call``. None for any other op, and for a kernel that was given
    no name (the component is then a scope or ``closed_call``: the caller
    decides what an unnamed kernel inside a scope means)."""
    parts = components(stack)
    if len(parts) >= 2 and parts[-1] == "pallas_call":
        return bare(parts[-2])
    return None


def module_of(module_event_name: str) -> Tuple[str, Optional[int]]:
    """``jit_paged_ragged_r16_w128(1234)`` -> (``jit_paged_ragged_r16_w128``, 1234)."""
    m = _MODULE.match(module_event_name)
    return (m.group("name"), int(m.group("id"))) if m else (module_event_name, None)


# ---------------------------------------------------------------------------
# joined to a reduced trace


def kernel_events(names: OpNames, dev, kernels: Sequence[str]) -> Dict[str, List]:
    """Device ``dev``'s leaf events that are the named Pallas kernels."""
    found: Dict[str, List] = {k: [] for k in kernels}
    for ev in dev.leaves:
        if "custom-call" not in ev.name:
            continue
        kernel = kernel_of(names.stack(dev.ordinal, ev.name))
        if kernel in found:
            found[kernel].append(ev)
    return found


def checked_kernel_events(names: OpNames, dev, calls: Dict[str, int], module_prefix: str = "") -> Optional[Dict[str, List]]:
    """``kernel_events`` after a count check: every program execution that
    lies whole inside the slice and holds any of these kernels (with
    ``module_prefix``: every one whose module name starts with it) must hold
    exactly ``calls[kernel]`` events of each, and at least one execution must
    be checked. A kernel called more or less often than the model's layers
    need is a changed program and must raise, not read as a gain. None where
    the trace names none of the kernels (a program without ``name=``)."""
    found = kernel_events(names, dev, list(calls))
    if not any(found.values()):
        return None
    checked = 0
    for m in dev.whole_modules:
        counts = {k: sum(1 for ev in evs if m.start <= ev.start and ev.end <= m.end) for k, evs in found.items()}
        if module_of(m.name)[0].startswith(module_prefix) if module_prefix else any(counts.values()):
            checked += 1
            if counts != calls:
                raise ValueError(f"device {dev.ordinal}: {m.name[:60]} holds kernel calls {counts} by name, the model needs {calls}")
    if not checked:
        raise ValueError(f"device {dev.ordinal}: no whole execution of a {module_prefix or 'kernel-holding'} program in the slice")
    return found


def scope_self_time(names: OpNames, dev, scope: str) -> float:
    """Seconds of device time in leaf ops traced inside ``scope``."""
    memo: Dict[str, bool] = {}
    total = 0.0
    for ev in dev.leaves:
        inside = memo.get(ev.name)
        if inside is None:
            inside = memo[ev.name] = in_scope(names.stack(dev.ordinal, ev.name), scope)
        if inside:
            total += ev.duration
    return total


def scope_share(trace, cell: Dict, scope: str) -> Optional[float]:
    """Percent of device busy time in ops inside ``scope``, mean over the
    chips; None where no op of the trace names the scope."""
    names = of_cell(cell)
    shares = [scope_self_time(names, dev, scope) / dev.busy_s() for dev in trace.devices]
    return 100.0 * sum(shares) / len(shares) if any(shares) else None
