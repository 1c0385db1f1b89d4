"""Post-optimization HLO text parsing.

The analysis passes that need *compile-time truth* — which donated buffers
XLA actually aliased, which collectives GSPMD actually inserted, whether a
host round-trip survived into the executable — read it from
``compiled.as_text()``. Lowered StableHLO is not enough: SPMD partitioning
inserts the collectives and the alias table is only fixed at compile time.

Everything here is plain-text parsing of the stable parts of HLO syntax
(``HloModule`` header attributes, ``%name = shape op-name(...)`` op lines);
each helper degrades to "no results" rather than raising when the dialect
drifts, so analysis stays best-effort on new XLA releases.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Set

# HLO primitive-type byte widths (packed 4-bit types round up per element)
_DTYPE_BYTES = {
    "pred": 1,
    "s2": 1, "u2": 1, "s4": 1, "u4": 1,
    "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}
# f8e4m3fn / f8e5m2 / f8e4m3b11fnuz ... — all one byte
_F8_RE = re.compile(r"^f8e\w+$")

_SHAPE_RE = re.compile(r"\b(pred|[suf]\d+|bf16|c64|c128|f8e\w+)\[([\d,]*)\]")

# collective op names as they appear in optimized HLO; async pairs are
# counted once on the -start half
COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
    "collective-broadcast",
)
_COLLECTIVE_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[\w\[\]{},]+))\s+("
    + "|".join(re.escape(op) for op in COLLECTIVE_OPS)
    + r")(-start|-done)?\("
)

# host-boundary ops: infeed/outfeed/send/recv plus python-callback
# custom-calls (pure_callback / io_callback / debug lowerings)
_HOST_OP_RE = re.compile(r"=\s*(?:\([^)]*\)|[\w\[\]{},]+)\s+(infeed|outfeed|send|recv)\(")
_CALLBACK_TARGET_RE = re.compile(
    r'custom_call_target="([^"]*(?:callback|python|host)[^"]*)"', re.IGNORECASE
)
_METADATA_OP_RE = re.compile(r'op_name="([^"]*)"')


def dtype_bytes(dtype: str) -> int:
    if dtype in _DTYPE_BYTES:
        return _DTYPE_BYTES[dtype]
    if _F8_RE.match(dtype):
        return 1
    return 4  # unknown type: assume word-sized rather than dropping the op


def _shapes_bytes(shapes) -> int:
    """Total bytes of ``(dtype, dims)`` pairs as matched by ``_SHAPE_RE`` —
    the ONE copy of the byte-accounting math every extractor shares."""
    total = 0
    for dtype, dims in shapes:
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * dtype_bytes(dtype)
    return total


def shape_list_bytes(shape_str: str) -> int:
    """Total bytes of every ``dtype[dims]`` shape inside ``shape_str``
    (handles tuple shapes: ``(f32[2,4]{1,0}, f32[])``). Shapes in optimized
    SPMD HLO are per-partition, so the result is bytes *per participating
    device*."""
    return _shapes_bytes(_SHAPE_RE.findall(shape_str))


def async_start_result_bytes(shape_str: str) -> int:
    """Bytes of the RESULT half of an async ``-start`` bundle shape
    (``(operands..., results...)``) — the convention that keeps sync and
    async lowerings of one collective reporting identical totals (operands
    would otherwise double-count). Trailing ``u32[]``/``s32[]`` scalars are
    scheduler context, not payload (collective-permute-start's
    ``(src, dest, u32[], u32[])`` form) — counting them as the "result
    half" would report ~8 bytes for an N-element permute. Falls back to
    every payload shape when the bundle doesn't split evenly."""
    shapes = _SHAPE_RE.findall(shape_str)
    while shapes and shapes[-1][0] in ("u32", "s32") and not shapes[-1][1]:
        shapes = shapes[:-1]
    if len(shapes) >= 2 and len(shapes) % 2 == 0:
        shapes = shapes[len(shapes) // 2 :]
    return _shapes_bytes(shapes)


def module_header(hlo_text: str) -> str:
    for line in hlo_text.splitlines():
        if line.startswith("HloModule"):
            return line
    return ""


def parse_input_output_aliases(hlo_text: str) -> Set[int]:
    """Parameter indices the compiled module aliases to an output — the
    donations XLA honored. Parsed from the header's
    ``input_output_alias={ {out}: (param, {path}, kind), ... }`` table."""
    header = module_header(hlo_text)
    m = re.search(r"input_output_alias=\{(.*?)\},\s*\w+=", header)
    if m is None:
        # table may be last attribute on the line
        m = re.search(r"input_output_alias=\{(.*)\}", header)
    if m is None:
        return set()
    return {int(p) for p in re.findall(r":\s*\(\s*(\d+)", m.group(1))}


_PARAM_LINE_RE = re.compile(
    r"=\s*((?:\((?:[^()]|\([^()]*\))*\))|[\w\[\]{},]+)\s+parameter\((\d+)\)"
)
_ENTRY_RESULT_RE = re.compile(r"->\s*(.*?)\s*\{\s*$")


def entry_parameter_shapes(hlo_text: str) -> Dict[int, str]:
    """{parameter index: shape string} of the ENTRY computation — the
    per-chip input buffers of the compiled executable (shapes in optimized
    SPMD HLO are per-partition). Best-effort: unparseable lines drop out."""
    out: Dict[int, str] = {}
    in_entry = False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            in_entry = True
            continue
        if not in_entry:
            continue
        m = _PARAM_LINE_RE.search(line)
        if m:
            out[int(m.group(2))] = m.group(1)
        if line.strip() == "}":
            break
    return out


def entry_result_shape(hlo_text: str) -> Optional[str]:
    """Shape string of the ENTRY computation's result (the ``-> shape {``
    of its header; falls back to the ROOT instruction line), or None."""
    in_entry = False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            in_entry = True
            m = _ENTRY_RESULT_RE.search(line)
            if m:
                return m.group(1)
            continue
        if not in_entry:
            continue
        s = line.strip()
        if s.startswith("ROOT "):
            m = re.search(r"=\s*((?:\((?:[^()]|\([^()]*\))*\))|[\w\[\]{},]+)\s+", s)
            if m:
                return m.group(1)
        if s == "}":
            break
    return None


def entry_parameter_count(hlo_text: str) -> Optional[int]:
    """Number of entry-computation parameters, or None if unparseable.
    Used to detect argument pruning (``len(flat args_info)`` mismatch)."""
    lines = hlo_text.splitlines()
    start = None
    for i, line in enumerate(lines):
        if line.startswith("ENTRY "):
            start = i
            break
    if start is None:
        return None
    idxs = []
    for line in lines[start:]:
        idxs.extend(int(i) for i in re.findall(r"=\s*[\w\[\]{},()]+\s+parameter\((\d+)\)", line))
        if line.strip() == "}":
            break
    return (max(idxs) + 1) if idxs else 0


def collect_collectives(hlo_text: str) -> Dict[str, Dict[str, Any]]:
    """Static collective schedule: per op kind, occurrence count and total
    payload bytes (per participating device, summed over occurrences).
    Async ``-start``/``-done`` pairs count once, on the start half —
    counting only the RESULT half of the start's ``(operands..., results...)``
    bundle shape, so sync and async lowerings of the same program report
    identical byte totals (async starts would otherwise double-count every
    operand)."""
    out: Dict[str, Dict[str, Any]] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        shape_str, op, suffix = m.group(1), m.group(2), m.group(3)
        if suffix == "-done":
            continue
        rec = out.setdefault(op, {"count": 0, "bytes": 0})
        rec["count"] += 1
        if suffix == "-start":
            rec["bytes"] += async_start_result_bytes(shape_str)
        else:
            rec["bytes"] += shape_list_bytes(shape_str)
    return out


# quantized wire dtypes: the EQuARX-style exchanges move int8 (or packed
# sub-byte / f8) payloads — 1 byte on the wire where fp32 moves 4
_QUANT_DTYPE_RE = re.compile(r"^([su](2|4|8)|f8e\w+)$")
# replica group forms: explicit {{0,1,2,3},{4,5,6,7}}, iota [2,4]<=[8],
# and the empty form {} (= one group of ALL participating devices)
_GROUPS_FIRST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_GROUPS_EMPTY_RE = re.compile(r"replica_groups=\{\s*\}")
_NUM_PARTITIONS_RE = re.compile(r"num_partitions=(\d+)")


def module_num_partitions(hlo_text: str) -> Optional[int]:
    """``num_partitions`` from the module header — the world size the
    empty ``replica_groups={}`` form implies."""
    m = _NUM_PARTITIONS_RE.search(module_header(hlo_text))
    return int(m.group(1)) if m else None


def replica_group_size(attrs: str, world: Optional[int] = None) -> Optional[int]:
    """Participants per replica group of a collective op line.
    ``replica_groups={}`` (XLA's spelling for one group of every
    participating device) resolves to ``world`` (the module's
    num_partitions) when given. None when absent/unparseable —
    best-effort contract."""
    m = _GROUPS_FIRST_RE.search(attrs)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(attrs)
    if m:
        return int(m.group(2))
    if _GROUPS_EMPTY_RE.search(attrs):
        return world
    return None


def wire_factor(op: str, group: Optional[int]) -> float:
    """Per-device wire bytes of a collective as a multiple of its payload
    bytes, under the standard ring/bidirectional cost model: an all-reduce
    moves its payload twice (reduce-scatter + all-gather phases, each
    ``(g-1)/g``); gather/scatter/exchange ops move it once. The factor is
    what turns the static payload schedule into the comm cost model PERF.md
    budgets (and what makes "int8 exchange = fp all-reduce / 4" an exact
    accounting identity: 2·(g-1)/g·4N fp bytes vs 2·(g-1)/g·N int8 bytes)."""
    if group is None or group <= 1:
        return 0.0 if group == 1 else 1.0
    frac = (group - 1) / group
    if op == "all-reduce":
        return 2.0 * frac
    if op in ("all-gather", "reduce-scatter", "all-to-all", "collective-broadcast"):
        return frac
    return 1.0  # collective-permute and anything unrecognized: one hop


def _payload_shapes(shape_str: str, is_start: bool):
    """(dtype, dims) payload pairs of one collective's shape string, with
    the async ``-start`` operand half trimmed per
    ``async_start_result_bytes``'s convention."""
    shapes = _SHAPE_RE.findall(shape_str)
    if is_start:
        while shapes and shapes[-1][0] in ("u32", "s32") and not shapes[-1][1]:
            shapes = shapes[:-1]
        if len(shapes) >= 2 and len(shapes) % 2 == 0:
            shapes = shapes[len(shapes) // 2 :]
    return shapes


def collect_collective_details(hlo_text: str) -> List[Dict[str, Any]]:
    """Per-occurrence collective records with dtype-aware byte accounting:
    ``{op, bytes, wire_bytes, quantized_bytes, quantized_wire_bytes,
    fp_equiv_wire_bytes, group}``. ``bytes`` matches
    ``collect_collectives``'s payload accounting; ``wire_bytes`` applies
    the per-device ring cost model (``wire_factor``); the ``quantized_*``
    fields isolate sub-byte/int8/f8 payloads (the EQuARX exchanges) and
    ``fp_equiv_wire_bytes`` prices the same element count at fp32 — the
    comparison the quantized-comms acceptance gate asserts."""
    out: List[Dict[str, Any]] = []
    world = module_num_partitions(hlo_text)
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        shape_str, op, suffix = m.group(1), m.group(2), m.group(3)
        if suffix == "-done":
            continue
        shapes = _payload_shapes(shape_str, suffix == "-start")
        group = replica_group_size(line, world=world)
        wf = wire_factor(op, group)
        rec = {
            "op": op,
            "group": group,
            "bytes": 0,
            "wire_bytes": 0.0,
            "quantized_bytes": 0,
            "quantized_wire_bytes": 0.0,
            "fp_equiv_wire_bytes": 0.0,
        }
        for dtype, dims in shapes:
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            b = n * dtype_bytes(dtype)
            rec["bytes"] += b
            rec["wire_bytes"] += b * wf
            if _QUANT_DTYPE_RE.match(dtype):
                rec["quantized_bytes"] += b
                rec["quantized_wire_bytes"] += b * wf
                rec["fp_equiv_wire_bytes"] += n * 4 * wf
        out.append(rec)
    return out


class HloInstruction:
    """One parsed op line of an HLO computation."""

    __slots__ = ("name", "op", "suffix", "shape_str", "operands", "attrs", "index")

    def __init__(self, name, op, suffix, shape_str, operands, attrs, index):
        self.name = name
        self.op = op  # base op name ("all-gather", "fusion", "dot", ...)
        self.suffix = suffix  # "-start" | "-done" | ""
        self.shape_str = shape_str
        self.operands = operands  # %-referenced names (over-approximate)
        self.attrs = attrs  # raw text after the operand list
        self.index = index  # position in the computation (the schedule
        # order: optimized modules carry is_scheduled=true)


_COMP_HEADER_RE = re.compile(r"^(ENTRY\s+)?%([\w.$-]+)\s*\(.*\)\s*->.*\{\s*$")
# the shape is matched lazily, up to the first `` opcode(``: no shape holds a
# space followed by a word and ``(``. A pattern of balanced parentheses
# stops short twice over and silently drops the instruction, which would let
# an exposed loop collective go unseen by the overlap pass: variadic async
# combiner starts (TPU AllGatherCombiner et al.) have ``((operands...),
# (results...))`` bundle shapes, and a TPU module writes a tiled layout into
# every shape (``bf16[8,128]{1,0:T(8,128)(2,1)S(1)}``)
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.$-]+)\s*=\s*(.+?)\s+([a-z][\w-]*)\("
)
_REF_RE = re.compile(r"%([\w.$-]+)")
_ASYNC_SUFFIX_RE = re.compile(r"^(.*?)(-start|-done)$")


def parse_computations(hlo_text: str):
    """{computation name: [HloInstruction]} for every computation in the
    module, plus the entry computation's name. Operand lists are the
    %-referenced names on the op line — an over-approximation (attribute
    refs like ``calls=%fused_computation.2`` point at computations, which
    never collide with same-computation instruction names, so they drop out
    of the dependency maps)."""
    comps: Dict[str, List[HloInstruction]] = {}
    entry: Optional[str] = None
    cur: Optional[str] = None
    for line in hlo_text.splitlines():
        h = _COMP_HEADER_RE.match(line)
        if h:
            cur = h.group(2)
            comps[cur] = []
            if h.group(1):
                entry = cur
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, shape_str, opname = m.group(1), m.group(2), m.group(3)
        suffix = ""
        am = _ASYNC_SUFFIX_RE.match(opname)
        if am and am.group(1) in COLLECTIVE_OPS:
            opname, suffix = am.group(1), am.group(2)
        rest = line[m.end() :]
        operands = [r for r in _REF_RE.findall(rest) if r != name]
        comps[cur].append(
            HloInstruction(
                name, opname, suffix, shape_str, operands, rest, len(comps[cur])
            )
        )
    return comps, entry


def while_body_computations(hlo_text: str) -> Set[str]:
    """Names of computations executed as while-loop bodies (the lowered form
    of ``lax.scan`` — where the training layer pipeline lives)."""
    return set(re.findall(r"body=%([\w.$-]+)", hlo_text))


def instruction_bytes(instr: "HloInstruction") -> int:
    """Result payload bytes of one instruction. Async ``-start`` bundle
    shapes carry ``(operands..., results...)`` — count the result half so
    sync and async lowerings report identical totals (collect_collectives'
    convention)."""
    if instr.suffix == "-start":
        return async_start_result_bytes(instr.shape_str)
    return shape_list_bytes(instr.shape_str)


def find_host_ops(hlo_text: str) -> List[Dict[str, str]]:
    """Host-boundary ops that survived into the executable: infeed/outfeed/
    send/recv and python-callback custom-calls, each with the jax op_name
    from its metadata when present."""
    found: List[Dict[str, str]] = []
    for line in hlo_text.splitlines():
        m = _HOST_OP_RE.search(line)
        kind = None
        if m:
            kind = m.group(1)
        else:
            cb = _CALLBACK_TARGET_RE.search(line)
            if cb and "custom-call" in line:
                kind = f"custom-call:{cb.group(1)}"
        if kind is None:
            continue
        meta = _METADATA_OP_RE.search(line)
        found.append({"op": kind, "jax_op": meta.group(1) if meta else ""})
    return found


# ---------------------------------------------------------------------------
# the computation graph: who calls whom, where the matmuls are
# ---------------------------------------------------------------------------
REAL_COMPUTE_OPS = {"dot", "convolution"}

_CALLEE_REF_RE = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation|"
    r"branch_computations)=\{?%([\w.$-]+)"
)
_CALLEE_REF_LIST_RE = re.compile(r"branch_computations=\{([^}]*)\}")


def callee_refs(attrs: str) -> Set[str]:
    refs = set(_CALLEE_REF_RE.findall(attrs))
    for m in _CALLEE_REF_LIST_RE.finditer(attrs):
        refs.update(re.findall(r"%([\w.$-]+)", m.group(1)))
    return refs


def computation_callees(comps) -> Dict[str, Set[str]]:
    """{computation: called-computation names} (fusion ``calls=``, while
    bodies/conditions, conditional branches, ``to_apply=``) — the one
    regex walk over every instruction's attrs, shared by transitive loop
    membership and compute reachability so the two always agree."""
    return {
        cname: set().union(*[callee_refs(i.attrs) for i in instrs]) if instrs else set()
        for cname, instrs in comps.items()
    }


def loop_computations(hlo_text: str, callees: Dict[str, Set[str]]) -> Set[str]:
    """While bodies and everything they call. Loop membership is
    TRANSITIVE: a computation called from a while body (a cond branch, a
    to_apply/call target, a nested loop) executes once per iteration too —
    a collective there is just as serialized as one directly in the body."""
    loop_comps = set(while_body_computations(hlo_text))
    frontier = list(loop_comps)
    while frontier:
        for ref in callees.get(frontier.pop(), ()):
            if ref not in loop_comps:
                loop_comps.add(ref)
                frontier.append(ref)
    return loop_comps


def computations_with_compute(comps, callees: Dict[str, Set[str]]) -> Set[str]:
    """Computation names that (transitively, through ``callees``) contain a
    dot/convolution — the "real compute" a collective can hide behind.
    Elementwise fusions don't count: a schedule is only overlapped if there
    is MXU-shaped work to run during the DMA."""
    has = {
        cname
        for cname, instrs in comps.items()
        if any(i.op in REAL_COMPUTE_OPS for i in instrs)
    }
    changed = True
    while changed:  # fixpoint: a computation calling a compute-bearing one counts too
        changed = False
        for cname, refs in callees.items():
            if cname not in has and refs & has:
                has.add(cname)
                changed = True
    return has


def is_real_compute(instr: "HloInstruction", compute_comps: Set[str]) -> bool:
    """dot/conv, or a fusion/conditional/while/call whose (transitive)
    callee computations contain one — a cond-wrapped attention block or a
    nested scan is schedulable work a collective can hide behind."""
    if instr.op in REAL_COMPUTE_OPS:
        return True
    if instr.op in ("fusion", "conditional", "while", "call"):
        return bool(callee_refs(instr.attrs) & compute_comps)
    return False


# ---------------------------------------------------------------------------
# the forms the TPU compiler writes a collective in
# ---------------------------------------------------------------------------
_TILED_LAYOUT_RE = re.compile(r"\{[\d,]*:T\(")
_CHAIN_ID_RE = re.compile(r'chain_id="(\d+)"')
_FUSION_CALLS_RE = re.compile(r"calls=%([\w.$-]+)")


def is_tpu_module(hlo_text: str) -> bool:
    """A tiled layout (``{1,0:T(8,128)(2,1)}``) is written by the TPU
    compiler alone: its modules are scheduled for one serial operations
    line, where a synchronous collective runs with nothing beside it."""
    return _TILED_LAYOUT_RE.search(hlo_text) is not None


def collective_schedule(hlo_text: str) -> List[Dict[str, Any]]:
    """Every collective the module EXECUTES (the entry's, the loop bodies',
    a called computation's; what stands inside a fusion is read through the
    fusion), one record each, in the form the compiler scheduled it:

    * ``sync`` — ``all-gather(...)`` and its kin as plain instructions (one
      with ``async_collective_name`` in its attributes was made asynchronous,
      found nothing between its halves and was folded back);
    * ``fused_sync`` — a ``fusion`` whose computation holds the collective
      and no chain: the TPU's fused reduce-scatter, ``calls=%all-reduce-
      scatter*`` (``op`` says ``all-reduce-scatter``), runs on the core like
      any other fusion;
    * ``start_done`` — a ``-start`` / ``-done`` pair;
    * ``fusion_chain`` — the TPU's ``async_collective_fusion``: a fusion that
      starts the collective (``AsyncCollectiveStart``), fusions that carry it
      on beside a matmul of their own, one that ends it
      (``AsyncCollectiveDone``), tied by the ``chain_id`` of the collective
      each holds. A chain belongs to the computation that STARTS it: one
      started before a loop may ride the loop's matmuls and end behind it.

    ``compute_between`` (the asynchronous forms): a dot/convolution, alone or
    in a fusion, is scheduled between the two halves and does not read the
    start — for a chain, one of its fusions holds a matmul of its own.
    ``independent_compute`` (the synchronous forms): the computation holds a
    dot/convolution with no dependency path to or from the collective — work
    a scheduler would be free to overlap, where there is one. ``bytes`` is the
    collective's result, per device; ``quantized`` says a sub-byte / int8 /
    f8 payload."""
    comps, _entry = parse_computations(hlo_text)
    callees = computation_callees(comps)
    loops = loop_computations(hlo_text, callees)
    compute_comps = computations_with_compute(comps, callees)
    fused = {
        callee
        for instrs in comps.values()
        for i in instrs
        if i.op == "fusion"
        for callee in _FUSION_CALLS_RE.findall(i.attrs)
    }

    def held_by(instr):
        """(collective inside a fusion's computation, that computation's name)"""
        for callee in _FUSION_CALLS_RE.findall(instr.attrs):
            for inner in comps.get(callee, ()):
                if inner.op in COLLECTIVE_OPS:
                    return inner, callee
        return None, None

    out: List[Dict[str, Any]] = []
    chains: Dict[str, Dict[str, Any]] = {}
    for cname, instrs in comps.items():
        if cname in fused:
            continue
        succ: Dict[str, List[str]] = {i.name: [] for i in instrs}
        pred: Dict[str, List[str]] = {i.name: [] for i in instrs}
        for i in instrs:
            for o in i.operands:
                if o in succ:
                    succ[o].append(i.name)
                    pred[i.name].append(o)
        compute = [i for i in instrs if is_real_compute(i, compute_comps)]

        def reach(name, edges):
            seen, frontier = {name}, [name]
            while frontier:
                for nxt in edges.get(frontier.pop(), ()):
                    if nxt not in seen:
                        seen.add(nxt)
                        frontier.append(nxt)
            return seen

        def between(start, done):
            after = reach(start.name, succ)
            return any(start.index < x.index < done.index and x.name not in after for x in compute)

        def record(instr, held, op, form, **more):
            start = held.suffix == "-start"
            out.append(
                {
                    "computation": cname,
                    "name": instr.name,
                    "op": op,
                    "form": form,
                    "bytes": instruction_bytes(held),
                    "in_loop": cname in loops,
                    "quantized": any(_QUANT_DTYPE_RE.match(d) for d, _ in _payload_shapes(held.shape_str, start)),
                    "folded_back": "async_collective_name" in instr.attrs,
                    "compute_between": None,
                    "independent_compute": None,
                    **more,
                }
            )

        def record_sync(instr, held, op, form):
            related = reach(instr.name, succ) | reach(instr.name, pred)
            record(instr, held, op, form, independent_compute=any(x.name not in related for x in compute))

        for i in instrs:
            if i.op in COLLECTIVE_OPS and i.suffix != "-done":
                done = None
                if i.suffix == "-start":
                    done = next(
                        (j for j in instrs if j.op == i.op and j.suffix == "-done" and i.name in j.operands), None
                    )
                if done is not None:
                    record(i, i, i.op, "start_done", compute_between=between(i, done))
                else:
                    record_sync(i, i, i.op, "sync")
            elif i.op == "fusion":
                inner, callee = held_by(i)
                if inner is None:
                    continue
                chain = _CHAIN_ID_RE.search(inner.attrs)
                if chain is None:
                    op = "all-reduce-scatter" if callee.startswith("all-reduce-scatter") else inner.op
                    record_sync(i, inner, op, "fused_sync")
                else:
                    # a chain belongs to the computation that starts it; its other pieces may lie in a loop it rides through
                    seen = chains.setdefault(chain.group(1), {"rides": False})
                    seen["rides"] |= callee in compute_comps
                    if any('"AsyncCollectiveStart"' in x.attrs for x in comps[callee]):
                        record(i, inner, inner.op, "fusion_chain")
                        seen["record"] = out[-1]
    for chain in chains.values():
        if "record" in chain:
            chain["record"]["compute_between"] = chain["rides"]
    return out


def loop_schedule_summary(records: List[Dict[str, Any]]) -> Dict[str, int]:
    """The loop bodies' collectives of :func:`collective_schedule`, counted:
    how many there are, how many are asynchronous with a matmul between
    their halves, and how many (with how many bytes) are left on the core."""
    loop = [r for r in records if r["in_loop"]]
    hidden = [r for r in loop if r["compute_between"]]
    return {
        "loop_collectives": len(loop),
        "async_with_compute_between": len(hidden),
        "sync_on_core": len(loop) - len(hidden),
        "sync_bytes": sum(r["bytes"] for r in loop if not r["compute_between"]),
    }
