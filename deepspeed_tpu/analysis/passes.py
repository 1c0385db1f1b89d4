"""Program-analysis passes over the engine's jitted programs.

Each pass consumes a :class:`ProgramArtifact` — a lazily traced / lowered /
compiled view of one named engine program, rebuilt from the abstract call
signature the compile telemetry captured at the program's cold dispatch —
and returns a :class:`PassResult` of violations + a machine-readable
summary. The properties the passes check are exactly the runtime guarantees
the engine claims (PR 1/2 asserted them ad hoc per test):

* ``donation``    — every declared donated argument is honored as an
  input/output alias in the compiled executable; unhonored donations are
  reported with the bytes they double-buffer (ZeRO's "no second copy of the
  training state" invariant, statically).
* ``dtype_promotion`` — no f32 matmul/conv is reachable from bf16/fp16
  data through an upcast (master-weight and softmax-boundary math is
  allowlisted structurally: elementwise/reduction f32 is fine, and an
  ``exp`` clears the taint — softmax-in-f32 is deliberate numerics).
* ``host_transfer`` — no callback primitive in the jaxpr and no
  infeed/outfeed/send/recv/python-callback custom-call in the compiled
  module: a hot-loop program must never bounce through the host.
* ``collectives``  — the static communication schedule (count + payload
  bytes per all-reduce/all-gather/reduce-scatter/all-to-all/…): surfaced as
  a summary, and gated when a ``collective_budget_bytes`` is configured
  (EQuARX-style static comms budget).

Passes are registered in ``PROGRAM_PASSES``; ``analyze_program`` runs a
selection against one artifact.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

from . import hlo as hlo_parse


class AnalysisError(RuntimeError):
    """Raised by ``analysis.verify: raise`` when a pass reports an
    error-severity violation on a freshly compiled engine program."""


@dataclass
class Violation:
    pass_name: str
    program: str
    message: str
    severity: str = "error"  # "error" | "warn"
    details: Dict[str, Any] = field(default_factory=dict)

    def as_dict(self) -> Dict[str, Any]:
        return {
            "pass": self.pass_name,
            "program": self.program,
            "message": self.message,
            "severity": self.severity,
            "details": self.details,
        }


@dataclass
class PassResult:
    violations: List[Violation] = field(default_factory=list)
    summary: Dict[str, Any] = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not any(v.severity == "error" for v in self.violations)

    def as_dict(self) -> Dict[str, Any]:
        return {
            "ok": self.ok,
            "violations": [v.as_dict() for v in self.violations],
            "summary": self.summary,
        }


class ProgramArtifact:
    """Lazily materialized views of one jitted program.

    ``trace`` → ``jaxpr`` (cheap, no XLA), ``lowered`` → ``args_info``
    (declared donations), ``compiled`` → optimized HLO text (honored
    aliases, SPMD collectives). Each stage is computed once and shared by
    every pass run against the artifact. Building from abstract
    ShapeDtypeStructs means no device buffer is touched; the cost of a full
    build is one extra trace + compile of the program.
    """

    def __init__(self, name: str, wrapper):
        self.name = name
        self._wrapper = wrapper
        self._traced = None
        self._lowered = None
        self._compiled = None
        self._hlo_text = None

    @property
    def traced(self):
        if self._traced is None:
            self._traced = self._wrapper.trace_abstract()
        return self._traced

    @property
    def jaxpr(self):
        return self.traced.jaxpr

    @property
    def lowered(self):
        if self._lowered is None:
            self._lowered = self.traced.lower()
        return self._lowered

    @property
    def flat_args_info(self) -> List[Any]:
        """Flattened ``jax.stages.ArgInfo`` list: ``.donated`` + shape/dtype
        per flat argument, in lowering parameter order."""
        return jax.tree_util.tree_leaves(self.lowered.args_info)

    @property
    def compiled(self):
        if self._compiled is None:
            self._compiled = self.lowered.compile()
        return self._compiled

    @property
    def hlo_text(self) -> str:
        if self._hlo_text is None:
            self._hlo_text = self.compiled.as_text()
        return self._hlo_text


def _arg_bytes(info) -> int:
    n = 1
    for d in getattr(info, "shape", ()):  # global logical bytes
        n *= int(d)
    try:
        import numpy as np

        return n * int(np.dtype(info.dtype).itemsize)
    except Exception:
        return n * 4


# ---------------------------------------------------------------------------
# donation-aliasing verifier
# ---------------------------------------------------------------------------
def donation_pass(art: ProgramArtifact, config: Optional[Dict[str, Any]] = None) -> PassResult:
    cfg = config or {}
    min_bytes = int(cfg.get("min_donation_bytes", 0))
    res = PassResult()
    infos = art.flat_args_info
    donated_idx = [i for i, a in enumerate(infos) if getattr(a, "donated", False)]
    aliased = hlo_parse.parse_input_output_aliases(art.hlo_text)
    n_params = hlo_parse.entry_parameter_count(art.hlo_text)

    donated_bytes = sum(_arg_bytes(infos[i]) for i in donated_idx)
    res.summary = {
        "declared_donations": len(donated_idx),
        "declared_donated_bytes": donated_bytes,
        "aliased_params": len(aliased),
    }
    if not donated_idx:
        return res

    if not aliased and "input_output_alias" in hlo_parse.module_header(art.hlo_text):
        # the attribute EXISTS in the header but our regex extracted
        # nothing: XLA's text format drifted past the parser. Degrade to a
        # warning (hlo.py's best-effort contract) instead of failing a
        # verify=raise deployment on a parse artifact. (A header with NO
        # input_output_alias attribute is the real "nothing aliased"
        # signal — XLA omits the attribute when the table is empty — and
        # falls through to the hard violations below.)
        res.summary["alias_table"] = "present_but_unparseable"
        res.violations.append(
            Violation(
                "donation",
                art.name,
                f"{len(donated_idx)} donated args; an input_output_alias "
                "attribute exists in the compiled module header but could "
                "not be parsed — donation unverifiable (HLO text drift?)",
                severity="warn",
                details={"donated_bytes": donated_bytes},
            )
        )
        return res

    if n_params is not None and n_params != len(infos):
        # jit pruned unused arguments: flat index ↔ HLO parameter mapping is
        # gone. Fall back to an aggregate check so we still catch "nothing
        # got aliased" without mis-blaming a specific argument.
        res.summary["arg_pruning"] = {"flat_args": len(infos), "hlo_params": n_params}
        if not aliased:
            res.violations.append(
                Violation(
                    "donation",
                    art.name,
                    f"{len(donated_idx)} donated args but the compiled module "
                    "aliases none of its parameters — the whole donated state "
                    f"(~{donated_bytes} bytes) is double-buffered",
                    severity="error" if donated_bytes >= min_bytes else "warn",
                    details={"donated_bytes": donated_bytes},
                )
            )
        elif len(aliased) < len(donated_idx):
            # some donations went unhonored but the pruned index mapping
            # cannot name which: report the shortfall rather than letting a
            # partial regression read as fully verified
            res.violations.append(
                Violation(
                    "donation",
                    art.name,
                    f"only {len(aliased)} of {len(donated_idx)} donated args "
                    "are aliased and argument pruning prevents per-arg "
                    "attribution — donation partially unverifiable",
                    severity="warn",
                    details={"aliased": len(aliased), "donated": len(donated_idx)},
                )
            )
        else:
            res.summary["alias_check"] = "aggregate_only"  # pruned: counts match
        return res

    unhonored = [i for i in donated_idx if i not in aliased]
    wasted = sum(_arg_bytes(infos[i]) for i in unhonored)
    res.summary["unhonored"] = len(unhonored)
    res.summary["double_buffered_bytes"] = wasted
    for i in unhonored:
        info = infos[i]
        b = _arg_bytes(info)
        sev = "error" if b >= min_bytes else "warn"
        res.violations.append(
            Violation(
                "donation",
                art.name,
                f"donated arg {i} ({getattr(info, 'dtype', '?')}"
                f"{list(getattr(info, 'shape', ()))}) is not aliased in the "
                f"compiled module: {b} bytes double-buffered",
                severity=sev,
                details={"arg_index": i, "bytes": b},
            )
        )
    return res


# ---------------------------------------------------------------------------
# jaxpr walking helpers (shared by dtype audit, host-transfer, shape scan)
# ---------------------------------------------------------------------------
def _sub_jaxprs(eqn) -> List[Any]:
    """Every jaxpr-valued param of an equation (pjit/scan/while/cond/
    custom_* call bodies), as ClosedJaxpr-or-Jaxpr objects."""
    subs = []
    for v in eqn.params.values():
        vals = v if isinstance(v, (list, tuple)) else [v]
        for item in vals:
            if hasattr(item, "jaxpr") and hasattr(item, "consts"):  # ClosedJaxpr
                subs.append(item)
            elif hasattr(item, "eqns") and hasattr(item, "invars"):  # Jaxpr
                subs.append(item)
    return subs


def _as_jaxpr(j):
    return j.jaxpr if hasattr(j, "jaxpr") and hasattr(j, "consts") else j


def iter_eqns(jaxpr):
    """Depth-first iteration over every equation, including call/control-flow
    sub-jaxprs (the closed-over bodies GSPMD actually runs)."""
    j = _as_jaxpr(jaxpr)
    for eqn in j.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def find_aval_shapes(jaxpr, shape: Tuple[int, ...]) -> List[Tuple[str, Tuple[int, ...]]]:
    """Equations (recursively) whose output aval matches ``shape`` exactly —
    the structural "does this program materialize a tensor of this shape"
    probe (e.g. the banned NH-wide GQA cache copy)."""
    shape = tuple(shape)
    hits = []
    for eqn in iter_eqns(jaxpr):
        for var in eqn.outvars:
            s = tuple(getattr(getattr(var, "aval", None), "shape", ()) or ())
            if s == shape:
                hits.append((str(eqn.primitive), s))
    return hits


def _src(eqn) -> str:
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info)
        if frame is not None:
            return f"{frame.file_name}:{frame.start_line}"
    except Exception:
        pass
    return ""


# ---------------------------------------------------------------------------
# dtype-promotion audit
# ---------------------------------------------------------------------------
_LOW_DTYPES = ("bfloat16", "float16")
_COMPUTE_PRIMS = {"dot_general", "conv_general_dilated"}
# numerics boundaries: an exp/sigmoid output is a softmax-style probability,
# deliberately computed in f32 — data flowing through it stops being "an
# upcast copy of low-precision values"
_TAINT_BOUNDARY_PRIMS = {"exp", "logistic", "erf"}


def _dtype_of(var) -> str:
    return str(getattr(getattr(var, "aval", None), "dtype", ""))


def _dtype_walk(jaxpr, tainted_in: set, violations: List[Violation], program: str) -> set:
    """Propagate "f32 upcast of low-precision data" taint through one jaxpr.
    ``tainted_in``: ids of tainted invars. Returns ids of tainted outvars."""
    j = _as_jaxpr(jaxpr)
    tainted = set(tainted_in)

    def is_tainted(v):
        return id(v) in tainted

    def is_low(v):
        return _dtype_of(v) in _LOW_DTYPES

    for eqn in j.eqns:
        prim = str(eqn.primitive)
        subs = _sub_jaxprs(eqn)
        if subs:
            # map outer taint positionally into each body (offset from the
            # end: pjit aligns exactly, cond skips the index operand, scan
            # aligns consts+carry+xs) and taint the eqn outputs from the
            # union of body outvar taints (offset from the end again)
            out_taint: set = set()
            for sub in subs:
                sj = _as_jaxpr(sub)
                off = len(eqn.invars) - len(sj.invars)
                sub_in = set()
                for i, sv in enumerate(sj.invars):
                    outer_i = i + off
                    if 0 <= outer_i < len(eqn.invars):
                        ov = eqn.invars[outer_i]
                        if is_tainted(ov):
                            sub_in.add(id(sv))
                sub_out = _dtype_walk(sub, sub_in, violations, program)
                ooff = len(eqn.outvars) - len(sj.outvars)
                for i, sv in enumerate(sj.outvars):
                    outer_i = i + ooff
                    if id(sv) in sub_out and 0 <= outer_i < len(eqn.outvars):
                        out_taint.add(id(eqn.outvars[outer_i]))
            tainted |= out_taint
            continue

        any_tainted_in = any(is_tainted(v) for v in eqn.invars if hasattr(v, "aval"))

        if prim == "convert_element_type":
            (inv,) = [v for v in eqn.invars if hasattr(v, "aval")][:1] or [None]
            outv = eqn.outvars[0]
            if inv is not None and _dtype_of(outv) == "float32" and (
                is_low(inv) or is_tainted(inv)
            ):
                tainted.add(id(outv))
            continue

        if prim in _COMPUTE_PRIMS:
            outv = eqn.outvars[0]
            if _dtype_of(outv) == "float32" and any_tainted_in:
                violations.append(
                    Violation(
                        "dtype_promotion",
                        program,
                        f"f32 {prim} consumes an upcast of bf16/fp16 data "
                        f"({_src(eqn) or 'source unknown'}): compute runs in "
                        "full precision where the model stores half precision",
                        details={"primitive": prim, "source": _src(eqn)},
                    )
                )
                tainted.add(id(outv))
            continue

        if prim in _TAINT_BOUNDARY_PRIMS:
            continue  # outputs are deliberate-f32 numerics, not upcast copies

        if any_tainted_in:
            for outv in eqn.outvars:
                if _dtype_of(outv) == "float32":
                    tainted.add(id(outv))

    return {id(v) for v in j.outvars if id(v) in tainted}


def dtype_promotion_pass(
    art: ProgramArtifact, config: Optional[Dict[str, Any]] = None
) -> PassResult:
    res = PassResult()
    jaxpr = art.jaxpr
    violations: List[Violation] = []
    _dtype_walk(jaxpr, set(), violations, art.name)
    # duplicate sites collapse to one violation per (prim, source)
    seen = set()
    for v in violations:
        key = (v.details.get("primitive"), v.details.get("source"))
        if key in seen:
            continue
        seen.add(key)
        res.violations.append(v)
    low_inputs = sum(
        1 for v in _as_jaxpr(jaxpr).invars if _dtype_of(v) in _LOW_DTYPES
    )
    res.summary = {"low_precision_inputs": low_inputs, "f32_upcast_compute_sites": len(res.violations)}
    return res


# ---------------------------------------------------------------------------
# host-transfer detector
# ---------------------------------------------------------------------------
_CALLBACK_PRIMS = {
    "pure_callback",
    "io_callback",
    "debug_callback",
    "callback",
    "outside_call",
    "host_callback_call",
}


def host_transfer_pass(
    art: ProgramArtifact, config: Optional[Dict[str, Any]] = None
) -> PassResult:
    res = PassResult()
    jaxpr_hits = []
    for eqn in iter_eqns(art.jaxpr):
        prim = str(eqn.primitive)
        if prim in _CALLBACK_PRIMS or prim == "debug_print":
            jaxpr_hits.append({"primitive": prim, "source": _src(eqn)})
    hlo_hits = hlo_parse.find_host_ops(art.hlo_text)
    for h in jaxpr_hits:
        res.violations.append(
            Violation(
                "host_transfer",
                art.name,
                f"host callback primitive {h['primitive']} inside a jitted "
                f"hot-loop program ({h['source'] or 'source unknown'}): every "
                "dispatch round-trips through python",
                details=h,
            )
        )
    # HLO hits: callback custom-calls are the lowered form of the jaxpr
    # callbacks already reported above (suppress those when a jaxpr hit
    # explains them); raw host-boundary ops (infeed/outfeed/send/recv) are
    # ALWAYS violations of their own — a callback elsewhere in the program
    # must not mask them
    for h in hlo_hits:
        is_callback_lowering = h["op"].startswith("custom-call:")
        if is_callback_lowering and jaxpr_hits:
            continue
        res.violations.append(
            Violation(
                "host_transfer",
                art.name,
                f"host-boundary op {h['op']} in the compiled module "
                f"(jax op: {h['jax_op'] or 'unknown'})",
                details=h,
            )
        )
    res.summary = {"jaxpr_callbacks": len(jaxpr_hits), "hlo_host_ops": len(hlo_hits)}
    return res


# ---------------------------------------------------------------------------
# collective schedule extractor
# ---------------------------------------------------------------------------
def collectives_pass(
    art: ProgramArtifact, config: Optional[Dict[str, Any]] = None
) -> PassResult:
    cfg = config or {}
    budget = cfg.get("collective_budget_bytes")
    res = PassResult()
    # ONE line scan: the per-occurrence detail records carry the same
    # payload-byte accounting collect_collectives defined, so the legacy
    # per-op aggregate folds out of them instead of re-parsing the module
    details = hlo_parse.collect_collective_details(art.hlo_text)
    # per-op-kind wire + quantized breakdown (ISSUE 20: the MoE dispatch/
    # combine all-to-alls get the same dtype-aware pricing the quantized
    # all-reduces got — the green gate reads ops["all-to-all"]["quantized"]
    # to assert the int8 arm's wire bytes are exactly fp/4)
    ops: Dict[str, Dict[str, Any]] = {}
    for d in details:
        rec = ops.setdefault(
            d["op"],
            {
                "count": 0,
                "bytes": 0,
                "wire_bytes": 0.0,
                "quantized": {
                    "count": 0,
                    "bytes": 0,
                    "wire_bytes": 0.0,
                    "fp_equiv_wire_bytes": 0.0,
                },
            },
        )
        rec["count"] += 1
        rec["bytes"] += d["bytes"]
        rec["wire_bytes"] += d["wire_bytes"]
        if d["quantized_bytes"]:
            q = rec["quantized"]
            q["count"] += 1
            q["bytes"] += d["quantized_bytes"]
            q["wire_bytes"] += d["quantized_wire_bytes"]
            q["fp_equiv_wire_bytes"] += d["fp_equiv_wire_bytes"]
    for rec in ops.values():
        rec["wire_bytes"] = int(round(rec["wire_bytes"]))
        rec["quantized"]["wire_bytes"] = int(round(rec["quantized"]["wire_bytes"]))
        rec["quantized"]["fp_equiv_wire_bytes"] = int(
            round(rec["quantized"]["fp_equiv_wire_bytes"])
        )
    total_bytes = sum(r["bytes"] for r in ops.values())
    total_count = sum(r["count"] for r in ops.values())
    res.summary = {"ops": ops, "total_bytes": total_bytes, "total_count": total_count}
    # dtype-aware wire accounting (ISSUE 13: quantized TP comms): the ring
    # cost model per occurrence, with int8/f8 payloads — the EQuARX-style
    # quantized all-reduce exchanges — isolated and priced against their
    # fp32 equivalent. Bytes on the wire reflect the QUANTIZED dtype; the
    # fp_equiv comparison is exact (2·(g-1)/g·N int8 vs ·4N fp bytes = /4).
    wire_total = sum(d["wire_bytes"] for d in details)
    q_count = sum(1 for d in details if d["quantized_bytes"])
    q_bytes = sum(d["quantized_bytes"] for d in details)
    q_wire = sum(d["quantized_wire_bytes"] for d in details)
    q_fp_wire = sum(d["fp_equiv_wire_bytes"] for d in details)
    res.summary["wire_bytes"] = int(round(wire_total))
    res.summary["quantized"] = {
        "count": q_count,
        "bytes": q_bytes,
        "wire_bytes": int(round(q_wire)),
        "fp_equiv_wire_bytes": int(round(q_fp_wire)),
        "wire_reduction": (q_fp_wire / q_wire) if q_wire else 0.0,
    }
    if budget is not None and total_bytes > int(budget):
        res.violations.append(
            Violation(
                "collectives",
                art.name,
                f"static collective payload {total_bytes} bytes/device exceeds "
                f"the configured budget {int(budget)}",
                details={"total_bytes": total_bytes, "budget": int(budget), "ops": ops},
            )
        )
    q_budget = cfg.get("quantized_budget_bytes")
    if q_budget is not None and q_wire > int(q_budget):
        res.violations.append(
            Violation(
                "collectives",
                art.name,
                f"quantized collective wire payload {int(round(q_wire))} "
                f"bytes/device exceeds the configured quantized budget "
                f"{int(q_budget)}",
                details={
                    "quantized_wire_bytes": int(round(q_wire)),
                    "budget": int(q_budget),
                },
            )
        )
    return res


# ---------------------------------------------------------------------------
# comm/compute overlap verifier
# ---------------------------------------------------------------------------
def overlap_pass(art: ProgramArtifact, config: Optional[Dict[str, Any]] = None) -> PassResult:
    """Static comm/compute-overlap verifier over the compiled schedule.

    For every collective the optimized module executes, in the form the
    compiler scheduled it (:func:`analysis.hlo.collective_schedule`; the
    order is the schedule: post-optimization HLO is ``is_scheduled=true``):

    * the asynchronous forms (a ``-start``/``-done`` pair; on the TPU an
      ``async_collective_fusion`` chain) are **hidden** when real compute
      (dot/conv, incl. inside fusions) sits between the two halves in
      schedule order without depending on the start, or the chain's own
      fusions hold a matmul — the latency-hiding scheduler actually
      separated them;
    * the synchronous forms (a plain collective; on the TPU also the fused
      ``all-reduce-scatter``, a ``fusion`` by opcode) depend on who compiled
      the module. The TPU's modules are scheduled for one serial operations
      line, so a synchronous collective there runs with nothing beside it:
      **exposed**, whatever else the computation holds. On the CPU mesh
      (no schedule exists) they are **hidden** when the computation
      contains real compute with no dependency path to or from the
      collective — independent work a scheduler would be free to overlap
      (the feasibility the pipelined gather/in-loop reduction create).

    ``overlap_verified`` means no collective inside a while-loop body (the
    scanned layer stack / microbatch loop — the hot path the pipeline owns;
    membership is transitive) is exposed; entry-level tail collectives only
    count toward ``exposed_bytes``. Exposed loop collectives are
    warn-severity findings (error with ``require_overlap``)."""
    cfg = config or {}
    res = PassResult()
    tpu = hlo_parse.is_tpu_module(art.hlo_text)
    n_hidden = n_exposed = hidden_bytes = exposed_bytes = async_pairs = 0
    loop_total = 0
    # quantized loop collectives (the EQuARX exchanges of a quantized TP
    # serving program) verified hidden — the gate asserts the quantized
    # comm schedule was actually SEEN on the hot path, not just absent
    loop_quantized = loop_quantized_hidden = 0
    loop_exposed: List[Dict[str, Any]] = []
    for c in hlo_parse.collective_schedule(art.hlo_text):
        if c["compute_between"] is None:
            hidden = c["independent_compute"] and not tpu
        else:
            async_pairs += 1
            hidden = c["compute_between"]
        if c["in_loop"]:
            loop_total += 1
            if c["quantized"]:
                loop_quantized += 1
                if hidden:
                    loop_quantized_hidden += 1
        if hidden:
            n_hidden += 1
            hidden_bytes += c["bytes"]
        else:
            n_exposed += 1
            exposed_bytes += c["bytes"]
            if c["in_loop"]:
                loop_exposed.append(
                    {"computation": c["computation"], "op": c["op"], "name": c["name"], "bytes": c["bytes"]}
                )

    verified = not loop_exposed
    res.summary = {
        "collectives": n_hidden + n_exposed,
        "hidden_count": n_hidden,
        "exposed_count": n_exposed,
        "hidden_bytes": hidden_bytes,
        "exposed_bytes": exposed_bytes,
        "async_pairs": async_pairs,
        "loop_collectives": loop_total,
        "loop_quantized": loop_quantized,
        "loop_quantized_hidden": loop_quantized_hidden,
        "loop_exposed": loop_exposed,
        "overlap_verified": verified,
    }
    severity = "error" if cfg.get("require_overlap") else "warn"
    for e in loop_exposed:
        res.violations.append(
            Violation(
                "overlap",
                art.name,
                f"{e['op']} ({e['bytes']} bytes/device) in loop body "
                f"{e['computation']} has no independent compute to hide "
                "behind: the collective is exposed on the critical path",
                severity=severity,
                details=e,
            )
        )

    # host-stream accounting mode (ZeRO-Infinity offload, ISSUE 16): the
    # engine declares its H2D/D2H stream schedule — per-bucket transfers,
    # each naming the compute program it hides behind — anchored to one
    # analyzed program. Transfers with no hiding program (pipeline knob
    # off), or naming a program NOT in the declared compute set (a schedule
    # cannot smuggle transfers behind phantom work), count as EXPOSED
    # stream bytes; the CI gate pins exposed_stream_bytes == 0.
    stream = cfg.get("offload_stream")
    if stream and art.name == stream.get("anchor"):
        known = set(stream.get("compute_programs", ()))
        transfers = list(stream.get("transfers", ()))
        s_h2d = s_d2h = s_exposed = 0
        stream_exposed: List[Dict[str, Any]] = []
        for t in transfers:
            b = int(t.get("bytes", 0))
            if t.get("direction") == "h2d":
                s_h2d += b
            else:
                s_d2h += b
            hide = t.get("hide_behind")
            if not hide or hide not in known:
                s_exposed += b
                stream_exposed.append(dict(t))
        res.summary.update(
            {
                "stream_transfers": len(transfers),
                "stream_h2d_bytes": s_h2d,
                "stream_d2h_bytes": s_d2h,
                "exposed_stream_bytes": s_exposed,
                "stream_exposed": stream_exposed,
                "stream_verified": s_exposed == 0,
            }
        )
        for t in stream_exposed:
            hide = t.get("hide_behind")
            why = (
                f"declares hiding program {hide!r} which is not in the "
                "declared compute set"
                if hide
                else "declares no hiding compute (pipeline knob off?)"
            )
            res.violations.append(
                Violation(
                    "overlap",
                    art.name,
                    f"offload {t.get('direction')} stream transfer "
                    f"{t.get('name')} ({t.get('bytes')} bytes) "
                    f"{why}: the stream is exposed on the step critical path",
                    severity=severity,
                    details=dict(t),
                )
            )
        budget = cfg.get("stream_budget_bytes")
        if budget is not None and budget >= 0 and (s_h2d + s_d2h) > budget:
            res.violations.append(
                Violation(
                    "overlap",
                    art.name,
                    f"declared offload stream traffic {s_h2d + s_d2h} bytes "
                    f"exceeds analysis.stream_budget_bytes={budget}",
                    severity="error",
                    details={"h2d_bytes": s_h2d, "d2h_bytes": s_d2h},
                )
            )
    return res


PROGRAM_PASSES: Dict[str, Callable[[ProgramArtifact, Optional[Dict[str, Any]]], PassResult]] = {
    "donation": donation_pass,
    "dtype_promotion": dtype_promotion_pass,
    "host_transfer": host_transfer_pass,
    "collectives": collectives_pass,
    "overlap": overlap_pass,
}


def analyze_program(
    name: str,
    wrapper,
    passes: Optional[Sequence[str]] = None,
    config: Optional[Dict[str, Any]] = None,
) -> Dict[str, PassResult]:
    """Run the selected passes (default: all) against one instrumented
    program. ``wrapper`` is a telemetry ``InstrumentedFunction`` (anything
    with ``trace_abstract()``)."""
    art = ProgramArtifact(name, wrapper)
    selected = list(passes) if passes else list(PROGRAM_PASSES)
    out: Dict[str, PassResult] = {}
    for pname in selected:
        if pname not in PROGRAM_PASSES:
            raise KeyError(
                f"unknown analysis pass {pname!r}; available: {sorted(PROGRAM_PASSES)}"
            )
        out[pname] = PROGRAM_PASSES[pname](art, config)
    return out
