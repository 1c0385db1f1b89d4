"""Repo AST lint: the python-level hazards this codebase has been bitten by.

The program passes see what XLA compiled; this lint sees what python will do
*before* tracing ever happens — the class of bug that never reaches an HLO.
Rules (each one traces back to a real incident in PERF.md / PR history):

* **DS-R001 repeat-on-cache** — ``jnp.repeat`` applied to a cache-like
  array (k/v/cache/page/pool names): materializes a G-times copy of the
  widest buffer in the program (the PR-2 GQA decode blowup).
* **DS-R002 host-sync-in-jit** — ``.item()`` / ``float()`` / ``int()`` /
  ``bool()`` / ``np.asarray`` / ``jax.device_get`` applied to traced values
  inside a jitted function: a ConcretizationTypeError at best, a silent
  per-step host round-trip at worst.
* **DS-R003 shape-branch-in-jit** (warn) — python ``if`` on ``.shape`` /
  ``len()`` inside a jitted function: every new shape recompiles the
  program (fine when deliberate — annotate with a pragma).
* **DS-R004 jit-missing-donation** (warn) — a ``jax.jit`` / ``instrument``
  call without ``donate_argnums`` whose wrapped function takes a
  buffer-named parameter (grad_acc/opt_state/master/cache/pages/...):
  likely double-buffering a state-sized array.
* **DS-R005 host-transfer-in-serving-loop** — ``jax.device_get`` /
  ``.item()`` / ``np.asarray``-on-a-device-value inside the serving step
  loop (the step/round methods of a ``*Server`` / ``*Scheduler`` class,
  and the routing methods — apply/gate/dispatch/combine — of a ``*Gate``
  / ``*MoE`` / ``*MoELayer`` class, which run inside every traced step):
  every fetch beyond the one budgeted token fetch per dispatch adds a
  synchronous device round trip (per-dispatch host cost, not measured on
  this chip) to EVERY serving round. The
  sanctioned single fetch per dispatch carries a pragma.
* **DS-R006 blocking-gather-in-scan-body** — a direct ``lax.all_gather`` /
  ``lax.psum`` on parameter-named values inside a function used as a
  ``lax.scan`` body: in the scanned layer stack those gathers belong to
  the comm-overlap pipeline (``zero.prefetch_layers``,
  ``runtime/zero/overlap.py``), which issues them a layer ahead of use —
  a hand-rolled blocking collective at the use point serializes the loop
  schedule the pipeline exists to overlap. Deliberate non-parameter or
  non-pipelined collectives carry a pragma.
* **DS-R008 non-atomic-persistence-write** — ``open(path, "w"/"wb")`` in a
  checkpoint / journal / bench-record code path (path or enclosing
  function named like one): a ``kill -9`` mid-write leaves a torn file
  that the ``latest`` marker, the known-good store, or a journal replay
  may then trust. Persist via write-to-temp → fsync → rename
  (``runtime/checkpoint_engine/atomic.py``); staged/temp writes (a
  tmp/staging/partial identifier in the path expression) are the
  sanctioned pattern and exempt. Append-mode opens are fine — append-only
  logs tolerate torn tails by design (CRC-gated replay).
* **DS-R009 raw-clock-in-step-loop** — a raw ``time.time()`` /
  ``time.perf_counter()`` / ``time.monotonic()`` call, or a ``device_sync``
  (full async-dispatch drain), inside a step-loop method of an
  ``*Engine`` / ``*Server`` / ``*Scheduler`` / ``*Loader`` class (an input
  pipeline runs on the same critical path), or a routing method of a
  ``*Gate`` / ``*MoE`` / ``*MoELayer`` class (the expert dispatch path runs inside every traced
  step — a clock there stalls the a2a overlap): ad-hoc timing forks a
  second, invisible timeline next to the unified tracer (ISSUE 10), and a
  stray ``device_sync`` serializes host and device on every step (the
  ``SynchronizedWallClockTimer.stop(sync=True)`` default this PR removed).
  Route timing through the engine's tracer/timers (``profiling/tracer.py``,
  ``utils/timer.py`` — both files are out of scope for the rule, as is
  ``utils/sync.py``); deliberate exceptions carry a pragma. The host-offload
  ``*Streamer`` stream/writer family (ISSUE 16) is in scope twice over:
  its bucket methods are step-loop code (raw clocks flagged like any
  engine method), AND raw host copies (``device_put`` / ``device_get`` /
  ``copy_to_host_async`` / ``block_until_ready``) outside the sanctioned
  stream helpers (``h2d_bucket`` / ``d2h_bucket`` / ``_land`` /
  ``materialize_writes`` / ``drain_writes``) are flagged — an
  unaccounted copy never shows up in the stream-overlap analysis, so the
  "fully hidden behind compute" gate would silently lie.
* **DS-R010 jax-import-in-host-only-module** — an ``import jax`` /
  ``from jax ...`` (incl. ``jax.numpy``) anywhere in a module declared
  pure-host: the fleet router (``inference/fleet.py``) and the tracer
  (``profiling/tracer.py``). These components supervise/observe device
  work from OUTSIDE the device path — the router must keep routing,
  migrating, and journal-replaying while a replica's device backend is
  wedged, and the tracer's zero-transfer/zero-program guarantee rests on
  never touching jax. A jax dependency creeping in would silently couple
  them to backend init (a backend that stalls would stall them too).
* **DS-R007 pool-internals-mutated-outside-pool** — writing ``PagePool``
  internals (page tables, seq lens, free lists, refcounts, the prefix
  index, or the device cache) from outside the pool's own methods: the
  prefix-sharing pool holds CoW/refcount invariants (an indexed page is
  immutable; a shared page is never written; free ∪ cached ∪ referenced
  exactly partitions the pool) that only its methods preserve — a direct
  ``pool.page_table[...] = x`` or ``pool._free.append(p)`` corrupts KV
  silently. Go through ``alloc_slot`` / ``prepare_write`` / ``advance`` /
  ``rollback`` / ``free_slot`` / ``set_cache``; deliberate surgery (tests,
  checkpoint restore) carries a pragma.

* **DS-R011 unsharded-pool-placement** — a ``device_put`` of a pool/param-
  sized value (cache/pool/page/param/weight/master/kv/opt-state/buffer
  names) on a mesh code path whose placement argument is not a sharding:
  the PR-12 transient-OOM pattern — a full-size array committed to ONE
  chip before any reshard, transiently costing tp× the steady-state
  per-chip footprint on exactly the buffers sized against aggregate mesh
  HBM. Allocate directly sharded (``jax.jit(..., out_shardings=...)``) or
  place with a ``NamedSharding``; deliberate per-shard/host placements
  carry a pragma.
* **DS-R012 baked-constant-in-jit** (warn) — a module-level ndarray
  constant (``np.array(...)`` / ``jnp.zeros(...)`` / ...) closed over by a
  jitted function: the constant is baked into EVERY program that captures
  it (per-program HBM copies the ledger never sees) and a rebind
  silently retraces. Pass it as an argument (donated if large) or wrap
  the jit so the constant hashes into the cache key deliberately.

Suppression: append ``# lint: allow(DS-RXXX)`` (or ``# noqa: DS-RXXX``) to
the offending line. Findings in ``tests/`` are always downgraded to
warnings by the CLI — the gate is for the library.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set

RULES = {
    "DS-R001": "jnp.repeat on a cache-like array (G-times buffer copy)",
    "DS-R002": "host sync on a traced value inside a jitted function",
    "DS-R003": "shape-dependent python branch inside a jitted function",
    "DS-R004": "jitted function with buffer-named args and no donate_argnums",
    "DS-R005": "host transfer inside the serving step loop (hot path)",
    "DS-R006": "blocking collective on parameters inside a scanned layer body",
    "DS-R007": "PagePool internals mutated outside the pool's own methods",
    "DS-R008": "non-atomic persistence write (open 'w' without temp+rename) in a checkpoint/journal/bench path",
    "DS-R009": "raw clock / device_sync / unsanctioned host copy inside an engine/scheduler/streamer step-loop method (route through the tracer/timer or the stream helpers)",
    "DS-R010": "jax import in a host-only module (the fleet router / tracer must stay pure host code)",
    "DS-R011": "device_put of a pool/param-sized value on a mesh path without a sharding (transient whole-buffer-on-one-chip OOM)",
    "DS-R012": "module-level ndarray constant closed over by a jitted function (baked per-program HBM copy + silent-retrace hazard)",
}
_WARN_ONLY = {"DS-R003", "DS-R004", "DS-R012"}

# DS-R010 scope: modules that must never import jax — the fleet router
# keeps serving decisions alive while device backends wedge, and the
# tracer's telemetry-is-free contract forbids any device coupling.
_R010_HOST_ONLY = re.compile(r"(inference/fleet\.py|profiling/tracer\.py)$")

# DS-R008 scope: files (or enclosing functions) that persist state other
# code will later trust — checkpoint layouts, journals, bench records.
_PERSIST_PATH = re.compile(r"(checkpoint|journal|bench|host_offload)", re.IGNORECASE)
_PERSIST_FN = re.compile(r"(checkpoint|journal|known_good|latest|marker)", re.IGNORECASE)
# the sanctioned atomic pattern: writes into a temp/staging sibling that a
# rename later commits
_TMPISH = re.compile(r"(tmp|temp|staging|partial|scratch)", re.IGNORECASE)

# DS-R007 scope: the pool state only pool methods may write. Distinctive
# names flag on ANY receiver; the generic ones (cache/_free/_owned/seq_lens
# collide with unrelated classes) only on a pool-ish receiver.
_POOL_ATTRS = {
    "page_table", "seq_lens", "cache", "_free", "_free_slots", "_owned",
    "_refcount", "_hash_index", "_page_hash", "_cached", "_chain_keys",
    "kv_sharding",
}
_POOL_DISTINCT = {
    "page_table", "_free_slots", "_refcount", "_hash_index", "_page_hash",
    "_chain_keys", "kv_sharding",
}
_POOLISH = re.compile(r"pool", re.IGNORECASE)
_POOL_CLASS = re.compile(r"Pool$")
_MUTATORS = {
    "append", "appendleft", "pop", "popleft", "popitem", "extend", "remove",
    "insert", "clear", "update", "setdefault", "sort", "reverse", "fill",
}

# DS-R006 operand scope: identifiers that look like model parameters — the
# values whose scan-body gathers the overlap pipeline owns. Activation /
# cotangent collectives (x, hidden, grads of activations) stay out of scope.
_PARAMISH = re.compile(
    r"(param|weight|^w$|^w\d+$|^w_|_w$|^wq$|^wk$|^wv$|^wo$|per_layer|layers?$)",
    re.IGNORECASE,
)
_SCAN_COLLECTIVES = {"all_gather", "psum"}

# DS-R005 scope: the per-round methods of a serving scheduler class — the
# code that runs between every device dispatch while requests stream. A
# class qualifies only when it BOTH matches the name pattern and defines a
# serving-specific round method, so host-only training-side schedulers
# (curriculum / random-LTD / compression `step()`s) stay out of scope.
# The ragged/TP family is in scope too (ISSUE 13): the sharded
# serving path runs the SAME one-fetch-per-dispatch budget, and a host
# transfer hidden in a tp/ragged step method costs every chip in the mesh.
# Beside the round methods by their generic names, ``_HOT_FN`` names the
# methods of ``inference/scheduler.py:PagedServer`` that lie between two
# dispatches (the pack, the enqueue, the wait, the settles):
# ``test_source_lint.py`` holds them against the class as it stands.
_HOT_CLASS = re.compile(r"(Server|Scheduler)$")
_ROUND_FN = r"(decode|prefill|verify|spec|ragged|tp)_(step|round)"
_SERVING_FN = re.compile(rf"^_?({_ROUND_FN}|serve)$")
_HOT_FN = re.compile(
    rf"^_?({_ROUND_FN}|pack|dispatch|wait_ragged_rows|settle_(ragged|fetched)_rows"
    r"|settle_spec_row|step|run|serve)$"
)

# DS-R005/DS-R009 MoE routing scope (ISSUE 20): the gate/dispatch methods
# of a ``*Gate`` / ``*MoE`` / ``*MoELayer`` class run INSIDE every traced
# training and serving step — a host sync there (a ``.item()`` on an
# exp_counts, a clock around the dispatch) stalls the a2a overlap pipeline
# exactly like a fetch in a serving round. Unlike the Server/Scheduler
# scope there is no serving-method qualifier: a routing class IS hot by
# construction.
_MOE_CLASS = re.compile(r"(Gate|MoE|MoELayer)$")
_MOE_HOT_FN = re.compile(
    r"^_?(apply|forward|route|gate|gating|top\d?k?gating|dispatch|combine)$"
)
_NP_CASTS = ("np.asarray", "np.array", "numpy.asarray", "numpy.array", "onp.asarray")

# DS-R009 scope: step-loop methods of engine/server/scheduler classes —
# the code that runs between (or around) every hot dispatch — plus the
# input-pipeline Loader classes (a loader's __next__ runs once per
# microbatch on the same critical path). The tracer / timer / sync
# modules OWN the clocks and are exempt by path.
_R009_EXEMPT_PATH = re.compile(r"(utils/timer\.py|utils/sync\.py|profiling/)")
_R009_CLASS = re.compile(r"(Engine|Server|Scheduler|Loader|Streamer)$")
_R009_FN = re.compile(
    r"^_?(forward|backward|step|train_batch|fused_train_batch|take_model_step"
    r"|take_offload_step|take_streamed_offload_step|generate"
    r"|(decode|prefill|verify|spec|ragged)"
    r"_(step|round)|admit|emit|run|serve|settle_spec_row|reserve_for_growth"
    r"|finish_step_bookkeeping|__next__|h2d_bucket|d2h_bucket"
    r"|materialize_writes|drain_writes|discard_staged|take_staged|land)$"
)
# call names that read a raw clock or drain the dispatch queue
_R009_BASES = {"perf_counter", "monotonic", "device_sync", "perf_counter_ns", "monotonic_ns"}
_R009_EXACT = {"time.time", "time.clock", "_sync"}

# DS-R009 stream-copy discipline (ISSUE 16): inside a host-offload
# ``*Streamer`` class, every raw host copy must live in one of the
# sanctioned stream helpers — those are the only call sites the stream
# accounting (``stream_schedule`` → the overlap pass) knows about, and
# the only ones the step pipelines (double-buffered H2D, async D2H
# writer) order correctly against donation. ``__init__`` (seeding host
# buffers before any stepping) and ``set_master_leaves`` (checkpoint
# restore surgery) are sanctioned entry points too.
_STREAMER_CLASS = re.compile(r"Streamer$")
_STREAM_HELPER_FN = re.compile(r"^(__init__|_?set_master|_?(h2d|d2h|land|materialize|drain))")
_STREAM_COPY_BASES = {"device_put", "device_get", "copy_to_host_async", "block_until_ready"}

# DS-R011 scope: values sized like the buffers that OOM when transiently
# committed whole to one chip, and the argument spellings that count as a
# real sharding. "device" is deliberately NOT shard-ish — device_put(pool,
# jax.devices()[0]) is exactly the PR-12 incident. A placement-less
# device_put only flags on a mesh/shard/tp code path (enclosing-function
# identifiers) — default-device placement of host data is fine elsewhere.
_SIZEDISH = re.compile(
    r"(cache|pool|page|param|weight|master|^kv$|kv_|_kv$|opt_state|buffer)",
    re.IGNORECASE,
)
_SHARDISH = re.compile(r"(shard|spec|mesh|replicated)", re.IGNORECASE)
_MESHY = re.compile(r"(mesh|shard|tp_|_tp$|^tp$)", re.IGNORECASE)

# DS-R012 creators: module-level calls that build a host ndarray constant
_CONST_MAKERS = re.compile(
    r"^(np|numpy|jnp|onp|jax\.numpy)\.(array|asarray|ones|zeros|arange|full|"
    r"linspace|eye)$"
)

_CACHEY = re.compile(
    r"(cache|page|pool|buffer|^kv$|^k$|^v$|^k_|^v_|_kv$|kv_)", re.IGNORECASE
)
_BUFFER_PARAMS = {
    "grad_acc",
    "opt_state",
    "master",
    "cache",
    "pages",
    "k_pages",
    "v_pages",
    "kv_pages",
    "scale_state",
}
_SHAPEISH = {"shape", "ndim", "size", "dtype"}
_PRAGMA = re.compile(r"(#\s*lint:\s*allow\(([^)]*)\)|#\s*noqa:\s*([\w,\s-]+))")


@dataclass
class LintFinding:
    path: str
    line: int
    rule: str
    message: str
    severity: str = "error"  # resolved by the caller per path

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}/{self.severity}] {self.message}"


def _dotted(node: ast.AST) -> str:
    """'jnp.repeat' for Attribute chains, 'float' for Names, '' otherwise."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _identifiers(node: ast.AST) -> Set[str]:
    names = set()
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            names.add(n.id)
        elif isinstance(n, ast.Attribute):
            names.add(n.attr)
    return names


def _is_shapeish(node: ast.AST) -> bool:
    """True when the expression only reads static structure (shapes, dims,
    literals) — a trace-time constant, not a traced value."""
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr in _SHAPEISH:
            return True
        if isinstance(n, ast.Call) and _dotted(n.func) == "len":
            return True
    return False


class _JitCollector(ast.NodeVisitor):
    """First walk: which function names / lambda nodes get jitted here."""

    JIT_FUNCS = {"jit", "jax.jit", "pjit", "_jit"}

    def __init__(self):
        self.jitted_names: Set[str] = set()
        self.jitted_lambdas: List[ast.Lambda] = []
        self.jit_calls: List[ast.Call] = []  # for DS-R004

    def _is_jit_call(self, call: ast.Call) -> bool:
        name = _dotted(call.func)
        return (
            name in self.JIT_FUNCS
            or name.endswith(".jit")
            or name.endswith(".instrument")
            or name == "instrument"
        )

    def visit_Call(self, node: ast.Call) -> None:
        if self._is_jit_call(node):
            self.jit_calls.append(node)
            for arg in node.args:
                if isinstance(arg, ast.Name):
                    self.jitted_names.add(arg.id)
                elif isinstance(arg, ast.Lambda):
                    self.jitted_lambdas.append(arg)
        self.generic_visit(node)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            name = _dotted(target)
            if name in self.JIT_FUNCS or name.endswith(".jit"):
                self.jitted_names.add(node.name)
            if isinstance(dec, ast.Call) and name.endswith("partial"):
                for a in dec.args:
                    if _dotted(a).endswith("jit"):
                        self.jitted_names.add(node.name)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef


def _fn_params(fn) -> Set[str]:
    args = fn.args
    names = [a.arg for a in args.args + args.posonlyargs + args.kwonlyargs]
    if args.vararg:
        names.append(args.vararg.arg)
    if args.kwarg:
        names.append(args.kwarg.arg)
    return set(names)


def lint_source(src: str, path: str = "<string>") -> List[LintFinding]:
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [LintFinding(path, e.lineno or 0, "DS-R000", f"syntax error: {e.msg}")]
    lines = src.splitlines()
    findings: List[LintFinding] = []

    def allowed(lineno: int, rule: str) -> bool:
        if 1 <= lineno <= len(lines):
            m = _PRAGMA.search(lines[lineno - 1])
            if m:
                codes = (m.group(2) or m.group(3) or "")
                return rule in codes or codes.strip() == "*"
        return False

    def add(lineno: int, rule: str, message: str) -> None:
        if not allowed(lineno, rule):
            findings.append(LintFinding(path, lineno, rule, message))

    collector = _JitCollector()
    collector.visit(tree)

    # resolve jitted names to FunctionDef nodes (module-wide, nearest wins
    # is irrelevant — scrutinize every def carrying a jitted name)
    fn_defs: Dict[str, List[ast.FunctionDef]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fn_defs.setdefault(node.name, []).append(node)

    jit_bodies: List[ast.AST] = list(collector.jitted_lambdas)
    for name in collector.jitted_names:
        jit_bodies.extend(fn_defs.get(name, []))

    # ---- DS-R001: anywhere in the file --------------------------------
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fname = _dotted(node.func)
        if not (fname.endswith(".repeat") and not fname.startswith("re.")):
            continue
        # the repeated array is args[0] in the function form
        # (jnp.repeat(k_cache, G)) and the RECEIVER in the method form
        # (k_cache.repeat(G)) — scan both
        idents = set()
        if node.args:
            idents |= _identifiers(node.args[0])
        if isinstance(node.func, ast.Attribute):
            idents |= _identifiers(node.func.value)
        if any(_CACHEY.search(i) for i in idents):
            add(
                node.lineno,
                "DS-R001",
                f"repeat on cache-like array ({', '.join(sorted(idents)[:3])}): "
                "use grouped einsum instead of expanding kv heads",
            )

    # ---- DS-R002/R003 inside jitted bodies ----------------------------
    seen_nodes: Set[int] = set()
    for body in jit_bodies:
        if id(body) in seen_nodes:
            continue
        seen_nodes.add(id(body))
        params = _fn_params(body)
        # closures: parameters of nested defs also count as traced values
        for n in ast.walk(body):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                params |= _fn_params(n)
        for n in ast.walk(body):
            if isinstance(n, ast.Call):
                fname = _dotted(n.func)
                if (
                    (fname == "item" or fname.endswith(".item"))
                    and isinstance(n.func, ast.Attribute)
                    and not n.args
                ):
                    add(n.lineno, "DS-R002", ".item() on a traced value inside jit")
                elif fname in ("jax.device_get", "device_get"):
                    add(n.lineno, "DS-R002", "jax.device_get inside a jitted function")
                elif fname in ("np.asarray", "np.array", "numpy.asarray", "numpy.array", "onp.asarray"):
                    if n.args and isinstance(n.args[0], ast.Name) and n.args[0].id in params:
                        add(
                            n.lineno,
                            "DS-R002",
                            f"{fname} on traced argument {n.args[0].id!r} inside jit",
                        )
                elif fname in ("float", "int", "bool") and n.args:
                    arg = n.args[0]
                    if (
                        not _is_shapeish(arg)
                        and not isinstance(arg, ast.Constant)
                        and (_identifiers(arg) & params)
                    ):
                        add(
                            n.lineno,
                            "DS-R002",
                            f"{fname}() on a traced value inside jit "
                            "(concretizes or silently syncs)",
                        )
            elif isinstance(n, ast.If):
                if _is_shapeish(n.test) and (_identifiers(n.test) & params):
                    add(
                        n.lineno,
                        "DS-R003",
                        "shape-dependent python branch inside a jitted function "
                        "(each new shape recompiles)",
                    )

    # ---- DS-R005: host transfers in the serving hot loop --------------
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        if _HOT_CLASS.search(cls.name):
            if not any(
                isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef))
                and _SERVING_FN.match(m.name)
                for m in cls.body
            ):
                continue  # a host-only scheduler, not the serving loop
            fn_re, kind = _HOT_FN, "serving hot path"
        elif _MOE_CLASS.search(cls.name):
            fn_re, kind = _MOE_HOT_FN, "MoE routing path"
        else:
            continue
        for fn in cls.body:
            if not (
                isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
                and fn_re.match(fn.name)
            ):
                continue
            where = f"{kind} {cls.name}.{fn.name}"
            for n in ast.walk(fn):
                if not isinstance(n, ast.Call):
                    continue
                fname = _dotted(n.func)
                if fname in ("jax.device_get", "device_get"):
                    add(n.lineno, "DS-R005", f"jax.device_get in {where}")
                elif (
                    (fname == "item" or fname.endswith(".item"))
                    and isinstance(n.func, ast.Attribute)
                    and not n.args
                ):
                    add(n.lineno, "DS-R005", f".item() in {where}")
                elif fname in _NP_CASTS and n.args and isinstance(
                    # literals (lists/tuples/constants) build host arrays;
                    # names/attributes/calls/subscripts can hide a device
                    # value whose np conversion is a blocking transfer
                    n.args[0], (ast.Name, ast.Attribute, ast.Call, ast.Subscript)
                ):
                    add(
                        n.lineno,
                        "DS-R005",
                        f"{fname} on a possible device value in {where} "
                        "(one fetch per dispatch is the budget)",
                    )

    # ---- DS-R009: raw clocks / device syncs in step-loop methods ------
    if not _R009_EXEMPT_PATH.search(path.replace(os.sep, "/")):
        for cls in ast.walk(tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            if _R009_CLASS.search(cls.name):
                fn_re = _R009_FN
            elif _MOE_CLASS.search(cls.name):
                fn_re = _MOE_HOT_FN  # gate/dispatch methods: same step path
            else:
                continue
            for fn in cls.body:
                if not (
                    isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and fn_re.match(fn.name)
                ):
                    continue
                where = f"step-loop method {cls.name}.{fn.name}"
                for n in ast.walk(fn):
                    if not isinstance(n, ast.Call):
                        continue
                    fname = _dotted(n.func)
                    base = fname.rsplit(".", 1)[-1]
                    if fname in _R009_EXACT or base in _R009_BASES:
                        add(
                            n.lineno,
                            "DS-R009",
                            f"raw {fname}() in {where}: ad-hoc clocks fork the "
                            "timeline (and device_sync serializes the step) — "
                            "route through the engine tracer/timer",
                        )

        # stream-copy discipline: raw host copies in a *Streamer class
        # outside the sanctioned stream helpers bypass the stream
        # accounting the overlap gate audits
        for cls in ast.walk(tree):
            if not (isinstance(cls, ast.ClassDef) and _STREAMER_CLASS.search(cls.name)):
                continue
            for fn in cls.body:
                if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                if _STREAM_HELPER_FN.match(fn.name):
                    continue  # the sanctioned copy helpers own the raw calls
                for n in ast.walk(fn):
                    if not isinstance(n, ast.Call):
                        continue
                    base = _dotted(n.func).rsplit(".", 1)[-1]
                    if base in _STREAM_COPY_BASES:
                        add(
                            n.lineno,
                            "DS-R009",
                            f"raw {base} in {cls.name}.{fn.name}: host copies "
                            "outside the sanctioned stream helpers (h2d_bucket/"
                            "d2h_bucket/materialize_writes/drain_writes) never "
                            "enter the stream accounting, so the overlap gate "
                            "can't see them",
                        )

    # ---- DS-R006: blocking param collectives in scan bodies -----------
    scan_bodies: List[ast.AST] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fname = _dotted(node.func)
        if not (fname == "scan" or fname.endswith(".scan")):
            continue
        if node.args:
            body_arg = node.args[0]
            if isinstance(body_arg, ast.Name):
                scan_bodies.extend(fn_defs.get(body_arg.id, []))
            elif isinstance(body_arg, ast.Lambda):
                scan_bodies.append(body_arg)
    seen_scan: Set[int] = set()
    for body in scan_bodies:
        if id(body) in seen_scan:
            continue
        seen_scan.add(id(body))
        for n in ast.walk(body):
            if not isinstance(n, ast.Call):
                continue
            fname = _dotted(n.func)
            base = fname.rsplit(".", 1)[-1]
            if base not in _SCAN_COLLECTIVES:
                continue
            operand_idents = _identifiers(n.args[0]) if n.args else set()
            if any(_PARAMISH.search(i) for i in operand_idents):
                add(
                    n.lineno,
                    "DS-R006",
                    f"blocking {base} on parameter-like value "
                    f"({', '.join(sorted(operand_idents)[:3])}) inside a "
                    "lax.scan body: the comm-overlap pipeline "
                    "(zero.prefetch_layers) should own this gather",
                )

    # ---- DS-R007: pool internals mutated outside the pool -------------
    def _pool_attr(node):
        """(attr, receiver) when ``node`` is ``<recv>.<protected attr>``
        (possibly through a subscript), else None."""
        if isinstance(node, ast.Subscript):
            node = node.value
        if isinstance(node, ast.Attribute) and node.attr in _POOL_ATTRS:
            return node.attr, _dotted(node.value)
        return None

    def _flag_r007(node, attr, recv, how):
        if attr in _POOL_DISTINCT or _POOLISH.search(recv or ""):
            add(
                node.lineno,
                "DS-R007",
                f"{how} of PagePool internal {recv or '<expr>'}.{attr} outside "
                "the pool's methods breaks the CoW/refcount invariants (use "
                "alloc_slot/prepare_write/advance/rollback/free_slot/set_cache)",
            )

    def _scan_r007(node, in_pool):
        if isinstance(node, ast.ClassDef) and _POOL_CLASS.search(node.name):
            in_pool = True  # the pool's own methods are the sanctioned writers
        if not in_pool:
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.Delete)):
                targets = (
                    [node.target] if isinstance(node, ast.AugAssign)
                    else node.targets
                )
                flat = []
                for t in targets:
                    flat.extend(
                        t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
                    )
                for t in flat:
                    hit = _pool_attr(t)
                    if hit:
                        _flag_r007(node, hit[0], hit[1], "write")
            elif isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
                if node.func.attr in _MUTATORS:
                    hit = _pool_attr(node.func.value)
                    if hit:
                        _flag_r007(node, hit[0], hit[1], f".{node.func.attr}()")
        for child in ast.iter_child_nodes(node):
            _scan_r007(child, in_pool)

    _scan_r007(tree, False)

    # ---- DS-R008: non-atomic persistence writes -----------------------
    file_in_scope = bool(_PERSIST_PATH.search(path.replace(os.sep, "/")))

    def _write_mode(call: ast.Call) -> Optional[str]:
        mode = None
        if len(call.args) >= 2 and isinstance(call.args[1], ast.Constant):
            mode = call.args[1].value
        for kw in call.keywords:
            if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
                mode = kw.value.value
        if isinstance(mode, str) and "w" in mode:
            return mode
        return None

    def _tmpish_path(arg: ast.AST) -> bool:
        for n in ast.walk(arg):
            if isinstance(n, ast.Constant) and isinstance(n.value, str):
                if _TMPISH.search(n.value):
                    return True
        return any(_TMPISH.search(i) for i in _identifiers(arg))

    def _scan_r008(node, fn_in_scope):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fn_in_scope = fn_in_scope or bool(_PERSIST_FN.search(node.name))
        if (
            isinstance(node, ast.Call)
            and _dotted(node.func) == "open"
            and (file_in_scope or fn_in_scope)
            and node.args
        ):
            mode = _write_mode(node)
            if mode is not None and not _tmpish_path(node.args[0]):
                add(
                    node.lineno,
                    "DS-R008",
                    f"open(..., {mode!r}) in a persistence path: a kill "
                    "mid-write leaves a torn file later readers trust — "
                    "write to a temp sibling and rename "
                    "(runtime/checkpoint_engine/atomic.py)",
                )
        for child in ast.iter_child_nodes(node):
            _scan_r008(child, fn_in_scope)

    _scan_r008(tree, False)

    # ---- DS-R010: jax imports in host-only modules --------------------
    if _R010_HOST_ONLY.search(path.replace(os.sep, "/")):
        for node in ast.walk(tree):
            bad = None
            if isinstance(node, ast.Import):
                bad = next(
                    (a.name for a in node.names
                     if a.name == "jax" or a.name.startswith("jax.")),
                    None,
                )
            elif isinstance(node, ast.ImportFrom) and node.module:
                if node.module == "jax" or node.module.startswith("jax."):
                    bad = node.module
            if bad:
                add(
                    node.lineno,
                    "DS-R010",
                    f"import of {bad!r} in host-only module {os.path.basename(path)}: "
                    "the fleet router / tracer must keep working while the "
                    "device backend is wedged — keep them pure host code",
                )

    # ---- DS-R011: unsharded pool-sized placements ---------------------
    def _scan_r011(node, fn_idents: Optional[Set[str]]):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # the enclosing function's identifier soup (its name, parameter
            # names, and every identifier in the body) decides whether a
            # placement-less device_put sits on a mesh path
            fn_idents = _identifiers(node) | _fn_params(node) | {node.name}
        if isinstance(node, ast.Call) and _dotted(node.func).rsplit(".", 1)[
            -1
        ] == "device_put":
            arg_idents = _identifiers(node.args[0]) if node.args else set()
            sized = sorted(i for i in arg_idents if _SIZEDISH.search(i))
            placement = node.args[1] if len(node.args) >= 2 else None
            if placement is None:
                for kw in node.keywords:
                    if kw.arg in ("device", "sharding", "shardings"):
                        placement = kw.value
            if sized:
                if placement is None:
                    if fn_idents is not None and any(
                        _MESHY.search(i) for i in fn_idents
                    ):
                        add(
                            node.lineno,
                            "DS-R011",
                            f"device_put of pool/param-sized value "
                            f"({', '.join(sized[:3])}) with no sharding on a "
                            "mesh path: the whole buffer transiently commits "
                            "to one chip (tp x the per-chip footprint) — "
                            "allocate directly sharded "
                            "(jit(..., out_shardings=...)) or pass a "
                            "NamedSharding",
                        )
                elif not any(_SHARDISH.search(i) for i in _identifiers(placement)):
                    add(
                        node.lineno,
                        "DS-R011",
                        f"device_put of pool/param-sized value "
                        f"({', '.join(sized[:3])}) onto a non-sharding "
                        "placement: the whole buffer lands on one chip before "
                        "any reshard (the PR-12 transient OOM) — place with a "
                        "NamedSharding or allocate via out_shardings",
                    )
        for child in ast.iter_child_nodes(node):
            _scan_r011(child, fn_idents)

    _scan_r011(tree, None)

    # ---- DS-R012: module-level ndarray constants captured by jit ------
    const_lines: Dict[str, int] = {}
    for stmt in tree.body:  # module level only: the bake-forever captures
        if isinstance(stmt, ast.Assign) and isinstance(stmt.value, ast.Call):
            if _CONST_MAKERS.match(_dotted(stmt.value.func)):
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        const_lines[t.id] = stmt.lineno
    if const_lines:
        seen_r012: Set[int] = set()
        for body in jit_bodies:
            if id(body) in seen_r012:
                continue
            seen_r012.add(id(body))
            local: Set[str] = set(_fn_params(body))
            for n in ast.walk(body):
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                    local |= _fn_params(n)
                elif isinstance(n, (ast.Assign, ast.AugAssign)):
                    targets = (
                        [n.target] if isinstance(n, ast.AugAssign) else n.targets
                    )
                    for t in targets:
                        if isinstance(t, ast.Name):
                            local.add(t.id)
            flagged: Set[str] = set()
            for n in ast.walk(body):
                if (
                    isinstance(n, ast.Name)
                    and isinstance(n.ctx, ast.Load)
                    and n.id in const_lines
                    and n.id not in local
                    and n.id not in flagged
                ):
                    flagged.add(n.id)
                    add(
                        n.lineno,
                        "DS-R012",
                        f"jitted function closes over module-level ndarray "
                        f"constant {n.id!r} (defined line "
                        f"{const_lines[n.id]}): the array is baked into every "
                        "capturing program (untracked per-program HBM) and a "
                        "rebind silently retraces — pass it as an argument",
                    )

    # ---- DS-R004: jit call sites without donation ---------------------
    for call in collector.jit_calls:
        kwnames = {kw.arg for kw in call.keywords if kw.arg}
        if "donate_argnums" in kwnames or "donate_argnames" in kwnames:
            continue
        for arg in call.args:
            fn = None
            if isinstance(arg, ast.Name):
                defs = fn_defs.get(arg.id)
                fn = defs[-1] if defs else None
            elif isinstance(arg, ast.Lambda):
                fn = arg
            if fn is None:
                continue
            hit = _fn_params(fn) & _BUFFER_PARAMS
            if hit:
                add(
                    call.lineno,
                    "DS-R004",
                    f"jitted function takes buffer args ({', '.join(sorted(hit))}) "
                    "but the jit call declares no donate_argnums",
                )
                break
    return findings


def lint_paths(paths: Sequence[str]) -> List[LintFinding]:
    findings: List[LintFinding] = []
    for root in paths:
        if os.path.isfile(root):
            files = [root]
        else:
            files = []
            for dirpath, dirnames, filenames in os.walk(root):
                dirnames[:] = [d for d in dirnames if d != "__pycache__"]
                files.extend(
                    os.path.join(dirpath, f) for f in filenames if f.endswith(".py")
                )
        for f in sorted(files):
            try:
                with open(f, "r", encoding="utf-8") as fh:
                    src = fh.read()
            except (OSError, UnicodeDecodeError):
                continue
            findings.extend(lint_source(src, f))
    return findings


def resolve_severity(finding: LintFinding, warn_prefixes: Sequence[str] = ("tests",)) -> str:
    """tests/ (and any other warn prefix) never fails the gate; warn-only
    rules never fail anywhere."""
    if finding.rule in _WARN_ONLY:
        return "warn"
    norm = finding.path.replace(os.sep, "/")
    for p in warn_prefixes:
        if norm.startswith(p.rstrip("/") + "/") or f"/{p.rstrip('/')}/" in norm:
            return "warn"
    return "error"


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    import json as _json

    ap = argparse.ArgumentParser(description="deepspeed_tpu repo AST lint")
    ap.add_argument("paths", nargs="*", default=["deepspeed_tpu", "tests"])
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument(
        "--json",
        action="store_true",
        help="shorthand for --format json (structured output for CI gates)",
    )
    ap.add_argument(
        "--rule",
        action="append",
        default=None,
        metavar="DS-RXXX",
        help="only report findings of these rule id(s); repeatable",
    )
    ap.add_argument(
        "--warn-prefix",
        action="append",
        default=None,
        help="path prefixes whose findings are warn-only (default: tests)",
    )
    ns = ap.parse_args(argv)
    if ns.json:
        ns.format = "json"
    warn_prefixes = ns.warn_prefix if ns.warn_prefix else ["tests"]
    findings = lint_paths(ns.paths)
    if ns.rule:
        wanted = set(ns.rule)
        unknown = wanted - set(RULES) - {"DS-R000"}
        if unknown:
            ap.error(f"unknown rule id(s): {', '.join(sorted(unknown))}")
        findings = [f for f in findings if f.rule in wanted]
    n_err = 0
    for f in findings:
        f.severity = resolve_severity(f, warn_prefixes)
        if f.severity == "error":
            n_err += 1
    if ns.format == "json":
        print(_json.dumps([f.__dict__ for f in findings], indent=1))
    else:
        for f in findings:
            print(f.render())
        print(f"lint: {len(findings)} finding(s), {n_err} error(s)")
    return 1 if n_err else 0
