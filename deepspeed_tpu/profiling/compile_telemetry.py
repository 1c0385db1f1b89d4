"""Compile/retrace telemetry for the engine's jitted programs.

The hot loop is a handful of long-lived jitted programs (fwd_bwd, step,
fused_step, fused_accum_step, eval); every unplanned retrace of one of them
costs a multi-second XLA compile — and, accumulated, stale executables have
wedged whole test sessions (PERF.md round 5). This module makes both visible:

* ``CompileTelemetry.instrument(name, fn, **jit_kwargs)`` wraps ``jax.jit``
  so each named program counts traces (re-entries of the python function by
  the tracing machinery), cold dispatches (calls that triggered a trace —
  i.e. compiles, or persistent-cache loads), total dispatches, and the wall
  time spent in trace-triggering calls. The counters survive program
  rebuilds: re-instrumenting under the same name accumulates into the same
  record, so a retrace-regression guard can assert "≤1 compile across N
  steps" without caring when the engine rebuilt its callables.
* ``use_compile_cache`` places JAX's on-disk compilation cache for the
  checkout's entry scripts (``chip_smoke.py``, ``benchmark/run.py``,
  ``tools/``) so repeated runs skip cold compiles.

The wrapper forwards ``lower``/``eval_shape``/``clear_cache`` to the
underlying jitted callable, so AOT inspection (donation sets, cost analysis)
and explicit executable release keep working through it.

The registry is also the capture point for the static-analysis layer
(``deepspeed_tpu/analysis``): each cold dispatch records the abstract call
signature (shape/dtype/sharding per argument leaf — metadata survives
buffer donation), so the analysis passes can re-derive the exact lowered
and compiled program later without holding any live buffers, and the
retrace-cause differ can name the argument whose aval/sharding changed
between two traces of the same program.
"""

from __future__ import annotations

import itertools
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax

# per-program cap on retained trace signatures: enough for the retrace
# differ (consecutive pairs) without unbounded growth in resize loops
_TRACE_LOG_CAP = 8


def _abstract_leaf(x):
    """ShapeDtypeStruct stand-in for an array leaf; any non-array leaf
    (python scalar, None-in-dict, string) passes through verbatim so a
    re-trace sees exactly the original weak-typed value. Shardings are kept
    only for COMMITTED arrays — an uncommitted array does not constrain
    jit's placement, but a ShapeDtypeStruct carrying its current
    (single-device) sharding would, and the re-trace would then reject the
    mesh-sharded neighbors it originally composed with."""
    if isinstance(x, jax.Array):
        try:
            if getattr(x, "_committed", True):
                return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        except Exception:
            pass
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    if hasattr(x, "shape") and hasattr(x, "dtype"):  # np.ndarray / np scalar
        return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
    return x


def describe_signature(args, kwargs) -> Dict[str, Dict[str, Any]]:
    """Flat {arg path: leaf description} for one call signature. Safe on
    donated (deleted) arrays — only metadata is read."""
    flat, _ = jax.tree_util.tree_flatten_with_path((args, kwargs or {}))
    out = {}
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            sharding = getattr(leaf, "sharding", None)
            out[key] = {
                "shape": tuple(leaf.shape),
                "dtype": str(leaf.dtype),
                "sharding": None if sharding is None else str(sharding),
            }
        else:
            out[key] = {"value": repr(leaf)[:80], "type": type(leaf).__name__}
    return out


@dataclass
class ProgramStats:
    """Counters for one named jitted program."""

    name: str
    traces: int = 0
    compiles: int = 0  # dispatches that triggered a trace (cold dispatches)
    dispatches: int = 0
    compile_seconds: float = 0.0  # wall time of trace-triggering dispatches
    invalidations: int = 0  # explicit clear_cache() calls
    first_compile_at: Optional[float] = field(default=None, repr=False)
    # one entry per cold dispatch: the flat signature description the
    # retrace-cause differ consumes (analysis/report.py)
    trace_log: List[Dict[str, Any]] = field(default_factory=list, repr=False)

    def snapshot(self) -> Dict[str, Any]:
        return {
            "traces": self.traces,
            "compiles": self.compiles,
            "dispatches": self.dispatches,
            "compile_seconds": round(self.compile_seconds, 4),
            "invalidations": self.invalidations,
        }

    def log_trace(self, signature: Dict[str, Any]) -> None:
        self.trace_log.append(signature)
        if len(self.trace_log) > _TRACE_LOG_CAP:
            del self.trace_log[0]


class InstrumentedFunction:
    """A ``jax.jit`` callable that feeds a shared ``ProgramStats`` record.

    A dispatch that re-enters the python function (trace counter moved) is a
    cold dispatch: trace + compile (or persistent-cache load) + first run —
    its whole wall time is charged to ``compile_seconds``. Warm dispatches
    only bump ``dispatches``. ``lower``/``eval_shape`` trace without
    dispatching, so they bump ``traces`` but never ``compiles``.
    """

    def __init__(
        self,
        fn: Callable,
        stats: ProgramStats,
        jit_kwargs: Dict[str, Any],
        on_compile: Optional[Callable[[str], None]] = None,
    ):
        self._stats = stats
        self._on_compile = on_compile
        # latest cold-dispatch signature as abstract pytrees: enough to
        # re-trace/lower/compile the program for analysis without pinning
        # any device buffer (donated args are captured as metadata)
        self._abstract_signature = None

        def traced(*args, **kwargs):
            stats.traces += 1
            return fn(*args, **kwargs)

        # the telemetry name is the program's name everywhere: the
        # compile_stats() key, the XLA module (``jit_<name>``) and, through
        # it, the module line and the op name stacks of a profiler trace
        traced.__name__ = traced.__qualname__ = stats.name
        self._jitted = jax.jit(traced, **jit_kwargs)

    def __call__(self, *args, **kwargs):
        st = self._stats
        st.dispatches += 1
        traces_before = st.traces
        t0 = time.perf_counter()
        out = self._jitted(*args, **kwargs)
        if st.traces > traces_before:
            st.compiles += 1
            st.compile_seconds += time.perf_counter() - t0
            if st.first_compile_at is None:
                st.first_compile_at = time.time()
            # cold dispatch: record the signature for the analysis layer.
            # Donated inputs are already consumed, but shape/dtype/sharding
            # metadata outlives the buffer, so the capture is free of
            # device memory. Best-effort: telemetry must never fail a step.
            try:
                self._abstract_signature = jax.tree_util.tree_map(
                    _abstract_leaf, (args, kwargs)
                )
                st.log_trace(describe_signature(args, kwargs))
            except Exception:
                pass
            if self._on_compile is not None:
                self._on_compile(st.name)
        return out

    # --- analysis surface ----------------------------------------------
    @property
    def abstract_signature(self):
        """(args, kwargs) pytrees of ShapeDtypeStructs (+ verbatim
        non-array leaves) from the latest cold dispatch, or None if the
        program has never dispatched."""
        return self._abstract_signature

    def trace_abstract(self):
        """Re-trace the program from the captured cold-dispatch signature.
        Raises if the program has never dispatched."""
        if self._abstract_signature is None:
            raise ValueError(
                f"program {self._stats.name!r} has no captured signature "
                "(never dispatched through this wrapper)"
            )
        args, kwargs = self._abstract_signature
        return self._jitted.trace(*args, **kwargs)

    # --- AOT / lifecycle pass-throughs ---------------------------------
    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def eval_shape(self, *args, **kwargs):
        return self._jitted.eval_shape(*args, **kwargs)

    def clear_cache(self) -> None:
        """Release this program's compiled executables (the fix for the
        PERF.md mid-suite wedge: rebinding the attribute alone leaves the
        stale executable alive in jit's cache)."""
        self._stats.invalidations += 1
        self._jitted.clear_cache()

    def cache_size(self) -> int:
        try:
            return int(self._jitted._cache_size())
        except Exception:
            return -1  # jit internals moved; telemetry stays best-effort

    @property
    def stats(self) -> ProgramStats:
        return self._stats


class CompileTelemetry:
    """Registry of named instrumented programs (one per engine)."""

    _uids = itertools.count()

    def __init__(self):
        self._programs: Dict[str, ProgramStats] = {}
        # latest wrapper per name: the analysis passes re-derive lowered/
        # compiled artifacts through it (only the newest build matters —
        # stale wrappers are dropped so their executables can be GC'd)
        self._fns: Dict[str, InstrumentedFunction] = {}
        # optional hook fired (with the program name) after each cold
        # dispatch completes — the engines use it for analysis.verify
        self.on_compile: Optional[Callable[[str], None]] = None
        # process-unique, never-recycled id: module-level program caches
        # (inference/decode.py) key compiled callables on it — ``id(self)``
        # could alias a dead registry at a recycled address
        self.uid = next(CompileTelemetry._uids)

    def instrument(self, name: str, fn: Callable, **jit_kwargs) -> InstrumentedFunction:
        """``jax.jit(fn, **jit_kwargs)`` with counters under ``name``.
        Re-instrumenting an existing name (engine rebuild) accumulates into
        the same record."""
        stats = self._programs.setdefault(name, ProgramStats(name))
        wrapper = InstrumentedFunction(
            fn, stats, jit_kwargs, on_compile=self._fire_on_compile
        )
        self._fns[name] = wrapper
        return wrapper

    def _fire_on_compile(self, name: str) -> None:
        # late-bound: engines set self.on_compile after instrument() calls
        if self.on_compile is not None:
            self.on_compile(name)

    def programs(self) -> Dict[str, InstrumentedFunction]:
        """{name: latest InstrumentedFunction} — the analysis layer's view."""
        return dict(self._fns)

    def lowered_text(self, name: str) -> str:
        """StableHLO text of a dispatched program, re-lowered from the
        signature its latest cold dispatch recorded (a trace, no compile).
        A compiled Pallas kernel shows in it as ``tpu_custom_call``; an
        interpreted one does not."""
        return self._fns[name].trace_abstract().lower().as_text()

    def compiled_text(self, name: str) -> str:
        """Optimized HLO text of a dispatched program as the backend
        scheduled it, from the signature its latest cold dispatch recorded.
        After that dispatch this is no second compile: the trace, the
        lowering and the executable are the ones jit keeps for the call."""
        return self._fns[name].trace_abstract().lower().compile().as_text()

    def program_stats(self, name: str) -> Optional[ProgramStats]:
        return self._programs.get(name)

    def stats(self) -> Dict[str, Dict[str, Any]]:
        """Per-program counter snapshot: {name: {traces, compiles,
        dispatches, compile_seconds, invalidations}}."""
        return {name: s.snapshot() for name, s in sorted(self._programs.items())}

    def totals(self) -> Dict[str, Any]:
        """Aggregate counters over every instrumented program."""
        out = {"traces": 0, "compiles": 0, "dispatches": 0, "compile_seconds": 0.0}
        for s in self._programs.values():
            out["traces"] += s.traces
            out["compiles"] += s.compiles
            out["dispatches"] += s.dispatches
            out["compile_seconds"] += s.compile_seconds
        out["compile_seconds"] = round(out["compile_seconds"], 4)
        return out

    def reset(self) -> None:
        self._programs.clear()
        self._fns.clear()


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is set in code, so whoever runs the program decides where the
    cache lives. Otherwise it is ``<checkout>/.jax_cache``: one fixed path,
    never derived from a temp name, pid or time, so the next process finds
    what this one compiled. Process-global (``jax.config``)."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
