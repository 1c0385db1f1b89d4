"""Profiling (reference: ``deepspeed/profiling/``) + TPU-native compile
telemetry (``compile_telemetry`` — per-program trace/compile counters and the
persistent-compilation-cache opt-in) + the unified tracing/metrics plane
(``tracer`` — step/request spans, metrics registry, Chrome-trace export,
flight recorder, observability hub)."""

from deepspeed_tpu.profiling.compile_telemetry import (  # noqa: F401
    CompileTelemetry,
    InstrumentedFunction,
    ProgramStats,
    use_compile_cache,
)
from deepspeed_tpu.profiling.tracer import (  # noqa: F401
    NULL_TRACER,
    Counter,
    FlightRecorder,
    Gauge,
    Histogram,
    MetricsRegistry,
    ObservabilityHub,
    Tracer,
)
