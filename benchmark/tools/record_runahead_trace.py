"""Records the small ``.xplane.pb`` that ``tests/benchmark/test_bench_exec_gap.py``
reads: a paged server of the program itself that runs one step ahead
(``scheduler.py`` since PR 35), a few dozen steps of a two-layer model under
the profiler, so that the trace holds what PR 36 put there: every span of a
step's life with the step's ``seq``, ``serve.enqueue`` around the jitted call,
``ahead`` on the dispatch. The model is two layers of a WIDE dense decoder
(hidden 8,192: ~3.4 GB of bf16 weights), so that a decode step takes the
device ~4 ms as the cells' steps take it 11-15: the readers pair an execution
with its enqueue by time, and a toy's 0.2 ms steps would lie closer together
than the two clocks can be aligned. Run on the chip machine:

    python3 benchmark/tools/record_runahead_trace.py chiprun_out/runahead_trace

and copy ``chiprun_out/runahead_trace/runahead_tpu.xplane.pb`` to
``tests/benchmark/data/``. As in the drivers every call of ``server.step()``
is under a ``server_step`` annotation, and ``bench_slice`` is around the run's
first SLICE_CALLS calls: the slice starts before the first step (the one step
that cannot run ahead) and ends between two calls with the run going on, so
the last step it enqueued is settled outside it. The file keeps what the
readers read: device 0's ``XLA Modules`` and ``XLA Ops`` lines and the
``python3`` line of ``/host:CPU``, where the annotations are; the HLO protos,
the runtime's own threads, the other planes and the device events' stats are
left out.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

MODEL = dict(vocab_size=4096, hidden_size=8192, intermediate_size=28672, num_layers=2, num_heads=64, num_kv_heads=8, head_dim=128,
             max_seq_len=512, norm="rmsnorm", position="rope", activation="swiglu", use_bias=False, tie_embeddings=False, dtype="bfloat16")
PAGED = {"page_size": 64, "max_slots": 4, "prefill_chunk": 128, "num_pages": 0, "max_seq_len": 512}
# tsl/profiler/protobuf/xplane.proto
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_METADATA, _LINE_NAME, _LINE_EVENTS, _EVENT_STATS, _MAP_VALUE, _META_STATS = 2, 3, 4, 2, 4, 4, 2, 5
# a device event keeps its name, start and length: its own stats (device offsets) and its metadata's (name stack, category,
# source: two thirds of the bytes) go, the four readers read neither. A host event's stats are a span's attributes and stay
_NO_STATS = {_PLANE_LINES: {_LINE_EVENTS: {_EVENT_STATS: None}}, _PLANE_EVENT_METADATA: {_MAP_VALUE: {_META_STATS: None}}}
KEEP = {"/device:TPU:0": ({"XLA Modules", "XLA Ops"}, _NO_STATS), "/host:CPU": ({"python3"}, {})}  # plane -> (its lines, what goes of them)
SLICE_CALLS = 36
BUDGET = 48  # tokens a request: the run goes on for a dozen calls past the slice


def _varint(value: int) -> bytes:
    out = bytearray()
    while value >= 0x80:
        out.append(value & 0x7F | 0x80)
        value >>= 7
    out.append(value)
    return bytes(out)


def _pieces(buf):
    """(field number, a length-delimited field's body or None, the field's bytes with tag and length) of one message."""
    from benchmark.op_scopes import wire_varint

    i = 0
    while i < len(buf):
        start = i
        tag, i = wire_varint(buf, i)
        if tag & 7 == 2:
            size, i = wire_varint(buf, i)
            body, i = buf[i : i + size], i + size
        elif tag & 7 == 0:
            body, i = None, wire_varint(buf, i)[1]
        else:
            raise ValueError("an XSpace holds varints and length-delimited fields only")
        yield tag >> 3, body, buf[start:i]


def _without(buf, drop) -> bytearray:
    """A message without the fields ``drop`` names: ``{field: None}`` drops
    the field, ``{field: {...}}`` goes on inside it; the rest is copied byte
    for byte, under new lengths."""
    out = bytearray()
    for field, body, raw in _pieces(buf):
        if field not in drop or body is None:
            out += raw
        elif drop[field] is not None:
            inner = _without(body, drop[field])
            out += _varint(field << 3 | 2) + _varint(len(inner)) + inner
    return out


def kept_planes_and_lines(xplane_path: str, keep, out_path: str) -> None:
    """Copy an ``.xplane.pb`` with the planes of ``keep`` alone and, of each,
    the lines named there, less what its rule drops."""
    from benchmark.op_scopes import wire_fields

    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out = bytearray()
    for field, plane, _ in _pieces(space):
        name = next((bytes(v).decode() for f, v in wire_fields(plane) if f == _PLANE_NAME), "") if field == 1 else ""
        if name not in keep:
            continue
        lines, drop = keep[name]
        body = bytearray()
        for f, value, raw in _pieces(plane):
            if f != _PLANE_LINES or next((bytes(v).decode() for g, v in wire_fields(value) if g == _LINE_NAME), "") in lines:
                body += raw
        body = _without(memoryview(bytes(body)), drop)
        out += _varint(1 << 3 | 2) + _varint(len(body)) + body
    with open(out_path, "wb") as f:
        f.write(out)


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation

    import deepspeed_tpu as ds
    from benchmark.serving import seeded_weights
    from benchmark.trace_reduce import find_xplane
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig

    out = sys.argv[1]
    rng = np.random.default_rng(0)
    model = TransformerLM(TransformerConfig(**MODEL))
    engine = ds.init_inference(model, dtype="bf16", paged_kv=PAGED)
    engine.set_params(seeded_weights(model, 0, jnp.bfloat16))
    prompts = [rng.integers(0, MODEL["vocab_size"], n, dtype=np.int32) for n in (150, 8, 40, 90)]
    engine.serve(prompts[:2], max_new_tokens=[4, 8])  # compiles both widths
    server = engine._paged_server
    server = getattr(server, "server", server)

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=options)
    for p in prompts:
        server.submit(p, max_new_tokens=BUDGET)

    def calls(n: int) -> None:
        while server.has_work() and n:
            with TraceAnnotation("server_step"):
                server.step()
            n -= 1

    with TraceAnnotation("bench_slice"):
        calls(SLICE_CALLS)
    calls(-1)
    jax.profiler.stop_trace()
    stats = server.serve_stats()
    print({k: stats[k] for k in ("dispatches", "ragged_steps", "run_ahead_steps", "drain_reasons", "turnaround_ms_p50")}, flush=True)
    path = os.path.join(out, "runahead_tpu.xplane.pb")
    kept_planes_and_lines(find_xplane(out), KEEP, path)
    print(f"{path}: {os.path.getsize(path)} bytes", flush=True)


if __name__ == "__main__":
    main()
