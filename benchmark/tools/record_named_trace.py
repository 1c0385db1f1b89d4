"""Records the small ``.xplane.pb`` that ``tests/benchmark/
test_bench_program_spans.py`` reads: a tiny training engine and a tiny paged
server of the program itself, a few steps each under the profiler, so that
the trace holds what PR 23 put there: the program's host spans with their
attributes, the named flash and ragged kernels, the named scopes and the
modules named after their ``compile_stats()`` keys. Run on the chip machine:

    python3 benchmark/tools/record_named_trace.py chiprun_out/named_trace

and copy ``chiprun_out/named_trace/named_tpu.xplane.pb`` to
``tests/benchmark/data/`` (``record_small_trace.py`` records the trace of the
older tests). That file is the profiler's ``.xplane.pb`` without its
``/host:metadata`` plane: the HLO protos, two thirds of the bytes, which
nothing here reads. Every serving step is under a ``server_step`` annotation
and every training step under ``train_step``, as in the drivers; there is no
``bench_slice``, so the whole trace is the slice.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

MODEL = dict(vocab_size=512, hidden_size=256, num_layers=2, num_heads=2, num_kv_heads=2, max_seq_len=256,
             norm="layernorm", position="learned", activation="gelu", dtype="bfloat16", flash_attention=True)
TRAIN = {"train_micro_batch_size_per_gpu": 2, "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
         "bf16": {"enabled": True}, "zero_optimization": {"stage": 1}, "gradient_clipping": 1.0, "steps_per_print": 10**6}
PAGED = {"page_size": 64, "max_slots": 4, "prefill_chunk": 128, "num_pages": 0, "max_seq_len": 256}


def without_plane(xplane_path: str, plane_name: str, out_path: str) -> None:
    """Copy an ``.xplane.pb`` leaving one plane out: an ``XSpace`` is a
    sequence of length-delimited planes, so the rest is copied byte for
    byte."""
    from benchmark.op_scopes import wire_fields, wire_varint

    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    kept, i = bytearray(), 0
    while i < len(space):
        start = i
        tag, i = wire_varint(space, i)
        if tag & 7 != 2:
            raise ValueError("an XSpace holds length-delimited fields only")
        size, i = wire_varint(space, i)
        body, i = space[i : i + size], i + size
        # XSpace.planes = 1, XPlane.name = 2
        name = next((bytes(v).decode() for f, v in wire_fields(body) if f == 2), "") if tag >> 3 == 1 else ""
        if name != plane_name:
            kept += space[start:i]
    with open(out_path, "wb") as f:
        f.write(kept)


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig

    out = sys.argv[1]
    rng = np.random.default_rng(0)

    engine, _, _, _ = ds.initialize(model=TransformerLM(TransformerConfig(**MODEL)), config=TRAIN)
    tokens = rng.integers(0, MODEL["vocab_size"], (2, 257), dtype=np.int32)
    batch = {"input_ids": tokens[:, :-1], "labels": tokens[:, 1:]}
    engine.init_params(batch, rng=jax.random.PRNGKey(0))

    def train_step():
        with TraceAnnotation("train_step"):
            loss = engine(batch)
            engine.backward(loss)
            engine.step()
        return loss

    train_step().block_until_ready()

    server_engine = ds.init_inference(TransformerLM(TransformerConfig(**MODEL)), dtype="bf16", paged_kv=PAGED)
    server_engine.set_params(jax.tree_util.tree_map(lambda a: jnp.array(a, jnp.bfloat16, copy=True), engine.get_params()))
    prompts = [rng.integers(0, MODEL["vocab_size"], n, dtype=np.int32) for n in (150, 8)]
    server_engine.serve(prompts, max_new_tokens=[4, 8])  # compiles both widths
    server = server_engine._paged_server
    server = getattr(server, "server", server)

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=options)
    for _ in range(3):
        train_step().block_until_ready()
    for p in prompts:
        server.submit(p, max_new_tokens=3)
    while server.has_work():
        with TraceAnnotation("server_step"):
            server.step()
    jax.profiler.stop_trace()
    from benchmark.trace_reduce import find_xplane

    without_plane(find_xplane(out), "/host:metadata", os.path.join(out, "named_tpu.xplane.pb"))


if __name__ == "__main__":
    main()
