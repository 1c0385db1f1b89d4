"""Records the small ``.xplane.pb`` that ``tests/benchmark/test_bench_ouro.py``
reads: a tiny Ouro-shaped paged server of the program itself (two layers run
four times: 8 cache layers over 2 of weights, 2 heads of 128, the sandwich
norms, the exit gate, an untied head), a few steps under the profiler, so that
the trace holds what PR 56 put there: the ``loop_pass`` scope around each
pass's layers with ``attention`` / ``mlp`` and the ragged kernel inside it,
``pass_norm`` between passes, in the narrow program and in the wide one (8 rows
x 128 slots: the token tiles). Run on the chip machine:

    python3 benchmark/tools/record_ouro_trace.py chiprun_out/ouro_trace

and copy ``chiprun_out/ouro_trace/ouro_tpu.xplane.pb`` to
``tests/benchmark/data/``; the printed ``rows_log`` is
``tests/benchmark/data/ouro_rows_log.json``. As in ``record_granite_trace.py``
the ``/host:metadata`` plane is left out, every step is under a ``server_step``
annotation, and the whole trace is the slice.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

MODEL = dict(vocab_size=512, hidden_size=256, intermediate_size=512, num_layers=2, num_heads=2, num_kv_heads=2, head_dim=128,
             max_seq_len=256, norm="rmsnorm", norm_eps=1e-6, position="rope", rope_theta=1e6, activation="swiglu", use_bias=False,
             tie_embeddings=False, num_loops=4, post_sublayer_norm=True, exit_gate=True, dtype="bfloat16")
PAGED = {"page_size": 64, "max_slots": 8, "prefill_chunk": 128, "num_pages": 0, "max_seq_len": 256}


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation

    import deepspeed_tpu as ds
    from benchmark.trace_reduce import find_xplane
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig

    spec = importlib.util.spec_from_file_location("record_named_trace", os.path.join(ROOT, "benchmark", "tools", "record_named_trace.py"))
    named = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(named)

    out = sys.argv[1]
    rng = np.random.default_rng(0)
    model = TransformerLM(TransformerConfig(**MODEL))
    engine = ds.init_inference(model, dtype="bf16", paged_kv=PAGED)
    params = jax.jit(lambda key: jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), model.init(key, np.zeros((1, 8), np.int32))))(jax.random.PRNGKey(0))
    engine.set_params(params)
    prompts = [rng.integers(0, MODEL["vocab_size"], n, dtype=np.int32) for n in (150, 8)]
    engine.serve(prompts, max_new_tokens=[4, 8])  # compiles both widths
    server = engine._paged_server
    server = getattr(server, "server", server)

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=options)
    for p in prompts:
        server.submit(p, max_new_tokens=3)
    rows_log = []
    chunk = PAGED["prefill_chunk"]
    while server.has_work():
        chunks = server.stats["prefill_chunks"]
        before = {r.uid: (r.pending is not None, r.consumed, r.prompt.size + len(r.generated)) for r in list(server._queue) + list(server._active)}
        with TraceAnnotation("server_step"):
            server.step()
        rows = []
        for decoding, consumed, size in before.values():
            q = 1 if decoding else min(chunk, size - consumed, chunk - consumed % chunk)
            rows.append([q, size if decoding else consumed + q])
        rows_log.append({"mixed": server.stats["prefill_chunks"] > chunks, "rows": rows})
    jax.profiler.stop_trace()
    named.without_plane(find_xplane(out), "/host:metadata", os.path.join(out, "ouro_tpu.xplane.pb"))
    with open(os.path.join(out, "ouro_rows_log.json"), "w") as f:
        json.dump(rows_log, f)
    print("steps", server.stats["ragged_steps"], "rows_log", json.dumps(rows_log))


if __name__ == "__main__":
    main()
