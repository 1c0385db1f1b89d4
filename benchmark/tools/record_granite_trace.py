"""Records the small ``.xplane.pb`` that ``tests/benchmark/test_bench_granite.py``
reads: a tiny granite-4.0-h-shaped paged server of the program itself (one
period ``[ssm, ssm, softmax]``: two Mamba-2 layers of 4 heads of 64 over a
state of 128 and a NoPE GQA layer of 4 heads of 64 over 2, a dense FFN in each,
the four multipliers, the tied table), a few steps under the profiler, so that
the trace holds what PR 52 put there: the ``ssm_mixer`` scope with
``ssd_recurrence`` and the ``ssd_decode`` kernel inside it, the chunk form's
ops for a prefill row, and the ``attention`` scope of heads of 64. Run on the
chip machine:

    python3 benchmark/tools/record_granite_trace.py chiprun_out/granite_trace

and copy ``chiprun_out/granite_trace/granite_tpu.xplane.pb`` to
``tests/benchmark/data/``; the printed ``rows_log`` is
``tests/benchmark/data/granite_rows_log.json``. As in ``record_solar_trace.py``
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

MODEL = dict(vocab_size=512, hidden_size=256, intermediate_size=512, num_layers=3, num_heads=4, num_kv_heads=2, head_dim=64,
             attn_softmax_scale=0.015625, max_seq_len=256, norm="rmsnorm", position="none", activation="swiglu", use_bias=False,
             tie_embeddings=True, layer_types=["ssm", "ssm", "softmax"], ssm_num_heads=4, ssm_head_dim=64, ssm_state=128,
             embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=8.0, num_experts=0, moe_top_k=0, moe_drop_tokens=False,
             dtype="bfloat16")
PAGED = {"page_size": 64, "max_slots": 4, "prefill_chunk": 128, "num_pages": 0, "max_seq_len": 256}


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation

    import deepspeed_tpu as ds
    from benchmark.trace_reduce import find_xplane
    from deepspeed_tpu.models.hybrid_moe import HybridMoEConfig, HybridMoETransformerLM

    spec = importlib.util.spec_from_file_location("record_named_trace", os.path.join(ROOT, "benchmark", "tools", "record_named_trace.py"))
    named = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(named)

    out = sys.argv[1]
    rng = np.random.default_rng(0)
    model = HybridMoETransformerLM(HybridMoEConfig(**MODEL))
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
    named.without_plane(find_xplane(out), "/host:metadata", os.path.join(out, "granite_tpu.xplane.pb"))
    with open(os.path.join(out, "granite_rows_log.json"), "w") as f:
        json.dump(rows_log, f)
    print("steps", server.stats["ragged_steps"], "rows_log", json.dumps(rows_log))


if __name__ == "__main__":
    main()
