"""Records the small ``.xplane.pb`` that ``tests/benchmark/test_bench_solar_open2.py``
reads: a tiny Solar-Open2-shaped paged server of the program itself (one
period: a gated GQA layer and three delta-rule layers of 8 heads of 128; 4 of
8 experts held, 3 a token, a shared expert), a few steps under the profiler,
so that the trace holds what PR 31 put there: the ``linear_attention`` scope
with ``kda_recurrence`` and the ``kda_decode`` kernel inside it,
``moe_shared``, and the ``serve.settle`` span's held and routed assignments.
Run on the chip machine:

    python3 benchmark/tools/record_solar_trace.py chiprun_out/solar_trace

and copy ``chiprun_out/solar_trace/solar_tpu.xplane.pb`` to
``tests/benchmark/data/``; the printed ``rows_log`` is the test's ``ROWS_LOG``.
As in ``record_moe_trace.py`` the ``/host:metadata`` plane is left out, every
step is under a ``server_step`` annotation, and the whole trace is the slice.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

MODEL = dict(vocab_size=512, hidden_size=256, intermediate_size=128, num_layers=4, num_heads=4, num_kv_heads=2, head_dim=128,
             max_seq_len=256, norm="rmsnorm", position="none", activation="swiglu", use_bias=False, tie_embeddings=False,
             layer_types=["softmax", "linear", "linear", "linear"], attn_output_gate=True, linear_num_heads=8, linear_head_dim=128,
             linear_gate_rank=32, num_experts=4, moe_router_experts=8, moe_expert_share=[0, 2], moe_top_k=3, moe_drop_tokens=False,
             moe_norm_topk_prob=True, moe_scoring="sigmoid", moe_select_bias=True, moe_shared_experts=1, dtype="bfloat16")
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
    named.without_plane(find_xplane(out), "/host:metadata", os.path.join(out, "solar_tpu.xplane.pb"))
    print("moe stats", {k: v for k, v in server.stats.items() if k.startswith("moe")}, "steps", server.stats["ragged_steps"])
    print("rows_log", json.dumps(rows_log))


if __name__ == "__main__":
    main()
