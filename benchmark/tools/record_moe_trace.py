"""Records the small ``.xplane.pb`` that ``tests/benchmark/test_bench_olmoe.py``
reads: a tiny OLMoE-shaped paged server of the program itself (8 experts, 3 a
token, QK-norm), a few steps under the profiler, so that the trace holds what
PR 25 put there: the ``moe_route`` and ``moe_experts`` scopes, the
``moe_grouped_matmul`` kernel and the ``serve.settle`` span's routing counts.
Run on the chip machine:

    python3 benchmark/tools/record_moe_trace.py chiprun_out/moe_trace

and copy ``chiprun_out/moe_trace/moe_tpu.xplane.pb`` to ``tests/benchmark/data/``.
As in ``record_named_trace.py`` the ``/host:metadata`` plane is left out, every
step is under a ``server_step`` annotation, and the whole trace is the slice.
"""

from __future__ import annotations

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

MODEL = dict(vocab_size=512, hidden_size=256, intermediate_size=128, num_layers=2, num_heads=2, num_kv_heads=2, head_dim=128,
             max_seq_len=256, norm="rmsnorm", position="rope", activation="swiglu", use_bias=False, tie_embeddings=False,
             qk_norm="projection", num_experts=8, moe_top_k=3, moe_drop_tokens=False, moe_norm_topk_prob=False, dtype="bfloat16")
PAGED = {"page_size": 64, "max_slots": 4, "prefill_chunk": 128, "num_pages": 0, "max_seq_len": 256}


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation

    import deepspeed_tpu as ds
    from benchmark.trace_reduce import find_xplane
    from deepspeed_tpu.models import MoETransformerLM
    from deepspeed_tpu.models.moe_transformer import MoETransformerConfig

    spec = importlib.util.spec_from_file_location("record_named_trace", os.path.join(ROOT, "benchmark", "tools", "record_named_trace.py"))
    named = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(named)

    out = sys.argv[1]
    rng = np.random.default_rng(0)
    model = MoETransformerLM(MoETransformerConfig(**MODEL))
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
    while server.has_work():
        with TraceAnnotation("server_step"):
            server.step()
    jax.profiler.stop_trace()
    named.without_plane(find_xplane(out), "/host:metadata", os.path.join(out, "moe_tpu.xplane.pb"))
    print("moe stats", {k: v for k, v in server.stats.items() if k.startswith("moe")}, "steps", server.stats["ragged_steps"])


if __name__ == "__main__":
    main()
