"""The grouped expert matmul alone, on the chip, at the OLMoE cell's shapes:
the Pallas kernel (``deepspeed_tpu/moe/grouped_matmul.py``) against
``jax.lax.ragged_dot`` (the TPU compiler's own lowering), a layer's three
matmuls (gate, up, down) over the 64-expert stack, for a narrow step's 128
assignments and a mixed step's 16,384 rows with 10% and 100% live, group
sizes drawn as a uniform router would. Prints microseconds a layer and the
share of the roofline (``benchmark/kernels/grouped_expert_matmul.py``).

    chiprun -- python3 benchmark/tools/grouped_matmul_bench.py
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

E, H, I, LAYERS, CALLS = 64, 2048, 1024, 4, 50


def main() -> None:
    global E, H, I, CALLS
    rehearse = "--rehearse" in sys.argv
    if rehearse:  # tiny, on the CPU: the control flow only
        os.environ["JAX_PLATFORMS"] = "cpu"
        E, H, I, CALLS = 8, 64, 32, 2
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import files
    from benchmark.kernels import grouped_expert_matmul as k
    from deepspeed_tpu.moe.grouped_matmul import grouped_matmul

    peak = files.load_json(files.HERE, "peaks.json")["TPU v5 lite" if rehearse else jax.devices()[0].device_kind]
    key = jax.random.PRNGKey(0)
    w_gate, w_up = (0.02 * jax.random.normal(jax.random.fold_in(key, i), (LAYERS * E, H, I), jnp.bfloat16) for i in (1, 2))
    w_down = 0.02 * jax.random.normal(jax.random.fold_in(key, 3), (LAYERS * E, I, H), jnp.bfloat16)
    rng = np.random.default_rng(0)

    def layer(impl):
        # the stacks are arguments: closed over, 3 GB would be baked into the program as constants
        def run(x, sizes, offset, w_gate, w_up, w_down):
            gate = grouped_matmul(x, w_gate, sizes, group_offset=offset, impl=impl)
            up = grouped_matmul(x, w_up, sizes, group_offset=offset, impl=impl)
            return grouped_matmul(jax.nn.silu(gate) * up, w_down, sizes, group_offset=offset, out_dtype=jnp.float32, impl=impl)

        def many(x, sizes, *stacks):
            # CALLS layers back to back in one program, walking the stack's layers
            def body(i, acc):
                return acc + jnp.sum(run(x, sizes, (i % LAYERS) * E, *stacks)[:8, :8])

            return jax.lax.fori_loop(0, CALLS, body, jnp.float32(0))

        return jax.jit(many)

    for rows, live in ((16, 16), (256, 26)) if rehearse else ((128, 128), (16384, 1664), (16384, 16384)):
        sizes = np.bincount(rng.integers(0, E, live), minlength=E).astype(np.int32)
        x = jax.random.normal(key, (rows, H), jnp.bfloat16)
        least, bound = k.min_seconds(live, int((sizes > 0).sum()), H, I, peak)
        line = {"rows": rows, "live": live, "experts_hit": int((sizes > 0).sum()), "least_us": 1e6 * least, "bound": bound}
        for impl in ("xla",) if rehearse else ("pallas", "xla"):
            fn = layer(impl)
            fn(x, jnp.asarray(sizes), w_gate, w_up, w_down).block_until_ready()
            t0 = time.perf_counter()
            fn(x, jnp.asarray(sizes), w_gate, w_up, w_down).block_until_ready()
            us = 1e6 * (time.perf_counter() - t0) / CALLS
            line[impl + "_us_a_layer"], line[impl + "_roofline_pct"] = us, 100.0 * 1e6 * least / us
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
