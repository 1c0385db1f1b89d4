"""Records the small ``.xplane.pb`` that ``tests/benchmark`` reduces: three
executions of a jitted four-iteration scan of matmuls on the chip, each under
a ``train_step`` annotation with a short sleep after it, the last two inside
``bench_slice``. Run on the chip machine:

    python3 benchmark/tools/record_small_trace.py chiprun_out/small_trace

and copy the ``.xplane.pb`` to ``tests/benchmark/data/small_tpu.xplane.pb``.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    out = sys.argv[1]

    @jax.jit
    def small_step(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), ()

        y, _ = jax.lax.scan(body, x, w)
        return y

    x = jnp.ones((512, 512), jnp.bfloat16)
    w = jnp.full((4, 512, 512), 0.01, jnp.bfloat16)
    small_step(x, w).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=options)

    def one():
        with TraceAnnotation("train_step"):
            small_step(x, w).block_until_ready()
        time.sleep(0.002)

    one()
    with TraceAnnotation("bench_slice"):
        one()
        one()
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main()
