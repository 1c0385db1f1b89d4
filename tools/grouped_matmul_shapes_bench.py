"""The grouped expert matmul alone, on the chip, at the five MoE serving
cells' shapes (``deepspeed_tpu/moe/grouped_matmul.py``; the benchmark's own
``benchmark/tools/grouped_matmul_bench.py`` holds OLMoE's shapes only): a
layer's gate, up and down calls over the cell's expert stack, for a narrow
step's rows and a mixed step's, the Pallas kernel against
``jax.lax.ragged_dot``. Group sizes are drawn as the cell's traffic makes
them: of the window's ``M`` assignment rows the chip's HELD share is live
(sorted first, as ``routed_ffn`` sorts them), and a narrow step's rows fall on
the cell's measured share of the held experts. Prints, a window and an
implementation, microseconds for one gate/up call, for one down call and for
the layer's three calls with the activation between them, and the layer's
share of the least time the chip could take
(``benchmark/kernels/grouped_expert_matmul.py::min_seconds``). It is how the
kernel's blocks were chosen (PERF.md, PR 37).

    chiprun -- python3 tools/grouped_matmul_shapes_bench.py [--shape solar,mimo,olmoe,glm,laguna,laguna_x16]
    python3 tools/grouped_matmul_shapes_bench.py --rehearse   # tiny, on the CPU: the control flow only
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
from typing import NamedTuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CALLS = 48


class Shape(NamedTuple):
    """A cell's expert stack and windows. ``narrow`` and ``mixed`` are
    (assignment rows of the window, live held rows among them, experts hit):
    the cells' own counts a layer (ledger, PR 36), ``None`` experts hit where
    the draw decides (a mixed step's rows reach every held expert)."""

    hidden: int
    inter: int
    experts: int  # held by the chip, of one layer
    layers: int  # of the stack the kernel walks
    narrow: tuple
    mixed: tuple


SHAPES = {
    # 64 rows x top-8 = 512 assignments, 11.6% of them held, on 63.9% of 40 experts; a 512-token tile with ~190 live tokens
    "solar": Shape(4096, 1280, 40, 4, (512, 59, 26), (4096, 190, None)),
    # 6.5% held, 13.7 of 16 experts hit
    "mimo": Shape(4096, 2048, 16, 6, (512, 33, 14), (4096, 99, None)),
    # every expert held: 16 rows x 8, 87.9% of 64 hit; a 1,024-token tile with ~143 live tokens
    "olmoe": Shape(2048, 1024, 64, 4, (128, 128, 56), (8192, 1144, None)),
    # GLM-4.7-Flash (PR 41): K 2,048 in one block, N 1,536 (gate and up are two calls; fused they would be 3,072);
    # 64 rows x top-4 = 256 assignments, 12.5% of them held, on all 8 held experts (98% by the binomial);
    # a 512-token tile's 2,048 assignment rows with ~190 live tokens
    "glm": Shape(2048, 1536, 8, 15, (256, 32, 8), (2048, 95, None)),
    # Laguna-S-2.1 (PR 45): K 3,072, N 1,024 (gate and up are two calls; fused they would be 2,048), 16 held of 256;
    # 64 rows x top-10 = 640 assignments, 6.25% of them held (40), on ~15 of the 16 held experts (92% by the binomial);
    # a 512-token tile's 5,120 assignment rows with ~190 live tokens
    "laguna": Shape(3072, 1024, 16, 8, (640, 40, 15), (5120, 119, None)),
    # the same layer under the deployment's load: each of the sixteen chips receives the tokens of all, 640 held
    # assignments a narrow step (40 an expert: still under the row tile of 128, so an expert's time is its bytes)
    "laguna_x16": Shape(3072, 1024, 16, 8, (10240, 640, 16), (5120, 1900, None)),
}
TINY = {"tiny": Shape(256, 128, 5, 2, (16, 6, 3), (256, 40, None))}


def draw_sizes(rng, experts: int, live: int, hit):
    """``live`` rows over ``experts`` groups; with ``hit``, over that many of
    them, each with a row at least."""
    import numpy as np

    if hit is None:
        return np.bincount(rng.integers(0, experts, live), minlength=experts).astype(np.int32)
    chosen = rng.choice(experts, hit, replace=False)
    sizes = np.zeros(experts, np.int32)
    sizes[chosen] = 1
    np.add.at(sizes, chosen[rng.integers(0, hit, live - hit)], 1)
    return sizes


@functools.lru_cache(maxsize=1)
def _stacks(shape: Shape):
    """The gate, up and down stacks of ``shape``: gigabytes, so one shape's at a time."""
    import jax
    import jax.numpy as jnp

    H, I, E, L = shape.hidden, shape.inter, shape.experts, shape.layers
    key = jax.random.PRNGKey(0)
    w_gate, w_up = (0.02 * jax.random.normal(jax.random.fold_in(key, i), (L * E, H, I), jnp.bfloat16) for i in (1, 2))
    return w_gate, w_up, 0.02 * jax.random.normal(jax.random.fold_in(key, 3), (L * E, I, H), jnp.bfloat16)


def bench(shape: Shape, matmul, rows: int, sizes, calls: int, repeats: int):
    """Seconds a call of ``matmul(x, w, sizes, group_offset=, out_dtype=)``
    for the gate/up shape, the down shape and the layer's three with the
    activation, each ``calls`` times back to back in one program that walks
    the stack's layers as a serving step's layer loop does."""
    import jax
    import jax.numpy as jnp

    H, I, E, L = shape.hidden, shape.inter, shape.experts, shape.layers
    key = jax.random.PRNGKey(0)
    w_gate, w_up, w_down = _stacks(shape)
    x = jax.random.normal(key, (rows, H), jnp.bfloat16)
    inner = jax.random.normal(key, (rows, I), jnp.bfloat16)
    sizes = jnp.asarray(sizes)

    def up(x, inner, sizes, offset, w_gate, w_up, w_down):
        return matmul(x, w_up, sizes, group_offset=offset, out_dtype=jnp.bfloat16)

    def down(x, inner, sizes, offset, w_gate, w_up, w_down):
        return matmul(inner, w_down, sizes, group_offset=offset, out_dtype=jnp.float32)

    def layer(x, inner, sizes, offset, w_gate, w_up, w_down):
        gate = matmul(x, w_gate, sizes, group_offset=offset, out_dtype=jnp.bfloat16)
        up = matmul(x, w_up, sizes, group_offset=offset, out_dtype=jnp.bfloat16)
        return matmul(jax.nn.silu(gate) * up, w_down, sizes, group_offset=offset, out_dtype=jnp.float32)

    seconds = {}
    for name, fn in (("up", up), ("down", down), ("layer", layer)):
        # the stacks are arguments: closed over, gigabytes would be baked into the program as constants
        def many(x, inner, sizes, *stacks, fn=fn):
            def body(i, acc):
                return acc + jnp.sum(fn(x, inner, sizes, (i % L) * E, *stacks)[:8, :8].astype(jnp.float32))

            return jax.lax.fori_loop(0, calls, body, jnp.float32(0))

        program = jax.jit(many)
        program(x, inner, sizes, w_gate, w_up, w_down).block_until_ready()
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            program(x, inner, sizes, w_gate, w_up, w_down).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        seconds[name] = best / calls
    return seconds


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="solar,mimo,olmoe,glm,laguna,laguna_x16")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    from benchmark import files
    from benchmark.kernels import grouped_expert_matmul as k
    from deepspeed_tpu.moe.grouped_matmul import grouped_matmul

    kernel = "pallas_interpret" if args.rehearse else "pallas"
    impls = {name: functools.partial(grouped_matmul, impl=impl) for name, impl in (("pallas", kernel), ("xla", "xla"))}
    shapes = TINY if args.rehearse else {name: SHAPES[name] for name in args.shape.split(",")}
    calls, repeats = (2, 1) if args.rehearse else (CALLS, 3)
    peak = files.load_json(files.HERE, "peaks.json")["TPU v5 lite" if args.rehearse else jax.devices()[0].device_kind]
    for name, shape in shapes.items():
        rng = np.random.default_rng(0)
        for window in ("narrow", "mixed"):
            rows, live, hit = getattr(shape, window)
            sizes = draw_sizes(rng, shape.experts, live, hit)
            hit = int((sizes > 0).sum())
            least, bound = k.min_seconds(live, hit, shape.hidden, shape.inter, peak)
            line = {"device": jax.devices()[0].device_kind, "shape": name, "window": window, "rows": rows, "live": live, "experts_hit": hit, "least_us_a_layer": 1e6 * least, "bound": bound}
            for impl, matmul in impls.items():
                seconds = bench(shape, matmul, rows, sizes, calls, repeats)
                line[impl] = {
                    "up_us": 1e6 * seconds["up"], "down_us": 1e6 * seconds["down"], "layer_us": 1e6 * seconds["layer"],
                    "layer_roofline_pct": 100.0 * least / seconds["layer"],
                }
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
