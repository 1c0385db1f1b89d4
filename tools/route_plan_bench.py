"""The routing block alone, on the chip, at the eight routed serving cells'
shapes (``deepspeed_tpu/moe/route_plan.py``, ``moe/routed_ffn.py``'s
``moe_route`` scope): everything ``routed_ffn`` does before the experts (the
scores, the top-k, the counts, each assignment's row, the gather of the
sorted ``rows``) and after them (each row back to its token, the mask, the
weighted sum), the experts left out (a sorted row's output is the row itself,
in float32). Two forms side by side: ``sorted`` (two stable ``argsort``s and
``lax.top_k``: what the scope held until PR 64, and ``auto``'s form where the
kernel does not run) and ``kernel`` (the ``pallas_call``), for a narrow step's
rows and for one token tile of a mixed step.

First, once a shape and before any time is taken, the kernel's plan is held
to the sorted form's ON THE CHIP, integer for integer (the weights to float32
rounding): at every cell's two shapes and at ``EDGE_SHAPES``, the smallest,
the odd and the ragged sizes ``kernel_fits`` admits. A difference ends the
run with exit code 1.

Then, a cell and a window, microseconds a call of the plan alone and of the
whole block: ``CALLS`` calls back to back in one program that walks a stack
of logits as a step's layer loop does, the host's clock around it, the best
of ``--repeats``, LESS the same loop over an empty body (the tool's own sums
over leaves of a plan's shapes, ``overhead_us``: about 13 us of a plan's raw
figure, where the kernel reads 2.2 us in a step's trace).

    chiprun -- python3 tools/route_plan_bench.py [--cell lfm2,laguna,...]
    python3 tools/route_plan_bench.py --rehearse   # tiny, on the CPU: the control flow only
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import NamedTuple, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CALLS = 64
LAYERS = 4  # distinct logits the program walks


class Cell(NamedTuple):
    """A cell's router and windows: width, k, the held experts (None: all),
    hidden size, scoring, whether a selection bias is added, whether the
    chosen gates are normalised to one; a narrow step's rows; a mixed step's
    token tile (``decode.token_tile``) and the live tokens of one (PERF.md
    section 5: ~190 of 512, OLMoE ~143 of 1,024)."""

    experts: int
    k: int
    held: Optional[int]
    hidden: int
    scoring: str
    bias: bool
    norm: bool
    narrow: int
    tile: int
    tile_live: int


CELLS = {
    "lfm2": Cell(64, 4, 8, 2048, "sigmoid", True, True, 64, 512, 190),
    "laguna": Cell(256, 10, 16, 3072, "softmax", False, True, 64, 512, 190),
    "glm": Cell(64, 4, 8, 2048, "sigmoid", True, True, 64, 512, 190),
    "mimo": Cell(256, 8, 16, 4096, "sigmoid", True, True, 64, 512, 190),
    "solar": Cell(320, 8, 40, 4096, "sigmoid", True, True, 64, 512, 190),
    "kimi": Cell(256, 8, 32, 2304, "sigmoid", True, True, 64, 512, 190),
    "nemotron": Cell(128, 6, 64, 2688, "sigmoid", True, True, 64, 512, 190),
    "olmoe": Cell(64, 8, None, 2048, "softmax", False, False, 16, 1024, 143),
}
TINY = {"tiny": Cell(8, 3, 5, 128, "sigmoid", True, True, 16, 64, 20)}
# (S, live, router) beyond the cells' own: the fewest tokens ``kernel_fits`` admits, sizes that are no multiple of 16,
# a last block of 8 tokens and one of 488
EDGE_SHAPES = ((8, 8, "olmoe"), (8, 5, "lfm2"), (24, 17, "laguna"), (40, 40, "solar"), (72, 50, "nemotron"), (520, 300, "lfm2"), (1000, 700, "olmoe"))
TINY_EDGES = ((8, 5, "tiny"), (24, 17, "tiny"))


def plan_of(cell: Cell, impl: str):
    """``(logits [S, E], bias [E], live [S]) -> RoutePlan`` in form ``impl``, as the cell's ``routed_ffn`` asks for it."""
    from deepspeed_tpu.moe.route_plan import route_plan

    def fn(logits, bias, live):
        return route_plan(
            logits, k=cell.k, norm_topk_prob=cell.norm, scoring=cell.scoring, select_bias=bias if cell.bias else None,
            live=live, held=None if cell.held is None else (0, cell.held), impl=impl,
        )

    return fn


def block(cell: Cell, impl: str, whole: bool):
    """``(tokens [S, H], logits [S, E], bias [E], live [S]) -> [S, H]``: the
    routing block in form ``impl``; ``whole`` False: the plan alone."""
    import jax.numpy as jnp

    from deepspeed_tpu.moe.routed_ffn import rows_at

    def fn(tokens, logits, bias, live):
        plan = plan_of(cell, impl)(logits, bias, live)
        if not whole:
            return plan
        out_rows = rows_at(tokens, plan.src).astype(jnp.float32)  # the experts' stand-in
        per_choice = jnp.where(plan.routed[..., None] != 0, rows_at(out_rows, plan.dest), 0.0)
        return jnp.sum(per_choice * plan.weights[..., None], axis=0).astype(tokens.dtype)

    return fn


def inputs(cell: Cell, rows: int, live_rows: int, seed: int):
    """(tokens [S, H] bfloat16, logits [LAYERS, S, E], bias [E], live [S])."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    tokens = jax.random.normal(key, (rows, cell.hidden), jnp.bfloat16)
    logits = 2.0 * jax.random.normal(jax.random.fold_in(key, 1), (LAYERS, rows, cell.experts), jnp.float32)
    bias = 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (cell.experts,), jnp.float32)
    return tokens, logits, bias, jnp.arange(rows) < live_rows


def differences(cell: Cell, rows: int, live_rows: int, kernel: str, seed: int) -> list:
    """The names of what the kernel's plan and the sorted form's differ in, on this device: [] where they agree."""
    import jax
    import numpy as np

    _, logits, bias, live = inputs(cell, rows, live_rows, seed)
    got, want = (jax.jit(plan_of(cell, impl))(logits[0], bias, live) for impl in (kernel, "sorted"))
    wrong = [name for name in ("chosen", "dest", "routed", "counts", "row_expert", "src") if not np.array_equal(getattr(got, name), getattr(want, name))]
    if not np.allclose(got.weights, want.weights, rtol=2e-6, atol=1e-7):
        wrong.append("weights")
    return wrong


def _sum_of(out):
    """What the loop keeps of a call's result: the sum of every leaf, whole, so that no part of it is dead code
    (the empty body pays the same sums)."""
    import jax
    import jax.numpy as jnp

    return sum(jnp.sum(leaf.astype(jnp.float32)) for leaf in jax.tree_util.tree_leaves(out))


def bench(fn, operands, calls: int, repeats: int) -> float:
    """Seconds a call of ``fn(tokens, logits[i], bias, live)``, ``calls`` of them back to back in one program."""
    import jax
    import jax.numpy as jnp

    def many(tokens, logits, bias, live):
        return jax.lax.fori_loop(0, calls, lambda i, acc: acc + _sum_of(fn(tokens, logits[i % LAYERS], bias, live)), jnp.float32(0))

    program = jax.jit(many)
    program(*operands).block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        program(*operands).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / calls


def empty_body(cell: Cell, operands, whole: bool):
    """A body that gives leaves of the block's shapes and does none of its
    work: a plan computed once, outside the loop, with a number of this
    call's logits added (so the loop cannot hoist it), or the tokens."""
    import jax

    tokens, logits, bias, live = operands
    ready = jax.jit(plan_of(cell, "sorted"))(logits[0], bias, live)

    def fn(tokens, logits, bias, live):
        if whole:
            return tokens + logits[0, 0].astype(tokens.dtype)
        return jax.tree_util.tree_map(lambda leaf: leaf + logits[0, 0].astype(leaf.dtype), ready)

    return fn


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default=",".join(CELLS))
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from deepspeed_tpu.moe.route_plan import kernel_fits

    device = jax.devices()[0].platform
    if not args.rehearse and device != "tpu":
        sys.exit("route_plan_bench: no TPU (a time off the chip is no device number); --rehearse runs the control flow on the CPU")
    routers = TINY if args.rehearse else CELLS
    cells = TINY if args.rehearse else {name: CELLS[name] for name in args.cell.split(",")}
    calls, repeats = (2, 1) if args.rehearse else (CALLS, args.repeats)
    kernel = "pallas_interpret" if args.rehearse else "kernel"

    shapes = [(name, cell, rows, live_rows) for name, cell in cells.items() for rows, live_rows in ((cell.narrow, cell.narrow), (cell.tile, cell.tile_live))]
    shapes += [(name, routers[name], rows, live_rows) for rows, live_rows, name in (TINY_EDGES if args.rehearse else EDGE_SHAPES)]
    checked, failed = [], []
    for name, cell, rows, live_rows in shapes:
        if not kernel_fits(rows, cell.experts, cell.k):
            continue
        wrong = differences(cell, rows, live_rows, kernel, args.seed)
        checked.append([name, rows, cell.experts, cell.k])
        if wrong:
            failed.append({"cell": name, "S": rows, "E": cell.experts, "k": cell.k, "differ": wrong})
    print(json.dumps({"check": "kernel == sorted", "device": device, "shapes": checked, "failed": failed}), flush=True)
    if failed:
        sys.exit(1)

    for name, cell in cells.items():
        for window, rows, live_rows in (("narrow", cell.narrow, cell.narrow), ("mixed", cell.tile, cell.tile_live)):
            operands = inputs(cell, rows, live_rows, args.seed)
            overhead = {whole: bench(empty_body(cell, operands, whole), operands, calls, repeats) for whole in (False, True)}
            line = {"cell": name, "window": window, "device": device, "S": rows, "E": cell.experts, "k": cell.k, "live": live_rows,
                    "overhead_us": {key: round(1e6 * overhead[whole], 2) for key, whole in (("plan_us", False), ("block_us", True))}}
            for impl in ("sorted", kernel):
                if impl != "sorted" and not kernel_fits(rows, cell.experts, cell.k):
                    continue
                line["sorted" if impl == "sorted" else "kernel"] = {
                    key: round(1e6 * (bench(block(cell, impl, whole), operands, calls, repeats) - overhead[whole]), 2)
                    for key, whole in (("plan_us", False), ("block_us", True))
                }
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
