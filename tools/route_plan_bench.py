"""The routing block alone, on the chip, at the eight routed serving cells'
shapes (``deepspeed_tpu/moe/route_plan.py``, ``moe/live_rows.py``,
``moe/routed_ffn.py``'s ``moe_route`` scope): everything ``routed_ffn`` does
before the experts (the scores, the top-k, the counts, each assignment's row,
the sorted ``rows``) and after them (each row back to its token with its
gate), the experts left out. Three forms side by side, for a narrow step's
rows and for one token tile of a mixed step:

* ``sorted``: two stable ``argsort``s and ``lax.top_k``, the rows by a gather
  of all ``S k``, back by a gather, a mask and a sum of k slabs: what the
  scope held until PR 64, and ``plan_path``'s form where the kernel does not run;
* ``kernel``: the plan by the ``moe_route_plan`` call, the same gathers
  around it: PR 64's form, the parent of PR 65;
* ``live_rows``: the plan's call and the two calls that walk the rows of the
  groups alone (``moe_dispatch_rows``, ``moe_combine_rows``): what
  ``plan_path`` takes on a TPU since PR 65.

In the two gather forms a sorted row's output is the row itself in float32
(the conversion rides in the gather's fusion). In ``live_rows`` the experts'
outputs are an array that is there, as they are in a layer, with one block of
this call's rows written into it: a conversion of all ``S k`` rows would be the
stand-in's cost and not the block's.

First, once a shape and before any time is taken, ON THE CHIP: the kernel's
plan is held to the sorted form's, integer for integer (the weights to
float32 rounding, ``row_weight`` over the rows of the groups), and
``live_rows``' sorted rows and output to the gathers' (the rows of the groups
bit for bit, the output in float32 to float32 rounding), at every cell's two
shapes and at ``EDGE_SHAPES``, the smallest, the odd and the ragged sizes
``kernel_fits`` admits. A difference ends the run with exit code 1.

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


def block(cell: Cell, impl: str, whole: bool, combine: str = "gather"):
    """``(tokens [S, H], logits [S, E], bias [E], live [S], stand_in [S k, H] float32) -> ([S, H], stand_in)``: the
    routing block with the plan in form ``impl`` and the two ways in form ``combine``; ``whole`` False: the plan alone."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.moe import live_rows

    def fn(tokens, logits, bias, live, stand_in):
        plan = plan_of(cell, impl)(logits, bias, live)
        if not whole:
            return plan, stand_in
        rows = live_rows.dispatch(tokens, plan, impl=combine)
        if combine == "gather":
            out_rows = rows.astype(jnp.float32)  # the experts' stand-in
        else:
            out_rows = stand_in = jax.lax.dynamic_update_slice(stand_in, rows[: live_rows.ROW_BLOCK].astype(jnp.float32), (0, 0))
        return live_rows.combine(out_rows, plan, tokens.dtype, masked=True, impl=combine), stand_in

    return fn


def inputs(cell: Cell, rows: int, live_rows: int, seed: int):
    """(tokens [S, H] bfloat16, logits [LAYERS, S, E], bias [E], live [S], the experts' outputs' stand-in [S k, H] float32)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    tokens = jax.random.normal(key, (rows, cell.hidden), jnp.bfloat16)
    logits = 2.0 * jax.random.normal(jax.random.fold_in(key, 1), (LAYERS, rows, cell.experts), jnp.float32)
    bias = 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (cell.experts,), jnp.float32)
    return tokens, logits, bias, jnp.arange(rows) < live_rows, jax.random.normal(jax.random.fold_in(key, 3), (rows * cell.k, cell.hidden), jnp.float32)


def differences(cell: Cell, rows: int, live_rows: int, kernel: str, ways: str, seed: int) -> list:
    """The names of what the kernel's plan and the sorted form's differ in, and ``ways``' sorted rows and output and the
    gathers', on this device: [] where they agree."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.moe import live_rows as ways_of

    tokens, logits, bias, live, out_rows = inputs(cell, rows, live_rows, seed)
    got, want = (jax.jit(plan_of(cell, impl))(logits[0], bias, live) for impl in (kernel, "sorted"))
    wrong = [name for name in ("chosen", "dest", "routed", "counts", "row_expert", "src") if not np.array_equal(getattr(got, name), getattr(want, name))]
    total = int(np.sum(want.counts))
    for name, exists in (("weights", slice(None)), ("row_weight", slice(0, total))):
        if not np.allclose(np.asarray(getattr(got, name))[..., exists], np.asarray(getattr(want, name))[..., exists], rtol=2e-6, atol=1e-7):
            wrong.append(name)

    def both(form):
        return jax.jit(lambda t, o, plan: (ways_of.dispatch(t, plan, impl=form), ways_of.combine(o, plan, jnp.float32, masked=True, impl=form)))(tokens, out_rows, got)

    (rows_got, out_got), (rows_want, out_want) = both(ways), both("gather")
    if not np.array_equal(np.asarray(rows_got[:total].astype(jnp.float32)), np.asarray(rows_want[:total].astype(jnp.float32))):
        wrong.append("dispatch")
    if not np.allclose(out_got, out_want, rtol=2e-6, atol=1e-6):
        wrong.append("combine")
    return wrong


def _sum_of(out):
    """What the loop keeps of a call's result: the sum of every leaf, whole, so that no part of it is dead code
    (the empty body pays the same sums)."""
    import jax
    import jax.numpy as jnp

    return sum(jnp.sum(leaf.astype(jnp.float32)) for leaf in jax.tree_util.tree_leaves(out))


def bench(fn, operands, calls: int, repeats: int) -> float:
    """Seconds a call of ``fn(tokens, logits[i], bias, live, stand_in)``, ``calls`` of them back to back in one program."""
    import jax
    import jax.numpy as jnp

    def many(tokens, logits, bias, live, stand_in):
        def call(i, carry):
            out, stand_in = fn(tokens, logits[i % LAYERS], bias, live, carry[1])
            return carry[0] + _sum_of(out), stand_in

        return jax.lax.fori_loop(0, calls, call, (jnp.float32(0), stand_in))[0]

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

    tokens, logits, bias, live, _ = operands
    ready = jax.jit(plan_of(cell, "sorted"))(logits[0], bias, live)

    def fn(tokens, logits, bias, live, stand_in):
        if whole:
            return tokens + logits[0, 0].astype(tokens.dtype), stand_in
        return jax.tree_util.tree_map(lambda leaf: leaf + logits[0, 0].astype(leaf.dtype), ready), stand_in

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
    kernel, ways = ("pallas_interpret", "pallas_interpret") if args.rehearse else ("kernel", "live_rows")

    shapes = [(name, cell, rows, live_rows) for name, cell in cells.items() for rows, live_rows in ((cell.narrow, cell.narrow), (cell.tile, cell.tile_live))]
    shapes += [(name, routers[name], rows, live_rows) for rows, live_rows, name in (TINY_EDGES if args.rehearse else EDGE_SHAPES)]
    checked, failed = [], []
    for name, cell, rows, live_rows in shapes:
        if not kernel_fits(rows, cell.experts, cell.k):
            continue
        wrong = differences(cell, rows, live_rows, kernel, ways, args.seed)
        checked.append([name, rows, cell.experts, cell.k])
        if wrong:
            failed.append({"cell": name, "S": rows, "E": cell.experts, "k": cell.k, "differ": wrong})
    print(json.dumps({"check": "kernel == sorted, live_rows == gather", "device": device, "shapes": checked, "failed": failed}), flush=True)
    if failed:
        sys.exit(1)

    for name, cell in cells.items():
        for window, rows, live_rows in (("narrow", cell.narrow, cell.narrow), ("mixed", cell.tile, cell.tile_live)):
            operands = inputs(cell, rows, live_rows, args.seed)
            overhead = {whole: bench(empty_body(cell, operands, whole), operands, calls, repeats) for whole in (False, True)}
            line = {"cell": name, "window": window, "device": device, "S": rows, "E": cell.experts, "k": cell.k, "live": live_rows,
                    "overhead_us": {key: round(1e6 * overhead[whole], 2) for key, whole in (("plan_us", False), ("block_us", True))}}
            for form, impl, combine in (("sorted", "sorted", "gather"), ("kernel", kernel, "gather"), ("live_rows", kernel, ways)):
                if form != "sorted" and not kernel_fits(rows, cell.experts, cell.k):
                    continue
                line[form] = {
                    key: round(1e6 * (bench(block(cell, impl, whole, combine), operands, calls, repeats) - overhead[whole]), 2)
                    for key, whole in (("block_us", True),) + ((("plan_us", False),) if form != "live_rows" else ())  # the plan is ``kernel``'s
                }
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
