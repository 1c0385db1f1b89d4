"""A routed layer's assignment plan, by counting and not by sorting.

What ``routed_ffn`` needs of the router's logits ``[S, E]``: each token's k
experts and their gates, the per-expert counts, for each of the ``S k``
assignments (token-major, ``i = s k + j``) its row in expert order, and for
each sorted row its token. Sorted stably by expert, with the dead and the
not-held in one bucket behind every group, an assignment's row is

    dest[i] = start[b_i] + #{ i' < i : b_i' == b_i }        start = exclusive prefix sum of the buckets' counts

which is ``argsort(argsort(buckets, stable=True))[i]``, and the rows' tokens
are the inverse of ``dest``. One kernel and one reference
(``impl="auto"``: the kernel on a TPU where the shape fits it, ``plan_path``,
the reference elsewhere; ``pallas_interpret`` is the kernel in Pallas's
interpreter, for tests off a TPU):

* ``sorted``: ``route``'s scores and ``lax.top_k``, then the two stable
  ``argsort``s, which is what ``routed_ffn`` ran until PR 64 and what runs
  off a TPU, above a token tile (a whole training sequence) and where the
  tokens fill no whole sublanes. (The same places by a ``cumsum`` of per-token
  bucket counts and a scatter of an iota, in ``jnp``, were built first and
  read no faster than the sorts at 64 tokens and slower from 512 up, 342
  against 184 us the plan at 1,024: PERF.md section 6, PR 64. They are not
  kept.)
* ``kernel``: ONE ``pallas_call`` (``moe_route_plan``) over ``2 x blocks``
  grid steps of up to ``TOKEN_BLOCK`` tokens. The first pass scores a block
  (softmax / sigmoid in float32, as ``route``), takes the k largest by k
  rounds of "largest, lowest index first, mask it" over the lanes (what
  ``lax.top_k`` picks, ties included), and adds the block's bucket counts to
  the running ones. The second pass, the totals known, takes each block's
  prefix counts as a product of a lower-triangular matrix with the block's
  ``[tokens, buckets]`` count matrix on the MXU (bfloat16 operands holding
  integers up to k, float32 sums: exact), the running counts carried from
  block to block, and reads ``dest`` off it; a sorted row's token is the
  number of tokens whose last row in the row's bucket lies before it,
  another exact product (the places split into base-256 digits) and a
  compare: all pairs of tokens and rows, which is why the kernel stops at a
  token tile. What it gives of each assignment it gives choice-major
  (``RoutePlan``), again by exact products with rows of the identity. No
  sort, no scatter, no gather. On the chip (``tools/route_plan_bench.py``,
  PERF.md section 6, PR 64): 2.2 us a call at 64 tokens, 10 us at a tile
  of 512, where the sorted form's plan alone took 27 and 70.

The plan's integers carry no gradient; ``weights`` is differentiable in the
logits in both (the kernel's by a ``custom_vjp`` through the ``jnp``
formulas at the chosen experts).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator import on_tpu

TOKEN_BLOCK = 512  # tokens a grid step; the prefix product is [block, block] x [block, buckets]
ROW_CHUNK = 512  # sorted rows whose tokens one product finds
MAX_TOKENS = 1024  # a serving window's token tile at most: a row's token is found among ALL tokens, so the kernel's work grows as S squared
MAX_ROWS = 1 << 16  # a place is two base-256 digits


class RoutePlan(NamedTuple):
    """Of each assignment, CHOICE-MAJOR ``[k, S]`` (the way back gathers
    ``[k S, H]`` rows and sums k slabs of ``[S, H]``; token-major, the rows
    would first be copied into tiles of k): ``weights`` float32, the chosen
    experts' gates; ``chosen`` int32, the router's experts; ``dest`` int32,
    its row in expert order; ``routed`` int32, 1 where that row belongs to a
    group (a held expert's, of a live token). ``counts`` [n] int32 (the held experts' alone, live tokens
    only); ``row_expert`` [S k] int32, each sorted row's expert (``n - 1``
    behind the groups); ``src`` [S k] int32, each sorted row's token;
    ``row_weight`` [S k] float32, the gate of the assignment that lies at
    each sorted row (``row_weight[dest] == weights``): defined for the
    ``sum(counts)`` rows of the groups, ANYTHING behind them (``sorted``: the
    gate all the same; the kernel: whatever the memory held)."""

    weights: jnp.ndarray
    chosen: jnp.ndarray
    dest: jnp.ndarray
    routed: jnp.ndarray
    counts: jnp.ndarray
    row_expert: jnp.ndarray
    src: jnp.ndarray
    row_weight: jnp.ndarray


class _Spec(NamedTuple):
    """The static half of a plan: what the kernel is built from, once a shape."""

    S: int
    E: int
    k: int
    scoring: str
    norm: bool
    held: Optional[Tuple[int, int]]
    has_select: bool
    has_bias: bool
    has_live: bool
    interpret: bool


# --- the gates (shared with ``routed_ffn.route``) -------------------------------


def scores(logits: jnp.ndarray, scoring: str) -> jnp.ndarray:
    """Every expert's gate, float32 ``[S, E]``."""
    if scoring == "softmax":
        return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if scoring == "sigmoid":
        return jax.nn.sigmoid(logits.astype(jnp.float32))
    raise ValueError(f"unknown router scoring {scoring!r}; expected softmax|sigmoid")


def chosen_gates(gates: jnp.ndarray, experts: jnp.ndarray, norm: bool) -> jnp.ndarray:
    """The gates of the chosen ``experts`` [S, k], renormalised to one where ``norm``."""
    chosen = jnp.take_along_axis(gates, experts, axis=-1)
    if norm:
        chosen = chosen / jnp.clip(jnp.sum(chosen, axis=-1, keepdims=True), min=jnp.finfo(jnp.float32).eps)
    return chosen


def top_k_route(logits, k: int, norm: bool, select_logits, scoring: str, select_bias):
    """``routed_ffn.route`` behind its checks: (gates [S, E], chosen [S, k] int32, their gates [S, k])."""
    gates = scores(logits, scoring)
    select = gates if select_logits is None else select_logits
    if select_bias is not None:
        select = select + select_bias.astype(jnp.float32)
    _, experts = jax.lax.top_k(select, k)
    return gates, experts.astype(jnp.int32), chosen_gates(gates, experts, norm)


def buckets_of(chosen: jnp.ndarray, num_experts: int, held, live) -> Tuple[jnp.ndarray, int]:
    """``chosen`` [S, k] -> (each assignment's bucket [S, k], n): the held
    expert's index, or n for an expert not held and for a dead token."""
    n = num_experts
    if held is not None:
        first, n = held
        chosen = jnp.where((chosen >= first) & (chosen < first + n), chosen - first, n)
    if live is not None:
        chosen = jnp.where(live[:, None], chosen, n)
    return chosen, n


# --- the reference: two stable sorts ------------------------------------------------


def sorted_plan(buckets: jnp.ndarray, n: int):
    """``buckets`` [S, k] in ``0 .. n`` -> ``(dest [S, k], counts [n],
    row_expert [S k], order [S k])`` by two stable sorts, ``order`` each sorted
    row's assignment ``s k + j`` (its token is ``order // k``): the form
    ``routed_ffn`` had until PR 64, what the kernel is held to, and what runs
    where the kernel does not (``plan_path``)."""
    S, k = buckets.shape
    flat = buckets.reshape(-1)
    counts = jnp.sum(flat[:, None] == jnp.arange(n, dtype=jnp.int32)[None, :], axis=0, dtype=jnp.int32)
    order = jnp.argsort(flat, stable=True)
    return jnp.argsort(order).reshape(S, k).astype(jnp.int32), counts, jnp.minimum(flat[order], n - 1), order.astype(jnp.int32)


# --- the kernel -----------------------------------------------------------------


def _digits(x):
    """A float32 array of whole numbers below 2**16 as two bfloat16 arrays of
    base-256 digits (each exact in bfloat16's eight bits)."""
    hi = jnp.floor(x * (1.0 / 256.0))
    return hi.astype(jnp.bfloat16), (x - 256.0 * hi).astype(jnp.bfloat16)


def _nt(a, b):
    """``a b^T`` of two bfloat16 arrays on the MXU with float32 sums."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.DEFAULT, preferred_element_type=jnp.float32)


def bf16_dot(a, b):
    """``a b`` of two bfloat16 arrays on the MXU with float32 sums: ONE pass,
    whatever ``jax_default_matmul_precision`` says (under ``highest``, which
    the float32 logits tools set, Mosaic refuses bfloat16 operands: "Bad lhs
    type"); the operands here are exact in bfloat16 by construction."""
    return jnp.dot(a, b, precision=jax.lax.Precision.DEFAULT, preferred_element_type=jnp.float32)


def _geometry(spec: _Spec):
    """(tokens a block, blocks, bucket lanes, sorted rows padded to lanes, rows a chunk)."""
    n = spec.E if spec.held is None else spec.held[1]
    TB = min(spec.S, TOKEN_BLOCK)
    Rp = -(-spec.S * spec.k // 128) * 128
    RB = Rp if Rp <= 2 * ROW_CHUNK else next(c for c in (ROW_CHUNK, 256, 128) if Rp % c == 0)
    return TB, -(-spec.S // TB), -(-(n + 1) // 128) * 128, Rp, RB


def bf16_parts(x):
    """A float32 array as three bfloat16 arrays whose sum it is (eight bits
    of its 24 each): a product of them with zeros and ones, summed in
    float32, moves any float32 through the MXU exactly."""
    hi = x.astype(jnp.bfloat16)
    mid = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32) - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _choice_major(x, rows: int, whole_numbers: bool):
    """``x`` [TB, 128] float32 with choice j in lane j -> ``[rows, TB]``: a
    product with rows of the identity, every sum of one term, exact for whole
    numbers below 2**16 (two digits) and for any float32 (``bf16_parts``)."""
    pick = (jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 0) == jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 1)).astype(jnp.bfloat16)
    if whole_numbers:
        hi, lo = _digits(x)
        return 256.0 * _nt(pick, hi) + _nt(pick, lo)
    return sum(_nt(pick, part) for part in bf16_parts(x))


def _kernel(*refs, spec: _Spec):
    S, E, k = spec.S, spec.E, spec.k
    first, n = (0, E) if spec.held is None else spec.held
    TB, _, Lb, Rp, RB = _geometry(spec)
    KP = -(-k // 16) * 16
    refs = list(refs)
    logits_ref = refs.pop(0)
    select_ref = refs.pop(0) if spec.has_select else None
    bias_ref = refs.pop(0) if spec.has_bias else None
    live_ref = refs.pop(0) if spec.has_live else None
    weights_ref, chosen_ref, dest_ref, routed_ref, counts_ref, row_expert_ref, src_ref, row_weight_ref, buckets_s, weights_s, totals_s, start_s, carry_s = refs
    f32, i32, bf16 = jnp.float32, jnp.int32, jnp.bfloat16
    phase, b = pl.program_id(0), pl.program_id(1)
    lane_c = jax.lax.broadcasted_iota(i32, (TB, 128), 1)  # a token's choice j lives in lane j
    lane_b = jax.lax.broadcasted_iota(i32, (TB, Lb), 1).astype(f32)
    at = pl.ds(pl.multiple_of(b * TB, 8), TB)

    def bucket_counts(buckets):
        """``buckets`` [TB, 128] -> the block's [TB, Lb] count matrix."""
        counts = jnp.zeros((TB, Lb), f32)
        for j in range(k):
            counts = counts + (lane_b == buckets[:, j : j + 1]).astype(f32)
        return counts

    @pl.when(phase == 0)
    def _():
        x = logits_ref[...].astype(f32)
        if spec.scoring == "softmax":
            unnormalised = jnp.exp(x - jnp.max(x, axis=1, keepdims=True))
            gates = unnormalised / jnp.sum(unnormalised, axis=1, keepdims=True)
        else:
            gates = jax.nn.sigmoid(x)
        select = select_ref[...].astype(f32) if spec.has_select else gates
        if spec.has_bias:
            select = select + bias_ref[...].astype(f32)
        token = b * TB + jax.lax.broadcasted_iota(i32, (TB, 1), 0)
        routed = token < S  # the rows a last block has past the tokens are dead ones
        if spec.has_live:
            routed = routed & (live_ref[...] != 0)
        lane = jax.lax.broadcasted_iota(i32, (TB, E), 1).astype(f32)
        taken = jnp.zeros((TB, E), jnp.bool_)
        weights, chosen, buckets = jnp.zeros((TB, 128), f32), jnp.zeros((TB, 128), f32), jnp.full((TB, 128), float(n + 1), f32)
        total = jnp.zeros((TB, 1), f32)
        for j in range(k):  # the largest left, the lowest index among equals
            left = jnp.where(taken, -jnp.inf, select)
            top = jnp.max(left, axis=1, keepdims=True)
            expert = jnp.min(jnp.where((left == top) & ~taken, lane, float(E)), axis=1, keepdims=True)
            hit = lane == expert
            gate = jnp.sum(jnp.where(hit, gates, 0.0), axis=1, keepdims=True)
            taken = taken | hit
            total = total + gate
            bucket = jnp.where(routed & (expert >= first) & (expert < first + n), expert - first, float(n))
            here = lane_c == j
            weights, chosen, buckets = jnp.where(here, gate, weights), jnp.where(here, expert, chosen), jnp.where(here, bucket, buckets)
        if spec.norm:
            weights = weights / jnp.maximum(total, jnp.finfo(f32).eps)
        weights_ref[...] = _choice_major(weights, KP, False)[:k]
        chosen_ref[...] = _choice_major(chosen, KP, True)[:k].astype(i32)
        buckets_s[at, :] = buckets
        weights_s[at, :] = weights
        block_totals = jnp.sum(bucket_counts(buckets), axis=0, keepdims=True)
        totals_s[...] = jnp.where(b == 0, block_totals, totals_s[...] + block_totals)

    @pl.when(phase == 1)
    def _():
        lane_row = jax.lax.broadcasted_iota(i32, (1, Lb), 1)

        @pl.when(b == 0)
        def _():
            totals = totals_s[...]
            above = (jax.lax.broadcasted_iota(i32, (Lb, Lb), 0) < jax.lax.broadcasted_iota(i32, (Lb, Lb), 1)).astype(bf16)
            hi, lo = _digits(jnp.broadcast_to(totals, (16, Lb)))
            start = (256.0 * bf16_dot(hi, above) + bf16_dot(lo, above))[:1]
            start_s[...] = start
            carry_s[...] = jnp.zeros((1, Lb), f32)
            counts_ref[...] = totals[:, :n].astype(i32)
            # a sorted row's expert: the groups that end at or before it
            ends = jnp.where(lane_row < n, start + totals, float(MAX_ROWS))
            for r0 in range(0, Rp, RB):
                row = (r0 + jax.lax.broadcasted_iota(i32, (RB, Lb), 0)).astype(f32)
                ended = _nt(jnp.ones((16, Lb), bf16), (ends <= row).astype(bf16))[:1]
                row_expert_ref[:, r0 : r0 + RB] = jnp.minimum(ended.astype(i32), n - 1)

        buckets = buckets_s[at, :]
        counts = bucket_counts(buckets)
        below = (jax.lax.broadcasted_iota(i32, (TB, TB), 1) < jax.lax.broadcasted_iota(i32, (TB, TB), 0)).astype(bf16)
        # the bucket's first row + its rows of the blocks before + of this block's tokens before
        place = start_s[...] + carry_s[...] + bf16_dot(below, counts.astype(bf16))
        dest, behind = jnp.zeros((TB, 128), f32), jnp.zeros((TB, 1), f32)
        for j in range(k):
            bucket = buckets[:, j : j + 1]
            mine = jnp.sum(jnp.where(lane_b == bucket, place, 0.0), axis=1, keepdims=True)
            # only the last bucket can hold two of one token's choices: they lie in the choices' order
            dest = jnp.where(lane_c == j, mine + jnp.where(bucket == n, behind, 0.0), dest)
            behind = behind + (bucket == n).astype(f32)
        dest_ref[...] = _choice_major(dest, KP, True)[:k].astype(i32)
        routed_ref[...] = _choice_major((buckets < n).astype(f32), KP, True)[:k].astype(i32)
        carry_s[...] = carry_s[...] + jnp.sum(counts, axis=0, keepdims=True)
        # a sorted row's token: how many tokens' rows of its bucket all lie before it
        past_hi, past_lo = _digits(place + counts)
        first_row, end_row = start_s[...], start_s[...] + totals_s[...]
        # a token's gate in each bucket (a held expert is chosen at most once a token)
        weights, gate = weights_s[at, :], jnp.zeros((TB, Lb), f32)
        for j in range(k):
            gate = gate + jnp.where(lane_b == buckets[:, j : j + 1], weights[:, j : j + 1], 0.0)
        gate = bf16_parts(gate)
        in_groups = jnp.sum(jnp.where(lane_row < n, totals_s[...], 0.0))  # the rows that exist: behind them no gate is looked up
        for r0 in range(0, Rp, RB):
            row = (r0 + jax.lax.broadcasted_iota(i32, (RB, Lb), 0)).astype(f32)
            of_bucket = ((first_row <= row) & (row < end_row)).astype(bf16)  # [RB, Lb], a one a row
            past = 256.0 * _nt(past_hi, of_bucket) + _nt(past_lo, of_bucket)  # [TB, RB]: where the token's rows in the row's bucket end
            row = (r0 + jax.lax.broadcasted_iota(i32, (1, RB), 1)).astype(f32)
            before = jnp.sum((past <= row).astype(f32), axis=0, keepdims=True).astype(i32)
            src_ref[:, r0 : r0 + RB] = jnp.where(b == 0, before, src_ref[:, r0 : r0 + RB] + before)

            @pl.when(r0 < in_groups)
            def _():
                # the row's gate: that of the token whose one row in the row's bucket ends just behind it
                in_bucket = sum(_nt(part, of_bucket) for part in gate)  # [TB, RB]: the token's gate in the row's bucket
                mine = jnp.sum(jnp.where(past == row + 1.0, in_bucket, 0.0), axis=0, keepdims=True)
                row_weight_ref[:, r0 : r0 + RB] = jnp.where(b == 0, mine, row_weight_ref[:, r0 : r0 + RB] + mine)


@functools.lru_cache(maxsize=64)
def _plan_call(spec: _Spec):
    """The ``pallas_call`` of ``_kernel``, built ONCE a shape: a serving
    process routes at several sites of its programs (the narrow and the wide
    program, a leading layer and the scanned ones), and the call's own
    ``jit`` traces the kernel's body anew for every callable it is handed."""
    S, E, k = spec.S, spec.E, spec.k
    n = E if spec.held is None else spec.held[1]
    TB, blocks, Lb, Rp, _ = _geometry(spec)
    last = blocks - 1

    def first_pass(p, b):  # read by the first pass; the second holds the last block
        return (jnp.where(p == 0, b, last), 0)

    def first_pass_out(p, b):
        return (0, jnp.where(p == 0, b, last))

    def second_pass_out(p, b):
        return (0, jnp.where(p == 0, 0, b))

    def whole(p, b):
        return (0, 0)

    in_specs = [pl.BlockSpec((TB, E), first_pass)]
    if spec.has_select:
        in_specs.append(pl.BlockSpec((TB, E), first_pass))
    if spec.has_bias:
        in_specs.append(pl.BlockSpec((1, E), whole))
    if spec.has_live:
        in_specs.append(pl.BlockSpec((TB, 1), first_pass))
    params = {}
    if not spec.interpret:
        # no ``vmem_limit_bytes``: the kernel fits the default 16 MiB, and a larger claim is taken from the ops AROUND
        # the call (at 64 MiB Kimi's narrow step read 12.81 ms where it reads 12.00: PERF.md section 6, PR 64)
        params["compiler_params"] = pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"))
    return pl.pallas_call(
        functools.partial(_kernel, spec=spec),
        grid=(2, blocks),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((k, TB), first_pass_out),
            pl.BlockSpec((k, TB), first_pass_out),
            pl.BlockSpec((k, TB), second_pass_out),
            pl.BlockSpec((k, TB), second_pass_out),
            pl.BlockSpec((1, n), whole),
            pl.BlockSpec((1, Rp), whole),
            pl.BlockSpec((1, Rp), whole),
            pl.BlockSpec((1, Rp), whole),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, S), jnp.float32),
            jax.ShapeDtypeStruct((k, S), jnp.int32),
            jax.ShapeDtypeStruct((k, S), jnp.int32),
            jax.ShapeDtypeStruct((k, S), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, Rp), jnp.int32),
            jax.ShapeDtypeStruct((1, Rp), jnp.int32),
            jax.ShapeDtypeStruct((1, Rp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blocks * TB, 128), jnp.float32),  # every token's buckets, choice j in lane j
            pltpu.VMEM((blocks * TB, 128), jnp.float32),  # and their gates
            pltpu.VMEM((1, Lb), jnp.float32),  # the buckets' totals
            pltpu.VMEM((1, Lb), jnp.float32),  # their first rows
            pltpu.VMEM((1, Lb), jnp.float32),  # their rows of the blocks passed
        ],
        interpret=spec.interpret,
        name="moe_route_plan",
        **params,
    )


def _kernel_forward(logits, select_logits, select_bias, live, spec: _Spec) -> RoutePlan:
    operands = [logits.astype(jnp.float32)]
    if spec.has_select:
        operands.append(select_logits.astype(jnp.float32))
    if spec.has_bias:
        operands.append(select_bias.astype(jnp.float32).reshape(1, spec.E))
    if spec.has_live:
        operands.append(live.astype(jnp.int32).reshape(spec.S, 1))
    weights, chosen, dest, routed, counts, row_expert, src, row_weight = _plan_call(spec)(*operands)
    rows = spec.S * spec.k
    return RoutePlan(weights, chosen, dest, routed, counts.reshape(-1), row_expert.reshape(-1)[:rows], src.reshape(-1)[:rows], row_weight.reshape(-1)[:rows])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _kernel_plan(logits, select_logits, select_bias, live, spec: _Spec) -> RoutePlan:
    return _kernel_forward(logits, select_logits, select_bias, live, spec)


def _kernel_plan_fwd(logits, select_logits, select_bias, live, spec):
    plan = _kernel_forward(logits, select_logits, select_bias, live, spec)
    return plan, (logits, plan.chosen, plan.dest, plan.routed)


def _kernel_plan_bwd(spec, saved, g):
    logits, chosen, dest, routed = saved
    _, vjp = jax.vjp(lambda lg: chosen_gates(scores(lg, spec.scoring), chosen.T, spec.norm).T, logits)
    # a group's row holds its assignment's gate; what lies behind the groups is no function of the logits
    return (*vjp(g.weights + jnp.where(routed != 0, g.row_weight[dest], 0.0)), None, None, None)


_kernel_plan.defvjp(_kernel_plan_fwd, _kernel_plan_bwd)


def kernel_fits(S: int, E: int, k: int) -> bool:
    """Whether the kernel takes this shape: whole sublanes of tokens, at most
    a token tile of them (a row's token is found among ALL tokens, so beyond
    it the sorts are the better inverse), a place in two digits. Compiled for
    a v5e from 8 tokens up, ragged last block included
    (``tests/unit/moe/test_routed_ffn.py``), and held to the sorts on the chip
    at those shapes (``tools/route_plan_bench.py``)."""
    padded = -(-S // TOKEN_BLOCK) * TOKEN_BLOCK if S > TOKEN_BLOCK else S  # a last block's dead rows have places too
    return S % 8 == 0 and S <= MAX_TOKENS and padded * k < MAX_ROWS and k <= 128


def plan_path(S: int, E: int, k: int) -> Dict[str, Any]:
    """What ``impl="auto"`` does with ``[S, E]`` logits and k choices here,
    from the shape and the backend alone: ``path`` (``kernel`` | ``sorted``),
    the kernel's ``blocks`` of tokens (0 where it does not run), and
    ``combine``, the form of ``routed_ffn``'s two ways between token order
    and expert order (``moe/live_rows.py``: ``live_rows`` beside the kernel,
    ``gather`` beside the sorts). What ``routed_ffn`` asks, and what an
    engine records of its programs' shapes where it builds them
    (``moe.route_plan``: the ops have no tracer)."""
    if on_tpu() and kernel_fits(S, E, k):
        return {"path": "kernel", "S": S, "E": E, "k": k, "blocks": -(-S // TOKEN_BLOCK), "combine": "live_rows"}
    return {"path": "sorted", "S": S, "E": E, "k": k, "blocks": 0, "combine": "gather"}


def route_plan(
    logits: jnp.ndarray,
    *,
    k: int,
    norm_topk_prob: Optional[bool],
    scoring: str = "softmax",
    select_logits: Optional[jnp.ndarray] = None,
    select_bias: Optional[jnp.ndarray] = None,
    live: Optional[jnp.ndarray] = None,
    held: Optional[Tuple[int, int]] = None,
    impl: str = "auto",
) -> RoutePlan:
    """The plan of ``logits`` [S, E] (module docstring); the arguments are
    ``routed_ffn``'s. ``impl``: ``auto`` (``plan_path``) | ``kernel`` |
    ``pallas_interpret`` | ``sorted``."""
    S, E = logits.shape
    if not 1 <= k <= E:
        raise ValueError(f"top-k routing needs 1 <= k <= num_experts, got k={k} of {E}")
    norm = k > 1 if norm_topk_prob is None else bool(norm_topk_prob)
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown router scoring {scoring!r}; expected softmax|sigmoid")
    if impl == "auto":
        impl = plan_path(S, E, k)["path"]
    if impl == "sorted":
        _, chosen, weights = top_k_route(logits, k, norm, select_logits, scoring, select_bias)
        buckets, n = buckets_of(chosen, E, held, live)
        dest, counts, row_expert, order = sorted_plan(buckets, n)
        return RoutePlan(weights.T, chosen.T, dest.T, (buckets < n).T.astype(jnp.int32), counts, row_expert, order // k, weights.reshape(-1)[order])
    if impl not in ("kernel", "pallas_interpret"):
        raise ValueError(f"route_plan impl must be auto, kernel, pallas_interpret or sorted, got {impl!r}")
    if not kernel_fits(S, E, k):
        raise ValueError(f"the route plan kernel does not take S={S}, E={E}, k={k} (kernel_fits)")
    spec = _Spec(S, E, k, scoring, norm, None if held is None else (int(held[0]), int(held[1])),
                 select_logits is not None, select_bias is not None, live is not None, impl == "pallas_interpret")
    return _kernel_plan(logits, select_logits, select_bias, live, spec)
