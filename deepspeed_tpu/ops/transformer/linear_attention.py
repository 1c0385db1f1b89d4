"""The gated delta rule with a decay per key channel: linear attention whose
state is one ``[Dk, Dv]`` matrix a head, whatever the context length.

Per head, with ``a_t`` in ``(0, 1)^Dk`` (the decay, given as ``log_a <= 0``),
``b_t`` in ``[0, 2]`` (``allow_neg_eigval`` doubles the sigmoid), ``k_t`` of
unit length and ``S`` float32::

    S_t = (I - b_t k_t k_t^T) diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

Three forms of the same mathematics:

* ``kda_recurrent``: the recurrence as a plain ``lax.scan`` over time. What
  the other two are tested against.
* ``kda_chunked``: chunks of ``CHUNK`` tokens, the state carried between
  chunks, so a window costs one read and one write of the state. Inside a
  chunk the updates ``u_t = b_t (v_t - S_{t-1}^T a_t k_t)`` solve the unit
  lower-triangular system ``(I + A) U = b (V - (K exp g) S_0)``, where
  ``g_t = sum_{i <= t} log a_i`` and
  ``A_ti = b_t sum_c k_tc k_ic exp(g_tc - g_ic)`` for ``i < t``; then
  ``O = (Q exp g) S_0 + P U`` with ``P_ti = sum_c q_tc k_ic exp(g_tc - g_ic)``,
  ``i <= t``, and ``S_C = exp(g_C) S_0 + (K exp(g_C - g))^T U``. The decay is
  per channel, so ``exp(g_t - g_i)`` does not factor into a row term and a
  column term that both stay in float32's range (``exp(-g_i)`` overflows where
  the decay is strong). Every ratio here is the exponential of a difference
  that is at most zero: inside a sub-chunk of ``SUB`` tokens the difference
  is formed directly, ``[SUB, SUB, Dk]`` at a time; between sub-chunks it
  goes through the later sub-chunk's first cumulative decay ``g_s``,
  ``exp(g_t - g_s) exp(g_s - g_i)``, both factors at most one, so the
  off-diagonal blocks stay matrix products. A dead position (``log_a`` 0,
  ``beta`` 0) leaves the state as it is.
* ``kda_decode``: one token a row, everything between a linear layer's
  projections and its output gate, in place on the state pool
  ``[L, slots + 1, H, Dk, Dv]`` and on the pool of the short convolution's
  tails ``[L, slots + 1, K - 1, 3, H, D]``: a Pallas kernel on the TPU (both
  pools aliased in to out), a gather and a scatter elsewhere. The kernel
  takes the row as the projections leave it, the 128 channels of a head on
  the lanes: the pre-convolution ``q~ k~ v~`` ``[R, 3, H, D]``, the log decay
  ``[R, H, D]``, ``b`` among the scalars. A grid step is one row's
  ``HEAD_BLOCK`` heads: the taps and SiLU on (tail, token), the l2 norms,
  ``exp``, the tail shifted and written back, one transposition of the
  block's ``[3 HEAD_BLOCK, D]`` tile of decay, k and q (the state has the
  key channel on its sublanes, so a head's three vectors are columns), then
  one read and one write of each head's state. Row r's state and tail are
  at ``slots[r]``; a row with ``fresh[r]`` starts from zero state and a zero
  tail, whatever the pools hold; a dead row is sent to the pools' last slot,
  which no request owns.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator import on_tpu

CHUNK = 64
SUB = 16
HEAD_BLOCK = 16  # heads a grid step of the decode kernel: 1 MB of state in, 1 MB out, a tile of a bfloat16 operand's sublanes
_HIGHEST = jax.lax.Precision.HIGHEST  # the recurrence is float32 throughout


def kda_step(S, q, k, v, log_a, beta):
    """One token: ``S`` [..., Dk, Dv], ``q k log_a`` [..., Dk], ``v`` [..., Dv],
    ``beta`` [...]. Returns (o [..., Dv], the new state)."""
    S = S * jnp.exp(log_a)[..., None]
    u = beta[..., None] * (v - jnp.sum(S * k[..., None], axis=-2))
    S = S + k[..., None] * u[..., None, :]
    return jnp.sum(S * q[..., None], axis=-2), S


def kda_recurrent(q, k, v, log_a, beta, state):
    """``q k log_a`` [B, T, H, Dk], ``v`` [B, T, H, Dv], ``beta`` [B, T, H],
    ``state`` [B, H, Dk, Dv], all float32. Returns (o [B, T, H, Dv], state)."""

    def step(S, x):
        o, S = kda_step(S, *x)
        return S, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, log_a, beta))
    state, o = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(o, 0, 1), state


def _chunk(S0, x):
    """One chunk for every (row, head): ``S0`` [B, H, Dk, Dv]; ``q k la``
    [B, H, C, Dk], ``v`` [B, H, C, Dv], ``beta`` [B, H, C]."""
    q, k, v, la, beta = x
    B, H, C, Dk = k.shape
    n = C // SUB
    g = jnp.cumsum(la, axis=2)
    # sub-chunk I's reference: the cumulative decay just before its first token
    gs = jnp.concatenate([jnp.zeros_like(g[:, :, :1]), g[:, :, SUB - 1 : C - 1 : SUB]], axis=2)  # [B, H, n, Dk]
    blocked = lambda a: a.reshape(B, H, n, SUB, a.shape[-1])
    gb, kb, qb = blocked(g), blocked(k), blocked(q)
    inner = jnp.exp(gb - gs[:, :, :, None])  # exp(g_t - g_s(t)) <= 1
    # exp(g_s(I) - g_i), used only for i before sub-chunk I, where it is <= 1
    outer = k[:, :, None] * jnp.exp(jnp.minimum(gs[:, :, :, None] - g[:, :, None], 0.0))  # [B, H, n, C, Dk]
    off = lambda rows: jnp.einsum("bhntc,bhnic->bhnti", rows * inner, outer, precision=_HIGHEST).reshape(B, H, C, C)
    # the diagonal blocks: the difference itself, masked before the exponential
    causal = jnp.tril(jnp.ones((SUB, SUB), bool))
    ratio = jnp.exp(jnp.where(causal[..., None], gb[:, :, :, :, None] - gb[:, :, :, None], -jnp.inf))
    diag = lambda rows: jnp.sum(rows[:, :, :, :, None] * kb[:, :, :, None] * ratio, axis=-1)  # [B, H, n, SUB, SUB]

    def full(rows):
        t = jnp.arange(C)
        earlier_block = (t[:, None] // SUB) > (t[None, :] // SUB)
        same = jnp.einsum("bhnts,nm->bhntms", diag(rows), jnp.eye(n, dtype=rows.dtype)).reshape(B, H, C, C)
        return jnp.where(earlier_block, off(rows), same)

    A = jnp.tril(full(kb), -1) * beta[..., None]
    Pm = full(qb)  # the diagonal blocks are lower triangular already
    eg = jnp.exp(g)
    rhs = beta[..., None] * (v - jnp.einsum("bhtc,bhcd->bhtd", k * eg, S0, precision=_HIGHEST))
    U = jax.scipy.linalg.solve_triangular(A + jnp.eye(C, dtype=A.dtype), rhs, lower=True, unit_diagonal=True)
    o = jnp.einsum("bhtc,bhcd->bhtd", q * eg, S0, precision=_HIGHEST) + jnp.einsum(
        "bhti,bhid->bhtd", Pm, U, precision=_HIGHEST
    )
    g_end = g[:, :, -1:]
    S = jnp.exp(g_end)[:, :, 0, :, None] * S0 + jnp.einsum(
        "bhic,bhid->bhcd", k * jnp.exp(g_end - g), U, precision=_HIGHEST
    )
    return S, o


def kda_chunked(q, k, v, log_a, beta, state, chunk: int = CHUNK):
    """``kda_recurrent``'s contract, computed chunk by chunk; ``T`` is padded
    to whole chunks with dead positions."""
    B, T, H, _ = q.shape
    pad = -T % chunk
    if pad:
        q, k, v, log_a, beta = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)) for a in (q, k, v, log_a, beta))
    N = (T + pad) // chunk
    # [B, T, H, D] -> [N, B, H, C, D]
    chunks = lambda a: jnp.moveaxis(a.reshape((B, N, chunk) + a.shape[2:]), (1, 3), (0, 2))
    xs = tuple(chunks(a) for a in (q, k, v, log_a)) + (jnp.moveaxis(beta.reshape(B, N, chunk, H), (1, 3), (0, 2)),)
    state, o = jax.lax.scan(_chunk, state, xs)
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, N * chunk, H, -1)
    return o[:, :T], state


# --- one token a row, in place on the pools ------------------------------------


def decode_qkv(w, taps):
    """What a one-token row's ``q k v`` are made of its pre-convolution token
    and tail: ``taps`` the ``K`` inputs, oldest first, each ``[..., 3, h, D]``,
    ``w`` ``[K, 3, h, D]``. The depthwise convolution and SiLU, the l2 norm of
    q and k over ``D`` and q's ``D ** -0.5``, all float32, term for term what
    ``models/hybrid_moe.py`` (``short_conv``, ``linear_qkv``) computes of a window."""
    y = jax.nn.silu(sum(w[j].astype(jnp.float32) * x.astype(jnp.float32) for j, x in enumerate(taps)))
    q, k, v = (y[..., i, :, :] for i in range(3))
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    return unit(q) * (q.shape[-1] ** -0.5), unit(k), v


def _decode_kernel(meta, x_ref, la_ref, w_ref, s_ref, t_ref, o_ref, s_out, t_out, cols, *, rows: int, heads: int):
    g, r = pl.program_id(0), pl.program_id(1)
    HB, K1 = HEAD_BLOCK, t_ref.shape[2]
    fresh = meta[1 + rows + r] != 0
    token = x_ref[0]  # [3, HB, D]
    taps = [jnp.where(fresh, 0, t_ref[0, 0, j]) for j in range(K1)] + [token]
    q, k, v = decode_qkv(w_ref[...], taps)  # [HB, D] each, a head a sublane
    for j in range(K1):  # the tail, shifted by the token
        t_out[0, 0, j] = taps[j + 1].astype(t_out.dtype)
    # the key-indexed vectors as columns, for a head's state [Dk, Dv] has the key channel on its sublanes: one
    # transposition of the block's [3 HB, Dk] tile, on the matrix unit, exact at HIGHEST (I X^T)
    Dk = q.shape[-1]
    x = jnp.concatenate([jnp.exp(la_ref[0]), k, q], axis=0)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (Dk, Dk), 0) == jax.lax.broadcasted_iota(jnp.int32, (Dk, Dk), 1)).astype(jnp.float32)
    cols[...] = jax.lax.dot_general(eye, x, (((1,), (1,)), ((), ())), precision=_HIGHEST, preferred_element_type=jnp.float32)
    for j in range(HB):
        a, kc, qc = (cols[:, i * HB + j : i * HB + j + 1] for i in range(3))  # [Dk, 1] each
        # b rides among the scalars as its bits, and is a float again once it lies along a row's lanes
        beta = jax.lax.bitcast_convert_type(jnp.full((1, Dk), meta[1 + 2 * rows + r * heads + g * HB + j]), jnp.float32)
        S = jnp.where(fresh, 0.0, s_ref[0, 0, j].astype(jnp.float32)) * a
        u = beta * (v[j : j + 1, :] - jnp.sum(S * kc, axis=0, keepdims=True))
        S = S + kc * u
        o_ref[0, j : j + 1, :] = jnp.sum(S * qc, axis=0, keepdims=True)
        s_out[0, 0, j] = S.astype(s_out.dtype)


def _decode_pallas(qkv, log_a, w, pool, tails, meta, interpret: bool):
    R, _, H, D = qkv.shape
    K = w.shape[0]
    HB, G = HEAD_BLOCK, H // HEAD_BLOCK
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"))
    state = pl.BlockSpec((1, 1, HB, D, D), lambda g, r, m: (m[0], m[1 + r], g, 0, 0))
    tail = pl.BlockSpec((1, 1, K - 1, 3, HB, D), lambda g, r, m: (m[0], m[1 + r], 0, 0, g, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, R),  # a head block's rows together: its taps are fetched once
        in_specs=[
            pl.BlockSpec((1, 3, HB, D), lambda g, r, m: (r, 0, g, 0)),
            pl.BlockSpec((1, HB, D), lambda g, r, m: (r, g, 0)),
            pl.BlockSpec((K, 3, HB, D), lambda g, r, m: (0, 0, g, 0)),
            state,
            tail,
        ],
        out_specs=[pl.BlockSpec((1, HB, D), lambda g, r, m: (r, g, 0)), state, tail],
        scratch_shapes=[pltpu.VMEM((D, 3 * HB), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, rows=R, heads=H),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, H, D), jnp.float32), jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct(tails.shape, tails.dtype)],
        input_output_aliases={4: 1, 5: 2},  # operands count from the scalars: the pools are the 5th and 6th
        # what a call touches of its operands: the rows' states and tails, not the pools. (It also decides where the
        # compiler keeps the tail pool: with no estimate Kimi's narrow program moves all 48 MB of it into fast memory
        # ahead of a scan trip's kernels and back behind them, for the 6 MB they touch. Pinning the pool to HBM by the
        # result's memory space ends every such copy, and aborts the compiler in a program that returns the pools
        # undonated, as the logits tools' does: PERF.md section 6, PR 50.)
        cost_estimate=pl.CostEstimate(
            flops=7 * R * H * D * D, transcendentals=5 * R * H * D,
            bytes_accessed=2 * R * H * D * (D * pool.dtype.itemsize + (K - 1) * 3 * tails.dtype.itemsize) + R * H * D * 16,
        ),
        interpret=interpret,
        name="kda_decode",
        **params,
    )(meta, qkv, log_a, w, pool, tails)


def kda_decode(qkv, log_a, beta, conv_w, pool, tails, layer, slots, live, fresh, impl: str = "auto") -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One token a row, from the projections to the recurrence's output, in
    place on the state pool ``pool`` [L, NS, H, D, D] float32 and the tail
    pool ``tails`` [L, NS, K - 1, 3, H, D]: ``qkv`` [R, 3, H, D] the row's
    pre-convolution ``q~ k~ v~``, ``log_a`` [R, H, D] and ``beta`` [R, H]
    float32, ``conv_w`` [K, 3, H, D] the taps; ``slots`` [R] int32 the rows'
    places in both pools, ``live`` [R] bool, ``fresh`` [R] bool (a row that
    starts from zero state AND a zero tail). A row that is not live leaves
    every request's state and tail alone: it works on the last slot,
    ``NS - 1``. ``impl``: ``auto`` (the kernel on a TPU, XLA elsewhere),
    ``pallas``, ``pallas_interpret``, ``xla``. Returns (o [R, H, D] float32,
    the state pool, the tail pool)."""
    if impl == "auto":
        impl = "pallas" if on_tpu() else "xla"
    NS = pool.shape[1]
    slots = jnp.where(live, jnp.asarray(slots, jnp.int32), NS - 1)
    fresh = fresh | ~live  # the spare slot never accumulates
    if impl in ("pallas", "pallas_interpret"):
        if qkv.shape[2] % HEAD_BLOCK:
            raise ValueError(f"kda_decode needs a multiple of {HEAD_BLOCK} heads, got {qkv.shape[2]}")
        bits = jax.lax.bitcast_convert_type(beta.astype(jnp.float32), jnp.int32).reshape(-1)
        meta = jnp.concatenate([jnp.asarray(layer, jnp.int32).reshape(1), slots, fresh.astype(jnp.int32), bits])
        return tuple(_decode_pallas(qkv, log_a, conv_w, pool, tails, meta, interpret=impl == "pallas_interpret"))
    if impl != "xla":
        raise ValueError(f"unknown kda_decode impl {impl!r}; expected auto|pallas|pallas_interpret|xla")
    zeroed = lambda a: jnp.where(fresh.reshape((-1,) + (1,) * (a.ndim - 1)), 0, a)
    tail = zeroed(tails[layer, slots])  # [R, K - 1, 3, H, D]
    taps = [tail[:, j] for j in range(tail.shape[1])] + [qkv]
    o, S = kda_step(zeroed(pool[layer, slots]).astype(jnp.float32), *decode_qkv(conv_w, taps), log_a, beta)
    shifted = jnp.stack([a.astype(tails.dtype) for a in taps[1:]], axis=1)
    return o, pool.at[layer, slots].set(S.astype(pool.dtype)), tails.at[layer, slots].set(shifted)
