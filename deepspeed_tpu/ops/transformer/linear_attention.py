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
* ``kda_decode``: one token a row, in place on the state pool
  ``[L, slots + 1, H, Dk, Dv]``: a Pallas kernel on the TPU (one read and one
  write of a live row's state; the pool is aliased in to out), a gather and a
  scatter elsewhere. Row r's state is ``pool[layer, slots[r]]``; a row with
  ``fresh[r]`` starts from zero, whatever the pool holds; a dead row is sent
  to the pool's last slot, which no request owns.
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
HEAD_BLOCK = 8  # heads a grid step of the decode kernel: 512 KB of state in, 512 KB out
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


# --- one token a row, in place on the pool -------------------------------------


def _decode_kernel(meta, kq_ref, v_ref, s_ref, o_ref, s_out, *, rows: int):
    r = pl.program_id(0)
    fresh = meta[1 + rows + r] != 0
    for j in range(HEAD_BLOCK):
        a, k, kb, q = (kq_ref[0, 0, i][:, j : j + 1] for i in range(4))  # [Dk, 1] each
        S = jnp.where(fresh, 0.0, s_ref[0, 0, j].astype(jnp.float32)) * a
        w = v_ref[0, j : j + 1, :] - jnp.sum(S * k, axis=0, keepdims=True)
        S = S + kb * w
        o_ref[0, j : j + 1, :] = jnp.sum(S * q, axis=0, keepdims=True)
        s_out[0, 0, j] = S.astype(s_out.dtype)


def _decode_pallas(q, k, v, a, beta, pool, meta, interpret: bool):
    R, H, Dk = q.shape
    Dv = v.shape[-1]
    G = H // HEAD_BLOCK
    # the four key-indexed vectors with the channel on sublanes and the head on
    # lanes, a head block apart: the kernel slices a head's column and
    # broadcasts it along the state's value axis
    kq = jnp.stack([a, k, k * beta[..., None], q], axis=1)  # [R, 4, H, Dk]
    kq = kq.reshape(R, 4, G, HEAD_BLOCK, Dk).transpose(0, 2, 1, 4, 3)  # [R, G, 4, Dk, HB]
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R, G),
        in_specs=[
            pl.BlockSpec((1, 1, 4, Dk, HEAD_BLOCK), lambda r, g, m: (r, g, 0, 0, 0)),
            pl.BlockSpec((1, HEAD_BLOCK, Dv), lambda r, g, m: (r, g, 0)),
            pl.BlockSpec((1, 1, HEAD_BLOCK, Dk, Dv), lambda r, g, m: (m[0], m[1 + r], g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, HEAD_BLOCK, Dv), lambda r, g, m: (r, g, 0)),
            pl.BlockSpec((1, 1, HEAD_BLOCK, Dk, Dv), lambda r, g, m: (m[0], m[1 + r], g, 0, 0)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, rows=R),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, H, Dv), jnp.float32), jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={3: 1},  # operands count from the scalars: the pool is the 4th
        interpret=interpret,
        name="kda_decode",
        **params,
    )(meta, kq, v, pool)


def kda_decode(q, k, v, log_a, beta, pool, layer, slots, live, fresh, impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """One token a row against ``pool`` [L, NS, H, Dk, Dv] float32, in place:
    ``q k log_a`` [R, H, Dk], ``v`` [R, H, Dv], ``beta`` [R, H], float32;
    ``slots`` [R] int32 the rows' states, ``live`` [R] bool, ``fresh`` [R] bool
    (a row that starts from zero state). A row that is not live leaves every
    request's state alone: it works on the last slot, ``NS - 1``. ``impl``:
    ``auto`` (the kernel on a TPU, XLA elsewhere), ``pallas``,
    ``pallas_interpret``, ``xla``. Returns (o [R, H, Dv] float32, the pool)."""
    if impl == "auto":
        impl = "pallas" if on_tpu() else "xla"
    NS = pool.shape[1]
    slots = jnp.where(live, jnp.asarray(slots, jnp.int32), NS - 1)
    fresh = fresh | ~live  # the spare slot never accumulates
    if impl in ("pallas", "pallas_interpret"):
        if q.shape[1] % HEAD_BLOCK:
            raise ValueError(f"kda_decode needs a multiple of {HEAD_BLOCK} heads, got {q.shape[1]}")
        meta = jnp.concatenate([jnp.asarray(layer, jnp.int32).reshape(1), slots, fresh.astype(jnp.int32)])
        return tuple(_decode_pallas(q, k, v, jnp.exp(log_a), beta, pool, meta, interpret=impl == "pallas_interpret"))
    if impl != "xla":
        raise ValueError(f"unknown kda_decode impl {impl!r}; expected auto|pallas|pallas_interpret|xla")
    S = jnp.where(fresh[:, None, None, None], 0.0, pool[layer, slots].astype(jnp.float32))
    o, S = kda_step(S, q, k, v, log_a, beta)
    return o, pool.at[layer, slots].set(S.astype(pool.dtype))
