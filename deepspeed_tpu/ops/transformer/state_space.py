"""The state-space recurrence of a Mamba-2 layer (SSD with a scalar decay a
head and ``G`` groups of ``B`` and ``C``): a head's state is one ``[P, N]`` matrix
(``P`` the head's width, ``N`` the state size), whatever the context length.

Per head, with ``dt_t > 0`` (after softplus), ``A < 0`` a head, ``B_t`` and
``C_t`` of ``N`` a GROUP of heads, ``x_t`` of ``P`` and ``S`` float32::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

``B`` and ``C`` are ``[.., N]`` (one group: shared by all heads; granite) or
``[.., G, N]`` with ``NH % G == 0``, head ``n`` reading group ``n // (NH / G)``
(Nemotron-H: 8 groups of 8 heads). The one-group form is computed as it always
was, operation for operation; the grouped form is the same sums with the
group's ``B`` and ``C`` in each head's place. The two stand side by side in
``ssd_step``, ``_chunk`` and the decode form for ONE reason: a model of one
group has to lower to the program it lowered to before there were groups (the
accepted benchmark cells are held to their programs' text), and ``[.., 1, N]``
through the grouped sums is other operations for the same numbers.

Four forms of the same mathematics:

* ``ssd_step``: one token, plain.
* ``ssd_recurrent``: a ``lax.scan`` of it over time. What the others are
  tested against.
* ``ssd_chunked``: chunks of ``CHUNK`` tokens from a carried state, so a
  window costs one read and one write of the state. With ``g_t = sum_{i <= t}
  dt_i A`` (float32, at most zero) a chunk is ``Y = (L * (C B^T)) (dt X) +
  exp(g) (C S_0^T) + D X`` with ``L_ti = exp(g_t - g_i)`` for ``i <= t`` (the
  difference formed before the exponential: every factor at most one), and
  ``S_C = exp(g_C) S_0 + sum_i exp(g_C - g_i) dt_i x_i B_i^T``. The decay is a
  scalar a head, so no sub-chunks are needed (``linear_attention.py`` needs
  them for its decay a channel). A dead position (``dt`` 0) leaves the state
  as it is. (The published kernel's tile, ``mamba_chunk_size`` 256, is its
  blocking and not mathematics; the served chunk is the engine's
  ``prefill_chunk``.)
* ``ssd_decode``: one token a row, everything between a state-space layer's
  input projection and its gated norm, in place on the state pool ``[L, slots
  + 1, NH, P, N]`` float32 and on the pool of the convolution's tails ``[L,
  slots + 1, K - 1, tail_rows(C), 128]`` (``C = NH P + 2 G N`` channels, a lane
  tile a row, the rows in whole sublane tiles of 16: 34 rows in 48, or the
  device keeps the pool with its SLOTS on the sublanes and the program copies
  it to this layout and back every step): a Pallas kernel on the TPU (both
  pools aliased in to out), a gather and a scatter elsewhere. A grid step is
  one row: the taps, the bias and SiLU on (tail, token), the tail shifted and
  written back, one transposition of
  the row's ``x`` tiles on the matrix unit (the state has a head's feature on
  its sublanes and the state's ``N`` on its lanes, so ``x`` is a column and
  a group's ``B`` and ``C`` are rows: the row's last ``2 G`` lane tiles), one
  read and one write of each head's state, and
  the read-out ``S C`` two heads at a time on the matrix unit (``C S^T`` with
  the pair's group's ``C``, exact
  at HIGHEST: a reduction over lanes costs the vector units more). Row r's
  state and tail are at ``slots[r]``; a row with ``fresh[r]`` starts from zero
  state and a zero tail, whatever the pools hold; a dead row computes nothing
  and leaves zeros in the pools' last slot, which no request owns.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator import on_tpu

CHUNK = 128
LANES = 128  # a row of the tail pool: one lane tile of the convolved channels
_HIGHEST = jax.lax.Precision.HIGHEST  # the recurrence is float32 throughout


def tail_rows(channels: int) -> int:
    """Rows of ``LANES`` channels that the tail pool keeps of one input: the
    channels' lane tiles in whole sublane tiles of 16 (the packing of a 16-bit
    type), the rows past the channels zeros."""
    return -(-(channels // LANES) // 16) * 16


def _grouped(x, B) -> bool:
    """Whether ``B`` (or ``C``) has a group axis ``[.., G, N]`` beside ``x`` [.., NH, P]; ``[.., N]`` is one group."""
    return B.ndim == x.ndim


def _heads_a_group(heads: int, groups: int) -> int:
    if heads % groups:
        raise ValueError(f"{heads} heads are no whole number of {groups} groups of B and C")
    return heads // groups


def _of_heads(B, heads: int):
    """``B`` [.., G, N] as each head reads it, [.., NH, N]: head n has group ``n // (NH / G)``."""
    return jnp.repeat(B, _heads_a_group(heads, B.shape[-2]), axis=-2)


def ssd_step(S, x, B, C, dt, A, D):
    """One token: ``S`` [..., NH, P, N], ``x`` [..., NH, P], ``B C`` [..., N]
    or [..., G, N], ``dt`` [..., NH], ``A D`` [NH]. Returns (y [..., NH, P], the new state)."""
    if _grouped(x, B):
        B, C = (_of_heads(a, x.shape[-2])[..., None, :] for a in (B, C))  # [..., NH, 1, N]
        S = S * jnp.exp(dt * A)[..., None, None] + (dt[..., None] * x)[..., None] * B
        return jnp.sum(S * C, axis=-1) + D[:, None] * x, S
    S = S * jnp.exp(dt * A)[..., None, None] + (dt[..., None] * x)[..., None] * B[..., None, None, :]
    return jnp.sum(S * C[..., None, None, :], axis=-1) + D[:, None] * x, S


def ssd_recurrent(x, B, C, dt, A, D, state):
    """``x`` [B, T, NH, P], ``B C`` [B, T, N] or [B, T, G, N], ``dt`` [B, T, NH],
    ``A D`` [NH], ``state`` [B, NH, P, N], all float32. Returns (y [B, T, NH, P], state)."""

    def step(S, t):
        y, S = ssd_step(S, *t, A, D)
        return S, y

    state, y = jax.lax.scan(step, state, tuple(jnp.moveaxis(a, 1, 0) for a in (x, B, C, dt)))
    return jnp.moveaxis(y, 0, 1), state


def _chunk(A, D, S0, t):
    """One chunk for every row: ``S0`` [B, NH, P, N]; ``x`` [B, NH, T, P],
    ``Bm Cm`` [B, T, N] or [B, T, G, N], ``dt`` [B, NH, T]."""
    x, Bm, Cm, dt = t
    T = x.shape[2]
    g = jnp.cumsum(dt * A[:, None], axis=-1)  # [B, NH, T], at most zero
    seen = jnp.tril(jnp.ones((T, T), bool))
    L = jnp.exp(jnp.where(seen, g[..., :, None] - g[..., None, :], -jnp.inf))  # exp(g_t - g_i), i <= t
    if Bm.ndim == 4:
        return _chunk_grouped(D, S0, x, Bm, Cm, dt, g, L)
    CB = jnp.einsum("btn,bin->bti", Cm, Bm, precision=_HIGHEST)
    dtx = dt[..., None] * x
    eg = jnp.exp(g)
    y = jnp.einsum("bhti,bhip->bhtp", L * CB[:, None], dtx, precision=_HIGHEST)
    y = y + eg[..., None] * jnp.einsum("btn,bhpn->bhtp", Cm, S0, precision=_HIGHEST) + D[:, None, None] * x
    to_end = jnp.exp(g[..., -1:] - g)  # exp(g_T - g_i)
    S = eg[..., -1, None, None] * S0 + jnp.einsum("bhip,bin->bhpn", to_end[..., None] * dtx, Bm, precision=_HIGHEST)
    return S, y


def _chunk_grouped(D, S0, x, Bm, Cm, dt, g, L):
    """``_chunk``'s three products with ``Bm Cm`` [B, T, G, N]: a group's
    ``NH / G`` heads against the group's ``B`` and ``C``."""
    Bt, NH, _, P = x.shape
    G = Bm.shape[2]
    by_group = lambda a: a.reshape((Bt, G, _heads_a_group(NH, G)) + a.shape[2:])
    CB = jnp.einsum("btgn,bign->bgti", Cm, Bm, precision=_HIGHEST)
    dtx = dt[..., None] * x
    eg = jnp.exp(g)
    y = jnp.einsum("bgkti,bgkip->bgktp", by_group(L) * CB[:, :, None], by_group(dtx), precision=_HIGHEST).reshape(x.shape)
    read = jnp.einsum("btgn,bgkpn->bgktp", Cm, by_group(S0), precision=_HIGHEST).reshape(x.shape)
    y = y + eg[..., None] * read + D[:, None, None] * x
    to_end = jnp.exp(g[..., -1:] - g)  # exp(g_T - g_i)
    new = jnp.einsum("bgkip,bign->bgkpn", by_group(to_end[..., None] * dtx), Bm, precision=_HIGHEST).reshape(S0.shape)
    return eg[..., -1, None, None] * S0 + new, y


def ssd_chunked(x, B, C, dt, A, D, state, chunk: int = CHUNK):
    """``ssd_recurrent``'s contract, computed chunk by chunk; ``T`` is padded
    to whole chunks with dead positions."""
    Bt, T, NH, P = x.shape
    chunk = min(chunk, T)
    pad = -T % chunk
    if pad:
        x, B, C, dt = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)) for a in (x, B, C, dt))
    n = (T + pad) // chunk
    xs = (
        jnp.moveaxis(x.reshape(Bt, n, chunk, NH, P), (1, 3), (0, 2)),  # [n, B, NH, chunk, P]
        jnp.moveaxis(B.reshape((Bt, n, chunk) + B.shape[2:]), 1, 0),
        jnp.moveaxis(C.reshape((Bt, n, chunk) + C.shape[2:]), 1, 0),
        jnp.moveaxis(dt.reshape(Bt, n, chunk, NH), (1, 3), (0, 2)),  # [n, B, NH, chunk]
    )
    state, y = jax.lax.scan(functools.partial(_chunk, A, D), state, xs)
    return jnp.moveaxis(y, (0, 2), (1, 3)).reshape(Bt, n * chunk, NH, P)[:, :T], state


# --- one token a row, in place on the pools ------------------------------------


def decode_conv(w, b, taps):
    """What a one-token row's convolved channels are made of its token and
    tail: ``taps`` the ``K`` inputs, oldest first, ``w`` ``[K, ...]`` and the
    bias ``b`` of their shape. The depthwise convolution, its bias and SiLU in
    float32, term for term what ``models/hybrid_moe.py::ssm_conv`` computes of
    a window."""
    return jax.nn.silu(b.astype(jnp.float32) + sum(w[j].astype(jnp.float32) * x.astype(jnp.float32) for j, x in enumerate(taps)))


def _decode_kernel(meta, x_ref, w_ref, b_ref, d_ref, s_ref, t_ref, o_ref, s_out, t_out, cols, *, rows: int, heads: int, dim: int):
    r = pl.program_id(0)
    K1, NT, N = t_ref.shape[2], x_ref.shape[1], s_ref.shape[-1]
    XT = heads * dim // LANES  # the lane tiles of x; behind them G of B and G of C, a group a tile
    G = (NT - XT) // 2
    per_tile = LANES // dim  # heads a lane tile of x
    tiles_per_group = XT // G  # a tile's heads are of one group
    fresh = meta[1 + rows + r] != 0
    live = meta[1 + 2 * rows + r] != 0

    @pl.when(jnp.logical_not(live))
    def _():  # a dead row: nothing of anybody's is read; the spare slot is left empty
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
        s_out[...] = jnp.zeros(s_out.shape, s_out.dtype)
        t_out[...] = jnp.zeros(t_out.shape, t_out.dtype)

    @pl.when(live)
    def _():
        taps = [jnp.where(fresh, 0, t_ref[0, 0, j, :NT]) for j in range(K1)] + [x_ref[0]]  # [NT, 128] each
        y = decode_conv(w_ref[...], b_ref[...], taps)
        t_out[...] = jnp.zeros(t_out.shape, t_out.dtype)  # the rows past the channels
        for j in range(K1):  # the tail, shifted by the token
            t_out[0, 0, j, :NT] = taps[j + 1].astype(t_out.dtype)
        row_of = lambda at: y[at : at + 1]
        x, Brows, Crows = y[:XT], [row_of(XT + grp) for grp in range(G)], [row_of(XT + G + grp) for grp in range(G)]
        # a head's x as a column, for its state [P, N] has the feature on its sublanes: one transposition of
        # the row's [XT, 128] tiles, on the matrix unit, exact at HIGHEST (I X^T)
        eye = (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0) == jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)).astype(jnp.float32)
        cols[...] = jax.lax.dot_general(eye, x, (((1,), (1,)), ((), ())), precision=_HIGHEST, preferred_element_type=jnp.float32)
        Crows = [jnp.broadcast_to(row, (8, N)) for row in Crows]
        splat = lambda bits: jax.lax.bitcast_convert_type(jnp.full((1, N), bits), jnp.float32)
        for i in range(XT):
            for j in range(per_tile):
                h = i * per_tile + j
                # dt and A ride among the scalars as their bits, and are floats again once they lie along a row's lanes
                dt, A = splat(meta[1 + 3 * rows + r * heads + h]), splat(meta[1 + 3 * rows + rows * heads + h])
                S = jnp.where(fresh, 0.0, s_ref[0, 0, h].astype(jnp.float32)) * jnp.exp(dt * A)
                s_out[0, 0, h] = (S + cols[j * dim : (j + 1) * dim, i : i + 1] * (dt * Brows[i // tiles_per_group])).astype(s_out.dtype)
            # the tile's heads' read-out together: C S^T, a lane-dense row of the output
            pair = s_out[0, 0, i * per_tile : (i + 1) * per_tile].astype(jnp.float32).reshape(LANES, N)
            out = jax.lax.dot_general(Crows[i // tiles_per_group], pair, (((1,), (1,)), ((), ())), precision=_HIGHEST, preferred_element_type=jnp.float32)
            o_ref[0, i : i + 1, :] = out[:1] + d_ref[i : i + 1, :] * x[i : i + 1, :]


# Traced ONCE a process for a model's shapes, and its one ``pallas_call`` laid into every caller's trace (``inline``):
# the kernel's body is ~20 operations a head unrolled over the heads, a second or two of tracing a call inside a
# serving step's trace, and a period's state-space layers (nine in granite's, in both of its programs) each traced it
# anew: two thirds of that cell's warm set-up (PERF.md section 6, PR 59). The caller's jaxpr is what it was.
@functools.partial(jax.jit, static_argnames=("interpret",), inline=True)
def _decode_pallas(xbc, w, b, d, pool, tails, meta, interpret: bool):
    R, NT, _ = xbc.shape
    _, _, NH, P, N = pool.shape
    K = w.shape[0]
    XT = NH * P // LANES
    params = {}
    if not interpret:
        # a row's state in and out, each double-buffered: 4 x NH P N x 4 bytes, beside the compiler's own temporaries
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=max(32 << 20, 6 * NH * P * N * pool.dtype.itemsize)
        )
    state = pl.BlockSpec((1, 1, NH, P, N), lambda r, m: (m[0], m[1 + r], 0, 0, 0))
    tail = pl.BlockSpec((1, 1, K - 1, tails.shape[3], LANES), lambda r, m: (m[0], m[1 + r], 0, 0, 0))
    whole = lambda a: pl.BlockSpec(a.shape, lambda r, m: (0,) * a.ndim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R,),
        in_specs=[pl.BlockSpec((1, NT, LANES), lambda r, m: (r, 0, 0)), whole(w), whole(b), whole(d), state, tail],
        out_specs=[pl.BlockSpec((1, XT, LANES), lambda r, m: (r, 0, 0)), state, tail],
        scratch_shapes=[pltpu.VMEM((LANES, XT), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, rows=R, heads=NH, dim=P),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, XT, LANES), jnp.float32), jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct(tails.shape, tails.dtype)],
        input_output_aliases={5: 1, 6: 2},  # operands count from the scalars: the pools are the 6th and 7th
        # what a call touches of its operands: the rows' states and tails, not the pools (linear_attention.py says
        # what the compiler does with the tail pool without it)
        cost_estimate=pl.CostEstimate(
            flops=7 * R * NH * P * N, transcendentals=R * (NT * LANES + NH * N),
            bytes_accessed=2 * R * (NH * P * N * pool.dtype.itemsize + (K - 1) * NT * LANES * tails.dtype.itemsize)
            + R * NT * LANES * xbc.dtype.itemsize + R * XT * LANES * 4,
        ),
        interpret=interpret,
        name="ssd_decode",
        **params,
    )(meta, xbc, w, b, d, pool, tails)


def ssd_decode(xbc, dt, conv_w, conv_b, A, D, pool, tails, layer, slots, live, fresh, impl: str = "auto") -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One token a row, from the input projection to the recurrence's output,
    in place on the state pool ``pool`` [L, NS, NH, P, N] float32 and the tail
    pool ``tails`` [L, NS, K - 1, tail_rows(C), 128]: ``xbc`` [R, C] the row's
    pre-convolution ``[x ; B ; C]`` (``B`` and ``C`` of ``G N`` each, ``G`` read off ``C``), ``dt`` [R, NH] float32 (after softplus),
    ``conv_w`` [K, C] the taps and ``conv_b`` [C] their bias, ``A`` [NH] (below
    zero) and ``D`` [NH] float32; ``slots`` [R] int32 the rows' places in both
    pools, ``live`` [R] bool, ``fresh`` [R] bool (a row that starts from zero
    state AND a zero tail). A row that is not live leaves every request's
    state and tail alone: it works on the last slot, ``NS - 1``. ``impl``:
    ``auto`` (the kernel on a TPU, XLA elsewhere), ``pallas``,
    ``pallas_interpret``, ``xla``. Returns (y [R, NH P] float32, the state
    pool, the tail pool)."""
    if impl == "auto":
        impl = "pallas" if on_tpu() else "xla"
    R, C = xbc.shape
    _, NS, NH, P, N = pool.shape
    NT = C // LANES
    G = (C - NH * P) // (2 * N)  # the groups of B and C, from the channels alone
    if G < 1 or NH % G or NH * P + 2 * G * N != C:
        raise ValueError(f"ssd_decode: {C} channels are not {NH} heads of {P} and a whole number of groups of B and C of {N} that divides the heads")
    slots = jnp.where(live, jnp.asarray(slots, jnp.int32), NS - 1)
    fresh = fresh | ~live  # the spare slot never accumulates
    tiles = lambda a: a.reshape(a.shape[:-1] + (NT, LANES))
    if impl in ("pallas", "pallas_interpret"):
        if N != LANES or LANES % P or P % 8 or (NH // G) % (LANES // P):
            raise ValueError(f"ssd_decode's kernel needs a state of {LANES}, heads that divide a lane tile and a lane tile's heads in one group, got P={P} N={N} C={C} G={G}")
        bits = lambda a: jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.int32).reshape(-1)
        meta = jnp.concatenate([jnp.asarray(layer, jnp.int32).reshape(1), slots, fresh.astype(jnp.int32), live.astype(jnp.int32), bits(dt), bits(A)])
        d = jnp.repeat(D.astype(jnp.float32), P).reshape(NH * P // LANES, LANES)  # a head's D on each of its features' lanes
        y, pool, tails = _decode_pallas(tiles(xbc), tiles(conv_w), tiles(conv_b), d, pool, tails, meta, interpret=impl == "pallas_interpret")
        return y.reshape(R, NH * P), pool, tails
    if impl != "xla":
        raise ValueError(f"unknown ssd_decode impl {impl!r}; expected auto|pallas|pallas_interpret|xla")
    zeroed = lambda a: jnp.where(fresh.reshape((-1,) + (1,) * (a.ndim - 1)), 0, a)
    tail = zeroed(tails[layer, slots])[:, :, :NT].reshape(R, -1, C)  # [R, K - 1, C]
    taps = [tail[:, j] for j in range(tail.shape[1])] + [xbc]
    conv = decode_conv(conv_w, conv_b, taps)
    x, Bm, Cm = conv[:, : NH * P].reshape(R, NH, P), conv[:, NH * P : NH * P + G * N], conv[:, NH * P + G * N :]
    if G > 1:
        Bm, Cm = Bm.reshape(R, G, N), Cm.reshape(R, G, N)
    y, S = ssd_step(zeroed(pool[layer, slots]).astype(jnp.float32), x, Bm, Cm, dt, A, D)
    alive = lambda a: jnp.where(live.reshape((-1,) + (1,) * (a.ndim - 1)), a, 0)  # a dead row leaves zeros in the spare slot, as the kernel does
    shifted = alive(jnp.stack([a.astype(tails.dtype) for a in taps[1:]], axis=1)).reshape(R, -1, NT, LANES)
    shifted = jnp.pad(shifted, ((0, 0), (0, 0), (0, tails.shape[3] - NT), (0, 0)))
    y, S = alive(y), alive(S)
    return (y.reshape(R, NH * P), pool.at[layer, slots].set(S.astype(pool.dtype)),
            tails.at[layer, slots].set(shifted))
