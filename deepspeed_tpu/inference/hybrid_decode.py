"""The ragged serving step of a model whose layers are of more than one kind
(``models/hybrid_moe.py``): softmax layers that keep keys and values in pages,
linear-attention layers that keep one recurrent state and a convolution tail a
row, every layer with its routed FFN.

``decode.build_ragged_step`` comes here, when the program is BUILT, for a
config that names ``layer_types``; a uniform model never reaches this file
and lowers to what it always did. The step is the same program in the
scheduler's eyes (the same two widths, the same names, one dispatch and one
fetch a step) with two more donated buffers and one more row array:

    step(params, tokens [R, W], k_pages, v_pages, state, conv,
         page_table [R, MAXP], lengths [R], q_lens [R], slots [R])
      -> (out, k_pages, v_pages, state, conv)

* ``k_pages / v_pages`` ``[softmax layers, NP, NKV, P, D]``: only the softmax
  layers have pages;
* ``state`` ``[linear layers, slots + 1, NH, Dk, Dv]`` float32 and ``conv``
  ``[linear layers, slots + 1, K - 1, 3 NH D]``: row r's are at ``slots[r]``,
  the last slot belongs to nobody and takes what dead rows write. A row whose
  window starts at position 0 (``lengths[r] == 0``) starts from zero state
  inside the program, so admission, preemption and re-admission need no
  reset dispatch.

One ``lax.scan`` over PERIODS runs the layers; its body holds the period's
layers in order and reaches each layer's weights, pages and state through an
index, so the donated buffers are the ones returned. Token-wise work
(norms, projections, gates, the FFN) runs over the packed live tokens in
tiles, as in ``decode._paged_layers``; a window of at most one tile is one
"tile" of its whole slab, through the same code. Only the two mixers see rows:

* softmax: ``ragged_paged_attention`` on the ``[R, W]`` window;
* linear: a row with ONE token (a decode row, in the narrow program or
  riding in a wide window) goes through ``kda_decode``, in place on the
  pool; a row with more (a prefill chunk) goes through the chunkwise form
  (``kda_chunked``), one row a trip of a loop whose count is data: one read
  and one write of a row's state a layer. (Four rows a trip, of which a steady
  mixed step fills one, read 41 ms a mixed step for 35: PERF.md, PR 31.)
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.compression.int8 import qmatmul
from deepspeed_tpu.inference import decode
from deepspeed_tpu.models import hybrid_moe as hm
from deepspeed_tpu.models.transformer import _norm
from deepspeed_tpu.ops.transformer.linear_attention import kda_chunked, kda_decode

class StateShapes(NamedTuple):
    """The per-slot store of a config's state layers, for ``max_slots`` rows."""

    state: tuple  # [linear layers, slots + 1, NH, Dk, Dv], float32
    conv: tuple  # [linear layers, slots + 1, K - 1, 3 NH D], the activations' type


def state_shapes(cfg, max_slots: int) -> StateShapes:
    n, NH, D = cfg.layers_of("linear"), cfg.linear_num_heads, cfg.linear_head_dim
    return StateShapes((n, max_slots + 1, NH, D, D), (n, max_slots + 1, cfg.linear_conv_kernel - 1, 3 * NH * D))


def _whole_slab(q_lens, B: int, T: int) -> decode._Packed:
    """The slab as its own packing: one tile, every slot where it is."""
    i = jnp.arange(B * T, dtype=jnp.int32)
    live = (jnp.arange(T, dtype=jnp.int32)[None, :] < q_lens[:, None]).reshape(-1)
    return decode._Packed(B * T, jnp.int32(1), i, i.reshape(B, T), live)


def _hybrid_layers(cfg, params, tokens, k_pages, v_pages, state, conv, page_table, lengths, q_lens, slots, attn_impl):
    """Embedding and layers. Returns ``(x [NP, H] packed, the four pools,
    moe_counts [L, E], packed)``."""
    from deepspeed_tpu.ops.transformer.paged_attention import ragged_paged_attention

    B, T = tokens.shape
    dtype = k_pages.dtype
    tile = decode.token_tile(cfg)
    tiled = bool(tile) and B * T > tile
    packed = decode._pack_window(q_lens, B, T, tile) if tiled else _whole_slab(q_lens, B, T)
    NPK = packed.slot.shape[0]

    def tiles(body, init):
        return packed.tiles(body, init) if tiled else body(jnp.int32(0), init)

    kv_lens = jnp.where(q_lens > 0, lengths + q_lens, 0)
    x = params["embed"]["tokens"].astype(dtype)[jnp.take(tokens.reshape(-1), packed.slot, mode="clip")]

    period, stacks = cfg.period, params["periods"]
    ns, nl, n = period.count("softmax"), period.count("linear"), len(period)
    E = cfg.num_experts
    expert_stacks = jax.tree_util.tree_map(lambda a: a.reshape((-1,) + a.shape[3:]), stacks["moe"]["experts"])
    moe_stacks = {k: v for k, v in stacks["moe"].items() if k != "experts"}
    NH, NKV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    LH, LD, K = cfg.linear_num_heads, cfg.linear_head_dim, cfg.linear_conv_kernel
    C3 = 3 * LH * LD
    scale = decode._softmax_scale(cfg, D)
    NS = state.shape[1]

    fresh = lengths == 0
    one_token = q_lens == 1
    starts = packed.index[:, 0]  # a row's first packed token
    write_slot = jnp.where(q_lens > 0, slots, NS - 1)
    if nl and T > 1:
        # the rows with a chunk first: the chunkwise loop runs as many times as there are
        chunk_rows = q_lens > 1
        n_chunk_rows = jnp.sum(chunk_rows, dtype=jnp.int32)
        order = jnp.argsort(~chunk_rows, stable=True).astype(jnp.int32)

    def weights_at(tree, per, j, start):
        """Layer ``j`` of period ``per`` out of its stacks, tied to the tile
        (or the compiler hoists the slices out of the tile loop and copies them)."""
        if tiled:
            tree, _ = jax.lax.optimization_barrier((tree, start))
        return jax.tree_util.tree_map(lambda a: jax.lax.dynamic_index_in_dim(a, per, keepdims=False)[j], tree)

    def put(buf, new, start):
        return jax.lax.dynamic_update_slice_in_dim(buf, new.astype(buf.dtype), start, axis=0)

    def ffn(x_tile, per, j, start):
        p = weights_at(moe_stacks, per, j, start)
        moe = functools.partial(
            hm.moe_ffn, live=packed.take(packed.live, start)[None], experts=expert_stacks, group_offset=(per * n + j) * E
        )
        out, counts = decode._ffn_body(cfg, {"moe": p}, x_tile, p["mlp_norm_scale"], None, moe_ffn=moe)
        return x_tile + out, counts

    def softmax_layer(x, kp, vp, per, js, j):
        layer = per * ns + js

        def before(start, qkv):
            p = weights_at(stacks["softmax"], per, js, start)
            h = _norm(packed.take(x, start), p["attn_norm_scale"], None, cfg.norm, cfg.norm_eps)
            new = (qmatmul(h, p["wq"]), qmatmul(h, p["wk"]), qmatmul(h, p["wv"]))
            if tiled:
                new = jax.lax.optimization_barrier(new)  # decode._paged_layers.project: keep the head split apart
            return tuple(put(buf, a, start) for buf, a in zip(qkv, new))

        with jax.named_scope("attention"):
            qkv = tiles(before, tuple(jnp.zeros((NPK, nh * D), dtype) for nh in (NH, NKV, NKV)))
            attn, kp, vp = ragged_paged_attention(
                *(packed.expand(a).reshape(B, T, nh, D) for a, nh in zip(qkv, (NH, NKV, NKV))),
                kp, vp, layer, page_table, kv_lens, q_lens, scale=scale, impl=attn_impl,
            )
            attn = attn.reshape(B * T, NH * D)

        def after(start, carry):
            x, counts = carry
            x_tile = packed.take(x, start)
            with jax.named_scope("attention"):
                p = weights_at(stacks["softmax"], per, js, start)
                h = _norm(x_tile, p["attn_norm_scale"], None, cfg.norm, cfg.norm_eps)
                a = jnp.take(attn, packed.take(packed.slot, start), axis=0, mode="clip")
                x_tile = x_tile + qmatmul(hm.softmax_gate(p, h, a), p["wo"]).astype(x.dtype)
            x_tile, tile_counts = ffn(x_tile[None], per, j, start)
            return put(x, x_tile[0], start), counts + tile_counts

        x, counts = tiles(after, (x, jnp.zeros((E,), jnp.int32)))
        return x, kp, vp, counts

    def linear_layer(x, st, cv, per, jl, j):
        layer = per * nl + jl

        def before(start, bufs):
            p = weights_at(stacks["linear"], per, jl, start)
            h = _norm(packed.take(x, start), p["attn_norm_scale"], None, cfg.norm, cfg.norm_eps)
            return tuple(put(buf, a, start) for buf, a in zip(bufs, hm.linear_inputs(cfg, p, h)))

        with jax.named_scope("linear_attention"):
            qkv, log_a, beta = tiles(before, (
                jnp.zeros((NPK, C3), dtype), jnp.zeros((NPK, LH * LD), jnp.float32), jnp.zeros((NPK, LH), jnp.float32),
            ))
            cp = weights_at({k: v for k, v in stacks["linear"].items() if k.startswith("conv_")}, per, jl, jnp.int32(0))
            tails = jnp.where(fresh[:, None, None], 0, cv[layer, slots])  # [B, K - 1, 3C]
            # the rows with one token: in place on the pool
            with jax.named_scope("kda_recurrence"):
                q1, k1, v1 = hm.linear_qkv(cfg, hm.short_conv(cp, tails, qkv[starts][:, None])[:, 0])
                o, st = kda_decode(
                    q1, k1, v1, log_a[starts].reshape(B, LH, LD), beta[starts], st, layer, slots, one_token, fresh
                )
            o = o.astype(dtype).reshape(B, 1, LH * LD)
            if T > 1:
                o = jnp.zeros((B, T, LH * LD), dtype).at[:, :1].set(o)

                def chunk_row(i, carry):
                    st, o = carry
                    r = order[i]
                    idx = packed.index[r]  # [T]
                    valid = (jnp.arange(T, dtype=jnp.int32) < q_lens[r])[:, None]
                    q, k, v = hm.linear_qkv(cfg, hm.short_conv(cp, tails[r][None], qkv[idx][None]))
                    la = jnp.where(valid, log_a[idx], 0.0).reshape(1, T, LH, LD)
                    b = jnp.where(valid, beta[idx], 0.0)[None]
                    S0 = jnp.where(fresh[r], 0.0, st[layer, slots[r]].astype(jnp.float32))[None]
                    with jax.named_scope("kda_recurrence"):
                        o_row, S = kda_chunked(q, k, v, la, b, S0)
                    st = jax.lax.dynamic_update_slice(st, S[None].astype(st.dtype), (layer, slots[r], 0, 0, 0))
                    o = jax.lax.dynamic_update_slice(o, o_row.astype(dtype).reshape(1, T, LH * LD), (r, 0, 0))
                    return st, o

                st, o = jax.lax.fori_loop(0, n_chunk_rows, chunk_row, (st, o))
            # the convolution's tail after the window: the last K - 1 of (old tail, the row's tokens)
            at = q_lens[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]  # [B, K - 1], in that sequence
            from_window = jnp.take(qkv, jnp.clip(starts[:, None] + at - (K - 1), 0, NPK - 1), axis=0)
            from_tail = jnp.take_along_axis(tails, jnp.minimum(at, K - 2)[..., None], axis=1)
            cv = cv.at[layer, write_slot].set(jnp.where((at >= K - 1)[..., None], from_window, from_tail))
            o = o.reshape(B * T, LH, LD)

        def after(start, carry):
            x, counts = carry
            x_tile = packed.take(x, start)
            with jax.named_scope("linear_attention"):
                p = weights_at(stacks["linear"], per, jl, start)
                h = _norm(x_tile, p["attn_norm_scale"], None, cfg.norm, cfg.norm_eps)
                o_tile = jnp.take(o, packed.take(packed.slot, start), axis=0, mode="clip")
                x_tile = x_tile + hm.linear_output(cfg, p, h, o_tile).astype(x.dtype)
            x_tile, tile_counts = ffn(x_tile[None], per, j, start)
            return put(x, x_tile[0], start), counts + tile_counts

        x, counts = tiles(after, (x, jnp.zeros((E,), jnp.int32)))
        return x, st, cv, counts

    def period_step(carry, per):
        x, kp, vp, st, cv = carry
        at = {"softmax": 0, "linear": 0}
        counts = []
        for j, kind in enumerate(period):
            if kind == "softmax":
                x, kp, vp, c = softmax_layer(x, kp, vp, per, at[kind], j)
            else:
                x, st, cv, c = linear_layer(x, st, cv, per, at[kind], j)
            at[kind] += 1
            counts.append(c)
        return (x, kp, vp, st, cv), jnp.stack(counts)

    (x, kp, vp, st, cv), counts = jax.lax.scan(
        period_step, (x, k_pages, v_pages, state, conv), jnp.arange(cfg.num_periods, dtype=jnp.int32)
    )
    return x, kp, vp, st, cv, counts.reshape(cfg.num_layers, E), packed


def hybrid_forward(cfg, params, tokens, k_pages, v_pages, state, conv, page_table, lengths, q_lens, slots,
                   attn_impl: str = "auto"):
    """``decode._paged_forward`` for a hybrid model: the window's logits
    ``[B, T, V]`` (a dead slot's are some live token's) and the four pools.
    What the parity tests and ``benchmark/tools/solar_logits_check.py``
    compare with the reference; the serving step takes its arg-max on the
    packed tiles instead."""
    x, kp, vp, st, cv, moe_counts, packed = _hybrid_layers(
        cfg, params, tokens, k_pages, v_pages, state, conv, page_table, lengths, q_lens, slots, attn_impl
    )
    return decode._final_logits(cfg, params, packed.expand(x)), kp, vp, st, cv, moe_counts


def build_hybrid_ragged_step(cfg, rows: int, width: int, page_size: int, attn_impl: str, telemetry, name: str, key):
    """``decode.build_ragged_step``'s program for a model with layers of more
    than one kind: the contract is at the top of this file; ``out`` is
    ``build_ragged_step``'s (with its MoE rows)."""
    W = int(width)

    def _step(params, tokens, k_pages, v_pages, state, conv, page_table, lengths, q_lens, slots):
        x, kp, vp, st, cv, moe_counts, packed = _hybrid_layers(
            cfg, params, tokens, k_pages, v_pages, state, conv, page_table, lengths, q_lens, slots, attn_impl
        )
        if packed.slot.shape[0] == packed.tile:  # the whole slab: one head over it
            logits = decode._final_logits(cfg, params, x.reshape(rows, W, -1))
            with jax.named_scope("head_sample"):
                greedy = decode._argmax(logits, None)
        else:
            greedy = decode._packed_greedy(cfg, params, x, packed)
        with jax.named_scope("head_sample"):
            accepted = decode._accepted_prefix(tokens, greedy, q_lens - 1)
            out = jnp.concatenate([accepted[:, None].astype(jnp.int32), greedy], axis=1)
            out = jnp.concatenate([out, decode._moe_stat_rows(moe_counts, W + 1)], axis=0)
        return out, kp, vp, st, cv

    fn = decode._jit(_step, telemetry, name, donate_argnums=(2, 3, 4, 5))
    decode._paged_program_cache[key] = fn
    return fn
