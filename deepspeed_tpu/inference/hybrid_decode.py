"""The ragged serving step of a model whose layers are of more than one kind
(``models/hybrid_moe.py``): softmax layers that keep keys and values in pages,
sliding-window layers that keep a row's newest pages in a ring, linear-attention
or state-space layers that keep one recurrent state and a convolution tail a row,
gated short-convolution layers that keep a convolution tail a row and NOTHING else, latent-attention
layers that keep one low-rank entry a token in pages of their own (a latent
layer with or without a low-rank query, with rotary or none); leading layers
with a dense FFN, a leading layer of any kind, then every layer with its
routed FFN, or (``num_experts`` 0) every layer with a dense FFN out of the
period's stacks; or, where ``layer_types`` names FFN blocks, blocks of ONE
sublayer each: a mixer block is norm, mixer, add and nothing else, an FFN
block norm, router, held experts and shared expert, add (``ffn_block``), each
with its own index into what its kind keeps: a state-space block counts the
state store's entries, a softmax block the pages' layers, an FFN block the
expert stacks and the routing counts.

``decode.build_ragged_step`` comes here, when the program is BUILT, for a
config that names ``layer_types``; a uniform model never reaches this file
and lowers to what it always did. The step is the same program in the
scheduler's eyes (the same two widths, the same names, one dispatch and one
fetch a step) with the per-slot store's buffers donated beside the pages
(``kv_pool.StateStore``, one argument: a field that is ``None`` is no
parameter of the program) and one more row array:

    step(params, tokens [R, W], k_pages, v_pages, store,
         page_table [R, MAXP], lengths [R], q_lens [R], slots [R])
      -> (out, k_pages, v_pages, store)

* ``k_pages`` ``[softmax layers, NP, NKV, P, Dk]`` and ``v_pages`` ``[..., Dv]``:
  only the softmax layers have pages under the page table; a key head wider
  than a lane tile is stored at whole tiles (``kv_pool.key_lanes``: 192 at 256),
  heads narrower than one ``f`` to a page, ``[.., NKV / f, P, f Dk]``
  (``kv_pool.heads_per_group``: granite's 8 of 64 as 4 groups of 128; the
  window layers' rings likewise). The mixers below hand ``ragged_paged_attention``
  q, k and v at the TRUE head count and width whatever the pool's, and the
  entry sees the grouping from the shapes;
* ``store.state`` ``[linear layers, slots + 1, NH, Dk, Dv]`` float32 and
  ``store.conv`` ``[linear layers, slots + 1, K - 1, 3, NH, D]`` (a head's
  channels on the lanes, as ``kda_decode`` reads them): row r's are at
  ``slots[r]``, the last slot belongs to nobody and takes what dead rows
  write; a leading dense layer that is a linear one has the kind's first
  entries, the scanned layers the entries behind them. A row whose window
  starts at position 0 (``lengths[r] == 0``) starts
  from zero state inside the program, so admission, preemption and
  re-admission need no reset dispatch. A model with state-space layers keeps
  THEIR state and tail in the same two fields, at the kind's shapes
  (``state_shapes``): ``[ssm layers, slots + 1, NH, P, N]`` float32 and ``[ssm
  layers, slots + 1, K - 1, tail_rows(C), 128]``, the ``C = NH P + 2 G N``
  convolved channels (``G`` groups of ``B`` and ``C``) a lane tile a row in
  whole sublane tiles, as ``ssd_decode`` reads them. A model with ``conv``
  layers keeps a tail ALONE: ``store.state`` is ``None`` (no parameter of the
  program) and ``store.conv`` ``[conv layers, slots + 1, K - 1,
  tail_rows(H), 128]`` holds a row's last ``K - 1`` gated products ``B * x~``,
  restarted, handed over after a chunk (``tail_after_chunk``) and spared for
  dead rows exactly as the other kinds' tails;
* ``store.window_k / window_v`` ``[window layers, 1 + R * ring, NKV', P, ..]``:
  the rings of the sliding-window layers, with their own KV-head count. Row r
  owns pages ``1 + slots[r] * ring ..`` and position ``p`` lives in ring page
  ``(p // P) % ring``; the table that says so is made here, from ``slots``,
  and the kernel starts its walk at the window's first page. A row that starts
  again at position 0 simply overwrites: what its ring held lies past its
  length or outside its window, and is masked. ``R`` is the pool's
  ``max_slots``;
* ``store.latent`` ``[latent layers, NP, P, lanes]``: the latent layers' pages,
  under the SAME page table and page ids as ``k_pages`` (one allocation of a
  page id holds a token's entry in every paged layer of either kind). A token
  is stored once, ``[c_kv ; k_rope]`` (``kv_lora_rank + qk_rope_head_dim``
  numbers, at whole lane tiles: 576 at 640), with no value array: the value is
  the entry's leading ``kv_lora_rank`` lanes.

The leading dense layers, then one ``lax.scan`` over the whole PERIODS, then the
layers of a partial last period (``cfg.remainder``: each with leaves of its own
under ``params["trailing"]``, its mixer's entries behind the scanned layers' and
its own routed FFN behind it; none: nothing is traced) run the layers;
the scan's body holds the period's layers in order and reaches each layer's
pages and state through an index, so the donated buffers are the ones
returned, and each layer's weights by one slice a leaf of the stacks the
parameters arrived in (``layer_of``), with the head split kept behind a
barrier, so a projection reads its matrix where it lies: no period's slice
and no other layout of it is written out. Token-wise work
(norms, projections, gates, the FFN) runs over the packed live tokens in
tiles, as in ``decode._paged_layers``; a window of at most one tile is one
"tile" of its whole slab, through the same code. Only the mixers see rows:

* softmax and window: ``ragged_paged_attention`` on the ``[R, 1]`` window of
  the narrow program; in the wide program the rows with one token through
  the same width-1 call and the rows with a chunk ``CHUNK_ROWS`` a trip of a
  loop whose count is data (``wide_attention``: the ``[R, W]`` window is
  never laid out); the window layers' calls with ``window`` and their sinks;
  each kind with its own query heads (``cfg.heads_of``), so the kernel's
  group is the kind's; an output gate (``hm.output_gate``: a feature's, or one
  scalar a head under the ``head_gate`` scope) from the same normed tile as q,
  between the kernel's output and ``Wo``, for decode rows and chunk rows alike;
* latent: ``latent_paged_attention`` (``ops/transformer/latent_attention.py``)
  in the absorbed form, for decode rows and prefill chunks alike, split by
  width as the softmax layers' calls are: the query is ``[q_nope Wk_b^T ;
  q_rope]`` against the stored entry, the output ``(P c_kv) Wv_b`` and then
  ``Wo``; the kernel reads a page once and writes the step's entries in place;
* linear: a row with ONE token (a decode row, in the narrow program or
  riding in a wide window) goes through ``kda_decode`` from its projections
  on: the kernel reads the row's pre-convolution ``q~ k~ v~``, log decay and
  ``b`` as the tile loop wrote them and the row's tail and state where they
  lie in the pools, and writes both back in place, so the narrow program
  gathers no tail, builds no operand and scatters nothing; a row with more (a
  prefill chunk) goes through ``hm.short_conv`` and the chunkwise form
  (``kda_chunked``), one row a trip of a loop whose count is data: one read
  and one write of a row's state and tail a layer. (Four rows a trip, of which
  a steady mixed step fills one, read 41 ms a mixed step for 35: PERF.md, PR 31.)
* ssm: the same split. A row with ONE token goes through ``ssd_decode`` from
  the input projection's ``[x ; B ; C]`` and ``dt`` on (the convolution with
  its bias, the tail, the recurrence and its read-out in one kernel, in place
  on both pools); a row with a chunk through ``hm.ssm_conv`` and
  ``ssd_chunked`` from its carried state, one row a trip. The gate ``z`` waits
  in a token buffer for the tile loop behind the mixer, which gates, norms
  (over each of the layer's ``ssm_groups`` groups apart) and projects
  (``hm.ssm_output``).

* conv: no kernel. The tile loop writes ``u = B * x~`` and the gate ``C`` of
  every live token; the rows with ONE token gather their tails, sum the ``K``
  taps and scatter the shifted tails back, all rows at once; a row with a chunk
  runs ``hm.gated_conv`` over ``[tail ; chunk]`` and leaves its last ``K - 1``
  REAL products, one row a trip; the tile loop behind the mixer gates and
  projects (``hm.conv_output``).
* softmax and window under ``qk_norm="head"``: the tile loop norms q and k a
  head before it rotates them (``hm.attn_heads``), so what is written to a
  page is the normed, rotated key.

The config's scalar multipliers (``embedding_multiplier``,
``residual_multiplier`` on both branches of every layer, ``logits_scaling`` in
``decode._final_logits``) are read when the program is built: at 1.0 no
multiply is traced and the program's text is what it was.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.compression.int8 import qmatmul
from deepspeed_tpu.inference import decode
from deepspeed_tpu.inference.kv_pool import StateStore, heads_per_group, key_lanes, page_shapes
from deepspeed_tpu.models import hybrid_moe as hm
from deepspeed_tpu.models.transformer import _norm
from deepspeed_tpu.ops.transformer.linear_attention import kda_chunked, kda_decode
from deepspeed_tpu.ops.transformer.state_space import LANES, ssd_chunked, ssd_decode, tail_rows

# Rows with a prefill chunk that one trip of a wide window's attention loop
# takes (``wide_attention``). A steady mixed step has one such row, the first
# steps of a full house as many as slots: a dead row of a trip costs the
# kernel a few scalar reads and XLA its 128-token window, 1/16 of what the
# whole 64-row window cost.
CHUNK_ROWS = 4


class StateShapes(NamedTuple):
    """The per-slot store of a config's state layers, for ``max_slots`` rows:
    the shapes are the kind's (``cfg.state_kind``; a config names one at most).
    A kind may keep a tail ALONE (``conv``): its ``state`` is None, no array
    anywhere and no parameter of any program."""

    state: Optional[tuple]  # linear: [layers, slots + 1, NH, Dk, Dv]; ssm: [layers, slots + 1, NH, P, N]; float32; conv: None
    # linear: [layers, slots + 1, K - 1, 3, NH, D]; ssm and conv: [layers, slots + 1, K - 1, tail_rows(C), 128] (conv: C = H); the activations' type
    conv: tuple


def state_shapes(cfg, max_slots: int) -> StateShapes:
    if cfg.state_kind == "conv":
        return StateShapes(None, (cfg.layers_of("conv"), max_slots + 1, cfg.conv_kernel - 1, tail_rows(cfg.hidden_size), LANES))
    if cfg.state_kind == "ssm":
        n, K = cfg.layers_of("ssm"), cfg.ssm_conv_kernel
        return StateShapes((n, max_slots + 1, cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state),
                           (n, max_slots + 1, K - 1, tail_rows(cfg.ssm_conv_channels), LANES))
    n, NH, D = cfg.layers_of("linear"), cfg.linear_num_heads, cfg.linear_head_dim
    return StateShapes((n, max_slots + 1, NH, D, D), (n, max_slots + 1, cfg.linear_conv_kernel - 1, 3, NH, D))


def window_shapes(cfg, max_slots: int, page_size: int, ring: int):
    """The window layers' key and value pools: ``ring`` pages a slot behind the trash page."""
    NKV = cfg.window_num_kv_heads
    f = heads_per_group(cfg.head_dim, cfg.v_head_dim, NKV)
    return page_shapes(cfg.layers_of("window"), 1 + max_slots * ring, NKV, page_size, cfg.head_dim, cfg.v_head_dim, f)


def paged_latent_shapes(cfg, num_pages: int, page_size: int):
    """``(latent, index)``: the pages of the layers that keep one entry a token
    under the page table (``cfg.paged_latent_kind``: an entry at whole lane
    tiles, 576 at 640) and, of a model whose such layers are ``sparse_latent``
    ones, the indexer's keys beside them; None for what the model has not."""
    kind = cfg.paged_latent_kind
    if kind is None:
        return None, None
    latent = (cfg.layers_of(kind), num_pages, page_size, key_lanes(cfg.latent_width))
    return latent, latent[:3] + (cfg.index_head_dim,) if kind == "sparse_latent" else None


def window_latent_shape(cfg, max_slots: int, page_size: int, ring: int):
    """The window_latent layers' rings: ``ring`` pages a slot behind the trash page, an entry the kind's latent at whole lane tiles (1,088 at 1,152)."""
    return (cfg.layers_of("window_latent"), 1 + max_slots * ring, page_size, key_lanes(cfg.latent_dims("window_latent").width))


def layer_of(tree, per, j: int):
    """Layer ``j`` of period ``per`` (a traced index) of a kind's stacks
    ``[periods, count, ...]``: ONE slice a leaf, straight out of the stack
    the parameters arrived in, which fuses into the matmul that reads it. A
    period's slice with a layer's behind it (``a[per][j]``) is shared by the
    period's ``count`` layers, fuses into none of them and is written out
    whole: three window layers' ``wq`` and ``wo``, 170 MB each, every trip of
    Laguna's scan, five ``[12288, 4096]`` in MiMo (PERF.md section 6, PR 46)."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_slice(a, (per, j) + (0,) * (a.ndim - 2), (1, 1) + a.shape[2:]).reshape(a.shape[2:]), tree
    )


def unfilled(shape, dtype) -> jax.Array:
    """A layer's token buffer ``[packed tokens, lanes]`` as the allocator hands
    it over: nothing fills it (on the TPU; XLA's CPU lowering writes zeros).
    The layer's tile loop writes the live tiles whole, and a chunk row's
    outputs go to the row's own slots; EVERY OTHER ROW HOLDS WHATEVER WAS
    THERE, NaN and Inf included, so a read of such a buffer is a slice of a
    live tile or a gather behind a select on the token being real
    (``_hybrid_layers``: ``real_rows``, ``rows_output``), never a product
    with a mask. Zeros cost a wide window 64 x 128 slots x 10,240-24,576
    lanes a layer for the ~200 tokens it holds: 7 ms of GLM-4.7-Flash's
    35.7 ms mixed step (PERF.md section 6, PR 47)."""
    return _token_major(jax.lax.empty(shape, dtype))


def _token_major(buf):
    """``buf`` with a token's lanes together in memory, the layout its
    gathers of rows read. A zero fill happened to pin it; left to the
    compiler an allocation takes the layout of whatever tile is written
    into it, lanes-major for GLM's q and for Laguna's gated output, and the
    whole ``[8192, lanes]`` buffer is then COPIED to this one a layer, which
    costs more than the fill did (the ``_w128`` texts compiled for a v5e:
    ``tests/unit/ops/test_tpu_compile.py::_slab_sized_fills``)."""
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(buf, Layout(major_to_minor=tuple(range(buf.ndim))))


def _whole_slab(q_lens, B: int, T: int) -> decode._Packed:
    """The slab as its own packing: one tile, every slot where it is."""
    i = jnp.arange(B * T, dtype=jnp.int32)
    live = (jnp.arange(T, dtype=jnp.int32)[None, :] < q_lens[:, None]).reshape(-1)
    return decode._Packed(B * T, jnp.int32(1), i, i.reshape(B, T), live)


def _hybrid_layers(cfg, params, tokens, k_pages, v_pages, store, page_table, lengths, q_lens, slots, attn_impl):
    """Embedding and layers. Returns ``(x [NP, H] packed, k_pages, v_pages,
    the store, moe_counts [routed layers, E], packed)``.

    A layer's token buffers (q, k, v or the latent entry, the linear layers'
    inputs, the chunk rows' outputs) are ``[NPK, lanes]`` with ``NPK`` the
    window's slots in whole tiles, 8,192 for 64 x 128, since a step MAY
    carry 64 chunks; a steady mixed step holds ~200 tokens. They are
    ``unfilled``. What a row of one may hold: a live tile's rows, the dead
    ones among them too, hold what the tile loop computed (finite); a row
    past the live tiles, and in the output buffer every row but a chunk
    row's real tokens, holds whatever the memory held. Who reads them:
    the tile loops, a live tile at a time; ``real_rows``, for the kernels'
    operands, and ``rows_output``, for the tile that follows the mixer,
    both behind selects; the convolution tail, behind its own. Nothing else
    may: not a reduction over a buffer, not a product with a mask. The
    narrow program (``T == 1``) writes every row of its 64 and reads them
    as it always did."""
    from deepspeed_tpu.ops.transformer.paged_attention import ragged_paged_attention

    B, T = tokens.shape
    dtype = k_pages.dtype
    state, conv, wk, wv, latent, index, latent_rings = store
    tile = decode.token_tile(cfg)
    tiled = bool(tile) and B * T > tile
    packed = decode._pack_window(q_lens, B, T, tile) if tiled else _whole_slab(q_lens, B, T)
    NPK = packed.slot.shape[0]

    def tiles(body, init):
        return packed.tiles(body, init) if tiled else body(jnp.int32(0), init)

    kv_lens = jnp.where(q_lens > 0, lengths + q_lens, 0)
    x = hm.scaled(params["embed"]["tokens"].astype(dtype)[jnp.take(tokens.reshape(-1), packed.slot, mode="clip")], cfg.embedding_multiplier)
    branch = functools.partial(hm.scaled, by=cfg.residual_multiplier)  # a layer's branch before it is added: at 1.0 itself
    positions = None
    if cfg.position == "rope":  # a packed token's absolute position
        positions = jnp.take((lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]).reshape(-1), packed.slot, mode="clip")

    period, stacks = cfg.period, params["periods"]
    n = cfg.ffns_per_period  # what the FFN stacks hold of a period: one a layer, or the period's FFN blocks
    single = cfg.single_sublayer  # a block is a mixer OR the FFN alone (``layer_types`` names FFN blocks)
    E = cfg.num_experts
    if E:
        expert_stacks = jax.tree_util.tree_map(lambda a: a.reshape((-1,) + a.shape[3:]), stacks["moe"]["experts"])
        moe_stacks = {k: v for k, v in stacks["moe"].items() if k != "experts"}
    NH, D, Dv = cfg.num_heads, cfg.head_dim, cfg.v_head_dim  # NH: a latent layer's heads; a softmax or window layer takes its kind's
    LH, LD, K = cfg.linear_num_heads, cfg.linear_head_dim, cfg.linear_conv_kernel
    C3 = 3 * LH * LD
    scale = decode._softmax_scale(cfg, D)

    fresh = lengths == 0
    one_token = q_lens == 1
    starts = packed.index[:, 0]  # a row's first packed token
    if T > 1:
        # the rows with a chunk first: the loops over them run as many times as there are
        chunk_rows = q_lens > 1
        n_chunk_rows = jnp.sum(chunk_rows, dtype=jnp.int32)
        order = jnp.argsort(~chunk_rows, stable=True).astype(jnp.int32)
    if latent_rings is not None:
        ring = (latent_rings.shape[1] - 1) // B  # ring pages a slot (``ring_latent_attention`` finds a position's page itself)
    if wk is not None:
        # the rings as a page table: slot i of row r on page i % ring of the row's own
        ring = (wk.shape[1] - 1) // B
        own = 1 + slots[:, None] * ring + (jnp.arange(page_table.shape[1], dtype=jnp.int32) % ring)[None, :]
        ring_table = jnp.where((q_lens > 0)[:, None], own, -1)

    def weights_at(tree, per, j, start):
        """Layer ``j`` of period ``per`` out of its stacks, one slice a leaf
        (``layer_of``; ``per`` None: a leading layer's own leaves), tied to
        the tile (or the compiler hoists the slices out of the tile loop and
        copies them)."""
        if tiled:
            tree, _ = jax.lax.optimization_barrier((tree, start))
        return tree if per is None else layer_of(tree, per, j)

    def put(buf, new, start):
        return _token_major(jax.lax.dynamic_update_slice_in_dim(buf, new.astype(buf.dtype), start, axis=0))

    def real_rows(buf, at, real):
        """Packed tokens ``at`` of a layer's buffer for a kernel: a token that
        is not ``real`` (a dead row's, a slot past a chunk's tokens: any row
        of the buffer, written or not) is zeros, by a select."""
        return jnp.where(real.reshape(real.shape + (1,) * (buf.ndim - 1)), jnp.take(buf, at, axis=0, mode="clip"), 0)

    def put_chunk(buf, new, start, real):
        """A chunk row's outputs ``new`` ``[T, lanes]`` at its tokens' place in
        a packed buffer: they lie together from ``start`` on (at most
        ``NPK - T``: the rows before it hold at most ``T`` each); the slots
        past its ``real`` tokens are the next rows' and keep what they hold.
        One slice read and written where a scatter goes row by row (0.28 ms
        for a trip's 512 rows of 10,240 lanes: PERF.md section 6, PR 47)."""
        old = jax.lax.dynamic_slice_in_dim(buf, start, T, axis=0)
        return put(buf, jnp.where(real[:, None], new.astype(buf.dtype), old), start)

    def slab_rows(out, start):
        """A tile's mixer outputs in the narrow program, whose ``out`` is in slab order (which a whole slab's packing is)."""
        return jnp.take(out, packed.take(packed.slot, start), axis=0, mode="clip")

    def rows_output(chunks, ones, start):
        """A tile's mixer outputs in a wide window: a chunk row's token's out
        of ``chunks`` (packed, written by ``put_chunk``), a one-token row's
        out of ``ones`` ``[B, lanes]``, zeros for a dead slot: a gather of a
        tile's rows where the 64 rows of ``ones`` used to be scattered into
        the slab-sized buffer, a layer."""
        row = packed.take(packed.slot, start) // T
        out = jnp.where(chunk_rows[row][:, None], packed.take(chunks, start), jnp.take(ones, row, axis=0))
        return jnp.where(packed.take(packed.live, start)[:, None], out, 0)

    def tail_after_chunk(tail, row, n, taps):
        """A convolution's tail after a chunk row: the last ``taps - 1`` of
        (the old ``tail`` ``[taps - 1, C]``, the row's ``n`` real tokens of
        ``row`` ``[T, C]``)."""
        at = n + jnp.arange(taps - 1, dtype=jnp.int32)  # in that sequence
        from_row = jnp.take(row, jnp.clip(at - (taps - 1), 0, T - 1), axis=0)
        return jnp.where((at >= taps - 1)[:, None], from_row, jnp.take(tail, jnp.minimum(at, taps - 2), axis=0))

    def ffn(x_tile, start, per, j, own=None):
        """The period's ``j``-th FFN of period ``per`` out of the stacks, or ``own``, a trailing layer's own leaves."""
        if not E:  # no expert anywhere: the layer's dense FFN out of the period's stacks
            return dense_ffn(x_tile, start, stacks["ffn"], per, j) if own is None else dense_ffn(x_tile, start, own["ffn"])
        if own is None:
            p, experts = weights_at(moe_stacks, per, j, start), expert_stacks
        else:  # its experts are ONE layer's, not a slice of the stacks
            p, experts = weights_at({k: v for k, v in own["moe"].items() if k != "experts"}, None, None, start), own["moe"]["experts"]
        moe = functools.partial(
            hm.moe_ffn, live=packed.take(packed.live, start)[None], experts=experts, group_offset=0 if own else (per * n + j) * E
        )
        out, counts = decode._ffn_body(cfg, {"moe": p}, x_tile, p["mlp_norm_scale"], None, moe_ffn=moe)
        return x_tile + branch(out), counts

    def dense_ffn(x_tile, start, p, per=None, j=None):
        p = weights_at(p, per, j, start)
        out, _ = decode._ffn_body(cfg, p, x_tile, p["mlp_norm_scale"], None)
        return x_tile + branch(out), jnp.zeros((E,), jnp.int32)

    def nothing_behind(x_tile, start):
        """What follows the mixer of a block that is a mixer alone."""
        return x_tile, jnp.zeros((E,), jnp.int32)

    def ffn_block(x, per, j):
        """A block that is the FFN alone, the period's ``j``-th: norm, router, held experts and shared expert (or the
        dense FFN), one add, a tile at a time. Returns ``(x, counts)``."""

        def body(start, carry):
            x, counts = carry
            x_tile, tile_counts = ffn(packed.take(x, start)[None], start, per, j)
            return put(x, x_tile[0], start), counts + tile_counts

        return tiles(body, (x, jnp.zeros((E,), jnp.int32)))

    def wide_attention(attend, operands, shapes, pools, layer, table, width):
        """A wide window's attention without the window's slab: the rows with
        ONE token (decode rows riding beside a prefill chunk) through one
        width-1 call, the rows with a chunk ``CHUNK_ROWS`` a trip of a loop
        whose count is data, each trip a ``[CHUNK_ROWS, T]`` window. Laid out
        as the whole ``[B, T]`` window, every layer's q is B T NH D lanes that
        XLA gathers, transposes and concatenates for the kernel, and the
        kernel gives each one-token row a whole query tile: 7 ms a layer of a
        64 x 128 window with one chunk in it (PERF.md section 6, PR 34).
        ``operands`` packed ``[NPK, ...]`` (``unfilled`` past the live tiles:
        read through ``real_rows``), each a token's ``shapes`` entry in a
        window; ``attend(*windows, *pools, layer, table, kv_lens, q_lens)``
        gives ``(out, *pools)``. Returns the pools behind ``out(start)``, a
        tile's outputs ``[tile, width]`` (``rows_output`` of the chunk rows'
        packed ``[NPK, width]``, ``unfilled`` but for their real tokens, and
        the one-token rows' ``[B, width]``)."""
        first = tuple(real_rows(a, starts, one_token).reshape((B, 1) + shape) for a, shape in zip(operands, shapes))
        o1, *pools = attend(*first, *pools, layer, table, jnp.where(one_token, kv_lens, 0), one_token.astype(jnp.int32))

        def trip(i, carry):
            attn, *pools = carry
            at = i * CHUNK_ROWS + jnp.arange(CHUNK_ROWS, dtype=jnp.int32)
            rows = order[jnp.minimum(at, B - 1)]
            lens = jnp.where(at < n_chunk_rows, q_lens[rows], 0)  # past the last chunk row: a dead row
            index = packed.index[rows]  # [CHUNK_ROWS, T]: a row's tokens lie together
            real = jnp.arange(T, dtype=jnp.int32)[None, :] < lens[:, None]
            window = tuple(real_rows(a, index, real).reshape((CHUNK_ROWS, T) + shape) for a, shape in zip(operands, shapes))
            o, *pools = attend(*window, *pools, layer, table[rows], jnp.where(lens > 0, kv_lens[rows], 0), lens)
            o = o.reshape(CHUNK_ROWS, T, width)
            for c in range(CHUNK_ROWS):
                attn = put_chunk(attn, o[c], index[c, 0], real[c])
            return (attn, *pools)

        attn, *pools = jax.lax.fori_loop(
            0, (n_chunk_rows + CHUNK_ROWS - 1) // CHUNK_ROWS, trip, (unfilled((NPK, width), dtype), *pools)
        )
        return (functools.partial(rows_output, attn, o1.reshape(B, width)), *pools)

    def attention_layer(kind, x, kp, vp, tree, per, jk, layer, ffn):
        """A softmax or a window layer: ``tree`` its kind's stacks (its own
        leaves where ``per`` is None), ``layer`` its entry in the kind's
        pools, ``ffn(x_tile, start)`` what follows the mixer."""
        NH, NKV = cfg.heads_of(kind), cfg.kv_heads_of(kind)
        # what ``attn_heads`` does to the projections: a norm a head, a rotation, a scale; none of them: it is not called
        rotary_or_scaled = cfg.position == "rope" or cfg.attn_value_scale != 1.0 or cfg.qk_norm == "head"

        def before(start, qkv):
            p = weights_at(tree, per, jk, start)
            h = _norm(packed.take(x, start), p["attn_norm_scale"], None, cfg.norm, cfg.norm_eps)
            new = jax.lax.optimization_barrier(hm.attn_project(p, h))  # decode._paged_layers.project: keep the head split apart
            if rotary_or_scaled:
                at = None if positions is None else packed.take(positions, start)[None]
                new = tuple(a.reshape(a.shape[1], -1) for a in hm.attn_heads(cfg, kind, *(a[None] for a in new), at, p))
            return tuple(put(buf, a, start) for buf, a in zip(qkv, new))

        with jax.named_scope(hm.SCOPES[kind]):
            qkv = tiles(before, tuple(unfilled((NPK, nh * d), dtype) for nh, d in ((NH, D), (NKV, D), (NKV, Dv))))
            extras = {}
            if kind == "window":
                extras["window"] = cfg.window
                if cfg.window_sinks:
                    extras["sinks"] = weights_at({"sinks": tree["sinks"]}, per, jk, jnp.int32(0))["sinks"]
            attend = functools.partial(ragged_paged_attention, scale=scale, impl=attn_impl, **extras)
            table = ring_table if kind == "window" else page_table
            if T == 1:
                attn, kp, vp = attend(
                    *(packed.expand(a).reshape(B, T, nh, -1) for a, nh in zip(qkv, (NH, NKV, NKV))),
                    kp, vp, layer, table, kv_lens, q_lens,
                )
                attn = functools.partial(slab_rows, attn.reshape(B * T, NH * Dv))
            else:
                attn, kp, vp = wide_attention(attend, qkv, ((NH, D), (NKV, D), (NKV, Dv)), (kp, vp), layer, table, NH * Dv)

        def after(start, carry):
            x, counts = carry
            x_tile = packed.take(x, start)
            with jax.named_scope(hm.SCOPES[kind]):
                p = weights_at(tree, per, jk, start)
                h = _norm(x_tile, p["attn_norm_scale"], None, cfg.norm, cfg.norm_eps)
                x_tile = x_tile + branch(qmatmul(hm.output_gate(p, h, attn(start)), p["wo"]).astype(x.dtype))
            x_tile, tile_counts = ffn(x_tile[None], start)
            return put(x, x_tile[0], start), counts + tile_counts

        x, counts = tiles(after, (x, jnp.zeros((E,), jnp.int32)))
        return x, kp, vp, counts

    def latent_layer(x, pages, tree, per, jk, layer, ffn):
        """A latent layer in the absorbed form: ``tree``, ``per``, ``jk``,
        ``layer`` and ``ffn`` as ``attention_layer``'s; ``pages`` the latent
        layers' pool. Returns ``(x, pages, counts)``."""
        from deepspeed_tpu.ops.transformer.latent_attention import latent_paged_attention

        C, Dl = cfg.kv_lora_rank, cfg.latent_width
        # the per-head matrices, sliced out of their stacks ONCE a layer, outside the tile loop: inside it the
        # compiler wants them token-minor for the head-wise products and, tied to the tile as the other leaves
        # are, transposes the whole stack of every layer on every layer's entry (0.65 ms a layer: PERF.md, PR 41)
        per_head = tuple(k for k in ("wq_b", "wk_b", "wv_b") if k in tree)  # a query with no low rank has no ``wq_b``
        heads = weights_at({k: tree[k] for k in per_head}, per, jk, jnp.int32(0))
        tree = {k: v for k, v in tree.items() if k not in per_head}

        def before(start, bufs):
            p = {**weights_at(tree, per, jk, start), **heads}
            h = _norm(packed.take(x, start), p["attn_norm_scale"], None, cfg.norm, cfg.norm_eps)
            at = None if positions is None else packed.take(positions, start)[None]
            q_nope, q_rope, entry = hm.latent_project(cfg, p, h[None], at)
            q = hm.latent_absorb(cfg, p, q_nope[0], q_rope[0])  # [tile, NH, Dl]: against the stored entry
            return put(bufs[0], q.reshape(q.shape[0], NH * Dl), start), put(bufs[1], entry[0], start)

        with jax.named_scope(hm.SCOPES["latent"]):
            q, entries = tiles(before, (unfilled((NPK, NH * Dl), dtype), unfilled((NPK, Dl), dtype)))
            attend = functools.partial(latent_paged_attention, value_lanes=C, scale=scale, impl=attn_impl)
            if T == 1:
                o, pages = attend(
                    packed.expand(q).reshape(B, T, NH, Dl), packed.expand(entries).reshape(B, T, Dl),
                    pages, layer, page_table, kv_lens, q_lens,
                )
                o = functools.partial(slab_rows, o.reshape(B * T, NH * C))
            else:
                o, pages = wide_attention(attend, (q, entries), ((NH, Dl), (Dl,)), (pages,), layer, page_table, NH * C)

        def after(start, carry):
            x, counts = carry
            x_tile = packed.take(x, start)
            with jax.named_scope(hm.SCOPES["latent"]):
                p = {**weights_at(tree, per, jk, start), **heads}
                x_tile = x_tile + branch(hm.latent_output(cfg, p, o(start).reshape(-1, NH, C)).astype(x.dtype))
            x_tile, tile_counts = ffn(x_tile[None], start)
            return put(x, x_tile[0], start), counts + tile_counts

        x, counts = tiles(after, (x, jnp.zeros((E,), jnp.int32)))
        return x, pages, counts

    def selective_latent_layer(kind, x, *args):
        """A ``sparse_latent`` or a ``window_latent`` layer (``ops/transformer/
        sparse_latent_attention.py``): ``tree``, ``per``, ``jk``, ``layer`` and
        ``ffn`` as ``attention_layer``'s behind the kind's pools (the latent
        pages and the indexer's keys, or the rings of latents). The WHOLE
        mixer, from the norm to the output projection, runs a row group at a
        time and not a token tile at a time: the rows with one token together,
        then a row with a chunk a trip of a loop whose count is data. A buffer
        of absorbed queries a packed token (128 heads x 576 numbers) would be
        2.4 GB for a 32 x 512 window, so no buffer holds more of a token than
        its mixer's output ``[NPK, H]``; a chunk's 512 tokens hide the stream
        of the layer's weights they meet. Returns ``(x, *pools, counts)``."""
        from deepspeed_tpu.ops.transformer.sparse_latent_attention import ring_latent_attention, sparse_latent_attention

        *held, tree, per, jk, layer, ffn = args
        d = cfg.latent_dims(kind)
        softmax_scale = cfg.attn_softmax_scale if cfg.attn_softmax_scale is not None else d.scale
        H = cfg.hidden_size

        def mixer(p, x_rows, pos, held, table, row_slots, kv, ql):
            """``x_rows`` [R, W, H] at ``pos`` [R, W]: the mixer's output [R, W, H] and the pools."""
            h = _norm(x_rows, p["attn_norm_scale"], None, cfg.norm, cfg.norm_eps)
            c_q = hm.latent_query_rank(cfg, p, h, kind)
            q = hm.latent_queries(cfg, p, c_q, pos, kind)
            q = hm.latent_absorb(cfg, p, q[..., : d.nope], q[..., d.nope :], kind)  # against the stored entry
            entry = hm.latent_entry(cfg, p, h, pos, kind)
            if kind == "sparse_latent":
                with jax.named_scope("sparse_index"):
                    qi, wi = hm.index_queries(cfg, p, c_q, h, pos)
                    ki = hm.index_key(cfg, p, h, pos)
                o, *held = sparse_latent_attention(
                    q, qi, wi, entry, ki, *held, layer, table, kv, ql, topk=cfg.index_topk, value_lanes=d.kv_rank, scale=softmax_scale,
                )
            else:
                o, *held = ring_latent_attention(
                    q, entry, *held, layer, row_slots, kv, ql, window=cfg.window, ring=ring, value_lanes=d.kv_rank, scale=softmax_scale,
                )
            return hm.latent_output(cfg, p, o, kind, h=h).astype(dtype), held

        with jax.named_scope(hm.SCOPES[kind]):
            p = weights_at(tree, per, jk, jnp.int32(0))  # one slice a leaf, once a layer: no tile loop reads them
            x1 = x[starts] if T == 1 else real_rows(x, starts, one_token)
            o1, held = mixer(p, x1[:, None], lengths[:, None], held, page_table, slots, jnp.where(one_token, kv_lens, 0), one_token.astype(jnp.int32))
            o1 = o1[:, 0]  # [B, H]
            if T == 1:
                out = functools.partial(slab_rows, o1)
            else:

                def chunk_row(i, carry):
                    *held, chunks = carry
                    r = order[i]
                    idx = packed.index[r]  # [T]
                    valid = jnp.arange(T, dtype=jnp.int32) < q_lens[r]
                    o_row, held = mixer(
                        p, real_rows(x, idx, valid)[None], (lengths[r] + jnp.arange(T, dtype=jnp.int32))[None], held,
                        page_table[r][None], slots[r][None], kv_lens[r][None], q_lens[r][None],
                    )
                    return (*held, put_chunk(chunks, o_row[0], idx[0], valid))

                *held, chunks = jax.lax.fori_loop(0, n_chunk_rows, chunk_row, (*held, unfilled((NPK, H), dtype)))
                out = functools.partial(rows_output, chunks, o1)

        def after(start, carry):
            x, counts = carry
            with jax.named_scope(hm.SCOPES[kind]):
                x_tile = packed.take(x, start) + branch(out(start).astype(x.dtype))
            x_tile, tile_counts = ffn(x_tile[None], start)
            return put(x, x_tile[0], start), counts + tile_counts

        x, counts = tiles(after, (x, jnp.zeros((E,), jnp.int32)))
        return (x, *held, counts)

    def linear_layer(x, st, cv, tree, per, jl, layer, ffn):
        """A linear layer: ``tree``, ``per``, ``jl``, ``layer`` (its entry of
        the state store) and ``ffn`` as ``attention_layer``'s; a row with one
        token through ``kda_decode`` in place, a chunk row through
        ``kda_chunked``. Returns ``(x, state, conv, counts)``."""

        def before(start, bufs):
            p = weights_at(tree, per, jl, start)
            h = _norm(packed.take(x, start), p["attn_norm_scale"], None, cfg.norm, cfg.norm_eps)
            return tuple(put(buf, a, start) for buf, a in zip(bufs, hm.linear_inputs(cfg, p, h)))

        with jax.named_scope("linear_attention"):
            qkv, log_a, beta = tiles(before, (
                unfilled((NPK, C3), dtype), unfilled((NPK, LH * LD), jnp.float32), unfilled((NPK, LH), jnp.float32),
            ))
            cp = weights_at({k: v for k, v in tree.items() if k.startswith("conv_")}, per, jl, jnp.int32(0))
            taps = jnp.stack([cp["conv_q"], cp["conv_k"], cp["conv_v"]], axis=1).reshape(K, 3, LH, LD)
            # the rows with one token: from the projections to the recurrence's output in one kernel, in place on both
            # pools (the narrow program wrote every row of its buffers and reads them as they lie)
            qkv1, log_a1, beta1 = (a[starts] if T == 1 else real_rows(a, starts, one_token) for a in (qkv, log_a, beta))
            with jax.named_scope("kda_recurrence"):
                o, st, cv = kda_decode(
                    qkv1.reshape(B, 3, LH, LD), log_a1.reshape(B, LH, LD), beta1, taps, st, cv, layer, slots, one_token, fresh
                )
            o = o.astype(dtype)  # [B, LH, LD]
            if T == 1:
                o = functools.partial(slab_rows, o)
            else:

                def chunk_row(i, carry):
                    st, cv, chunks = carry
                    r = order[i]
                    idx = packed.index[r]  # [T]
                    valid = jnp.arange(T, dtype=jnp.int32) < q_lens[r]
                    own = (layer, slots[r]) + (0,) * (cv.ndim - 2)
                    tail = jnp.where(fresh[r], 0, jax.lax.dynamic_slice(cv, own, (1, 1) + cv.shape[2:]).reshape(1, K - 1, C3))
                    row_qkv = real_rows(qkv, idx, valid)  # [T, 3C]
                    q, k, v = hm.linear_qkv(cfg, hm.short_conv(cp, tail, row_qkv[None]))
                    la = real_rows(log_a, idx, valid).reshape(1, T, LH, LD)
                    b = real_rows(beta, idx, valid)[None]
                    S0 = jnp.where(fresh[r], 0.0, st[layer, slots[r]].astype(jnp.float32))[None]
                    with jax.named_scope("kda_recurrence"):
                        o_row, S = kda_chunked(q, k, v, la, b, S0)
                    st = jax.lax.dynamic_update_slice(st, S[None].astype(st.dtype), (layer, slots[r], 0, 0, 0))
                    last = tail_after_chunk(tail[0], row_qkv, q_lens[r], K)
                    cv = jax.lax.dynamic_update_slice(cv, last.reshape((1, 1) + cv.shape[2:]).astype(cv.dtype), own)
                    return st, cv, put_chunk(chunks, o_row.reshape(T, LH * LD), idx[0], valid)

                st, cv, chunks = jax.lax.fori_loop(0, n_chunk_rows, chunk_row, (st, cv, unfilled((NPK, LH * LD), dtype)))
                o = functools.partial(rows_output, chunks, o.reshape(B, LH * LD))

        def after(start, carry):
            x, counts = carry
            x_tile = packed.take(x, start)
            with jax.named_scope("linear_attention"):
                p = weights_at(tree, per, jl, start)
                h = _norm(x_tile, p["attn_norm_scale"], None, cfg.norm, cfg.norm_eps)
                x_tile = x_tile + branch(hm.linear_output(cfg, p, h, o(start).reshape(-1, LH, LD)).astype(x.dtype))
            x_tile, tile_counts = ffn(x_tile[None], start)
            return put(x, x_tile[0], start), counts + tile_counts

        x, counts = tiles(after, (x, jnp.zeros((E,), jnp.int32)))
        return x, st, cv, counts

    def ssm_layer(x, st, cv, tree, per, js, layer, ffn):
        """A state-space layer: ``tree``, ``per``, ``js``, ``layer`` (its entry
        of the state store) and ``ffn`` as ``attention_layer``'s; a row with
        one token through ``ssd_decode`` in place, a chunk row through
        ``ssd_chunked``. Returns ``(x, state, conv, counts)``."""
        SH, SK, inner, SC = cfg.ssm_num_heads, cfg.ssm_conv_kernel, cfg.ssm_inner, cfg.ssm_conv_channels

        def before(start, bufs):
            p = weights_at(tree, per, js, start)
            h = _norm(packed.take(x, start), p["attn_norm_scale"], None, cfg.norm, cfg.norm_eps)
            return tuple(put(buf, a, start) for buf, a in zip(bufs, hm.ssm_inputs(cfg, p, h)))

        with jax.named_scope(hm.SCOPES["ssm"]):
            z, xbc, dt = tiles(before, (unfilled((NPK, inner), dtype), unfilled((NPK, SC), dtype), unfilled((NPK, SH), jnp.float32)))
            # the recurrence's own leaves, sliced out of their stacks once a layer
            rp = weights_at({k: tree[k] for k in ("conv_w", "conv_b", "A_log", "D")}, per, js, jnp.int32(0))
            A, D = -jnp.exp(rp["A_log"].astype(jnp.float32)), rp["D"].astype(jnp.float32)
            # the rows with one token: from the projection to the recurrence's output in one kernel, in place on both pools
            xbc1, dt1 = (a[starts] if T == 1 else real_rows(a, starts, one_token) for a in (xbc, dt))
            with jax.named_scope("ssd_recurrence"):
                y, st, cv = ssd_decode(xbc1, dt1, rp["conv_w"], rp["conv_b"], A, D, st, cv, layer, slots, one_token, fresh)
            y = y.astype(dtype)  # [B, inner]
            if T == 1:
                y = functools.partial(slab_rows, y)
            else:

                def chunk_row(i, carry):
                    st, cv, chunks = carry
                    r = order[i]
                    idx = packed.index[r]  # [T]
                    valid = jnp.arange(T, dtype=jnp.int32) < q_lens[r]
                    own = (layer, slots[r]) + (0,) * (cv.ndim - 2)
                    held = jax.lax.dynamic_slice(cv, own, (1, 1) + cv.shape[2:])[0, 0, :, : SC // LANES]  # the rows past the channels are zeros
                    tail = jnp.where(fresh[r], 0, held.reshape(1, SK - 1, SC))
                    row_xbc = real_rows(xbc, idx, valid)  # [T, C]
                    with jax.named_scope("ssd_recurrence"):
                        xs, Bm, Cm = hm.ssm_split(cfg, hm.ssm_conv(rp, tail, row_xbc[None]))
                        S0 = jnp.where(fresh[r], 0.0, st[layer, slots[r]].astype(jnp.float32))[None]
                        # a slot past the row's tokens is a dead position: dt 0 leaves the state as it is
                        y_row, S = ssd_chunked(xs, Bm, Cm, real_rows(dt, idx, valid)[None], A, D, S0)
                    st = jax.lax.dynamic_update_slice(st, S[None].astype(st.dtype), (layer, slots[r], 0, 0, 0))
                    last = tail_after_chunk(tail[0], row_xbc, q_lens[r], SK).reshape(SK - 1, SC // LANES, LANES)
                    last = jnp.pad(last, ((0, 0), (0, cv.shape[3] - SC // LANES), (0, 0)))  # zeros in the rows past the channels
                    cv = jax.lax.dynamic_update_slice(cv, last[None, None].astype(cv.dtype), own)
                    return st, cv, put_chunk(chunks, y_row.reshape(T, inner), idx[0], valid)

                st, cv, chunks = jax.lax.fori_loop(0, n_chunk_rows, chunk_row, (st, cv, unfilled((NPK, inner), dtype)))
                y = functools.partial(rows_output, chunks, y)

        def after(start, carry):
            x, counts = carry
            x_tile = packed.take(x, start)
            with jax.named_scope(hm.SCOPES["ssm"]):
                p = weights_at(tree, per, js, start)
                x_tile = x_tile + branch(hm.ssm_output(cfg, p, packed.take(z, start), y(start)).astype(x.dtype))
            x_tile, tile_counts = ffn(x_tile[None], start)
            return put(x, x_tile[0], start), counts + tile_counts

        x, counts = tiles(after, (x, jnp.zeros((E,), jnp.int32)))
        return x, st, cv, counts

    def conv_layer(x, cv, tree, per, jc, layer, ffn):
        """A gated short-convolution layer: ``tree``, ``per``, ``jc``, ``layer``
        (its entry of the tail store) and ``ffn`` as ``attention_layer``'s. All
        it keeps of a row is the tail, the last ``K - 1`` gated products ``u =
        B * x~``: a row with one token reads its tail, sums the ``K`` taps and
        writes the tail shifted by its own ``u``, every such row at once; a
        chunk row runs the same convolution over ``[tail ; chunk]`` and
        leaves its last ``K - 1`` REAL products (``tail_after_chunk``), one
        row a trip. Plain ``jnp`` under the scope: no kernel. Returns ``(x,
        conv, counts)``."""
        H, CK = cfg.hidden_size, cfg.conv_kernel
        lane_rows = H // LANES  # the rows of the store's entry that hold channels; those past them are zeros

        def before(start, bufs):
            p = weights_at(tree, per, jc, start)
            h = _norm(packed.take(x, start), p["attn_norm_scale"], None, cfg.norm, cfg.norm_eps)
            return tuple(put(buf, a, start) for buf, a in zip(bufs, hm.conv_inputs(p, h)))

        def stored(tail):
            """``tail`` [..., K - 1, H] as the store's entry [..., K - 1, tail_rows(H), 128]."""
            rows = tail.reshape(tail.shape[:-1] + (lane_rows, LANES))
            return jnp.pad(rows, ((0, 0),) * (rows.ndim - 2) + ((0, cv.shape[3] - lane_rows), (0, 0))).astype(cv.dtype)

        with jax.named_scope(hm.SCOPES["conv"]):
            u, gate = tiles(before, (unfilled((NPK, H), dtype), unfilled((NPK, H), dtype)))
            taps = weights_at({"conv_w": tree["conv_w"]}, per, jc, jnp.int32(0))
            # the rows with one token, all at once: their tails out of the store, the three-term sum, the tails shifted back
            u1 = u[starts] if T == 1 else real_rows(u, starts, one_token)  # [B, H]
            held = jnp.take(jax.lax.dynamic_index_in_dim(cv, layer, 0, keepdims=False), slots, axis=0)[:, :, :lane_rows]
            tail = jnp.where(fresh[:, None, None], 0, held.reshape(B, CK - 1, H))
            v = hm.gated_conv(taps, tail, u1[:, None])[:, 0].astype(dtype)  # [B, H]
            # a row that is dead or has a chunk writes to the slot that is nobody's
            cv = cv.at[layer, jnp.where(one_token, slots, cv.shape[1] - 1)].set(stored(hm.shifted_tail(tail, u1)))
            if T == 1:
                v = functools.partial(slab_rows, v)
            else:

                def chunk_row(i, carry):
                    cv, chunks = carry
                    r = order[i]
                    idx = packed.index[r]  # [T]
                    valid = jnp.arange(T, dtype=jnp.int32) < q_lens[r]
                    own = (layer, slots[r]) + (0,) * (cv.ndim - 2)
                    held = jax.lax.dynamic_slice(cv, own, (1, 1) + cv.shape[2:])[0, 0, :, :lane_rows]
                    tail = jnp.where(fresh[r], 0, held.reshape(1, CK - 1, H))
                    row_u = real_rows(u, idx, valid)  # [T, H]
                    v_row = hm.gated_conv(taps, tail, row_u[None])[0]
                    last = tail_after_chunk(tail[0], row_u, q_lens[r], CK)
                    cv = jax.lax.dynamic_update_slice(cv, stored(last)[None, None], own)
                    return cv, put_chunk(chunks, v_row, idx[0], valid)

                cv, chunks = jax.lax.fori_loop(0, n_chunk_rows, chunk_row, (cv, unfilled((NPK, H), dtype)))
                v = functools.partial(rows_output, chunks, v)

        def after(start, carry):
            x, counts = carry
            x_tile = packed.take(x, start)
            with jax.named_scope(hm.SCOPES["conv"]):
                p = weights_at(tree, per, jc, start)
                x_tile = x_tile + branch(hm.conv_output(p, packed.take(gate, start), v(start).astype(jnp.float32)).astype(x.dtype))
            x_tile, tile_counts = ffn(x_tile[None], start)
            return put(x, x_tile[0], start), counts + tile_counts

        x, counts = tiles(after, (x, jnp.zeros((E,), jnp.int32)))
        return x, cv, counts

    # a kind's pools: the full layers' pages, the window layers' rings, the latent layers' pages, the linear or the
    # state-space layers' states and convolution tails, or the conv layers' tails alone
    state_kind = cfg.state_kind or "linear"  # the kind whose states and tails the store's two fields hold
    pools = {"softmax": (k_pages, v_pages), "window": (wk, wv), "latent": (latent,),
             state_kind: (conv,) if state_kind == "conv" else (state, conv)}
    if index is not None:  # the paged latent layers are sparse ones: the latents and the indexer's keys, written together
        pools["sparse_latent"] = (latent, index)
    if latent_rings is not None:
        pools["window_latent"] = (latent_rings,)
    mixers = {"softmax": functools.partial(attention_layer, "softmax"), "window": functools.partial(attention_layer, "window"),
              "latent": latent_layer, "linear": linear_layer, "ssm": ssm_layer, "conv": conv_layer,
              "sparse_latent": functools.partial(selective_latent_layer, "sparse_latent"),
              "window_latent": functools.partial(selective_latent_layer, "window_latent")}
    # the leading dense layers, each with its own weights and the first entries of its kind's pools
    for i, kind in enumerate(cfg.layer_types[: cfg.leading_dense_layers]):
        lead = params["leading"][i]
        x, *written, _ = mixers[kind](
            x, *pools[kind], lead["mixer"], None, None, cfg.layer_types[:i].count(kind),
            functools.partial(dense_ffn, p=lead["ffn"]),
        )
        pools[kind] = tuple(written)

    def period_step(carry, per):
        x, pools = carry
        pools = dict(pools)
        at = {kind: 0 for kind in hm.LAYER_KINDS + (hm.FFN_BLOCK,)}
        counts = []
        for j, kind in enumerate(period):
            if kind == hm.FFN_BLOCK:
                x, c = ffn_block(x, per, at[kind])
            else:
                layer = cfg.leading_of(kind) + per * period.count(kind) + at[kind]
                x, *written, c = mixers[kind](
                    x, *pools[kind], stacks[kind], per, at[kind], layer,
                    nothing_behind if single else functools.partial(ffn, per=per, j=j),
                )
                pools[kind] = tuple(written)
            at[kind] += 1
            if kind == hm.FFN_BLOCK or not single:  # the blocks with an FFN: a mixer alone routes nothing
                counts.append(c)
        return (x, pools), jnp.stack(counts)

    (x, pools), counts = jax.lax.scan(period_step, (x, pools), jnp.arange(cfg.num_periods, dtype=jnp.int32))
    counts = counts.reshape(max(cfg.num_moe_layers - len(cfg.remainder), 0), E)  # the scanned layers'
    # the layers behind the last whole period (``cfg.remainder``), as the leading ones: each with its own weights, a mixer
    # with the kind's entries behind the scanned layers' and the layer's own FFN behind it; none: nothing is traced
    for i, kind in enumerate(cfg.remainder):
        own = params["trailing"][i]
        x, *written, c = mixers[kind](
            x, *pools[kind], own["mixer"], None, None, cfg.trailing_of(kind) + cfg.remainder[:i].count(kind),
            functools.partial(ffn, per=None, j=None, own=own),
        )
        pools[kind] = tuple(written)
        counts = jnp.concatenate([counts, c[None]])
    held = (None, *pools["conv"]) if state_kind == "conv" else pools[state_kind]
    paged = pools["sparse_latent"] if index is not None else (*pools["latent"], None)
    store = StateStore(*held, *pools["window"], *paged, *pools.get("window_latent", (None,)))
    return x, *pools["softmax"], store, counts, packed


def hybrid_forward(cfg, params, tokens, k_pages, v_pages, state, conv, page_table, lengths, q_lens, slots,
                   attn_impl: str = "auto", window=None, latent=None, index=None, latent_rings=None):
    """``decode._paged_forward`` for a hybrid model: the window's logits
    ``[B, T, V]`` (a dead slot's are some live token's) and the pools:
    ``(logits, k_pages, v_pages, state, conv, moe_counts)`` and, for a model
    with sliding-window layers, whose rings ``window = (window_k, window_v)``
    gives, the rings after them; for a model with latent layers, whose pages
    ``latent`` gives, those pages last; behind them a sparse model's indexer
    keys (``index``) and a model's rings of latents (``latent_rings``), each
    where given. What the parity tests and the benchmark's
    logits tools compare with the reference; the serving step takes its
    arg-max on the packed tiles instead."""
    x, kp, vp, store, moe_counts, packed = _hybrid_layers(
        cfg, params, tokens, k_pages, v_pages, StateStore(state, conv, *(window or (None, None)), latent, index, latent_rings), page_table,
        lengths, q_lens, slots, attn_impl,
    )
    out = (decode._final_logits(cfg, params, packed.expand(x)), kp, vp, store.state, store.conv, moe_counts)
    if window is not None:
        out += ((store.window_k, store.window_v),)
    out += tuple(after for given, after in ((latent, store.latent), (index, store.index), (latent_rings, store.window_latent)) if given is not None)
    return out


def build_hybrid_ragged_step(cfg, rows: int, width: int, page_size: int, attn_impl: str, telemetry, name: str, key):
    """``decode.build_ragged_step``'s program for a model with layers of more
    than one kind: the contract is at the top of this file; ``out`` is
    ``build_ragged_step``'s (with its MoE rows)."""
    W = int(width)

    def _step(params, tokens, k_pages, v_pages, store, page_table, lengths, q_lens, slots):
        x, kp, vp, store, moe_counts, packed = _hybrid_layers(
            cfg, params, tokens, k_pages, v_pages, store, page_table, lengths, q_lens, slots, attn_impl
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
            if cfg.num_experts:  # a model with no expert has no routing counts: its result is the rows alone
                out = jnp.concatenate([out, decode._moe_stat_rows(moe_counts, W + 1)], axis=0)
        return out, kp, vp, store

    fn = decode._jit(_step, telemetry, name, donate_argnums=(2, 3, 4))
    decode._paged_program_cache[key] = fn
    return fn
