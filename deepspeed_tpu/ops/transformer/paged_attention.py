"""Ragged paged attention — serving-layer front end.

The serving layer (``inference/kv_pool.py`` + ``inference/scheduler.py``)
stores every sequence's KV cache as fixed-size pages in one shared pool
``[L, num_pages, NKV, page_size, D]`` for all layers, addressed through
per-sequence page tables; heads narrower than a lane tile lie ``f = 128 // D``
to a page, ``[L, num_pages, NKV / f, page_size, f D]`` (``kv_pool.heads_per_group``:
KV head ``j`` in group ``j // f`` at lanes ``(j % f) D ..``), so that a page is
whole lane tiles: ``ragged_paged_attention`` sees it from the shapes and
hands its implementations an ordinary attention at heads of ``f D``
(``_share_lanes``). This module is the single attention entry point
for that layout. Every entry takes the whole stack and a ``layer`` index and
reaches the layer's pages through it (``pool[layer, ids]``, the kernels'
index maps): the serving step carries the stack through its layer loop, and
a slice of it would be a copy of one layer's pool.

* ``ragged_paged_attention`` — the entry the one-program ragged serving
  step dispatches (``decode.py:build_ragged_step``): mixed
  prefill-chunk / decode / verify rows in one ``[R, W]`` window, driven
  entirely by per-row ``(kv_len, q_len)`` metadata arrays so the mix
  never retraces. It WRITES the window's k/v into the pool and attends over
  it, and returns the pools. Pallas kernel on TPU
  (``decode_attention.ragged_paged_attention``: one fused kernel on the
  aliased stacks — a grid step a row walks the row's live pages only,
  fetched by the kernel's own DMAs, merges the new rows into the pages that
  receive them, causal in-window mask; a dead row and the dead tail of a
  table cost a few scalar reads), XLA scatter + gather elsewhere —
  interpret-mode Pallas inside a per-step serving program would dominate
  CPU-mesh test time.
* ``scatter_pages`` and ``paged_prefill_attention`` — the entry's XLA form,
  and the reference the kernel's tests compare with: the write of a token
  slab's k/v into the pool, then the slab ``[B, T]`` attending causally over
  each row's own pages (prefix + the slab itself), per-row ``kv_lens``
  masking the pad slots past a row's live prefix out of every score.

GQA is handled by grouping — queries reshape to ``[B, NKV, G, D]`` and each
kv head's rows are read once — so no path here (kernel or fallback) ever
materializes an NH-wide copy of the cache the way a ``jnp.repeat`` expansion
would.

Page-table conventions (shared with ``inference/kv_pool.py``): ids < 0 or
>= num_pages are sentinels for unallocated slots; they are clamped to page 0
(the pool's reserved trash page) and their scores masked by the length, so
padded tables are always safe to read.

Tensor-parallel contract (``inference/tp.py``): every entry point here is
**shard-oblivious**. Under multi-chip serving the ragged step runs these
inside ``shard_map`` with the page pools sharded on the kv-head axis — the
kernel then simply sees the LOCAL ``NKV/tp`` kv heads of every page and the
matching ``NH/tp`` query heads (the GQA group size ``NH/NKV`` is invariant
under the split, and head blocks are contiguous, so q-head block i attends
exactly its kv-head block i). Page tables, lengths, and q_lens arrive
replicated. Nothing in this module reads a mesh axis: the same code is the
single-chip and the per-shard implementation.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.accelerator import on_tpu
from deepspeed_tpu.ops.transformer.decode_attention import (
    NEG_INF,
    ragged_paged_attention as _pallas_ragged_paged,
)


def _scale_or_default(scale: Optional[float], head_dim: int) -> float:
    return float(scale) if scale is not None else 1.0 / float(np.sqrt(head_dim))


def _gather_pages(pages: jnp.ndarray, layer, page_table: jnp.ndarray) -> jnp.ndarray:
    """[L, NP, NKV, P, D] pool + layer + [B, MAXP] table -> [B, MAXP*P, NKV, D]
    linear view (kv position s lives in table slot s // P at offset s % P)."""
    _, NP, NKV, P, D = pages.shape
    B, maxp = page_table.shape
    pt = jnp.clip(jnp.asarray(page_table, jnp.int32), 0, NP - 1)
    # [B, MAXP, NKV, P, D] -> [B, MAXP, P, NKV, D] -> [B, S, NKV, D]
    return pages[layer, pt].transpose(0, 1, 3, 2, 4).reshape(B, maxp * P, NKV, D)


def scatter_pages(pages, layer, vals, page_table, positions, valid):
    """Write [B, T, NKV, D] new k/v rows into ``layer`` of the page pool
    [L, NP, NKV, P, D] at absolute ``positions`` [B, T] through the page table
    [B, MAXP]. Sentinel table entries (< 0, i.e. unallocated/dead rows)
    clamp onto the reserved trash page 0, so dead padding rows write garbage
    only where nothing lives. ``valid`` (bool [B, T])
    force-redirects masked positions onto the trash page regardless of the
    table: a window's slots past the row's real tokens sit past its ensured
    pages, where ``positions // page_size`` could alias a LIVE page after
    the maxp clamp."""
    _, NP, _, P, _ = pages.shape
    maxp = page_table.shape[1]
    slot = jnp.clip(positions // P, 0, maxp - 1)
    pid = jnp.clip(jnp.take_along_axis(page_table, slot, axis=1), 0, NP - 1)
    pid = jnp.where(valid, pid, 0)  # page 0 = the reserved trash page
    off = positions % P
    # advanced-index scatter: (layer, pid, off) broadcast to [B, T] and land
    # first, giving the [B, T, NKV, D] update window vals fills exactly
    return pages.at[layer, pid, :, off, :].set(vals.astype(pages.dtype))


def _share_lanes(q, k_new, v_new, f: int):
    """A window's operands for a pool that holds ``f`` KV heads a group:
    ``k_new`` and ``v_new`` ``[R, W, NKV, D]`` as ``[R, W, NKV / f, f D]`` (a
    reshape: a group's heads are neighbours), and ``q`` ``[R, W, NH, D]`` as
    ``[R, W, NH, f D]``, a head's own lanes where its KV head's lie in the
    group and zeros elsewhere. Query heads keep their order and a group's
    ``f Hg`` are contiguous, so this is GQA at ``NKV / f`` heads of ``f D``. A
    zero lane adds an exact zero to a float32 sum and a softmax row is one
    query head's, so scores and weights are the head's own; the lanes of
    ``p v`` that are its neighbours' values are dropped (``_own_lanes``)."""
    R, W, NH, D = q.shape
    NKV = k_new.shape[2]
    own = jnp.eye(f, dtype=bool).reshape(f, 1, f, 1)  # [head in group, ., lanes of head, .]
    q = jnp.where(own, q.reshape(R, W, NKV // f, f, NH // NKV, 1, D), 0).reshape(R, W, NH, f * D)
    return q, k_new.reshape(R, W, NKV // f, -1), v_new.reshape(R, W, NKV // f, -1)


def _own_lanes(out, f: int, groups: int):
    """``out`` ``[R, W, NH, f D]`` of a shared-lane attention -> ``[R, W, NH, D]``:
    each head's own lanes."""
    R, W, NH, lanes = out.shape
    out = out.reshape(R, W, groups, f, NH // (groups * f), f, lanes // f)
    return jnp.stack([out[:, :, :, j, :, j] for j in range(f)], axis=3).reshape(R, W, NH, lanes // f)


def ragged_paged_attention(
    q: jnp.ndarray,  # [R, W, NH, D] — per-row padded token windows
    k_new: jnp.ndarray,  # [R, W, NKV, D] — the windows' keys, not yet in the pool
    v_new: jnp.ndarray,
    k_pages: jnp.ndarray,  # [L, NP, NKV, P, D], or [L, NP, NKV / f, P, f D]
    v_pages: jnp.ndarray,
    layer,  # int32 scalar
    page_table: jnp.ndarray,  # [R, MAXP] int32
    kv_lens: jnp.ndarray,  # [R] live kv length INCLUDING this step's tokens
    q_lens: jnp.ndarray,  # [R] real tokens in the window (0 = dead row)
    scale: Optional[float] = None,
    impl: str = "auto",
    window: Optional[int] = None,
    sinks: Optional[jnp.ndarray] = None,
):
    """Unified mixed-row write-and-attend for the one-program ragged serving
    step (arXiv 2604.15464): row r's ``q_lens[r]`` new keys and values go
    into its pages at positions ``kv_lens[r] - q_lens[r] ..`` (window slots
    past ``q_lens[r]`` to the trash page), then the row attends causally
    over its own pages. The per-row ``(kv_len, q_len)`` metadata rides in as
    arrays — a decode row (q_len 1), a verify row (q_len K+1), and a prefill
    chunk (q_len C) all take the same code path, so shifting the mix never
    changes the program. ``impl``: ``auto`` picks the fused Pallas kernel on
    TPU and XLA's scatter + gather elsewhere; ``pallas`` / ``xla`` force one
    (``pallas`` off-TPU runs in interpret mode — tests only). Both leave the
    same bytes in every page but the trash page. ``window`` (static: a query
    sees the newest ``window`` keys only, itself included), ``sinks`` ([NH]:
    one more softmax column a head, with no value), a value head narrower
    than a key head and a key pool wider than ``q`` (zero lanes) are the
    kernel's (``decode_attention.ragged_paged_attention``). A pool with
    ``NKV / f`` heads of ``f D`` where ``k_new`` has ``NKV`` of ``D`` holds ``f``
    heads a group (``kv_pool.heads_per_group``): both implementations then
    see the group as one KV head of ``f D`` lanes, each query head with zeros
    on its neighbours' lanes (``_share_lanes``), and ``scale`` is the true
    head's. Returns
    ``(out [R, W, NH, Dv], k_pages, v_pages)``: rows with ``kv_lens == 0``
    are exact zeros; window slots past ``q_lens`` are garbage the caller
    ignores."""
    if impl == "auto":
        impl = "pallas" if on_tpu() else "xla"
    f = k_new.shape[2] // k_pages.shape[2]
    if f > 1:  # heads that share a page's lanes
        if not (k_pages.shape[-1] == v_pages.shape[-1] == f * q.shape[-1] and k_new.shape[2] == f * k_pages.shape[2]):
            raise ValueError(
                f"{k_new.shape[2]} kv heads of {q.shape[-1]} on pools {k_pages.shape[2:]} / {v_pages.shape[2:]}: "
                "neither a head a page nor whole groups of heads that share its lanes (kv_pool.heads_per_group)"
            )
        scale = _scale_or_default(scale, q.shape[-1])
        q, k_new, v_new = _share_lanes(q, k_new, v_new, f)
    extras = {} if window is None and sinks is None else dict(window=window, sinks=sinks)
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown ragged attention impl {impl!r}; expected auto|pallas|xla")
    attend = _pallas_ragged_paged if impl == "pallas" else _xla_ragged_paged
    out, k_pages, v_pages = attend(q, k_new, v_new, k_pages, v_pages, layer, page_table, kv_lens, q_lens, scale=scale, **extras)
    return (_own_lanes(out, f, k_pages.shape[2]) if f > 1 else out), k_pages, v_pages


def _xla_ragged_paged(q, k_new, v_new, k_pages, v_pages, layer, page_table, kv_lens, q_lens, scale=None, **extras):
    """``ragged_paged_attention`` as XLA's scatter and gather."""
    W = q.shape[1]
    scale = _scale_or_default(scale, q.shape[-1])  # of the head's own width, before any zero lanes
    if k_pages.shape[-1] > q.shape[-1]:  # a key head stored wider than it is: zero lanes
        lanes = ((0, 0),) * 3 + ((0, k_pages.shape[-1] - q.shape[-1]),)
        q, k_new = jnp.pad(q, lanes), jnp.pad(k_new, lanes)
    lens = jnp.asarray(kv_lens, jnp.int32)
    qlens = jnp.asarray(q_lens, jnp.int32)
    # absolute query positions: the row's write base (kv_len - q_len) plus
    # the in-window offset — the causal mask then bounds every real slot,
    # and the kv_lens cap silences pad slots' reads above the live prefix
    offs = jnp.arange(W, dtype=jnp.int32)[None, :]
    q_positions = (lens - qlens)[:, None] + offs
    valid = offs < qlens[:, None]
    k_pages = scatter_pages(k_pages, layer, k_new, page_table, q_positions, valid)
    v_pages = scatter_pages(v_pages, layer, v_new, page_table, q_positions, valid)
    out = paged_prefill_attention(
        q, k_pages, v_pages, layer, page_table, q_positions, lens, scale=scale, **extras
    )
    return out, k_pages, v_pages


def paged_prefill_attention(
    q: jnp.ndarray,  # [B, T, NH, D] — a prompt chunk's queries
    k_pages: jnp.ndarray,  # [L, NP, NKV, P, D]
    v_pages: jnp.ndarray,
    layer,  # int32 scalar
    page_table: jnp.ndarray,  # [B, MAXP] int32
    q_positions: jnp.ndarray,  # [B, T] absolute positions of the chunk tokens
    kv_lens: jnp.ndarray,  # [B] live kv bound (incl. the slab)
    scale: Optional[float] = None,
    window: Optional[int] = None,
    sinks: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Causal slab attention over each sequence's own pages: query at
    absolute position p sees kv positions <= p (the slab's k/v have already
    been scattered into the pages, so the slab attends to itself too).
    Positions past a slab's real end (pad tail) produce garbage rows the
    caller ignores — their writes land on the trash page and their reads are
    causally bounded, so they never contaminate live positions. ``kv_lens``
    also caps every row's visible kv range (a window's pad slots
    sit ABOVE live positions, where causality alone would let them read
    unwritten pages); rows with ``kv_lens == 0`` (dead padding) return
    exact zeros."""
    B, T, NH, D = q.shape
    _, NP, NKV, P, _ = k_pages.shape
    if NH % NKV:
        raise ValueError(f"query heads {NH} not a multiple of kv heads {NKV}")
    G = NH // NKV
    S = page_table.shape[1] * P
    scale_f = _scale_or_default(scale, D)
    k = _gather_pages(k_pages, layer, page_table)  # [B, S, NKV, D]
    v = _gather_pages(v_pages, layer, page_table)
    qg = q.reshape(B, T, NKV, G, D)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(jnp.float32) * scale_f
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    lens = jnp.asarray(kv_lens, jnp.int32)
    mask = q_positions[:, None, None, :, None] >= kv_pos[None, None, None, None, :]
    mask = mask & (kv_pos[None, None, None, None, :] < lens[:, None, None, None, None])
    if window is not None:
        mask = mask & (q_positions[:, None, None, :, None] - kv_pos[None, None, None, None, :] < window)
    scores = jnp.where(mask, scores, NEG_INF)
    if sinks is not None:  # one more column a head, dropped after the softmax
        column = jnp.broadcast_to(sinks.astype(jnp.float32).reshape(1, NKV, G, 1, 1), scores.shape[:-1] + (1,))
        probs = jax.nn.softmax(jnp.concatenate([scores, column], axis=-1), axis=-1)[..., :-1].astype(v.dtype)
    else:
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    out = out.reshape(B, T, NH, v.shape[-1])
    return jnp.where((lens > 0)[:, None, None, None], out, 0)
