"""Ragged paged decode attention — serving-layer front end.

The serving layer (``inference/kv_pool.py`` + ``inference/scheduler.py``)
stores every sequence's KV cache as fixed-size pages in one shared pool
``[num_pages, NKV, page_size, D]`` per layer, addressed through per-sequence
page tables. This module is the single attention entry point for that
layout:

* ``paged_decode_attention`` — one generated token per sequence attends over
  its live pages. Dispatches to the Pallas kernel
  (``decode_attention._pallas_paged_decode``: the kv grid walks the page
  table via scalar prefetch, online softmax, GQA groups ride the sublane
  dim) on TPU, and to a gather-based XLA implementation everywhere else —
  interpret-mode Pallas inside a per-step serving program would dominate
  CPU-mesh test time.
* ``paged_prefill_attention`` — a token slab ``[B, T]`` attends causally
  over each row's own pages (prefix + the slab itself, already scattered
  in). Pure XLA: the slab paths are matmul-bound. Two callers: chunked
  prompt prefill (B = 1, T = chunk) and the speculative verify program
  (B = slot bucket, T = K+1 draft-and-bonus slots), which also passes
  per-row ``kv_lens`` so pad draft slots past a row's live prefix are
  masked out of every score.
* ``ragged_paged_attention`` — the unified entry the one-program ragged
  serving step dispatches (``decode.py:build_ragged_step``): mixed
  prefill-chunk / decode / verify rows in one ``[R, W]`` window, driven
  entirely by per-row ``(kv_len, q_len)`` metadata arrays so the mix
  never retraces. Pallas kernel on TPU
  (``decode_attention.ragged_paged_attention``: kv grid walks the page
  table via scalar prefetch, causal in-window mask, pages past a row's
  live length skipped), XLA gather fallback elsewhere.

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
    paged_decode_attention as _pallas_paged_decode,
    ragged_paged_attention as _pallas_ragged_paged,
)


def _scale_or_default(scale: Optional[float], head_dim: int) -> float:
    return float(scale) if scale is not None else 1.0 / float(np.sqrt(head_dim))


def _gather_pages(pages: jnp.ndarray, page_table: jnp.ndarray) -> jnp.ndarray:
    """[NP, NKV, P, D] pool + [B, MAXP] table -> [B, MAXP*P, NKV, D] linear
    view (kv position s lives in table slot s // P at offset s % P)."""
    NP, NKV, P, D = pages.shape
    B, maxp = page_table.shape
    pt = jnp.clip(jnp.asarray(page_table, jnp.int32), 0, NP - 1)
    # [B, MAXP, NKV, P, D] -> [B, MAXP, P, NKV, D] -> [B, S, NKV, D]
    return pages[pt].transpose(0, 1, 3, 2, 4).reshape(B, maxp * P, NKV, D)


def paged_decode_attention_xla(
    q: jnp.ndarray,  # [B, NH, D]
    k_pages: jnp.ndarray,  # [NP, NKV, P, D]
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, MAXP] int32
    kv_len,  # [B] int32 live lengths (or scalar)
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Gather-based reference/fallback: linearize each row's pages and run
    grouped-GQA masked attention. Rows with length 0 return exact zeros
    (matching the Pallas kernel's empty-accumulator output)."""
    B, NH, D = q.shape
    NP, NKV, P, _ = k_pages.shape
    assert v_pages.shape == k_pages.shape
    if NH % NKV:
        raise ValueError(f"query heads {NH} not a multiple of kv heads {NKV}")
    G = NH // NKV
    S = page_table.shape[1] * P
    scale_f = _scale_or_default(scale, D)
    k = _gather_pages(k_pages, page_table)  # [B, S, NKV, D]
    v = _gather_pages(v_pages, page_table)
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    qg = q.reshape(B, NKV, G, D)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k).astype(jnp.float32) * scale_f
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    live = kv_pos[None, None, None, :] < lens[:, None, None, None]
    scores = jnp.where(live, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v)
    out = jnp.where((lens > 0)[:, None, None, None], out, 0)
    return out.reshape(B, NH, D)


def paged_decode_attention(
    q: jnp.ndarray,  # [B, NH, D]
    k_pages: jnp.ndarray,  # [NP, NKV, P, D]
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, MAXP] int32
    kv_len,  # [B] int32 live lengths
    scale: Optional[float] = None,
    impl: str = "auto",
) -> jnp.ndarray:
    """Single-token paged attention. ``impl``: ``auto`` picks the Pallas
    kernel on TPU and the XLA gather fallback elsewhere; ``pallas`` / ``xla``
    force one (``pallas`` off-TPU runs in interpret mode — tests only)."""
    if impl == "auto":
        impl = "pallas" if on_tpu() else "xla"
    if impl == "pallas":
        return _pallas_paged_decode(q, k_pages, v_pages, page_table, kv_len, scale=scale)
    if impl == "xla":
        return paged_decode_attention_xla(q, k_pages, v_pages, page_table, kv_len, scale=scale)
    raise ValueError(f"unknown paged attention impl {impl!r}; expected auto|pallas|xla")


def ragged_paged_attention(
    q: jnp.ndarray,  # [R, W, NH, D] — per-row padded token windows
    k_pages: jnp.ndarray,  # [NP, NKV, P, D]
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # [R, MAXP] int32
    kv_lens: jnp.ndarray,  # [R] live kv length INCLUDING this step's tokens
    q_lens: jnp.ndarray,  # [R] real tokens in the window (0 = dead row)
    scale: Optional[float] = None,
    impl: str = "auto",
) -> jnp.ndarray:
    """Unified mixed-row attention for the one-program ragged serving step
    (arXiv 2604.15464): every row attends causally over its own pages with
    per-row ``(kv_len, q_len)`` metadata riding in as arrays — a decode row
    (q_len 1), a verify row (q_len K+1), and a prefill chunk (q_len C) all
    take the same code path, so shifting the mix never changes the program.
    ``impl``: ``auto`` picks the Pallas ragged kernel on TPU and the XLA
    gather fallback elsewhere; ``pallas`` / ``xla`` force one (``pallas``
    off-TPU runs in interpret mode — tests only). Rows with
    ``kv_lens == 0`` return exact zeros; window slots past ``q_lens``
    return garbage the caller ignores."""
    if impl == "auto":
        impl = "pallas" if on_tpu() else "xla"
    if impl == "pallas":
        return _pallas_ragged_paged(
            q, k_pages, v_pages, page_table, kv_lens, q_lens, scale=scale
        )
    if impl != "xla":
        raise ValueError(f"unknown ragged attention impl {impl!r}; expected auto|pallas|xla")
    R, W = q.shape[:2]
    lens = jnp.asarray(kv_lens, jnp.int32)
    qlens = jnp.asarray(q_lens, jnp.int32)
    # absolute query positions: the row's write base (kv_len - q_len) plus
    # the in-window offset — the causal mask then bounds every real slot,
    # and the kv_lens cap silences pad slots' reads above the live prefix
    q_positions = (lens - qlens)[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    return paged_prefill_attention(
        q, k_pages, v_pages, page_table, q_positions, scale=scale, kv_lens=lens
    )


def paged_prefill_attention(
    q: jnp.ndarray,  # [B, T, NH, D] — a prompt chunk's queries
    k_pages: jnp.ndarray,  # [NP, NKV, P, D]
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, MAXP] int32
    q_positions: jnp.ndarray,  # [B, T] absolute positions of the chunk tokens
    scale: Optional[float] = None,
    kv_lens: Optional[jnp.ndarray] = None,  # [B] live kv bound (incl. the slab)
) -> jnp.ndarray:
    """Causal slab attention over each sequence's own pages: query at
    absolute position p sees kv positions <= p (the slab's k/v have already
    been scattered into the pages, so the slab attends to itself too).
    Positions past a slab's real end (pad tail) produce garbage rows the
    caller ignores — their writes land on the trash page and their reads are
    causally bounded, so they never contaminate live positions. ``kv_lens``
    additionally caps every row's visible kv range (the verify program's
    pad slots sit ABOVE live positions, where causality alone would let
    them read unwritten pages); rows with ``kv_lens == 0`` (dead bucket
    padding) return exact zeros."""
    B, T, NH, D = q.shape
    NP, NKV, P, _ = k_pages.shape
    if NH % NKV:
        raise ValueError(f"query heads {NH} not a multiple of kv heads {NKV}")
    G = NH // NKV
    S = page_table.shape[1] * P
    scale_f = _scale_or_default(scale, D)
    k = _gather_pages(k_pages, page_table)  # [B, S, NKV, D]
    v = _gather_pages(v_pages, page_table)
    qg = q.reshape(B, T, NKV, G, D)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(jnp.float32) * scale_f
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    mask = q_positions[:, None, None, :, None] >= kv_pos[None, None, None, None, :]
    if kv_lens is not None:
        lens = jnp.asarray(kv_lens, jnp.int32)
        mask = mask & (kv_pos[None, None, None, None, :] < lens[:, None, None, None, None])
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    out = out.reshape(B, T, NH, D)
    if kv_lens is not None:
        out = jnp.where((lens > 0)[:, None, None, None], out, 0)
    return out
