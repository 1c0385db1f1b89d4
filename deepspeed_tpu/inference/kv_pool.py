"""Block-pool KV cache for paged serving, with page-level prefix sharing.

The dense decode workspace (``inference/decode.py:init_cache``) allocates
``[L, B, max_len, NKV, D]`` per batch — HBM scales with ``batch × max_len``
whether or not those tokens exist. Here the cache is a shared pool of
fixed-size pages ``[L, num_pages, NKV, page_size, D]`` (heads narrower than a
lane tile ``f`` to a group, ``[L, num_pages, NKV / f, page_size, f * D]``:
``heads_per_group``) plus a per-sequence page table: HBM holds
``live_tokens × bytes_per_token`` rounded up to page
granularity, and any free page can serve any sequence (the vLLM block-table
layout; the reference approximates it with contiguous per-sequence
workspaces — ``allocate_workspace`` in
``csrc/transformer/inference/csrc/pt_binding.cpp``).

Split of responsibilities:

* ``PagedKVCache`` — the device arrays. Jitted programs read/write them
  through ``ops/transformer/paged_attention.py`` and the scatter in
  ``inference/decode.py``; they are donated into every serving program so
  updates alias in place.
* ``PagePool`` — the host-side allocator: free list, per-slot page tables
  and live lengths (numpy; they ride into each dispatch as plain int32
  arrays, so allocation changes never retrace a program), alloc/free/defrag,
  and the **prefix index**.

Prefix sharing (production traffic: N requests carrying the same system
prompt must pay its prefill and HBM once):

* every FULL page a sequence writes can be *registered* under a
  **chain hash** — ``hash(previous block's chain key, this block's token
  content)`` — so a key identifies a whole prefix, not just a block;
* a new request *matches* its prompt against the index block-by-block and
  **attaches** the longest indexed prefix: the shared pages enter its page
  table, the per-page **refcount** rises, and prefill resumes after them;
* pages reachable from the index are **immutable**. The write barrier
  (``prepare_write``) enforces it: a shared page (refcount > 1) in the
  about-to-be-written span is replaced by a private **copy-on-write**
  duplicate (divergence), and an exclusively-owned indexed page is
  dropped from the index before the write lands;
* releasing the last reference to an indexed page parks it on a
  **cached LRU** instead of the free list — the prefix survives its
  author, and the allocator reclaims cached pages (oldest first) only
  when the free list runs dry.

``free_pages()`` therefore counts *reclaimable* pages (free + cached), so
admission control never refuses a request that evicting cold prefixes
could host. Page 0 is the reserved TRASH page: it is never allocated,
table sentinels (-1) clamp onto it inside the kernels, and dead-slot
writes land there — a padded batch row can never corrupt a live
sequence's pages.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.config import TransformerConfig, cache_layers

TRASH_PAGE = 0

# root of every prefix hash chain (arbitrary constant; only equality of
# chain keys matters, and keys are process-local like python hash())
_ROOT_CHAIN = 0x9E3779B9

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}


def _copy_page(k_pages, v_pages, src, dst):
    """Copy page ``src`` over page ``dst`` in both pools — jitted with the
    pools DONATED, so XLA aliases them in place and a CoW event costs one
    page's bytes, not a rebuild of the whole cache."""
    kp = jax.lax.dynamic_index_in_dim(k_pages, src, axis=1, keepdims=True)
    vp = jax.lax.dynamic_index_in_dim(v_pages, src, axis=1, keepdims=True)
    return (
        jax.lax.dynamic_update_slice_in_dim(k_pages, kp, dst, axis=1),
        jax.lax.dynamic_update_slice_in_dim(v_pages, vp, dst, axis=1),
    )


# one compiled copier per (shape, dtype) — shared across pools
_copy_page_cache: dict = {}


def _sharding_key(sharding):
    """Hashable identity of a NamedSharding for the copier cache (None for
    the unsharded pools)."""
    if sharding is None:
        return None
    from deepspeed_tpu.parallel.mesh import mesh_fingerprint

    return (str(sharding.spec), mesh_fingerprint(sharding.mesh))


def _copy_page_fn(k_pages, sharding=None):
    key = (k_pages.shape, str(k_pages.dtype), _sharding_key(sharding))
    fn = _copy_page_cache.get(key)
    if fn is None:
        kwargs = {}
        if sharding is not None:
            # pin the outputs to the pool's kv-head sharding so the donated
            # inputs alias shard-for-shard (an unconstrained output could
            # legally come back resharded, silently breaking the alias)
            kwargs["out_shardings"] = (sharding, sharding)
        fn = jax.jit(_copy_page, donate_argnums=(0, 1), **kwargs)
        _copy_page_cache[key] = fn
    return fn


class PagedKVCache(NamedTuple):
    """Device page pool, one stacked array per K and V.

    Layout ``[L, num_pages, NKV, page_size, D]``, ``L`` the model's CACHE
    layers (``models/config.py::cache_layers``: a layer of weights owns one in
    every pass of a looped stack, ``num_loops * num_layers``, pass ``t``'s
    layer ``l`` at ``t * num_layers + l``; every configuration with one pass
    has as many as layers of weights): the serving step reaches a layer
    through its index, and each layer slice is exactly the ``[NP, NKV, P, D]``
    pool the paged attention kernels take. Where ``heads_per_group`` is ``f > 1`` the layout is
    ``[L, num_pages, NKV / f, page_size, f * D]``: KV head ``j`` lives in group
    ``j // f`` at lanes ``(j % f) * D ..``, which the attention entry sees from
    the shapes (``ops/transformer/paged_attention.py``); a page's bytes, the
    axis a mesh shards and everything that moves whole pages are what they were.
    """

    k_pages: jax.Array
    v_pages: jax.Array
    heads_per_group: int = 1

    @property
    def num_pages(self) -> int:
        return self.k_pages.shape[1]

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[3]

    @property
    def bytes_per_token(self) -> int:
        """HBM bytes one cached token costs across all layers (K + V)."""
        L, _, NKV, _, D = self.k_pages.shape
        return L * NKV * (D + self.v_pages.shape[-1]) * self.k_pages.dtype.itemsize

    def hbm_bytes(self) -> int:
        return self.k_pages.nbytes + self.v_pages.nbytes


def init_paged_cache(
    cfg: TransformerConfig, num_pages: int, page_size: int, dtype=None,
    sharding=None,
) -> PagedKVCache:
    """Allocate the device page pools ``[L, num_pages, NKV, page_size, D]``,
    ``L = cache_layers(cfg)``: a looped model's ``num_loops * num_layers``, a
    multi-kind model's softmax layers (none, zero-sized arrays, where every
    paged layer is a latent one), ``num_layers`` otherwise. ``sharding``
    (tensor-parallel serving) places them kv-head-sharded across the mesh —
    the page CONTENTS shard on axis 2 while the host-side tables stay
    replicated, so per-chip KV HBM is ``hbm_bytes() / tp``."""
    if dtype is None:
        dtype = _DTYPES[cfg.dtype]
    layers = cache_layers(cfg)
    v_head_dim = getattr(cfg, "v_head_dim", None) or cfg.head_dim
    # the heads a shard holds decide: a group never spans two chips
    tp = 1 if sharding is None else sharding.mesh.shape[sharding.spec[2]]
    f = heads_per_group(cfg.head_dim, v_head_dim, cfg.num_kv_heads // tp)
    k_shape, v_shape = page_shapes(layers, num_pages, cfg.num_kv_heads, page_size, cfg.head_dim, v_head_dim, f)
    if sharding is not None:
        # allocate DIRECTLY sharded: a full-size zeros + device_put would
        # transiently commit the whole pool to one chip — tp× the
        # steady-state per-chip footprint, an OOM at bring-up on exactly
        # the pools sized against aggregate mesh HBM
        zeros = jax.jit(
            lambda: (jnp.zeros(k_shape, dtype), jnp.zeros(v_shape, dtype)),
            out_shardings=(sharding, sharding),
        )
        k, v = zeros()
    else:
        k, v = jnp.zeros(k_shape, dtype), jnp.zeros(v_shape, dtype)
    return PagedKVCache(k_pages=k, v_pages=v, heads_per_group=f)


def key_lanes(head_dim: int) -> int:
    """A key head's width in a page: the head's own up to one lane tile, whole
    lane tiles beyond (192 is stored at 256). A page that is no whole number
    of 128-lane tiles is one the ragged kernel cannot fetch by DMA
    (``ops/transformer/decode_attention.py``). The pad lanes hold zeros and
    q's are zeros, so every product is what it was. A head NARROWER than a
    lane tile is not padded (that would double the pool): it shares the tile
    with its neighbours (``heads_per_group``)."""
    return head_dim if head_dim <= 128 else -(-head_dim // 128) * 128


def heads_per_group(head_dim: int, v_head_dim: int, kv_heads: int) -> int:
    """KV heads that lie side by side on the lanes of one page: ``f = 128 //
    head_dim`` where keys and values are equally wide, narrower than a lane
    tile and divide it, and the ``kv_heads`` a shard holds are whole groups of
    ``f``; 1 otherwise (today's pool, and the grid kernel of
    ``decode_attention._ragged_by_grid``). A page ``[P, 64]`` is half a lane
    tile: the device does NOT pad it, it gives such a pool the layout with the
    page index on the lanes, and a program that hands the pool to a kernel
    transposes it whole at entry and back at exit (both of granite's, 1.61 GB
    a step: ``PERF.md`` section 6, PR 53). Two heads of 64 to a page make it
    ``[P, 128]``, whole tiles, row-major by itself, at the same bytes."""
    if head_dim != v_head_dim or head_dim >= 128 or 128 % head_dim:
        return 1
    f = 128 // head_dim
    return f if kv_heads % f == 0 else 1


def page_shapes(layers: int, num_pages: int, kv_heads: int, page_size: int, head_dim: int, v_head_dim: int, f: int):
    """The key and the value pool of ``kv_heads`` heads, ``f`` to a group."""
    k_shape = (layers, num_pages, kv_heads // f, page_size, f * key_lanes(head_dim))
    return k_shape, k_shape[:-1] + (f * v_head_dim,)


def window_ring_pages(window: int, page_size: int, prefill_chunk: int) -> int:
    """Pages a slot's ring of a window layer holds: the pages a step writes
    (a prefill chunk's, which start on page boundaries because chunks start
    on the chunk grid: ``scheduler.py::_next_chunk_len``) and the pages that
    hold the ``window - 1`` keys before them. A chunk that is no whole number
    of pages may start inside a page, and its walk then spans one more."""
    aligned = prefill_chunk % page_size == 0
    return -(-prefill_chunk // page_size) + -(-(window - 1) // page_size) + (0 if aligned else 1)


class StateStore(NamedTuple):
    """The second kind of cache: what a model's layers keep of a row whatever
    its length, one entry a SLOT (the pool's slots are the index), donated
    into every serving program beside the pages and returned in place.
    Nothing here is allocated or freed.

    * recurrent-state layers (delta-rule linear attention OR Mamba-2
      state-space layers: a model names one kind at most): a state and a
      convolution tail, whose shapes are the KIND's
      (``hybrid_decode.state_shapes``: a linear layer's square state a head
      and three convolved streams, a state-space layer's ``[heads, head_dim,
      state]`` and one stream of ``[x ; B ; C]`` a lane tile a row); entry
      ``max_slots`` belongs to nobody and takes dead rows' writes. A slot's
      entry is restarted from zero by the program when a row's window begins
      at position 0 (``inference/hybrid_decode.py``).
    * gated short-convolution layers (``conv``, in place of those two kinds):
      a convolution tail ALONE, the last ``K - 1`` gated products of a row, a
      lane tile a row as a state-space layer's; ``state`` is then ``None``
      (no array, no parameter of any program) and what this store, the
      pool's ``cache_bytes`` and its ``memory_report`` call a slot's "state"
      is the tails.
    * sliding-window layers: pools of their own shape in which slot ``s`` owns
      pages ``1 + s * ring ..`` (page 0 is the trash page, as in the page pool)
      as a RING: position ``p`` of its row lives in ring page ``(p // P) %
      ring``, whatever the row's length, ``ring`` being
      ``window_ring_pages``. What a page held a lap ago lies outside the
      window or past the row's length, and is masked. ``None`` for a model
      with no such layer.

    And, beside those, the one array here that IS under the page table:

    * latent-attention layers: ``latent`` holds ``[c_kv ; k_rope]`` of a
      token ONCE (no value array: the value is the entry's leading
      ``kv_lora_rank`` lanes), in pages with the page ids of ``PagedKVCache``'s
      (the pool's free list, tables and trash page 0 serve both), an entry at
      ``key_lanes`` of its width. It rides here because the serving step
      takes and returns this store in place; ``None`` for a model with no
      such layer. A ``sparse_latent`` layer's entries lie here too, and
      ``index`` holds its indexer's key of the same token (``index_head_dim``
      numbers) under the same page ids: the two are written together, and a
      step reads a row's live indexer keys and then only the CHOSEN latents.
    * ``window_latent`` layers: ``window_latent`` is a ring a slot like
      ``window_k``'s, whose entries are latents ``[c_kv ; k_rope]`` of the
      kind's own rank (no value array, no heads); a model has rings of one
      of the two kinds.

    A model may have state layers AND latent layers and no layer with keys
    and values a head: the pool's K and V arrays are then empty, a page id
    holds a token's entry in every latent layer while every state layer holds
    a slot's state, and ``PagePool.cache_bytes`` says which of the two the
    live rows' bytes are in."""

    state: Optional[jax.Array]  # [state layers, max_slots + 1, NH, Dk, Dv] float32 (ssm: [.., NH, P, N]); None: the kind keeps a tail alone
    conv: jax.Array  # [state layers, max_slots + 1, K - 1, 3, NH, D] (ssm and conv: [.., K - 1, tail_rows(C), 128])
    window_k: Optional[jax.Array] = None  # [window layers, 1 + max_slots * ring, NKV, P, Dk] (narrow heads: NKV / f, P, f Dk)
    window_v: Optional[jax.Array] = None
    latent: Optional[jax.Array] = None  # [latent (or sparse_latent) layers, num_pages, P, lanes]
    index: Optional[jax.Array] = None  # [sparse_latent layers, num_pages, P, index_head_dim]: the indexer's keys, beside the latents they index
    window_latent: Optional[jax.Array] = None  # [window_latent layers, 1 + max_slots * ring, P, lanes]: rings of latents

    def window_bytes(self) -> int:
        return sum(a.nbytes for a in (self.window_k, self.window_v, self.window_latent) if a is not None)

    def latent_bytes(self) -> int:
        """The bytes under the page table beside K and V: the latents and, of a sparse layer, the indexer's keys."""
        return sum(a.nbytes for a in (self.latent, self.index) if a is not None)

    def state_bytes(self) -> int:
        """The per-slot entries' bytes: the states, where the kind keeps one, and the convolution tails."""
        return (0 if self.state is None else self.state.nbytes) + self.conv.nbytes

    def hbm_bytes(self) -> int:
        return self.state_bytes() + self.window_bytes() + self.latent_bytes()


def _refuse_with_state(states, what: str) -> None:
    if states is not None and states.latent is not None:
        raise NotImplementedError(
            f"{what} is not supported for a model with latent-attention layers: the pool copies, shares and rolls "
            "back pages of the K and V arrays (kv_pool._copy_page, the prefix index), and a latent layer's pages are a "
            "third array (StateStore.latent; a sparse_latent layer's indexer keys a fourth, StateStore.index) that none "
            "of them knows yet"
        )
    rings = states is not None and (states.window_k is not None or states.window_latent is not None)
    if states is not None and (states.conv.size or rings):  # every state kind keeps a tail
        raise NotImplementedError(
            f"{what} is not supported for a model with recurrent-state or sliding-window layers: keys and values "
            "of a full-attention layer can be shared, copied or rolled back a page at a time; the recurrent state "
            "of a row (or a convolution's tail, where a layer keeps that alone) cannot without a snapshot of it at "
            "that position, nor can a window layer's page ring (of keys and values a head, or of a window_latent "
            "layer's latents), which holds a row's newest positions only, and the per-slot store keeps neither"
        )


class PagePool:
    """Host-side page allocator over a ``PagedKVCache``.

    A *slot* is one concurrently-running sequence (a row of the serving
    batch); each slot owns a page-table row of ``max_pages_per_slot``
    entries. ``seq_lens[slot]`` counts tokens already written. Sequences
    acquire pages lazily as they grow and release them on ``free_slot`` —
    total cache HBM is fixed at ``num_pages``, but the *live* footprint is
    ``used_pages × page_size × bytes_per_token``. Pages are refcounted:
    prefix sharing lets one page appear in many tables, and a page only
    becomes reclaimable when its last reference drops.

    Every mutation of the page tables, free list, refcounts, or prefix
    index goes through the pool's own methods — lint DS-R007 flags outside
    writes, because a bypassed write barrier corrupts the CoW/refcount
    invariants silently.
    """

    def __init__(
        self,
        cfg: TransformerConfig,
        num_pages: int,
        page_size: int,
        max_slots: int,
        max_seq_len: Optional[int] = None,
        dtype=None,
        kv_sharding=None,
        prefill_chunk: Optional[int] = None,
    ):
        if page_size < 1 or num_pages < 2:
            raise ValueError("need page_size >= 1 and num_pages >= 2 (page 0 is reserved)")
        self.page_size = int(page_size)
        self.max_slots = int(max_slots)
        self.weight_layers = int(cfg.num_layers)  # the memory report says them apart from the cache layers
        self.max_seq_len = int(max_seq_len or cfg.max_seq_len)
        self.max_pages_per_slot = -(-self.max_seq_len // self.page_size)
        # tensor-parallel serving: the page contents shard over the kv-head
        # axis; every host-side structure below (tables, free lists,
        # refcounts, prefix index) is replicated logic and never changes
        self.kv_sharding = kv_sharding
        self.cache = init_paged_cache(
            cfg, num_pages, page_size, dtype=dtype, sharding=kv_sharding
        )
        # a model with layers of more than one kind: the per-slot store of its
        # recurrent-state layers, sized by max_slots (no such layer: empty arrays,
        # the hybrid step's arguments all the same), and the page rings of its
        # sliding-window layers, sized by max_slots, the window and the chunk
        self.states: Optional[StateStore] = None
        self.window_ring = 0
        self.window_keys = 0  # keys a window layer's query sees
        self.window_kv_heads = 0  # a window layer's KV heads (its ring's axis 2 holds them in groups)
        self.query_heads: dict = {}  # a layer kind's query heads, for the memory report
        self.state_kind: Optional[str] = None  # the kind whose layers' states the store holds: linear | ssm | conv (tails alone)
        if getattr(cfg, "layer_types", None):
            from deepspeed_tpu.inference.hybrid_decode import paged_latent_shapes, state_shapes, window_latent_shape, window_shapes

            shapes = state_shapes(cfg, self.max_slots)
            self.state_kind = cfg.state_kind
            kv_dtype = self.cache.k_pages.dtype
            ring_kind = "window_latent" if cfg.layers_of("window_latent") else "window"
            self.query_heads = {"softmax": cfg.heads_of("softmax"), "window": cfg.heads_of(ring_kind)}
            rings, latent_ring = (None, None), None
            if cfg.layers_of(ring_kind):
                if not prefill_chunk:
                    raise ValueError("a model with sliding-window layers needs prefill_chunk to size its page rings")
                self.window_ring = window_ring_pages(cfg.window, self.page_size, int(prefill_chunk))
                self.window_keys = cfg.window
                if ring_kind == "window":
                    self.window_kv_heads = cfg.kv_heads_of("window")
                    rings = tuple(jnp.zeros(shape, kv_dtype) for shape in window_shapes(cfg, self.max_slots, self.page_size, self.window_ring))
                else:  # rings of latents: no heads, no value array
                    latent_ring = jnp.zeros(window_latent_shape(cfg, self.max_slots, self.page_size, self.window_ring), kv_dtype)
            # one entry a token under the pool's own page ids, no value array; a sparse layer's indexer key beside it
            latent, index = (None if shape is None else jnp.zeros(shape, kv_dtype) for shape in paged_latent_shapes(cfg, num_pages, self.page_size))
            state = None if shapes.state is None else jnp.zeros(shapes.state, jnp.float32)
            self.states = StateStore(state, jnp.zeros(shapes.conv, kv_dtype), *rings, latent, index, latent_ring)
        # LIFO free list keeps hot pages hot; page 0 stays out of circulation
        self._free = list(range(num_pages - 1, TRASH_PAGE, -1))
        self._free_slots = list(range(max_slots - 1, -1, -1))
        self.page_table = np.full((max_slots, self.max_pages_per_slot), -1, np.int32)
        self.seq_lens = np.zeros(max_slots, np.int32)
        self._owned = np.zeros(max_slots, np.int32)  # pages held per slot
        # --- prefix sharing state ---------------------------------------
        self._refcount = np.zeros(num_pages, np.int32)  # table refs per page
        self._hash_index: dict = {}  # chain key -> page id (full-page content)
        self._page_hash: dict = {}  # page id -> chain key (reverse map)
        self._cached: "OrderedDict[int, None]" = OrderedDict()  # ref-0 indexed, LRU
        # per slot: chain key per leading full page whose content-chain is
        # known (published, or found already indexed under another page)
        self._chain_keys: List[List[int]] = [[] for _ in range(max_slots)]
        self.stats = {
            "prefix_lookups": 0,
            "prefix_query_tokens": 0,  # prompt tokens offered to match_prefix
            "prefix_hit_tokens": 0,  # tokens served by attaching cached pages
            "prefix_hit_pages": 0,
            "registered_pages": 0,
            "cow_copies": 0,
            "index_invalidations": 0,  # exclusive indexed pages rewritten
            "cache_evictions": 0,  # cold cached pages reclaimed for allocation
        }

    # --- capacity accounting -------------------------------------------
    @property
    def num_pages(self) -> int:
        return self.cache.num_pages

    def free_pages(self) -> int:
        """Reclaimable pages: truly free plus cached (refcount-0 prefix
        pages the allocator may evict on demand)."""
        return len(self._free) + len(self._cached)

    def used_pages(self) -> int:
        """Pages referenced by at least one live slot (trash page and
        cached-but-unreferenced prefix pages excluded)."""
        return self.num_pages - 1 - self.free_pages()

    def cached_pages(self) -> int:
        return len(self._cached)

    def pages_for(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.page_size)

    def live_tokens(self) -> int:
        return int(self.seq_lens.sum())

    @property
    def latent_bytes_per_token(self) -> int:
        """HBM bytes one cached token costs across the latent layers: one entry a layer, no value; in a sparse layer
        its indexer's key too."""
        if self.states is None:
            return 0
        return sum(a.shape[0] * a.shape[-1] * a.dtype.itemsize for a in (self.states.latent, self.states.index) if a is not None)

    @property
    def state_bytes_per_slot(self) -> int:
        """HBM bytes a slot's recurrent states and convolution tails (a conv layer's: the tails alone) take over the
        state layers, whatever its row's length."""
        if self.states is None:
            return 0
        return self.states.state_bytes() // (self.max_slots + 1)

    def cache_bytes(self) -> dict:
        """Which of the two caches the live rows' bytes are in: the slots in
        use times a slot's state (of a model whose state kind keeps a tail
        alone: its tails), and the pages in use times a page's latent
        entries. What ``serve.step`` carries; ``{}`` for a model with neither
        (a uniform one: its pages are ``live_hbm_bytes``)."""
        if self.states is None:
            return {}
        return {
            "state_bytes_in_use": (self.max_slots - len(self._free_slots)) * self.state_bytes_per_slot,
            "latent_bytes_in_use": self.used_pages() * self.page_size * self.latent_bytes_per_token,
        }

    def live_hbm_bytes(self) -> int:
        """HBM actually pinned by live sequences (page-granular)."""
        return self.used_pages() * self.page_size * (self.cache.bytes_per_token + self.latent_bytes_per_token)

    def memory_report(self) -> dict:
        """Residency accounting for the analysis HBM ledger, read from the
        live page arrays' shards (where the bytes are, not where a sharding
        was declared to put them): total device bytes of the page pools,
        the largest per-chip share (``total / tp`` under the kv-head
        sharding — the tensor-parallel serving contract), and the host-side
        scheduling structures (page table, sequence lengths, refcounts,
        ownership) that stay replicated host RAM, never HBM."""
        per_device: dict = {}
        for pages in (self.cache.k_pages, self.cache.v_pages):
            for shard in pages.addressable_shards:
                per_device[shard.device] = per_device.get(shard.device, 0) + shard.data.nbytes
        state = {}
        if self.states is not None:
            in_use = self.max_slots - len(self._free_slots)
            cache = self.cache_bytes()
            state = {
                # the paged (full or latent) layers' query heads; a window layer's are with its ring's entries
                "paged_query_heads": self.query_heads["softmax"],
                "state_kind": self.state_kind,  # None: no recurrent-state layer (empty arrays)
                # a slot's state in one layer, float32; a kind that keeps a tail alone: none, and the bytes below are the tails'
                "state_shape": [] if self.states.state is None else list(self.states.state.shape[2:]),
                "tail_shape": list(self.states.conv.shape[2:]),  # a slot's convolution tail in one layer, the activations' type
                "state_layers": self.states.conv.shape[0],
                "state_total_bytes": self.states.state_bytes(),
                "state_bytes_per_slot": self.state_bytes_per_slot,
                "state_bytes_in_use": cache["state_bytes_in_use"],
                "state_slots": self.max_slots,
                "state_slots_in_use": in_use,
            }
            ring = self.states.window_k if self.states.window_latent is None else self.states.window_latent
            if ring is not None:
                # the same for a row of any length: ring pages a slot, not pages a token
                state.update(
                    window_total_bytes=self.states.window_bytes(),
                    window_bytes_per_slot=self.states.window_bytes() // (1 + self.max_slots * self.window_ring) * self.window_ring,
                    window_ring_pages=self.window_ring,
                    window_keys=self.window_keys,
                    window_layers=ring.shape[0],
                    window_query_heads=self.query_heads["window"],
                    window_kv_heads=self.window_kv_heads,
                    window_slots=self.max_slots,
                    window_slots_in_use=in_use,
                )
                if self.states.window_latent is not None:
                    state.update(window_latent_lanes=ring.shape[-1])  # a ring entry is a latent at whole lane tiles, no head
            if self.states.latent is not None:
                latent = self.states.latent
                state.update(
                    latent_total_bytes=self.states.latent_bytes(),
                    latent_bytes_per_token=self.latent_bytes_per_token,
                    latent_layers=latent.shape[0],
                    latent_lanes=latent.shape[-1],  # an entry's stored width (its own at whole lane tiles)
                    latent_live_bytes=cache["latent_bytes_in_use"],
                )
                if self.states.index is not None:  # a sparse layer's indexer keys, counted in the per-token and live bytes above
                    state.update(index_total_bytes=self.states.index.nbytes, index_lanes=self.states.index.shape[-1])
        return {
            **state,
            "kv_total_bytes": self.cache.hbm_bytes(),
            # a layer of weights owns a cache layer in every pass of a looped stack
            "cache_layers": self.cache.k_pages.shape[0],
            "weight_layers": self.weight_layers,
            "kv_bytes_per_token": self.cache.bytes_per_token,
            "kv_bytes_per_chip": max(per_device.values()),
            "kv_devices": len(per_device),
            "live_kv_bytes": self.live_hbm_bytes(),
            "page_table_location": "host",
            "host_table_bytes": int(
                self.page_table.nbytes
                + self.seq_lens.nbytes
                + self._refcount.nbytes
                + self._owned.nbytes
            ),
        }

    def utilization(self) -> float:
        """Live tokens over allocated page capacity (1.0 = no page waste;
        prefix sharing can push it past 1.0 — N sequences reading one
        page's tokens count N times against a single allocation)."""
        cap = self.used_pages() * self.page_size
        return self.live_tokens() / cap if cap else 0.0

    def set_cache(self, new_k: jax.Array, new_v: jax.Array) -> None:
        """Install the page arrays a serving program returned (the donated
        buffers aliased in place). The one sanctioned external write."""
        self.cache = self.cache._replace(k_pages=new_k, v_pages=new_v)

    def set_states(self, states: StateStore) -> None:
        """Same, for the state store's buffers."""
        self.states = states

    # --- page acquisition / release -------------------------------------
    def _acquire_page(self) -> Optional[int]:
        """One page off the free list, or — when it is dry — the coldest
        cached prefix page, dropped from the index first."""
        if self._free:
            return self._free.pop()
        if self._cached:
            page, _ = self._cached.popitem(last=False)  # oldest first
            self._drop_index(int(page))
            self.stats["cache_evictions"] += 1
            return int(page)
        return None

    def _release_page(self, page: int) -> None:
        """Last reference dropped: indexed pages park on the cached LRU
        (the prefix outlives its author), the rest return to the free list."""
        if page in self._page_hash:
            self._cached[page] = None  # newest end of the LRU
        else:
            self._free.append(page)

    def _drop_index(self, page: int) -> None:
        key = self._page_hash.pop(page, None)
        if key is not None and self._hash_index.get(key) == page:
            del self._hash_index[key]

    # --- prefix index ----------------------------------------------------
    def _block_key(self, chain: int, block: np.ndarray) -> int:
        return hash((chain, np.ascontiguousarray(block, np.int32).tobytes()))

    def match_prefix(self, tokens) -> List[Tuple[int, int]]:
        """Longest indexed full-page prefix of ``tokens`` as
        ``[(page_id, chain_key), ...]``. Capped at ``len(tokens) - 1``
        tokens: at least one prompt token is always left to prefill, so the
        request's first output token has logits to come from."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        P = self.page_size
        max_blocks = min(max(tokens.size - 1, 0) // P, self.max_pages_per_slot)
        out: List[Tuple[int, int]] = []
        chain = _ROOT_CHAIN
        for b in range(max_blocks):
            key = self._block_key(chain, tokens[b * P : (b + 1) * P])
            page = self._hash_index.get(key)
            if page is None:
                break
            out.append((int(page), key))
            chain = key
        return out

    def register_prefix(self, slot: int, tokens, upto: Optional[int] = None) -> int:
        """Publish ``slot``'s leading full pages into the prefix index so
        later requests can attach them. ``tokens`` is the slot's canonical
        context (prompt + accepted tokens); pages holding ``tokens[:upto]``
        (default: the slot's live length) are hashed block-by-block chained
        on the prefix. Incremental — pages already chained are skipped, so
        the per-step cost is one hash per newly-FILLED page. Returns the
        number of full pages chained. When a block's content is already
        indexed under another page, the existing entry wins (first writer)
        and this slot's page stays private."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        live = int(self.seq_lens[slot])
        upto = live if upto is None else min(int(upto), live, tokens.size)
        P = self.page_size
        n_full = upto // P
        chain_list = self._chain_keys[slot]
        chain = chain_list[-1] if chain_list else _ROOT_CHAIN
        i = len(chain_list)
        while i < n_full:
            key = self._block_key(chain, tokens[i * P : (i + 1) * P])
            page = int(self.page_table[slot, i])
            if key not in self._hash_index and page not in self._page_hash:
                self._hash_index[key] = page
                self._page_hash[page] = key
                self.stats["registered_pages"] += 1
            chain_list.append(key)
            chain = key
            i += 1
        return n_full

    def prefix_stats(self) -> dict:
        """Counters + derived prefix observability for ``serve_stats()``:
        ``prefix_hit_rate`` = fraction of looked-up prompt tokens served by
        attaching already-cached pages."""
        s = dict(self.stats)
        s["indexed_pages"] = len(self._page_hash)
        s["cached_pages"] = len(self._cached)
        q = s["prefix_query_tokens"]
        s["prefix_hit_rate"] = s["prefix_hit_tokens"] / q if q else 0.0
        return s

    # --- slot lifecycle -------------------------------------------------
    def can_admit(self, n_tokens: int) -> bool:
        """A free slot exists and the pool can hold ``n_tokens`` now
        (before any prefix credit — attaching cached pages only helps)."""
        return (
            bool(self._free_slots)
            and n_tokens <= self.max_seq_len
            and self.pages_for(n_tokens) <= self.free_pages()
        )

    def alloc_slot(self, n_tokens: int = 0, prefix_tokens=None) -> Optional[int]:
        """Claim a slot, pre-reserving pages for ``n_tokens``; None if the
        pool cannot host it right now (caller keeps the request queued).

        With ``prefix_tokens`` (the request's context) the longest indexed
        full-page prefix is ATTACHED first: the shared pages enter the page
        table with their refcount raised, ``seq_lens[slot]`` starts at the
        attached length, and only the remainder draws fresh pages — N
        requests sharing a system prompt allocate (and prefill) its KV
        exactly once."""
        if not self._free_slots:
            return None
        want = max(int(n_tokens), 1)
        if want > self.max_seq_len:
            return None
        matched: List[Tuple[int, int]] = []
        if prefix_tokens is not None:
            _refuse_with_state(self.states, "attaching a cached prefix")
            matched = self.match_prefix(prefix_tokens)
        # attached cached pages leave the reclaimable set, so discount them
        fresh = self.pages_for(want) - len(matched)
        avail = self.free_pages() - sum(1 for p, _ in matched if p in self._cached)
        if fresh > avail:
            return None
        slot = self._free_slots.pop()
        if prefix_tokens is not None:
            # counted only on successful admission: a stalled request retried
            # every step must not dilute the reported hit rate
            self.stats["prefix_lookups"] += 1
            self.stats["prefix_query_tokens"] += int(
                np.asarray(prefix_tokens).reshape(-1).size
            )
        self.seq_lens[slot] = 0
        self._chain_keys[slot] = []
        for i, (page, key) in enumerate(matched):
            self.page_table[slot, i] = page
            if self._refcount[page] == 0:
                self._cached.pop(page, None)
            self._refcount[page] += 1
            self._owned[slot] += 1
            self._chain_keys[slot].append(key)
        if matched:
            self.seq_lens[slot] = len(matched) * self.page_size
            self.stats["prefix_hit_pages"] += len(matched)
            self.stats["prefix_hit_tokens"] += len(matched) * self.page_size
        if n_tokens and not self.ensure(slot, n_tokens):
            self.free_slot(slot)
            return None
        return slot

    def ensure(self, slot: int, new_len: int) -> bool:
        """Grow ``slot``'s table to cover ``new_len`` tokens. All-or-nothing:
        on a pool-exhausted failure nothing is allocated (the caller decides
        whom to preempt and retries). Cold cached prefix pages are evicted
        (oldest first) when the free list alone cannot cover the growth."""
        if new_len > self.max_seq_len:
            return False
        need = self.pages_for(new_len) - self._owned[slot]
        if need <= 0:
            return True
        if need > self.free_pages():
            return False
        for _ in range(int(need)):
            page = self._acquire_page()
            self.page_table[slot, self._owned[slot]] = page
            self._refcount[page] = 1
            self._owned[slot] += 1
        return True

    def prepare_write(self, slot: int, new_len: int) -> bool:
        """Write barrier: make positions ``[seq_lens[slot], new_len)``
        writable, then guarantee every page in that span is EXCLUSIVE and
        UNINDEXED. Shared pages (refcount > 1 — a prefix some other
        sequence still reads) are replaced by private copy-on-write
        duplicates; exclusively-owned pages still in the index are dropped
        from it (an indexed page's content is immutable, and it is about
        to change). All-or-nothing like ``ensure``: False means nothing
        was allocated or copied and the caller should preempt and retry.
        Serving schedulers must call this (not bare ``ensure``) before
        every dispatch that writes KV."""
        cur = int(self.seq_lens[slot])
        if new_len > self.max_seq_len:
            return False
        if new_len <= cur:
            return True
        P = self.page_size
        first = cur // P
        last_w = (new_len - 1) // P
        owned = int(self._owned[slot])
        span = range(first, min(last_w + 1, owned))
        shared = [
            i for i in span if self._refcount[self.page_table[slot, i]] > 1
        ]
        grow = max(self.pages_for(new_len) - owned, 0)
        if grow + len(shared) > self.free_pages():
            return False
        if not self.ensure(slot, new_len):
            return False
        for i in shared:
            src = int(self.page_table[slot, i])
            dst = self._acquire_page()
            # one donated in-place page copy per divergence event — never
            # per step, and never a rebuild of the whole cache
            copy = _copy_page_fn(self.cache.k_pages, self.kv_sharding)
            new_k, new_v = copy(
                self.cache.k_pages, self.cache.v_pages,
                jnp.int32(src), jnp.int32(dst),
            )
            self.cache = self.cache._replace(k_pages=new_k, v_pages=new_v)
            self.page_table[slot, i] = dst
            self._refcount[dst] = 1
            self._refcount[src] -= 1
            if self._refcount[src] == 0:
                self._release_page(src)
            self.stats["cow_copies"] += 1
        for i in span:
            page = int(self.page_table[slot, i])
            if page in self._page_hash:
                self._drop_index(page)
                self.stats["index_invalidations"] += 1
        # pages from the first written one on are no longer a published
        # prefix of this slot
        if first < len(self._chain_keys[slot]):
            del self._chain_keys[slot][first:]
        return True

    def can_write(self, slots: Sequence[int], n_tokens: Sequence[int]) -> bool:
        """Whether ``prepare_write(slot, seq_lens[slot] + n)`` would succeed
        for every ``(slot, n)`` in turn with the pages reclaimable now, from
        counts alone and with nothing changed: each slot's page growth plus a
        copy-on-write page for every shared page in its written span. Never
        True where a ``prepare_write`` would fail (a copy may hand its source
        back, which is not counted): the scheduler asks before it packs a
        step behind one still in flight, where nobody can be preempted."""
        idx = np.asarray(slots, np.int32)
        cur = self.seq_lens[idx]
        new = cur + np.asarray(n_tokens, np.int32)
        if (new > self.max_seq_len).any():
            return False
        P = self.page_size
        pages = int(np.maximum(-(-new // P) - self._owned[idx], 0).sum())
        if self.stats["prefix_hit_pages"]:  # only an attached prefix is ever shared
            for slot, first, last in zip(idx, cur // P, np.minimum((new - 1) // P, self._owned[idx] - 1)):
                pages += int((self._refcount[self.page_table[slot, first : last + 1]] > 1).sum())
        return pages <= self.free_pages()

    def advance(self, slot: int, n_tokens: int) -> None:
        """Record ``n_tokens`` newly written to ``slot`` (pages must already
        be ensured)."""
        new_len = int(self.seq_lens[slot]) + int(n_tokens)
        assert self.pages_for(new_len) <= self._owned[slot], (
            f"slot {slot}: advancing to {new_len} tokens past its "
            f"{int(self._owned[slot])} allocated pages"
        )
        self.seq_lens[slot] = new_len

    def rollback(self, slot: int, n_tokens: int) -> int:
        """Un-write the last ``n_tokens`` of ``slot`` — speculative decode's
        rejected draft tail: shrink the live length and release every page
        past the new length (refcount-aware: a still-shared page survives
        for its other readers; an exclusive indexed page parks on the
        cached LRU; the rest return to the free list LIFO, so tail pages
        are the first reused). The data in the rolled-back region is NOT
        cleared — the length mask makes it invisible, and the next write at
        those positions overwrites it (through the write barrier). Returns
        how many pages this slot released."""
        n_tokens = int(n_tokens)
        if n_tokens:
            _refuse_with_state(self.states, "rolling back written tokens (speculative decode's rejected tail)")
        new_len = int(self.seq_lens[slot]) - n_tokens
        if n_tokens < 0 or new_len < 0:
            raise ValueError(
                f"rollback({slot}, {n_tokens}): slot holds "
                f"{int(self.seq_lens[slot])} tokens"
            )
        self.seq_lens[slot] = new_len
        keep = self.pages_for(new_len)
        freed = 0
        while self._owned[slot] > keep:
            self._owned[slot] -= 1
            i = int(self._owned[slot])
            page = int(self.page_table[slot, i])
            self.page_table[slot, i] = -1
            self._refcount[page] -= 1
            if self._refcount[page] == 0:
                self._release_page(page)
            freed += 1
        del self._chain_keys[slot][min(len(self._chain_keys[slot]), keep):]
        return freed

    def free_slot(self, slot: int) -> int:
        """Release the slot and drop its page references (pages whose last
        reference this was go back to the pool — or to the cached LRU when
        they still serve the prefix index); returns how many pages the slot
        held."""
        n = int(self._owned[slot])
        for i in range(n):
            page = int(self.page_table[slot, i])
            self._refcount[page] -= 1
            if self._refcount[page] == 0:
                self._release_page(page)
        self.page_table[slot, :] = -1
        self.seq_lens[slot] = 0
        self._owned[slot] = 0
        self._chain_keys[slot] = []
        self._free_slots.append(slot)
        return n

    # --- maintenance ----------------------------------------------------
    def integrity_check(self) -> None:
        """Verify the pool partition invariant: every allocatable page is
        exactly one of {free, cached, referenced}, refcounts equal the
        number of table references, cached pages are indexed, and every
        slot's live length fits its owned pages. Raises ``RuntimeError``
        naming the first violation. Used after crash recovery (the rebuilt
        pool must be internally consistent before serving resumes) and by
        the randomized soak tests."""
        refs: dict = {}
        for s in range(self.max_slots):
            owned = int(self._owned[s])
            if self.pages_for(int(self.seq_lens[s])) > owned:
                raise RuntimeError(
                    f"pool integrity: slot {s} holds {int(self.seq_lens[s])} "
                    f"tokens but only {owned} pages"
                )
            for i in range(owned):
                p = int(self.page_table[s, i])
                if p <= TRASH_PAGE or p >= self.num_pages:
                    raise RuntimeError(
                        f"pool integrity: slot {s} table entry {i} is {p}"
                    )
                refs[p] = refs.get(p, 0) + 1
        free = set(self._free)
        cached = set(int(p) for p in self._cached)
        referenced = set(refs)
        for name_a, set_a, name_b, set_b in (
            ("free", free, "cached", cached),
            ("free", free, "referenced", referenced),
            ("cached", cached, "referenced", referenced),
        ):
            overlap = set_a & set_b
            if overlap:
                raise RuntimeError(
                    f"pool integrity: page {min(overlap)} is both {name_a} "
                    f"and {name_b}"
                )
        allocatable = set(range(TRASH_PAGE + 1, self.num_pages))
        missing = allocatable - free - cached - referenced
        if missing:
            raise RuntimeError(f"pool integrity: page {min(missing)} leaked")
        for p, n in refs.items():
            if int(self._refcount[p]) != n:
                raise RuntimeError(
                    f"pool integrity: page {p} refcount {int(self._refcount[p])} "
                    f"but {n} table reference(s)"
                )
        for p in cached:
            if p not in self._page_hash:
                raise RuntimeError(
                    f"pool integrity: cached page {p} is not in the prefix index"
                )

    def defrag(self) -> int:
        """Compact live pages into the lowest ids (one device gather per
        K/V), rewriting tables, refcounts, and the prefix index, and
        rebuilding the free list. Live = referenced by any slot OR parked
        on the cached LRU (their bytes still serve future prefix matches).
        Shared pages move once and every referencing table row follows.
        Returns the number of pages that moved."""
        live: List[int] = []
        seen = set()
        for s in range(self.max_slots):
            for i in range(int(self._owned[s])):
                p = int(self.page_table[s, i])
                if p not in seen:
                    seen.add(p)
                    live.append(p)
        for p in self._cached:  # refcount 0: never in a table
            live.append(int(p))
        perm = np.arange(self.num_pages, dtype=np.int32)  # new_id -> old_id
        remap = {}  # old_id -> new_id
        nxt = TRASH_PAGE + 1
        for old in live:
            remap[old] = nxt
            perm[nxt] = old
            nxt += 1
        # unassigned tail: the remaining (free) pages in any order
        rest = [p for p in range(TRASH_PAGE + 1, self.num_pages) if p not in remap]
        perm[nxt:] = np.asarray(rest, np.int32)
        moves = sum(1 for old, new in remap.items() if old != new)
        if moves == 0:
            return 0
        gather = jnp.asarray(perm)
        self.cache = self.cache._replace(
            k_pages=self.cache.k_pages[:, gather],
            v_pages=self.cache.v_pages[:, gather],
        )
        if self.states is not None and self.states.latent is not None:
            self.states = self.states._replace(latent=self.states.latent[:, gather])
            if self.states.index is not None:
                self.states = self.states._replace(index=self.states.index[:, gather])
        for s in range(self.max_slots):
            for i in range(int(self._owned[s])):
                self.page_table[s, i] = remap[int(self.page_table[s, i])]
        new_rc = np.zeros_like(self._refcount)
        for old, new in remap.items():
            new_rc[new] = self._refcount[old]
        self._refcount = new_rc
        self._page_hash = {remap[p]: k for p, k in self._page_hash.items()}
        self._hash_index = {k: remap[p] for k, p in self._hash_index.items()}
        self._cached = OrderedDict((remap[int(p)], None) for p in self._cached)
        self._free = list(range(self.num_pages - 1, nxt - 1, -1))
        return moves

    # --- dispatch views -------------------------------------------------
    def rows(self, slots) -> Tuple[np.ndarray, np.ndarray]:
        """(page_table_rows, seq_lens) for a list of slots, as the int32
        arrays a serving program takes. Padding to a bucket is the caller's
        job (``-1`` rows / length 0 are always safe: trash-page semantics)."""
        idx = np.asarray(slots, np.int32)
        return self.page_table[idx], self.seq_lens[idx]
