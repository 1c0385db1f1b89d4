"""Continuous-batching scheduler over the paged KV pool.

The dense decode path (``inference/decode.py:generate``) runs fixed-shape
lockstep batches: every sequence prefills together, decodes together, and
the whole batch holds its HBM until the longest row finishes. This module
replaces that with request-level scheduling (DeepSpeed-Inference / Orca /
vLLM style):

* requests are **admitted** whenever a slot and enough pages exist, and
  **evicted** the step they finish — cache HBM tracks live tokens;
* prompts prefill in fixed-size **chunks interleaved with decode steps**,
  so a long prompt never stalls tokens already streaming;
* when the pool runs dry the **youngest** running request is preempted
  (pages freed, request requeued); greedy decoding makes its recomputed
  continuation token-exact, so preemption is invisible in the output;
* with **speculative decoding** enabled, each round first asks a host-side
  ``Drafter`` (``inference/spec_decode.py``) for up to K plausible next
  tokens per running request, then verifies drafts + bonus token inside
  the step's ONE dispatch — the accepted prefix advances
  ``mean accepted + 1`` tokens per dispatch, the rejected tail's pages roll
  back to the free list, and greedy outputs stay byte-identical to
  speculation-off serving (the step argmax-compares in-program);
* with **prefix caching** enabled the pool's hash-of-block index
  (``inference/kv_pool.py``) is consulted at admission: the longest cached
  full-page prefix of the request's context attaches by reference (its KV
  pays nothing), prefill resumes after it realigned to the cold-prefill
  chunk grid (so every position is computed by the same (chunk, row)
  geometry — byte-identical streams), and each newly filled full page is
  published back to the index;
* every scheduler step is ONE dispatch of the unified
  ``build_ragged_step`` program: prefill chunks, pending
  decode tokens, and drafted verify rows pack into a single
  ``[max_slots, W]`` window whose per-row ``(kv_len, q_len)`` metadata
  ride in as arrays (Ragged Paged Attention, arXiv 2604.15464) — so
  chunked prefill COEXISTS with decoding instead of stealing steps,
  spec-K varies per request, and shifting the mix never retraces. Total
  compiled serving programs is ≤ 2 (the narrow decode/verify width plus
  the chunk-covering mixed width), and steady state is one dispatch per
  step — enforced by the serving tests via the engine's compile
  telemetry. Prefix sharing adds zero dispatches and zero programs:
  attach/register are host-side table and hash work;
* **the host works while the device does**: a call of ``step()`` admits,
  enqueues the step the call before it packed (n+1; packed again first if
  someone was just admitted, so a newcomer never waits a step for having
  come between two calls), then settles the step before that one (n, whose
  result that call waited for), packs the step after (n+2) behind the one
  now running, and ends with the wait for the device (``serve.fetch``). So
  what the host does for a step but the enqueue itself runs while the
  device is busy, the device is idle between two calls (whoever stops a
  profiler or reads a clock there cuts no execution), and the tokens a call
  emits are those of the step dispatched one call earlier. Everything the
  host can know from counts is committed when a step is dispatched (a decode
  row writes one position and a prefill row its chunk: ``pool.advance``,
  ``consumed``, the prompt's prefix pages; which rows still have budget; the
  width); everything a token's VALUE decides waits for the settle (the
  stream, EOS, the journal, the policy's stamps, ``_finish``). A row's next
  decode token never visits the host on its way: ``decode.build_token_feed``
  gathers it on the device from the unsettled result, and the operands the
  host makes are sent ahead at the pack. An EOS is therefore seen one step
  late: the row already rides in the next step, whose result for it is
  discarded (``overshoot_rows``) and whose settle releases its slot; the
  stream is what it always was. Whenever the host needs values before it can
  pack — a drafter armed, a reservation that would have to preempt, an
  entry point that reads or moves a request
  (``extract_request``, ``restore_request``, ``recover``,
  ``finalize_migration``, ``compact_journal``, ``settle``) — the unsettled
  step is settled first and what was packed behind it dropped
  (``drain_reasons``), and the call goes on as a synchronous server's
  would. No knob: the depth is 1 or 0 by what the server sees in its own
  state. ``has_work()`` counts an unsettled step and the last call of a run
  only settles, so ``run()`` / ``serve()`` return settled streams. A step's
  life thus spans three calls, and every span of it (``serve.pack``,
  ``serve.dispatch`` > ``serve.enqueue``, ``serve.fetch``, ``serve.emit`` >
  ``serve.settle``) carries the step's ``seq``; ``serve.pack`` also holds
  the step's own record, what it sends to the kernel (``mixed``,
  ``kv_tokens`` and ``row_lens``, the live rows' ``(q_len, kv_len)``: a
  step packed again keeps its ``seq``, so the last pack before the
  enqueue is the step the device ran); the histogram
  ``serve.turnaround_ms`` times the host between one step's wait and the
  next step's enqueue, the part of its work the device waits for;
* admission order and preemption victims are delegated to a
  ``SchedulingPolicy`` (default: FIFO admission, youngest-first
  preemption — the original behavior). ``inference/traffic.py`` layers
  SLA-aware multi-tenant scheduling on the same hooks.

``InferenceEngine.serve()`` (``inference/engine.py``) owns a ``PagedServer``
configured from the ``inference.paged_kv`` + ``inference.spec_decode`` (+
``inference.traffic``) knobs.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from deepspeed_tpu.inference.decode import (
    MOE_STAT_ROWS,
    _refuse_state_layers,
    build_ragged_step,
    build_token_feed,
    ragged_program_name,
    token_tiles,
)
from deepspeed_tpu.inference.journal import JournaledRequest, RequestJournal
from deepspeed_tpu.inference.kv_pool import PagePool
from deepspeed_tpu.inference.spec_decode import Drafter, NGramDrafter
from deepspeed_tpu.models.config import TransformerConfig
from deepspeed_tpu.profiling.tracer import (
    NULL_TRACER,
    MetricsRegistry,
    percentile_summary,
)
from deepspeed_tpu.utils import chaos


# bounds of ``serve.turnaround_ms``: tenths of a millisecond where a healthy
# host sits, coarser above
_TURNAROUND_BUCKETS_MS = (
    0.02, 0.05, 0.1, 0.15, 0.2, 0.3, 0.4, 0.5, 0.75, 1.0, 1.5, 2.0, 3.0, 5.0, 10.0, 25.0, 100.0,
)


def _spec_knob(spec, name, default):
    """Read a knob off a SpecDecodeConfig, a plain dict, or None."""
    if spec is None:
        return default
    if isinstance(spec, dict):
        return spec.get(name, default)
    return getattr(spec, name, default)


def _row_lens(q_lens: np.ndarray, kv_lens: np.ndarray) -> str:
    """A step's live rows as ``serve.pack`` records them (``row_lens``): one
    word a row in pack order, ``kv`` for a row of one query token and
    ``q:kv`` for any other, ``kv`` counting the row's keys once the step's
    own are written (what the attention kernel reads). A string, so that the
    flight recorder's JSON and the profiler's event stats hold it as it went
    in; a lone decode row keeps its ``1:``, because the profiler's stats give
    a string of digits alone back as a number."""
    text = " ".join(str(kv) if q == 1 else f"{q}:{kv}" for q, kv in zip(q_lens.tolist(), kv_lens.tolist()))
    return "1:" + text if text.isdigit() else text


def compiled_serving_programs(compile_stats: Dict) -> int:
    """Count the serving programs a telemetry snapshot saw compile: every
    ``paged_*`` entry (the ``paged_<kind>_r<rows>_w<width>`` naming of the
    ragged builder) with at least one cold dispatch. The compile-budget
    gate asserts this ≤ 2 for a full mixed serve."""
    return sum(
        1
        for name, rec in compile_stats.items()
        if name.startswith("paged_") and rec.get("compiles", 0) > 0
    )


class SchedulingPolicy:
    """Admission-order / preemption-victim policy for ``PagedServer``.

    The defaults reproduce the original single-policy behavior: FIFO
    admission (head of the queue or nothing — no head-of-line bypass) and
    youngest-first recompute preemption. ``inference/traffic.py``'s
    ``SLAPolicy`` overrides these with per-tenant budget/priority
    scheduling; the ``on_*`` hooks feed it the accounting."""

    def next_admission(
        self, queue: Sequence["Request"], server: "PagedServer"
    ) -> Optional["Request"]:
        return queue[0] if queue else None

    def preemption_victim(
        self,
        candidates: Sequence["Request"],
        server: "PagedServer",
        for_req: Optional["Request"] = None,
    ) -> "Request":
        return candidates[-1]  # latest admission

    def on_admit(self, req: "Request", server: "PagedServer") -> None:
        pass

    def on_emit(self, req: "Request", server: "PagedServer") -> None:
        pass

    def on_finish(self, req: "Request", server: "PagedServer") -> None:
        pass


class YoungestFirstPolicy(SchedulingPolicy):
    """The original policy, by its name."""


@dataclass
class Request:
    """One generation request moving through the scheduler."""

    uid: int
    prompt: np.ndarray  # [Lp] int32, immutable
    max_new_tokens: int
    eos_token_id: Optional[int] = None
    tenant: str = "default"
    generated: List[int] = field(default_factory=list)
    slot: Optional[int] = None
    consumed: int = 0  # prefill progress over context()
    pending: Optional[int] = None  # sampled but not yet written token
    done: bool = False
    admissions: int = 0  # > 1 means the request was preempted and resumed
    prefix_cached: int = 0  # context tokens attached from the prefix index
    spec_drafted: int = 0  # draft tokens this request sent to verification
    spec_accepted: int = 0  # draft tokens accepted for this request
    t_submit: float = 0.0  # server-clock timestamps for TTFT / TPOT
    t_first: Optional[float] = None
    t_finish: Optional[float] = None
    # capacity-doubling context buffer: context() sits on the serving hot
    # path (drafting reads it every speculative round), so appending the
    # newly emitted tokens must not re-concatenate the whole history
    _ctx_buf: Optional[np.ndarray] = field(default=None, repr=False)
    _ctx_len: int = field(default=0, repr=False)

    def context(self) -> np.ndarray:
        """Tokens to (re)compute on admission: the prompt plus everything
        already emitted — after a preemption the resumed prefill re-derives
        the exact greedy continuation. Returns a read-only view; amortized
        cost is O(tokens emitted since the last call)."""
        n = self.prompt.size + len(self.generated)
        buf = self._ctx_buf
        if buf is None or buf.size < n:
            grown = np.empty(max(16, 2 * n), np.int32)
            grown[: self.prompt.size] = self.prompt
            grown[self.prompt.size : n] = self.generated
            self._ctx_buf = buf = grown
        elif self._ctx_len < n:
            buf[self._ctx_len : n] = self.generated[self._ctx_len - self.prompt.size :]
        self._ctx_len = n
        view = buf[:n]
        view.flags.writeable = False  # a mutating Drafter must not corrupt
        return view                   # the re-prefill source after preemption

    def output(self) -> np.ndarray:
        return self.context().copy()


@dataclass
class _Packed:
    """One ragged step packed and not yet enqueued: its rows and, already on
    their way to the device, the operands the host makes."""

    seq: int  # the step's number on every span of its life: ``stats["dispatches"]`` at the pack
    rows: List[Request]
    chunk_len: Dict[int, int]  # uid -> chunk length, the prefill rows
    q_lens: np.ndarray
    width: int
    program: str
    live_tokens: int
    token_tiles: int
    step_fn: object  # the width's step program, looked up while the device is busy
    operands: tuple  # device: the token window, then page table, lengths, q_lens (and the state slots)


@dataclass
class _Dispatched:
    """One ragged step the device has been handed and the host has not yet
    settled: what its settle needs."""

    seq: int  # as its ``_Packed``'s
    rows: List[Request]
    out: object  # device [R (+ MOE_STAT_ROWS), W + 1], the step's one result
    next_tokens: object  # device [R]: the greedy token after each row's last live position
    chunk_len: Dict[int, int]
    q_lens: np.ndarray
    # uid -> row of every row whose next input token this step computes and
    # nothing else (a plain decode row, a prompt's last chunk): the rows the
    # next step can take before the settle
    feeds: Dict[int, int]
    # ``tracer.clock()`` when the wait for it returned (the device has finished it: a call
    # that ran ahead ended with that wait); None until then
    waited_at: Optional[float] = None


class PagedServer:
    """Owns the page pool and the admit → ragged-step loop."""

    def __init__(
        self,
        cfg: TransformerConfig,
        params,
        page_size: int = 16,
        num_pages: int = 0,
        max_slots: int = 8,
        max_seq_len: int = 0,
        prefill_chunk: int = 32,
        attn_impl: str = "auto",
        dtype=None,
        telemetry=None,
        spec_decode=None,
        drafter: Optional[Drafter] = None,
        prefix_cache: bool = False,
        policy: Optional[SchedulingPolicy] = None,
        clock=None,
        journal: Optional[RequestJournal] = None,
        tracer=None,
        metrics: Optional[MetricsRegistry] = None,
        tp=None,
    ):
        self.cfg = cfg
        # tensor-parallel serving (inference/tp.py:TPServing): the SAME
        # ragged programs run under shard_map on the mesh — weights
        # column/row-parallel, kv pages sharded on the kv-head axis, page
        # TABLES (and every other host structure: queues, prefix index,
        # journal, fleet routing) replicated and untouched.
        self.tp = tp
        # MoE serving (ISSUE 20): the per-layer "moe" subtree routes inside
        # the same paged programs (decode.py:_moe_ffn) — but only when the
        # expert stack scans with the layers. Interleaved dense/MoE stacks
        # (moe_layer_freq > 1) keep expert params OUTSIDE params["layers"],
        # which the scanned serving body cannot see; and expert placement is
        # the 'expert' mesh axis, not a TP weight split.
        is_moe = isinstance(params, dict) and (
            "moe" in params.get("layers", {}) or "moe_layers" in params or "periods" in params
        )
        if is_moe and "moe_layers" in params:
            raise NotImplementedError(
                "paged serving supports MoE only with moe_layer_freq == 1 "
                "(a scanned [L, E, ...] expert stack, routed by capacity or, "
                "with moe_drop_tokens=False, dropless); interleaved "
                "dense/MoE stacks keep experts outside the layer scan"
            )
        if is_moe and tp is not None and tp.degree > 1:
            raise NotImplementedError(
                "tensor-parallel MoE serving is not supported (with or without "
                "moe_drop_tokens): expert placement is the 'expert' mesh axis, "
                "not a TP weight split"
            )
        # an MoE model's ragged step appends its routing counts to the
        # step's one result (decode.py:_moe_stat_rows); a dense model's
        # result, stats keys and spans are as they were
        moe_layers = getattr(cfg, "num_moe_layers", cfg.num_layers)  # fewer behind leading dense layers
        self._moe_slots = moe_layers * cfg.num_experts if is_moe else 0
        # a chip that holds a share of its router's experts (models/hybrid_moe.py)
        # counts the held assignments in the program; every live token routes
        # moe_top_k a layer, so all routed assignments are counted here
        self._moe_routed_per_token = (
            moe_layers * cfg.moe_top_k if is_moe and getattr(cfg, "moe_router_experts", None) else 0
        )
        if tp is not None:
            if tp.degree > 1:
                tp.validate_cfg(cfg)
            params = tp.shard_params(cfg, params)
        self.params = params
        # unified tracing (profiling/tracer.py): per-step phase spans
        # (admit / pack / dispatch > enqueue / emit > fetch, settle /
        # journal_sync, each also an event of the profiler's trace) and
        # per-request lifecycle spans (submit → admit → first_token →
        # finish, with tenant / prefix-hit / spec-accept attributes).
        # Host-side only — the step's device work stays one enqueue + one
        # budgeted fetch. Every span of a step's life carries the step's
        # ``seq``: ``stats["dispatches"]`` as it stands at the pack, which
        # is what it still is at the enqueue (a step packed again gets the
        # same number).
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        # the scheduler's state at the top of each step: the same three
        # readings go on the ``serve.step`` span and out through
        # ``monitor_events`` (the registry's gauges)
        self._g_waiting = self.metrics.gauge("serve.waiting")
        self._g_running = self.metrics.gauge("serve.running")
        self._g_pages = self.metrics.gauge("serve.kv_pages_in_use")
        # host milliseconds from the return of a step's wait to the next
        # step's jitted call, where no drain lies between: a lower bound of
        # the device's idle time between the two executions that needs no
        # profiler ("my host holds the chip back" against "the model is slow")
        self._turnaround = self.metrics.histogram("serve.turnaround_ms", _TURNAROUND_BUCKETS_MS)
        self.prefill_chunk = int(prefill_chunk)
        self.attn_impl = attn_impl
        self.telemetry = telemetry
        self.prefix_cache = bool(prefix_cache)
        # recurrent-state and sliding-window layers: a row's state, or its page
        # ring, exists at its newest positions only, so whatever re-enters a
        # sequence part-way is refused here
        refused = {
            "paged_kv.prefix_cache (and copy-on-write forks of shared pages)": self.prefix_cache,
            "spec_decode (verify rows roll their rejected tail back)": drafter is not None or bool(_spec_knob(spec_decode, "enable", False)),
        }
        for what, asked in refused.items():
            if asked:
                _refuse_state_layers(cfg, what)
        self.policy = policy or YoungestFirstPolicy()
        # crash-recovery journal (inference/journal.py): admissions and
        # emitted tokens are appended per event and made durable ONCE per
        # scheduler step (journal.sync() at the end of step()); restart
        # replays it via recover() and every stream resumes byte-identically
        self.journal = journal
        # injectable clock: TTFT/TPOT stamps and the load harness's virtual
        # time both read it (default: wall)
        self.clock = clock or time.perf_counter
        # speculation: a SpecDecodeConfig / dict of knobs, or an explicit
        # Drafter instance (tests inject oracles this way) — either enables
        self.max_draft = int(_spec_knob(spec_decode, "max_draft", 4))
        if drafter is None and _spec_knob(spec_decode, "enable", False):
            drafter = NGramDrafter(
                ngram_order=int(_spec_knob(spec_decode, "ngram_order", 3))
            )
        self.drafter = drafter
        if self.drafter is not None and self.max_draft < 1:
            raise ValueError(f"speculation needs max_draft >= 1, got max_draft={self.max_draft}")
        # the two ragged widths: decode/verify rows need 1 + max_draft
        # slots, prefill chunks need prefill_chunk — a step dispatches the
        # narrow program unless it carries a chunk row, so total compiled
        # serving programs is ≤ 2 regardless of traffic
        self._ragged_w_decode = (self.max_draft + 1) if self.drafter is not None else 1
        self._ragged_w_mixed = max(self.prefill_chunk, self._ragged_w_decode)
        max_seq = int(max_seq_len or cfg.max_seq_len)
        if num_pages <= 0:
            # worst-case sizing: every slot at max length, plus the trash
            # page — no preemption can ever trigger. Shrink num_pages to
            # oversubscribe HBM and trade it for preemptions. (A page id
            # holds a token's entry in every paged layer, softmax or
            # latent; the state layers' store is sized by max_slots alone,
            # so a state + latent model's pool is pages x latent bytes a
            # token + slots x state bytes a slot: PagePool.memory_report.)
            num_pages = max_slots * (-(-max_seq // page_size)) + 1
        self.pool = PagePool(
            cfg, num_pages, page_size, max_slots,
            max_seq_len=max_seq, dtype=dtype,
            kv_sharding=None if tp is None else tp.kv_sharding,
            prefill_chunk=prefill_chunk,
        )
        self._queue: deque[Request] = deque()
        self._active: List[Request] = []  # admission order (oldest first)
        # the dispatched step whose result has not been settled (at most
        # one), and the step packed behind it for the next call to enqueue
        self._in_flight: Optional[_Dispatched] = None
        self._packed: Optional[_Packed] = None
        self._next_tokens, self._feed_tokens = build_token_feed(tp)
        self._no_tokens = np.zeros(max_slots, np.int32)  # what the feed takes with nothing in flight
        self._results: Dict[int, np.ndarray] = {}
        self._next_uid = 0
        # per-tenant serving observability (created lazily per tenant name):
        # request counters, emitted tokens, and bounded TTFT/TPOT samples
        self._tenant_stats: Dict[str, Dict] = {}
        # (tenant, ttft_ms, tpot_ms|None, n_tokens) per finished request —
        # the load harness derives SLA goodput from this
        self._finished_log: deque = deque(maxlen=65536)
        # migrated-out records appended since the last full compaction —
        # the journal's garbage counter (see finalize_migration)
        self._migrated_since_compact = 0
        # requests that migrated to a JOURNAL-LESS replica: THIS journal
        # keeps their only durable claim (state as of the migration) until
        # the fleet reports them finished — see retain_migrated_claim
        self._foreign_claims: Dict[int, "JournaledRequest"] = {}
        self.stats = {
            "admitted": 0,
            "preempted": 0,
            "finished": 0,
            "recovered": 0,  # live requests rebuilt from the journal
            "migrated_out": 0,  # live requests extracted for fleet migration
            "migrated_in": 0,  # live requests adopted from another replica
            "journal_compactions": 0,  # full-state rewrites (amortized)
            "prefix_cached_tokens": 0,  # context tokens attached, not prefilled
            "prefill_chunks": 0,
            # every scheduler step is ONE ragged dispatch; decode_steps /
            # spec_rounds count the dispatches that carried plain-decode /
            # drafted rows (a mixed dispatch can count as both)
            "ragged_steps": 0,
            # ragged steps dispatched while the one before was still in
            # flight; a row-step run for a row whose EOS the step before
            # had produced (its result discarded); and why an in-flight step
            # was settled before the next could be packed, by reason
            "run_ahead_steps": 0,
            "overshoot_rows": 0,
            "drain_reasons": {},
            # `dispatches` counts every serving dispatch and `emitted_tokens`
            # every generated token, so dispatches_per_token is derivable
            "dispatches": 0,
            "emitted_tokens": 0,
            # the mixed-width dispatches, the live tokens they carried and the
            # token tiles the program ran for them (decode.token_tiles)
            "mixed_steps": 0,
            "mixed_live_tokens": 0,
            "mixed_token_tiles": 0,
            "decode_steps": 0,  # dispatches that carried a plain decode row
            "spec_rounds": 0,  # dispatches that carried a drafted row
            "spec_drafted": 0,  # draft tokens sent to verification
            "spec_accepted": 0,  # draft tokens accepted
            # draft-hit histogram: accept_hist[n] counts (request, round)
            # pairs whose accepted prefix was exactly n drafts long
            "spec_accept_hist": [0] * (self.max_draft + 1),
        }
        if self._moe_slots:
            # live (token, expert) assignments over all layers; experts hit
            # (>= 1 live token), summed over layers; the largest load any one
            # expert of any layer took in one step
            self.stats.update(moe_assignments=0, moe_experts_hit=0, moe_max_expert_load=0)
            self._g_moe_hit = self.metrics.gauge("serve.moe_experts_hit_share")
            if self._moe_routed_per_token:
                self.stats["moe_routed_assignments"] = 0  # held or not; moe_assignments: the held
        if self.pool.states is not None:
            self._g_state_slots = self.metrics.gauge("serve.state_slots_in_use")
            if self.pool.window_ring:
                self._g_window_slots = self.metrics.gauge("serve.window_slots_in_use")

    # --- request intake -------------------------------------------------
    def _tenant(self, name: str) -> Dict:
        ts = self._tenant_stats.get(name)
        if ts is None:
            ts = self._tenant_stats[name] = {
                "submitted": 0,
                "finished": 0,
                "tokens": 0,
                "ttft_ms": deque(maxlen=4096),
                "tpot_ms": deque(maxlen=4096),
            }
        return ts

    def queued_count(self, tenant: Optional[str] = None) -> int:
        if tenant is None:
            return len(self._queue)
        return sum(1 for r in self._queue if r.tenant == tenant)

    def live_count(self, tenant: Optional[str] = None) -> int:
        if tenant is None:
            return len(self._active)
        return sum(1 for r in self._active if r.tenant == tenant)

    def submit(
        self,
        prompt,
        max_new_tokens: int = 32,
        eos_token_id: Optional[int] = None,
        tenant: str = "default",
    ) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        total = prompt.size + int(max_new_tokens)
        if total > self.pool.max_seq_len:
            raise ValueError(
                f"prompt {prompt.size} + max_new_tokens {max_new_tokens} exceeds "
                f"the serving max_seq_len {self.pool.max_seq_len}"
            )
        if self.pool.pages_for(total) > self.pool.num_pages - 1:
            raise ValueError(
                f"request needs {self.pool.pages_for(total)} pages but the pool "
                f"holds {self.pool.num_pages - 1} allocatable"
            )
        uid = self._next_uid
        self._next_uid += 1
        now = self.clock()
        self._queue.append(
            Request(uid=uid, prompt=prompt, max_new_tokens=int(max_new_tokens),
                    eos_token_id=eos_token_id, tenant=tenant,
                    t_submit=now)
        )
        self._tenant(tenant)["submitted"] += 1
        # the request's lifecycle span opens at submit (queue wait included,
        # matching the TTFT definition) and closes at finish
        self.tracer.begin_async("request", uid, f"req{uid}", tenant=tenant)
        if self.journal is not None:
            self.journal.append_submit(
                uid, prompt, int(max_new_tokens), eos_token_id, tenant,
                t_submit=now,
            )
            # admissions are durable at submit time, not at the next step:
            # a request accepted then crashed-on must survive the restart
            self.journal.sync()
        return uid

    def recover(
        self,
        states: Dict[int, "JournaledRequest"],
        next_uid: int = 0,
        migrated_in: bool = False,
    ) -> int:
        """Rebuild the server from replayed journal state (a restart after
        a crash). Finished requests land directly in the results map (their
        output is fully journaled); every live request is re-queued with
        its journaled emissions pre-seeded, so its re-admission prefills
        ``prompt + generated`` on the cold chunk grid — the exact machinery
        that makes recompute-preemption invisible — and the stream resumes
        **byte-identically** from its last emitted token. Prefix caching
        (when on) makes re-prefill of shared prompts nearly free. Every
        replayed request — live ones as seeded submit records, finished
        ones as seeded submit+finish — is re-journaled into the fresh
        segment, which then alone replays to the same state, so the
        superseded pre-crash segments are retired (journal growth stays
        bounded across crash/recover cycles). Returns the number of live
        requests recovered.

        ``migrated_in=True`` is the LIVE-fleet form (this server is a
        migration/re-route target in a running fleet): the requests'
        original ``t_submit``/``t_first`` stamps are preserved — the fleet
        shares one clock, and resetting them would erase pre-move queue
        wait from TTFT, flattering exactly the requests a kill hurt — and
        the tenant ``submitted``/``recovered`` counters are NOT bumped
        (the source replica already counted them and stays in the merged
        stats); inbound moves count under ``stats['migrated_in']``. The
        default is the fresh-process form: stamps restart with the clock
        and the counters are this server's to claim."""
        self._drain("recover")
        recovered = 0
        for uid in sorted(states):
            st = states[uid]
            if st.done:
                out = np.concatenate(
                    [np.asarray(st.prompt, np.int32),
                     np.asarray(st.generated, np.int32)]
                )
                self._results[uid] = out
                if self.journal is not None:
                    # finished results ride the compacted segment too, so
                    # the pre-crash segments become fully superseded and
                    # retire_older_segments below can drop them
                    self.journal.append_submit(
                        uid, st.prompt, st.max_new_tokens, st.eos_token_id,
                        st.tenant, generated=st.generated,
                    )
                    self.journal.append_finish(uid)
                continue
            req = Request(
                uid=uid, prompt=np.asarray(st.prompt, np.int32),
                max_new_tokens=int(st.max_new_tokens),
                eos_token_id=st.eos_token_id, tenant=st.tenant,
                generated=[int(t) for t in st.generated],
                t_submit=(
                    st.t_submit
                    if migrated_in and st.t_submit is not None
                    else self.clock()
                ),
                t_first=st.t_first if migrated_in else None,
            )
            self._queue.append(req)
            # re-open the request's lifecycle span on THIS timeline —
            # extraction (or the crash) closed/lost the previous one, and
            # _finish will end this span when the stream completes
            self.tracer.begin_async("request", uid, f"req{uid}", tenant=st.tenant)
            if not migrated_in:
                self._tenant(st.tenant)["submitted"] += 1
            if self.journal is not None:
                # re-seed with the Request's OWN stamps (not st's): they are
                # consistent with this server's clock domain whichever path
                # built the request
                self.journal.append_submit(
                    uid, st.prompt, st.max_new_tokens, st.eos_token_id,
                    st.tenant, generated=st.generated,
                    t_submit=req.t_submit, t_first=req.t_first,
                )
            recovered += 1
        self._next_uid = max(self._next_uid, int(next_uid))
        self.stats["migrated_in" if migrated_in else "recovered"] += recovered
        if self.journal is not None:
            # the compaction (seeded submits + finished results) is durable
            # before the superseded pre-crash segments are dropped — this
            # bounds journal growth across repeated crash/recover cycles
            self.journal.sync()
            self.journal.retire_older_segments()
        return recovered

    def extract_request(self, uid: int) -> Optional["JournaledRequest"]:
        """Remove a live or queued request from THIS server and return its
        replay state — the source half of a fleet migration
        (``inference/fleet.py``): the target re-admits the state via
        ``recover()``, re-prefills ``prompt + generated`` on the cold
        chunk grid (the recompute-preemption machinery, ~free for shared
        prompts under prefix caching), and the stream continues
        byte-identically from its last emitted token. No journal record
        is written here — the request's journal hand-off happens in
        ``finalize_migration`` AFTER the target has durably re-seeded it,
        so no crash instant leaves the request claimed by neither
        journal. Returns None when the uid is not live here (already
        finished or never admitted)."""
        self._drain("extract_request")
        req = next((r for r in self._active if r.uid == uid), None)
        if req is not None:
            self.pool.free_slot(req.slot)
            req.slot = None
            req.pending = None
            req.consumed = 0
            self._active.remove(req)
        else:
            req = next((r for r in self._queue if r.uid == uid), None)
            if req is None:
                return None
            self._queue.remove(req)
        if self.drafter is not None:
            self.drafter.drop(uid)
        self.stats["migrated_out"] += 1
        if self.tracer.enabled:
            # close the request's lifecycle span on this timeline — the
            # target replica's timeline picks the request up at recover
            self.tracer.end_async(
                "request", uid, f"req{uid}", migrated=True,
                tokens=len(req.generated),
            )
        return JournaledRequest(
            uid=req.uid,
            prompt=np.asarray(req.prompt, np.int32),
            max_new_tokens=int(req.max_new_tokens),
            eos_token_id=req.eos_token_id,
            tenant=req.tenant,
            generated=[int(t) for t in req.generated],
            t_submit=req.t_submit,
            t_first=req.t_first,
        )

    def restore_request(self, state: "JournaledRequest") -> None:
        """Inverse of ``extract_request`` for a migration that found no
        target: re-queue the state on THIS server (stamps preserved — the
        clock never changed) and undo the extraction's migration
        accounting, since nothing actually moved."""
        self._drain("restore_request")
        self.recover({state.uid: state}, 0, migrated_in=True)
        self.stats["migrated_out"] -= 1
        self.stats["migrated_in"] -= 1

    def retain_migrated_claim(self, uid: int, state: "JournaledRequest") -> None:
        """The request migrated to a JOURNAL-LESS target, which can never
        durably claim it — so THIS journal must keep the claim (state as
        of the migration) or a crash finds the request in neither journal
        and its acked tokens are lost. The claim rides every compaction
        until ``release_migrated_claim``; tokens the target emits after
        the move were never durable anywhere, which is what running a
        journal-less replica means."""
        if self.journal is None:
            return
        self._foreign_claims[uid] = state

    def release_migrated_claim(self, uid: int) -> None:
        """The migrated-away request finished and its output was
        delivered: disclaim it (durability no longer matters once the
        caller holds the bytes), so a later replay cannot resurrect it."""
        if self._foreign_claims.pop(uid, None) is None:
            return
        if self.journal is not None:
            self.journal.append_migrate(uid)
            self.journal.sync()
            self._migrated_since_compact += 1
            self._maybe_compact_migrated()

    def _maybe_compact_migrated(self) -> None:
        """Compact when migrated-out garbage outweighs the live state
        still worth rewriting — the shared trigger for BOTH disclaim
        paths (finalize_migration and release_migrated_claim), so journal
        growth stays bounded even when every migration flows through
        journal-less targets."""
        if self._migrated_since_compact > len(self._queue) + len(self._active):
            self.compact_journal()

    def finalize_migration(self, uid: int) -> None:
        """Source-side journal hand-off after a migration landed on the
        target: append the migrated-out record (durable immediately — the
        source must not resurrect the request on a later replay), then
        compact only when the migrated-out garbage outweighs the live
        state still worth rewriting. A drain of N requests therefore pays
        O(N) total journal I/O (compactions at the halving points plus one
        final at empty, which is also what keeps the drained journal at
        ≤1 segment) instead of N full-state rewrites — and a single
        rebalancing move off a busy replica costs one record + sync, not
        a rewrite of every resident request."""
        self._drain("finalize_migration")
        if self.journal is None:
            return
        self.journal.append_migrate(uid)
        self.journal.sync()
        self._migrated_since_compact += 1
        self._maybe_compact_migrated()

    def compact_journal(self) -> int:
        """Re-seed this server's FULL current state into a fresh journal
        segment and retire every older one (``journal.begin_compaction``):
        live requests as seeded submits, unclaimed finished results as
        byte-preserving submit+finish records (their original
        prompt/budget split is gone at finish — the replayed result is the
        output array verbatim, which is all a result needs). The live-
        server form of the compaction ``recover()`` performs on restart;
        ``finalize_migration`` triggers it when migrated-out garbage
        outweighs live state, so journal growth stays bounded. Returns
        the number of segments retired."""
        self._drain("compact_journal")
        if self.journal is None:
            return 0
        self._migrated_since_compact = 0
        self.stats["journal_compactions"] += 1
        self.journal.begin_compaction()
        for st in self._foreign_claims.values():
            # claims held for requests living on journal-less replicas
            # survive the rewrite — dropping them here would silently
            # break the neither-journal-loses-it invariant
            self.journal.append_submit(
                st.uid, st.prompt, st.max_new_tokens, st.eos_token_id,
                st.tenant, generated=st.generated,
                t_submit=st.t_submit, t_first=st.t_first,
            )
        for req in list(self._queue) + list(self._active):
            self.journal.append_submit(
                req.uid, req.prompt, req.max_new_tokens, req.eos_token_id,
                req.tenant, generated=req.generated,
                t_submit=req.t_submit, t_first=req.t_first,
            )
        for uid, out in self._results.items():
            self.journal.append_submit(uid, out, 1, None, "default")
            self.journal.append_finish(uid)
        self.journal.sync()
        return self.journal.retire_older_segments()

    def has_work(self) -> bool:
        """Something queued, running, or dispatched and not yet settled."""
        return bool(self._queue or self._active or self._in_flight is not None)

    def settle(self) -> None:
        """Fetch and settle the step in flight, if any: afterwards every
        token the device has been asked for is in its stream. For a caller
        that reads requests between steps (the fleet router before a
        hand-off or a drain); ``run()`` and ``serve()`` end settled anyway."""
        self._drain("settle")

    def _drain(self, reason: str) -> None:
        """Settle the in-flight step now because the host needs its values
        before it can go on; ``reason`` is counted in ``drain_reasons``."""
        step, self._in_flight = self._in_flight, None
        self._packed = None  # packed from counts the settle may overturn; nothing of it was committed
        if step is None:
            return
        reasons = self.stats["drain_reasons"]
        reasons[reason] = reasons.get(reason, 0) + 1
        with self.tracer.span("serve.emit", seq=step.seq, drain=reason):
            self._settle_ragged_rows(step)

    def result(self, uid: int) -> Optional[np.ndarray]:
        return self._results.get(uid)

    def take_result(self, uid: int) -> Optional[np.ndarray]:
        """Pop a finished output: a long-lived server must not retain every
        output ever generated (both ``serve()`` fronts drain through this)."""
        return self._results.pop(uid, None)

    # --- one scheduler iteration ---------------------------------------
    def step(self) -> None:
        """One scheduler round: ONE dispatch covering every active row's next
        tokens (prefill chunks, decodes, and drafted verifies together).
        The call admits what fits, enqueues the step the call before it
        packed (packed again first if someone was just admitted), settles
        the step before that one behind the enqueue, packs the next step
        while the device runs, and ends with the wait for the device — so
        the tokens a call emits are those of the step dispatched one call
        earlier, and the device is idle between two calls. A first call, a
        drained server and one with a drafter armed pack and enqueue in the
        same call."""
        waiting, running = len(self._queue), len(self._active)
        pages_in_use = self.pool.used_pages()
        self._g_waiting.set(waiting)
        self._g_running.set(running)
        self._g_pages.set(pages_in_use)
        seq = self.stats["dispatches"]  # of the step this call enqueues, if it enqueues one
        with self.tracer.span(
            "serve.step", waiting=waiting, running=running,
            pages_in_use=pages_in_use, pages_total=self.pool.num_pages - 1,
            **self.pool.cache_bytes(),  # a model with state or latent layers: which cache the live rows' bytes are in
        ) as step_span:
            packed, self._packed = self._packed, None
            if packed is None:
                self._drain("idle")  # nothing was packed behind it: the call settles first
            with self.tracer.span("serve.admit") as admit_span:
                admitted = self.stats["admitted"]
                self._admit()
                admitted = self.stats["admitted"] - admitted
                admit_span.set(admitted=admitted)
            if packed is not None:
                if admitted:
                    # whoever came since the step was packed rides in it all
                    # the same: it is packed again, with them (the one pack
                    # the device waits for; a request's wait for its first
                    # chunk is a synchronous server's)
                    packed = self._pack()
                # the step goes to the device first, and the one before it is
                # settled behind that enqueue
                if packed is not None:
                    self._dispatch(packed)
            if self._in_flight is None:
                # nothing was packed ahead (a first step, a drained or a
                # synchronous server): pack and enqueue in this call
                packed = self._pack()
                if packed is not None:
                    self._dispatch(packed)
            if self._in_flight is not None:
                # drafts are proposed from settled contexts: with a drafter
                # armed the step is settled in the call that dispatched it,
                # and the server is the synchronous one. Otherwise the next
                # step is packed while the device runs this one, and the call
                # ends when the device does: nothing executes between two calls
                if self.drafter is not None:
                    self._drain("draft")
                else:
                    self._packed = self._pack()
                    if self._in_flight is not None:
                        self._wait_ragged_rows(self._in_flight)
            if self.stats["dispatches"] > seq:
                step_span.set(seq_enqueued=seq)
            # the round's dispatch and the emissions of the step before it
            # happened; the chaos point models dying BEFORE the journal flush
            # (and with a step in flight, which dies unseen) — the un-synced
            # tokens are re-derived identically on recovery (greedy
            # re-prefill). A ChaosKilled unwinds through the open spans
            # (the flight recorder saw them as open at dump time).
            chaos.point("serve.mid_step")
            if self.journal is not None:
                with self.tracer.span("serve.journal_sync"):
                    self.journal.sync()
        self.metrics.counter("serve.steps").inc()

    def run(self) -> Dict[int, np.ndarray]:
        while self.has_work():
            self.step()
        return self._results

    def serve(
        self,
        prompts: Sequence,
        max_new_tokens=32,
        eos_token_id: Optional[int] = None,
        tenant: str = "default",
    ) -> List[np.ndarray]:
        """Submit a batch (scalar or per-request ``max_new_tokens``), run to
        completion, return outputs in submission order."""
        if isinstance(max_new_tokens, (int, np.integer)):
            max_new_tokens = [max_new_tokens] * len(prompts)
        if len(max_new_tokens) != len(prompts):
            raise ValueError(
                f"{len(prompts)} prompts but {len(max_new_tokens)} max_new_tokens"
            )
        uids = [
            self.submit(p, max_new_tokens=int(n), eos_token_id=eos_token_id,
                        tenant=tenant)
            for p, n in zip(prompts, max_new_tokens)
        ]
        self.run()
        return [self.take_result(u) for u in uids]

    # --- phases ---------------------------------------------------------
    def _admit(self) -> None:
        while self._queue:
            # the deque is handed to the policy directly (policies iterate /
            # peek, never mutate); the FIFO default peeks [0] so the common
            # path stays O(1) via popleft below
            req = self.policy.next_admission(self._queue, self)
            if req is None:
                break
            ctx = req.context()
            # reserve the whole context plus the first decode write so a
            # prefill can never die halfway through its own prompt; with
            # prefix caching the pool first attaches the longest indexed
            # prefix of the context by reference (match is capped to
            # ctx.size - 1, so at least one token always prefills and the
            # first output token has logits to come from)
            slot = self.pool.alloc_slot(
                ctx.size + 1,
                prefix_tokens=ctx if self.prefix_cache else None,
            )
            if slot is None:
                break
            if self._queue[0] is req:
                self._queue.popleft()
            else:
                self._queue.remove(req)
            req.slot = slot
            cached = int(self.pool.seq_lens[slot])
            req.consumed = cached
            req.prefix_cached = cached
            self.stats["prefix_cached_tokens"] += cached
            req.pending = None
            req.admissions += 1
            self._active.append(req)
            self.stats["admitted"] += 1
            self.tracer.instant_async(
                "request", req.uid, "admit",
                slot=slot, prefix_cached=cached, admissions=req.admissions,
                queue_wait_ms=round((self.clock() - req.t_submit) * 1e3, 3),
            )
            self.policy.on_admit(req, self)

    def _next_chunk_len(self, req: "Request", ctx_size: int) -> int:
        """Tokens the request's next prefill chunk covers. A prefix attach
        that landed mid chunk-grid realigns to the cold-prefill chunk
        boundaries, so every position is computed by the same (chunk, row)
        geometry as sharing-off serving — byte-identical streams by
        construction."""
        C = self.prefill_chunk
        start = req.consumed
        real = min(C, ctx_size - start)
        if start % C:
            real = min(real, C - start % C)
        return real

    # --- the ragged one-program step -------------------------------------
    def _rows_to_pack(self) -> List[Request]:
        """The running rows the next step carries: all of them, but for a row
        whose budget ends with the token still in flight (it has nothing
        left to ask for, and finishes when that step settles)."""
        feeds = self._in_flight.feeds if self._in_flight is not None else ()
        return [
            r for r in self._active
            if not (r.uid in feeds and len(r.generated) + 1 >= r.max_new_tokens)
        ]

    def _fits_without_preemption(self, rows: List[Request]) -> bool:
        """Whether the pool can host the next step's writes as it stands. It
        is asked only behind a step in flight, where a row decodes one token
        or prefills its next chunk."""
        feeds = self._in_flight.feeds
        grow = [
            1 if r.uid in feeds or r.pending is not None else self._next_chunk_len(r, r.context().size)
            for r in rows
        ]
        return self.pool.can_write([r.slot for r in rows], grow)

    def _pack(self) -> Optional[_Packed]:
        """Pack ONE dispatch for the whole round: every active row
        contributes its next tokens — a prefill chunk, the pending decode
        token, or the pending token plus host-side drafts — in a single
        ``[max_slots, W]`` window whose per-row ``(kv_len, q_len)`` metadata
        ride in as arrays. A chunk row no longer steals a step from
        decoders (they share the dispatch), spec-K varies freely per row,
        and only the WIDTH can differ between steps (narrow decode/verify
        vs chunk-covering mixed), bounding compiled programs at 2. The
        width is the window the attention kernel sees; what the wide
        program computes besides follows the step's live tokens, in token
        tiles (``decode.token_tiles``: ``serve.pack``'s ``live_tokens`` and
        ``token_tiles``, summed over mixed steps in ``stats``).

        Behind a step in flight the pack reads counts only: a row that
        decoded (or finished its prompt) there takes its token from that
        step's result on the device (``decode.build_token_feed``). The
        operands the host makes are sent to the device here, while it is
        busy, so that the enqueue (``_dispatch``) has nothing left to move.
        Nothing is committed: a drain may drop what this returns."""
        rows = self._rows_to_pack()
        if self._in_flight is not None and rows and not self._fits_without_preemption(rows):
            # settle first, then preempt on settled state as a synchronous
            # server does: a victim never has a token in flight
            self._drain("preempt")
            rows = self._rows_to_pack()
        if not rows:
            return None
        prev = self._in_flight
        feeds = prev.feeds if prev is not None else {}
        seq = self.stats["dispatches"]
        with self.tracer.span("serve.pack", seq=seq) as pack_span:
            drafts: Dict[int, np.ndarray] = {}
            if self.drafter is not None:
                drafts = self._propose_drafts([r for r in rows if r.pending is not None])
            chunk_len: Dict[int, int] = {}
            need: Dict[int, int] = {}
            for r in rows:
                if r.uid in feeds:
                    need[r.uid] = 1  # its token is on the device; nothing is drafted from it
                elif r.pending is None:
                    chunk_len[r.uid] = self._next_chunk_len(r, r.context().size)
                    need[r.uid] = chunk_len[r.uid]
                else:
                    d = drafts.get(r.uid)
                    if d is None:
                        d = drafts[r.uid] = np.zeros(0, np.int32)
                    need[r.uid] = d.size + 1
            rows = self._reserve_for_growth(rows, need)
            if not rows:
                return None
            mixed = any(r.uid in chunk_len for r in rows)
            W = self._ragged_w_mixed if mixed else self._ragged_w_decode
            # pad to the single fixed row budget — never re-bucketed; lengths
            # == consumed for prefill rows, so one write base serves every mode
            R = self.pool.max_slots
            page_table, lengths = self._dispatch_rows(rows, R)
            tokens = np.zeros((R, W), np.int32)
            q_lens = np.zeros(R, np.int32)
            # rows whose first token is row src[i]'s of the step in flight
            src = np.full(R, -1, np.int32)
            for i, r in enumerate(rows):
                fed = feeds.get(r.uid)
                if fed is not None:
                    src[i] = fed
                    q_lens[i] = 1
                elif r.uid in chunk_len:
                    real = chunk_len[r.uid]
                    tokens[i, :real] = r.context()[r.consumed : r.consumed + real]
                    q_lens[i] = real
                else:
                    d = drafts[r.uid]
                    tokens[i, 0] = r.pending
                    tokens[i, 1 : 1 + d.size] = d
                    q_lens[i] = 1 + d.size
            program = ragged_program_name(R, W, self.tp)
            # the live rows as the kernel gets them: the step's own record,
            # under its ``seq`` (a step packed again keeps its number: the
            # last pack before the enqueue is the one the device ran)
            live = q_lens > 0
            live_q = q_lens[live]
            live_kv = lengths[live] + live_q
            # pages the ragged kernel walks a layer (the live rows' own)
            kv_pages = int((-(-live_kv // self.pool.page_size)).sum())
            # what the program computes: its live tokens, in that many token
            # tiles (0: a window of one tile at most, computed whole)
            live_tokens = int(live_q.sum())
            tiles = token_tiles(self.cfg, R, W, live_tokens)
            pack_span.set(
                rows=len(rows), width=W, program=program, mixed=int(mixed), kv_pages=kv_pages,
                kv_tokens=int(live_kv.sum()), live_tokens=live_tokens, token_tiles=tiles,
                row_lens=_row_lens(live_q, live_kv),
            )
            topk = getattr(self.cfg, "index_topk", 0)
            if topk:
                # a model whose full layers attend chosen keys: what its queries may see and what they attend of it (a
                # query at position p has p + 1 live keys and attends min(p + 1, index_topk))
                q, kv = live_q.astype(np.int64), live_kv.astype(np.int64)
                below = np.clip(topk - (kv - q), 0, q)  # of a row's q queries, those that see at most index_topk keys: all of them
                pack_span.set(
                    keys_live=int((q * kv - q * (q - 1) // 2).sum()),
                    keys_chosen=int((below * (kv - q) + below * (below + 1) // 2 + (q - below) * topk).sum()),
                )
            host_made = [page_table, lengths, q_lens]
            states = self.pool.states
            if states is not None:
                # the rows' entries of the state store; dead rows go to the spare one
                slots = np.full(R, self.pool.max_slots, np.int32)
                slots[: len(rows)] = [r.slot for r in rows]
                host_made.append(slots)
                self._g_state_slots.set(len(rows))
                if self.pool.window_ring:
                    self._g_window_slots.set(len(rows))
            # the window with the in-flight rows' tokens laid in on the device
            # (queued behind the step that computes them), and the rest
            window = self._feed_tokens(tokens, prev.next_tokens if prev is not None else self._no_tokens, src)
            operands = (window, *jax.device_put(host_made))
            step_fn = build_ragged_step(
                self.cfg, R, W, self.pool.page_size, attn_impl=self.attn_impl,
                telemetry=self.telemetry, tp=self.tp,
            )
        return _Packed(seq, rows, chunk_len, q_lens, W, program, live_tokens, tiles, step_fn, operands)

    def _dispatch(self, packed: _Packed) -> None:
        """Enqueue a packed step (jit returns futures; the fetch is where
        device time surfaces) and the gather of the tokens the step after it
        may take, commit what its counts decide, and only then settle the
        step before it, whose result the call before waited for."""
        prev = self._in_flight
        seq, rows, W = packed.seq, packed.rows, packed.width
        with self.tracer.span("serve.dispatch", seq=seq, rows=len(rows), width=W, program=packed.program, ahead=int(prev is not None)):
            step_fn = packed.step_fn
            window, page_table, lengths, q_lens, *slots = packed.operands
            states = () if self.pool.states is None else (self.pool.states,)  # with their slots, or neither
            # the jitted call alone: what the host spends inside jax and the
            # runtime before the device can start. The rest of the dispatch
            # is this file's Python, behind the enqueue
            with self.tracer.span("serve.enqueue", seq=seq, program=packed.program):
                if prev is not None and prev.waited_at is not None:  # a drained step is gone: nothing is observed across a drain
                    self._turnaround.observe((self.tracer.clock() - prev.waited_at) * 1e3)
                out, new_k, new_v, *new_states = step_fn(
                    self.params, window, self.pool.cache.k_pages, self.pool.cache.v_pages,
                    *states, page_table, lengths, q_lens, *slots,
                )
            if new_states:
                self.pool.set_states(*new_states)
            self.pool.set_cache(new_k, new_v)
            self._in_flight = _Dispatched(
                seq, rows, out, self._next_tokens(out, q_lens), packed.chunk_len, packed.q_lens,
                feeds=self._commit_dispatched(rows, packed.chunk_len, packed.q_lens),
            )
        self.stats["ragged_steps"] += 1
        self.stats["dispatches"] += 1
        if packed.chunk_len:
            self.stats["mixed_steps"] += 1
            self.stats["mixed_live_tokens"] += packed.live_tokens
            self.stats["mixed_token_tiles"] += packed.token_tiles
        if prev is not None:
            self.stats["run_ahead_steps"] += 1
            with self.tracer.span("serve.emit", seq=prev.seq):
                self._settle_ragged_rows(prev)

    def _commit_dispatched(self, rows, chunk_len, q_lens) -> Dict[int, int]:
        """What a dispatch settles by counts alone, so that the next step can
        be packed before this one's result is read: every row's written
        positions, a prefill row's progress and the prefix pages its chunk
        filled (their tokens are the prompt's). Returns the step's ``feeds``:
        the rows that will hold exactly one new token, whose value only the
        device knows yet."""
        feeds: Dict[int, int] = {}
        had_decode = had_spec = False
        for i, r in enumerate(rows):
            n = int(q_lens[i])
            # a verify row's rejected tail rolls back at its settle
            self.pool.advance(r.slot, n)
            if r.uid in chunk_len:
                ctx = r.context()
                r.consumed += n
                self.stats["prefill_chunks"] += 1
                if self.prefix_cache:
                    self.pool.register_prefix(r.slot, ctx, r.consumed)
                if r.consumed == ctx.size:
                    feeds[r.uid] = i  # the first generated token
            elif n == 1:
                had_decode = True
                feeds[r.uid] = i
            else:
                had_spec = True
        self.stats["decode_steps"] += had_decode
        self.stats["spec_rounds"] += had_spec
        return feeds

    def _wait_ragged_rows(self, step: _Dispatched) -> None:
        """``serve.fetch``: the wait for the device. A call that runs ahead
        ends with it, so that the device is idle between two calls and the
        settle, the admissions and the next pack all ran while it was busy.
        It waits for the step, not for its result's way to the host: that
        round trip (~0.3 ms on a v5e) is left to the settle, which the next
        call makes behind its own enqueue."""
        if step.waited_at is None:
            with self.tracer.span("serve.fetch", seq=step.seq):
                step.out.block_until_ready()
                step.waited_at = self.tracer.clock()

    def _settle_ragged_rows(self, step: _Dispatched) -> None:
        """A dispatched step's settle: the wait for the device, if the call
        that dispatched it has not made it, the step's single host fetch
        ([R, W+1] = accepted counts + the greedy token after each position),
        then the per-row emit/publish that the tokens' values decide
        (``serve.settle``)."""
        self._wait_ragged_rows(step)
        out = np.asarray(step.out)  # lint: allow(DS-R005)
        with self.tracer.span("serve.settle", seq=step.seq) as settle_span:
            emitted = self.stats["emitted_tokens"]
            if self._moe_slots:
                out, moe = out[:-MOE_STAT_ROWS], out[-MOE_STAT_ROWS:, 0]
                assignments, hit, max_load = (int(v) for v in moe)
                self.stats["moe_assignments"] += assignments
                self.stats["moe_experts_hit"] += hit
                self.stats["moe_max_expert_load"] = max(self.stats["moe_max_expert_load"], max_load)
                self._g_moe_hit.set(hit / self._moe_slots)
                settle_span.set(moe_assignments=assignments, moe_experts_hit=hit, moe_max_expert_load=max_load)
                if self._moe_routed_per_token:
                    routed = int(step.q_lens.sum()) * self._moe_routed_per_token
                    self.stats["moe_routed_assignments"] += routed
                    settle_span.set(moe_routed_assignments=routed)
            self._settle_fetched_rows(step, out)
            settle_span.set(tokens=self.stats["emitted_tokens"] - emitted)

    def _settle_fetched_rows(self, step: _Dispatched, out) -> None:
        for i, r in enumerate(step.rows):
            if r.done:
                # its EOS came out of the step before this one, which had
                # been packed by then: the row-step ran for nothing, and the
                # slot it wrote is released now that it has settled
                self.stats["overshoot_rows"] += 1
                self.pool.free_slot(r.slot)
                r.slot = None
                continue
            if r.uid in step.chunk_len:
                if r.uid in step.feeds:
                    # the context's last chunk, and the first generated
                    # token: greedy after the chunk's last real position
                    self._emit(r, int(out[i, step.chunk_len[r.uid]]))
                continue
            d = int(step.q_lens[i]) - 1
            # acc is bounded by the drafted count in-program; all d+1
            # written positions advanced at the dispatch, the rejected tail
            # rolls back here — net advance is the accepted prefix + bonus token
            self._settle_spec_row(r, d, int(out[i, 0]), out[i])

    def _reserve_for_growth(self, running: List[Request], need: Dict[int, int]) -> List[Request]:
        """Make every running row writable for its next ``need[uid]`` tokens
        (default 1) — page growth plus the pool's copy-on-write barrier for
        any shared prefix page in the written span — preempting the
        policy's victim (default: youngest active request) when the pool is
        dry; vLLM's recompute preemption: the victim's greedy continuation
        is re-derived exactly on re-admission. Mutates and returns
        ``running`` (preempted rows leave the round)."""
        idx = 0
        while idx < len(running):
            req = running[idx]
            grow = need.get(req.uid, 1)
            while not self.pool.prepare_write(
                req.slot, int(self.pool.seq_lens[req.slot]) + grow
            ):
                if self._in_flight is not None:
                    raise RuntimeError("a preemption behind a step in flight: its victim may hold an unsettled token")
                candidates = [r for r in self._active if r is not req]
                if not candidates:
                    # unreachable while submit() validates total size, kept
                    # as a hard stop against a silent infinite loop
                    raise RuntimeError(
                        f"page pool exhausted by a single sequence (len "
                        f"{int(self.pool.seq_lens[req.slot])}): the pool holds "
                        f"{self.pool.num_pages - 1} pages x {self.pool.page_size} tokens"
                    )
                victim = self.policy.preemption_victim(candidates, self, for_req=req)
                self._preempt(victim)
                if victim in running:
                    vi = running.index(victim)
                    running.remove(victim)
                    if vi < idx:
                        idx -= 1
            idx += 1
        return running

    def _dispatch_rows(self, running: List[Request], pad_to: int):
        """(page_table, lengths) padded to ``pad_to`` rows (the fixed row
        budget) — rows past ``len(running)`` are dead padding (-1 tables /
        length 0: trash-page semantics make them always safe)."""
        page_table = np.full((pad_to, self.pool.max_pages_per_slot), -1, np.int32)
        lengths = np.zeros(pad_to, np.int32)
        rows_pt, rows_len = self.pool.rows([r.slot for r in running])
        n = len(running)
        page_table[:n] = rows_pt
        lengths[:n] = rows_len
        return page_table, lengths

    def _settle_spec_row(self, req: Request, d: int, acc: int, out_row) -> None:
        """A decode/verify row's settle — its ``d + 1`` written positions
        advanced at the dispatch: roll the rejected tail's pages back,
        update the speculation stats, emit the accepted prefix + bonus/
        correction token (stopping at EOS / budget), and republish the
        prefix."""
        self.pool.rollback(req.slot, d - acc)
        self.stats["spec_drafted"] += d
        self.stats["spec_accepted"] += acc
        req.spec_drafted += d
        req.spec_accepted += acc
        if d:
            hist = self.stats["spec_accept_hist"]
            hist[min(acc, len(hist) - 1)] += 1
        for tok in out_row[1 : acc + 2]:
            self._emit(req, int(tok))
            if req.done:  # EOS / budget inside the accepted run
                break
        if self.prefix_cache and not req.done:
            # post-rollback length is the canonical accepted context
            self.pool.register_prefix(
                req.slot, req.context(), int(self.pool.seq_lens[req.slot])
            )

    # --- speculative rounds ---------------------------------------------
    def _propose_drafts(self, running: List[Request]) -> Dict[int, np.ndarray]:
        """Host-side drafting: up to ``max_draft`` tokens per request,
        clamped so drafts never outrun the request's remaining budget (the
        bonus token always needs one slot) — which also keeps every write
        inside ``max_seq_len``."""
        drafts: Dict[int, np.ndarray] = {}
        for req in running:
            budget = req.max_new_tokens - len(req.generated)  # >= 1 while running
            k = min(self.max_draft, budget - 1)
            d = np.zeros(0, np.int32)
            if k > 0:
                d = np.asarray(
                    self.drafter.propose(req.uid, req.context(), k), np.int32
                ).reshape(-1)[:k]
            drafts[req.uid] = d
        return drafts

    # --- bookkeeping ----------------------------------------------------
    def _emit(self, req: Request, token: int) -> None:
        """Record a newly sampled token and retire the request if it just
        hit EOS or its budget (the token is included, matching
        ``decode.generate``'s output contract)."""
        if req.t_first is None:
            req.t_first = self.clock()
            self.tracer.instant_async("request", req.uid, "first_token")
            if self.journal is not None:
                self.journal.append_first_token(req.uid, req.t_first)
        req.generated.append(token)
        req.pending = token
        self.stats["emitted_tokens"] += 1
        self.metrics.counter("serve.tokens").inc()
        if self.journal is not None:
            self.journal.append_emit(req.uid, token)
        self._tenant(req.tenant)["tokens"] += 1
        self.policy.on_emit(req, self)
        if (
            req.eos_token_id is not None and token == req.eos_token_id
        ) or len(req.generated) >= req.max_new_tokens:
            self._finish(req)

    def _finish(self, req: Request) -> None:
        req.done = True
        req.t_finish = self.clock()
        if self._in_flight is None or req.uid not in self._in_flight.feeds:
            self.pool.free_slot(req.slot)
            req.slot = None
        # else an EOS seen a step late: the row rides in the step in flight,
        # which still writes the slot's pages; its settle releases the slot
        self._active.remove(req)
        self._results[req.uid] = req.output()
        self.stats["finished"] += 1
        ts = self._tenant(req.tenant)
        ts["finished"] += 1
        ttft_ms = (req.t_first - req.t_submit) * 1e3
        ts["ttft_ms"].append(ttft_ms)
        tpot_ms = None
        if len(req.generated) > 1:
            tpot_ms = (req.t_finish - req.t_first) * 1e3 / (len(req.generated) - 1)
            ts["tpot_ms"].append(tpot_ms)
        self._finished_log.append((req.tenant, ttft_ms, tpot_ms, len(req.generated)))
        if self.tracer.enabled:
            self.tracer.end_async(
                "request", req.uid, f"req{req.uid}",
                tenant=req.tenant, tokens=len(req.generated),
                prefix_cached=req.prefix_cached, admissions=req.admissions,
                spec_drafted=req.spec_drafted, spec_accepted=req.spec_accepted,
                ttft_ms=round(ttft_ms, 3),
                tpot_ms=None if tpot_ms is None else round(tpot_ms, 3),
            )
        # the SLA histograms come from the request's clock timestamps, not
        # the tracer — they record even with tracing disabled
        self.metrics.histogram("serve.ttft_ms").observe(ttft_ms)
        if tpot_ms is not None:
            self.metrics.histogram("serve.tpot_ms").observe(tpot_ms)
        if self.journal is not None:
            self.journal.append_finish(req.uid)
        self.policy.on_finish(req, self)
        if self.drafter is not None:
            self.drafter.drop(req.uid)

    # --- observability ---------------------------------------------------
    @staticmethod
    def _percentiles(values) -> Dict:
        """{count, mean, p50, p99} ms summary ({} count 0 when empty) —
        the one shared definition (the fleet router reports through it
        too)."""
        return percentile_summary(values)

    def finished_log(self):
        """Per-finished-request (tenant, ttft_ms, tpot_ms|None, n_tokens)
        tuples, oldest first (bounded) — the load harness's goodput input."""
        return list(self._finished_log)

    def serve_stats(self) -> Dict:
        """Scheduler counters (incl. ``ragged_steps`` — one per unified
        dispatch — and ``dispatches_per_token`` over every serving dispatch
        and emitted token) plus derived speculation observability
        (acceptance rate, mean accepted drafts per round, draft-hit
        histogram), pool occupancy/utilization, prefix-cache counters
        (hit rate, CoW copies, cached pages), and latency SLOs — aggregate
        and per-tenant p50/p99 TTFT (submit → first token, queue wait
        included) and TPOT (per generated token after the first) — the
        payload ``InferenceEngine.serve_stats()`` surfaces."""
        s = dict(self.stats)
        s["spec_accept_hist"] = list(self.stats["spec_accept_hist"])
        s["drain_reasons"] = dict(self.stats["drain_reasons"])
        # how often a step was enqueued behind one still in flight: near 1 in
        # steady serving, 0 for a server that has to be synchronous
        s["run_ahead_share"] = s["run_ahead_steps"] / s["ragged_steps"] if s["ragged_steps"] else 0.0
        # the host's median milliseconds between a step's wait and the next
        # step's enqueue (``serve.turnaround_ms``); 0.0 before two steps ran back to back
        s["turnaround_ms_p50"] = self._turnaround.percentile(50)
        drafted, rounds = s["spec_drafted"], s["spec_rounds"]
        s["spec_accept_rate"] = s["spec_accepted"] / drafted if drafted else 0.0
        s["spec_mean_accepted_per_round"] = (
            s["spec_accepted"] / rounds if rounds else 0.0
        )
        # every serving dispatch over every emitted token (the rows a step
        # carries and the drafts it accepts both lower it); 0.0 before
        # anything has been emitted
        s["dispatches_per_token"] = (
            s["dispatches"] / s["emitted_tokens"] if s["emitted_tokens"] else 0.0
        )
        # a mixed step's live tokens and token tiles, on average: 1.0 tiles
        # means every mixed step cost one tile of the weights
        mixed = max(s["mixed_steps"], 1)
        s["mixed_tokens_per_step"] = s["mixed_live_tokens"] / mixed
        s["mixed_tiles_per_step"] = s["mixed_token_tiles"] / mixed
        # tensor-parallel serving: the sharding degree this server runs at
        # (1 = single-chip) and whether the row-parallel all-reduces are
        # EQuARX-quantized — fleet observability keys on these
        # the model as served: passes over the layer stack a token (1 but for a
        # looped model) and the layers of K and V cache a token keeps
        s["loop_passes"] = getattr(self.cfg, "num_loops", 1)
        s["cache_layers"] = self.pool.cache.k_pages.shape[0]
        s["tp_degree"] = self.tp.degree if self.tp is not None else 1
        s["tp_quantized_allreduce"] = (
            bool(self.tp.quantized_allreduce) if self.tp is not None else False
        )
        s.update(
            live_tokens=self.pool.live_tokens(),
            used_pages=self.pool.used_pages(),
            free_pages=self.pool.free_pages(),
            live_hbm_bytes=self.pool.live_hbm_bytes(),
            pool_utilization=self.pool.utilization(),
        )
        all_ttft: List[float] = []
        all_tpot: List[float] = []
        tenants: Dict[str, Dict] = {}
        for name, ts in self._tenant_stats.items():
            all_ttft.extend(ts["ttft_ms"])
            all_tpot.extend(ts["tpot_ms"])
            tenants[name] = {
                "submitted": ts["submitted"],
                "finished": ts["finished"],
                "tokens": ts["tokens"],
                "ttft_ms": self._percentiles(ts["ttft_ms"]),
                "tpot_ms": self._percentiles(ts["tpot_ms"]),
            }
        s["ttft_ms"] = self._percentiles(all_ttft)
        s["tpot_ms"] = self._percentiles(all_tpot)
        s["tenants"] = tenants
        s["prefix"] = self.pool.prefix_stats()
        return s

    def _preempt(self, req: Request) -> None:
        self.pool.free_slot(req.slot)
        req.slot = None
        req.pending = None
        req.consumed = 0
        self._active.remove(req)
        self._queue.appendleft(req)
        self.stats["preempted"] += 1
        self.tracer.instant_async(
            "request", req.uid, "preempt", tokens=len(req.generated)
        )
