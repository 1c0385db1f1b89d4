"""What the two serving drivers share: building the server through the
public path, the token stamps, one stepping loop, and the correctness
checks. The arrival logic (open or closed loop) lives in the drivers.

The server is built as a user builds it (``ds.init_inference`` on a
``TransformerLM``, weights installed with ``set_params``, a first
``serve()``), then stepped through ``submit()`` / ``step()``. Tokens are
stamped by the benchmark itself in the public ``SchedulingPolicy.on_emit``
hook: the server's own ``ttft_ms`` counts from ``submit()``, not from when a
request was due, and it keeps no per-token time.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from benchmark import files
from benchmark.loadgen import TrafficRequest


@dataclasses.dataclass
class Served:
    """One request's life as the benchmark saw it (host clock, seconds)."""

    req: TrafficRequest
    due: float
    submitted: Optional[float] = None
    uid: Optional[int] = None
    stamps: List[float] = dataclasses.field(default_factory=list)  # one per token
    finished: Optional[float] = None
    output: Optional[np.ndarray] = None  # the whole stream, once finished
    partial: Optional[np.ndarray] = None  # prompt + tokens so far, if still in flight at the end
    rejected: bool = False

    def ok(self) -> bool:
        """Finished: prompt echoed, then exactly the budget, a stamp a token."""
        p = self.req.prompt
        return (
            self.output is not None
            and self.output.shape == (p.size + self.req.max_new_tokens,)
            and np.array_equal(self.output[: p.size], p)
            and len(self.stamps) == self.req.max_new_tokens
        )

    def ok_so_far(self) -> bool:
        """Finished and ``ok``, or still in flight with a consistent stream:
        prompt echoed, no more than the budget, a stamp a token."""
        if self.output is not None or self.partial is None:
            return self.ok()
        p, made = self.req.prompt, self.partial.size - self.req.prompt.size
        return np.array_equal(self.partial[: p.size], p) and made == len(self.stamps) <= self.req.max_new_tokens

    def stream(self) -> Optional[np.ndarray]:
        return self.output if self.output is not None else self.partial


def build_policy(session: "ServeSession"):
    from deepspeed_tpu.inference.scheduler import YoungestFirstPolicy

    class StampingPolicy(YoungestFirstPolicy):
        """The default scheduling, plus a clock reading per event."""

        def on_admit(self, req, server):
            session.live[req.uid] = req
            if session.rows_log is not None:
                session.step_rows.append(session._row(req))

        def on_emit(self, req, server):
            rec = session.by_uid.get(req.uid)
            if rec is not None:
                rec.stamps.append(time.perf_counter())

        def on_finish(self, req, server):
            session.live.pop(req.uid, None)
            rec = session.by_uid.get(req.uid)
            if rec is not None:
                rec.finished = time.perf_counter()
                session.just_finished.append(rec)

    return StampingPolicy()


def seeded_weights(model, seed: int, dtype):
    """The model's own ``init`` from ``--seed``, cast to the served type
    inside ONE jitted call, so that no float32 copy of a multi-GB model is
    ever held (``engine.init_params`` builds the float32 tree leaf by leaf
    on the device first: 14 GB for this configuration)."""

    def init(key):
        params = model.init(key, np.zeros((1, 8), np.int32))
        return jax.tree_util.tree_map(lambda a: a.astype(dtype), params)

    return jax.jit(init)(jax.random.PRNGKey(seed))


class ServeSession:
    def __init__(self, config: Dict, seed: int):
        import deepspeed_tpu as ds

        model, self.shape = files.build_model(config)
        self.model_section = config["model"]
        self.reference = files.reference_of(config)
        self.serve_cfg = config["engine"]["init_inference"]
        self.check = config["engine"]["check"]
        self.engine = ds.init_inference(model, **self.serve_cfg)
        dtype = jnp.bfloat16 if self.serve_cfg["dtype"] == "bf16" else jnp.dtype(self.serve_cfg["dtype"])
        self.params = seeded_weights(model, seed, dtype)
        self.engine.set_params(self.params)
        self.paged = self.serve_cfg["paged_kv"]
        self.by_uid: Dict[int, Served] = {}
        self.live: Dict[int, object] = {}
        self.just_finished: List[Served] = []
        self.records: List[Served] = []
        self.rows_log: Optional[List[Dict]] = None  # filled only inside a traced slice
        self.step_rows: List[tuple] = []
        self.done_count = 0  # requests finished or rejected so far
        self.server = None

    # --- set-up -----------------------------------------------------------
    def warm_up(self, seed: int) -> None:
        """A first ``serve()`` that runs both ragged programs (a prompt
        longer than one chunk beside a short one, then decode alone), so that
        the window compiles nothing. Then the step-wise server is taken from
        the engine (there is no public accessor yet: PERF.md section 7) and
        given the stamping policy; scheduling is unchanged."""
        rng = np.random.default_rng([seed, 0x3A2])
        chunk = self.paged["prefill_chunk"]
        prompts = [rng.integers(0, self.shape["vocab_size"], n, dtype=np.int32) for n in (chunk + chunk // 2, 8)]
        outs = self.engine.serve(prompts, max_new_tokens=[4, 8])
        for p, o, n in zip(prompts, outs, (4, 8)):
            if np.asarray(o).shape != (p.size + n,):
                raise RuntimeError("warm-up stream has the wrong length")
        server = self.engine._paged_server
        self.server = getattr(server, "server", server)
        self.server.policy = build_policy(self)

    def counters(self) -> Dict:
        """The program's own counters the per-layer metrics read."""
        stats = self.server.stats
        out = {k: stats[k] for k in ("dispatches", "ragged_steps", "prefill_chunks", "emitted_tokens", "preempted", "admitted", "finished")}
        compiles = self.engine.compile_stats()
        out["compiles"] = sum(rec["compiles"] for rec in compiles.values())
        chunk = self.paged["prefill_chunk"]
        out["mixed_dispatches"] = sum(
            rec["dispatches"] for name, rec in compiles.items() if name.startswith("paged_ragged") and name.endswith(f"_w{chunk}")
        )
        return out

    def window_counters(self, before: Dict, after: Dict, prompt_tokens: int, rows_log: Optional[List[Dict]]) -> Dict:
        """What the per-layer readers get: the counters' change over the
        window, the mixed program's geometry, the prompt tokens the window
        prefilled and, from a traced run, the slice's rows."""
        out = {k: after[k] - before[k] for k in after}
        out.update(rows=self.paged["max_slots"], width=self.paged["prefill_chunk"], prompt_tokens=prompt_tokens, model=self.shape)
        if rows_log is not None:
            out["rows_log"] = rows_log
        return out

    def traced_slice(self, tracer, loop, settle_s: float, slice_s: float) -> List[Dict]:
        """Go on with ``loop(until)`` under the profiler: ``settle_s`` for its
        start to pass, then ``slice_s`` inside the ``bench_slice`` annotation
        with every step's rows logged. Returns the rows log."""
        tracer.start_trace()
        loop(time.perf_counter() + settle_s)
        self.rows_log = []
        with TraceAnnotation("bench_slice"):
            loop(time.perf_counter() + slice_s)
        rows_log, self.rows_log = self.rows_log, None
        tracer.stop_trace()
        return rows_log

    # --- the loop ---------------------------------------------------------
    def submit(self, rec: Served) -> None:
        with TraceAnnotation("submit"):
            try:
                rec.uid = self.server.submit(rec.req.prompt, max_new_tokens=rec.req.max_new_tokens)
                self.by_uid[rec.uid] = rec
            except ValueError:
                rec.rejected = True
                self.done_count += 1
            rec.submitted = time.perf_counter()
        self.records.append(rec)

    def _row(self, r) -> tuple:
        """(q_len, kv_len) of a live request's row in the step about to run."""
        if r.pending is not None:
            return (1, r.prompt.size + len(r.generated))
        chunk = self.paged["prefill_chunk"]
        left = r.prompt.size + len(r.generated) - r.consumed
        q = min(chunk, left, chunk - r.consumed % chunk)
        return (q, r.consumed + q)

    def step(self) -> None:
        """One ``server.step()`` under the ``server_step`` annotation. Inside
        a traced slice each step's rows are kept too, as (q_len, kv_len)
        pairs (those admitted inside the step are added by ``on_admit``): the
        ragged kernel's bytes and operations come from them, and ``mixed``
        says which of the two programs ran."""
        tracing = self.rows_log is not None
        if tracing:
            self.step_rows = [self._row(r) for r in self.live.values()]
        chunks_before = self.server.stats["prefill_chunks"]
        with TraceAnnotation("server_step"):
            self.server.step()
        if tracing:
            mixed = self.server.stats["prefill_chunks"] > chunks_before
            self.rows_log.append({"mixed": mixed, "rows": self.step_rows})
        for rec in self.just_finished:
            rec.output = self.server.take_result(rec.uid)
        self.done_count += len(self.just_finished)
        self.just_finished.clear()

    def drain(self, limit_s: float) -> None:
        """Step until nothing is left or ``limit_s`` has passed; what is
        still in flight then keeps its stream so far."""
        end = time.perf_counter() + limit_s
        while self.server.has_work() and time.perf_counter() < end:
            self.step()
        for rec in self.records:
            if rec.output is None and not rec.rejected:
                made = self.live[rec.uid].generated if rec.uid in self.live else []  # else still queued
                rec.partial = np.concatenate([rec.req.prompt, np.asarray(made, np.int32)])

    # --- correctness --------------------------------------------------------
    def check_streams(self, records: List[Served], seed: int, in_flight_ok: bool = False) -> Dict:
        """Every stream is its prompt plus exactly its budget (or, with
        ``in_flight_ok``, a consistent part of it when the run ended first);
        and for a seeded sample of requests the plain reference runs once
        over the first ``check.max_context`` tokens of prompt + served
        tokens: each served token's reference logit must lie within
        ``check.logit_margin`` of that position's maximum, and the mean of
        those gaps within ``check.mean_logit_gap`` (one near-tie resolved the
        other way moves the worst gap, lower precision moves the mean).
        Logits and not tokens: with random weights near-ties are common and PR 21 showed a
        stream depends on which program width served each step."""
        good = (lambda r: r.ok_so_far()) if in_flight_ok else (lambda r: r.ok())
        failed = [r for r in records if r.rejected or not good(r)]
        T = self.check["max_context"]
        # something generated inside the first T positions
        fits = [r for r in records if not r.rejected and good(r) and r.req.prompt.size < T and r.stream().size > r.req.prompt.size]
        rng = np.random.default_rng([seed, 0xC4EC])
        sample = [fits[i] for i in rng.permutation(len(fits))[: self.check["sample"]]]
        gaps = np.zeros(0)
        if sample:
            tokens = np.zeros((len(sample), T), np.int32)
            for i, r in enumerate(sample):
                n = min(T, r.stream().size)
                tokens[i, :n] = r.stream()[:n]
            lg = self.reference.logits(self.model_section, self.params, tokens)
            nxt = jnp.asarray(np.roll(tokens, -1, axis=1))
            gap = np.asarray(jnp.max(lg, axis=-1) - jnp.take_along_axis(lg, nxt[..., None], axis=-1)[..., 0])
            # position j's logits predict token j + 1: served tokens sit at p .. n - 1
            gaps = np.concatenate([gap[i, r.req.prompt.size - 1 : min(T, r.stream().size) - 1] for i, r in enumerate(sample)])
        worst, mean = (float(gaps.max()), float(gaps.mean())) if gaps.size else (0.0, 0.0)
        within = worst <= self.check["logit_margin"] and mean <= self.check["mean_logit_gap"]
        return {
            "failed": len(failed),
            "reference_sample": len(sample),
            "reference_tokens": int(gaps.size),
            "worst_logit_gap": worst,
            "mean_logit_gap": mean,
            "served_is_reference_argmax_share": float(np.mean(gaps == 0.0)) if gaps.size else None,
            "logit_margin": self.check["logit_margin"],
            "mean_logit_gap_limit": self.check["mean_logit_gap"],
            "correct": not failed and bool(sample) and within,
        }
