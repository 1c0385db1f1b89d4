"""The one traffic generator. A traffic mix is a data file under
``benchmark/traffic/``; this module turns its parameters and ``--seed`` into
requests, arrival instants and training batches. A later PR adds a mix by
adding a file, never code.

The idea (a seeded trace of heavy-tailed arrivals) is that of
``deepspeed_tpu/utils/loadgen.py``; three things differ, on purpose:

* lengths are **stratified**: request *i* of *N* takes the ``(i + 0.5) / N``
  quantile of the stated distribution, so every seed offers the same multiset
  of work. In an open loop the seed permutes the pairing and the order; in a
  closed loop both are a rule of the round's number and the seed draws the
  token ids alone (``request_stream`` says why);
* an open loop has a fixed number of arrivals, ``round(rate * horizon)``,
  whose seeded gaps are scaled to span the horizon exactly (a Poisson
  process conditioned on its count), so every seed offers the same load;
* every request carries the instant it is **due**; latency is timed from
  there, not from when a busy driver got round to sending it.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class TrafficRequest:
    index: int
    prompt: np.ndarray  # int32 token ids
    max_new_tokens: int
    due_s: float = 0.0  # offset from the window's start (open loop)
    measured: bool = True  # False: one of the first tenth, which only fills the batch


def quantile(dist: Dict, u: float) -> float:
    """Inverse CDF of a length distribution at ``u`` in (0, 1)."""
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return min(max(x, dist.get("min", x)), dist.get("max", x))


def stratified_lengths(dist: Dict, n: int) -> np.ndarray:
    """The ``(i + 0.5) / n`` quantiles, as whole numbers of tokens."""
    return np.asarray([int(round(quantile(dist, (i + 0.5) / n))) for i in range(n)], np.int64)


def arrival_offsets(arrival: Dict, rate_rps: float, horizon_s: float, rng: np.random.Generator) -> np.ndarray:
    """``round(rate * horizon)`` due instants in ``[0, horizon)``: seeded
    gaps (``poisson``: exponential; ``pareto``: tail index ``alpha``) scaled
    so that they, and one trailing gap, span the horizon exactly."""
    n = int(round(rate_rps * horizon_s))
    if n < 1:
        raise ValueError(f"rate {rate_rps}/s over {horizon_s}s gives no request")
    process = arrival.get("process", "poisson")
    if process == "poisson":
        g = rng.exponential(1.0, n + 1)
    elif process == "pareto":
        g = rng.pareto(float(arrival["alpha"]), n + 1) + 1e-9
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    g *= horizon_s / g.sum()
    return np.cumsum(g)[:n] - g[0] * 0.5  # first arrival half a gap in


def request_stream(mix: Dict, block: int, vocab_size: int, seed: int):
    """An endless supply for a closed loop: block after block of ``block``
    requests, each block holding the same stratified multiset of lengths.
    A block's order, and its pairing of prompt with output length, follow
    from the block's number alone, the same for every seed: in a closed loop
    the order decides how many requests, prefill chunks and long contexts
    fall inside the window, so an order drawn from the seed let the seed
    move the work (Solar's cell: 843-854 prefill chunks a window by the seed
    before, 838-846 by where the window ends since; PERF.md section 2,
    PR 44). The seed draws the token ids."""
    k = 0
    while True:
        for r in make_requests(mix, block, vocab_size, seed, salt=k, seeded_order=False):
            r.index += k * block
            yield r
        k += 1


def make_requests(mix: Dict, n: int, vocab_size: int, seed: int, salt: int = 0, seeded_order: bool = True) -> List[TrafficRequest]:
    """``n`` requests of the mix. Prompt and output lengths are stratified,
    then paired and ordered by a permutation: the seed's, or with
    ``seeded_order`` False one that ``salt`` alone decides. Token ids are
    uniform and always the seed's, so no two prompts share a prefix."""
    rng = np.random.default_rng([seed, 0x7AFF1C, salt])
    order = rng if seeded_order else np.random.default_rng([0x7AFF1C, salt])
    prompt_lens = order.permutation(stratified_lengths(mix["prompt_len"], n))
    out_lens = order.permutation(stratified_lengths(mix["output_len"], n))
    return [
        TrafficRequest(i, rng.integers(0, vocab_size, int(prompt_lens[i]), dtype=np.int32), int(out_lens[i]))
        for i in range(n)
    ]


def open_loop_trace(mix: Dict, window_s: float, vocab_size: int, seed: int, tail_s: float = 0.0) -> List[TrafficRequest]:
    """The requests of an open loop over ``window_s`` with their due
    offsets, in due order. The first tenth of them only fills the batch
    (``measured`` False); they and the measured rest are stratified apart,
    so the measured requests are the same multiset of lengths for every
    seed. ``tail_s`` adds arrivals at the same rate for that long after the
    window (the stretch a traced run goes on for): a third stratified set
    on a random stream of its own, not measured, which leaves the window
    exactly as it is without a tail."""
    arrival, rate = mix.get("arrival", {}), float(mix["rate_rps"])
    due = arrival_offsets(arrival, rate, window_s, np.random.default_rng([seed, 0xA221]))
    warm = int(round(0.1 * len(due)))
    groups = [(0, warm, False), (1, len(due) - warm, True)]
    if round(rate * tail_s) >= 1:
        tail = window_s + arrival_offsets(arrival, rate, tail_s, np.random.default_rng([seed, 0xA222]))
        groups.append((2, len(tail), False))
        due = np.concatenate([due, tail])
    reqs: List[TrafficRequest] = []
    for salt, n, measured in groups:
        for r in make_requests(mix, n, vocab_size, seed, salt=salt) if n else []:
            r.index, r.measured = len(reqs), measured
            reqs.append(r)
    for r, t in zip(reqs, due):
        r.due_s = float(t)
    return reqs


def train_batches(mix: Dict, rows: int, vocab_size: int, seed: int) -> np.ndarray:
    """``pool_batches`` host batches of ``rows`` sequences of
    ``seq_len + 1`` uniform token ids, int32, made in one call. The first
    batch repeats its first two sequences: the correctness check compares
    the engine's batch-mean loss with the plain reference on those two."""
    rng = np.random.default_rng([seed, 0x7EA1])
    toks = rng.integers(0, vocab_size, (int(mix["pool_batches"]), rows, int(mix["seq_len"]) + 1), dtype=np.int32)
    toks[0] = toks[0][np.arange(rows) % 2]
    return toks


def percentile(values, q: float) -> Optional[float]:
    """The ``q``-th percentile (0-100) with linear interpolation; ``inf``
    samples stay ``inf``; None for no sample."""
    if len(values) == 0:
        return None
    v = np.sort(np.asarray(values, np.float64))
    pos = (len(v) - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if not np.isfinite(v[hi]):
        return float("inf")
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))
