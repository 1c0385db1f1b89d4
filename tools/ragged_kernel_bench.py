"""The ragged paged-attention kernel alone, on the chip, at the serving cells'
shapes (``deepspeed_tpu/ops/transformer/decode_attention.py::ragged_paged_attention``):
``CALLS`` calls in one program, walking the pool's layers as a serving step's
layer loop does, for three mixes of rows

* ``decode16``: 16 decode rows holding 200-1,500 tokens (the decode-heavy cells),
* ``chat4``: 2-4 live rows of 16 (the chat cell),
* ``mixed``: a prefill chunk of the wide window among decode rows,
* ``decode_long``: every row a decode row holding 600-4,096 tokens, ~1,650 in
  the mean (what ``long_decode`` leaves in a 64-row cell),
* ``chunk1``: one prefill chunk of the wide window and dead rows beside it
  (a trip of ``hybrid_decode.wide_attention``: 4 rows of 128),

and prints microseconds a call against the least the chip could take
(``benchmark/kernels/ragged_paged_attention.py::min_seconds``), the seconds
the program took to trace and to lower (what every process pays at set-up,
whatever the compilation cache holds), and whether outputs and pools agree
with XLA's scatter + gather. It is how the tile sizes at the head of the
kernel were chosen. The ``laguna_*`` models are Laguna-S-2.1's two kinds of
layer, groups of 6 (48 query heads over 8 KV heads, every key) and of 9 (72
over 8, a window of 512 keys on a ring of 10 pages a row), at 64 decode rows
and at a wide trip's 4 rows; a model with a window is measured against
``benchmark/kernels/windowed_paged_attention.py::min_seconds``.
``mimo_window`` is MiMo-V2.5's window layer (64 query heads over 8 KV heads,
keys of 192 in pages of 256 lanes, values of 128, a sink a head, a window of
128 keys on a ring of 4 pages a row). ``--rows-per-step 1,2,4,8,16`` runs a
window model's narrow mixes once a value with that many rows a grid step
(``window_rows_per_grid_step`` in the printout is what the kernel was built
with; 1 is the form that walks one row a step in halves), and each ``--set
_RING_SLOTS=N`` once more with that constant of the kernel's file: how
``_BLOCK_ROWS`` and ``_RING_SLOTS`` there were chosen.

The model ``glm47`` is the latent kernel's (``ops/transformer/latent_attention.py::
latent_paged_attention``: 20 heads over one entry of 576 a token in pages of
640 lanes, GLM-4.7-Flash's 16 layers of 4,097 pages) under the mix

* ``decode64_long``: 64 decode rows holding 600-4,096 tokens, ~1,650 in the
  mean (what ``long_decode`` leaves in ``glm47_flash_long_decode``),

and ``decode64_short`` (the same rows at a quarter of those contexts: the
decode-heavy cells' lengths), against
``benchmark/kernels/latent_paged_attention.py::min_seconds``. It is how the
narrow form's half, key tiles and ring were chosen: each ``--set`` runs the
mixes once more with other values of that file's constants, and ``--root``
measures another checkout's kernel (the parent's) beside them.

The model ``dots3_sparse`` is a sparse latent layer's decode row over its
chosen keys (``ops/transformer/sparse_latent_attention.py``: dots3-note-prev's
128 heads over one entry of 576 in pages of 640 lanes, 32 rows, a table of 256
pages, 3 layers) under the mix ``decode32``: the longest row at 4k, 8k and 16k
live keys and the others down to three quarters of it, each with 26% of the
longest row's keys chosen and with all of them, seeded scores. The
two forms of the T = 1 attention, selection included, us a call: ``gather``
(one stable sort with the pool address as payload, a gather of the chosen
entries, the softmax over them) and ``walk`` (``hybrid_moe.chosen_keys``'s
mask, the kernel over the row's live pages), each selection alone (``sort``,
``counts``, and the same mask from one sort of the scores), the floor of the walk's bytes beside them, and the two rates the
form rule's constant (``WALK_MAX_MULTIPLE``) is reckoned from: ns a key the
kernel walks, ns an entry XLA gathers and attends. The walk form is held to
the gather form ON THE CHIP at every shape before any timing; each ``--set``
runs the walk once more with other values of ``_WALK_HALF_KEYS``,
``_WALK_TILE_KEYS``, ``_WALK_RING``.

    chiprun -- python3 tools/ragged_kernel_bench.py [--models mistral7b,olmoe] [--mixes decode16,chat4,mixed]
    chiprun -- python3 tools/ragged_kernel_bench.py --models dots3_sparse [--set _WALK_HALF_KEYS=2048,_WALK_RING=2 ...]
    chiprun -- python3 tools/ragged_kernel_bench.py --models laguna_window,mimo_window --mixes decode_long --rows-per-step 1,2,4,8,16
    chiprun -- python3 tools/ragged_kernel_bench.py --models glm47 [--root DIR] [--mixes decode64_long] [--set _NARROW_HALF_KEYS=768,_NARROW_RING=2 ...]
    python3 tools/ragged_kernel_bench.py --rehearse [--models glm47]      # tiny, on the CPU: the control flow only
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CALLS = 100
# (query heads, kv heads, head size, layers, rows, pages a row, page, wide window, keys a query sees or None: all)
MODELS = {
    "mistral7b": (32, 8, 128, 16, 16, 38, 64, 128, None),
    "olmoe": (16, 16, 128, 12, 16, 24, 64, 128, None),
    "mistral7b_tp4": (8, 2, 128, 16, 8, 38, 64, 128, None),
    "laguna_full": (48, 8, 128, 3, 64, 64, 64, 128, None),
    "laguna_window": (72, 8, 128, 6, 64, 64, 64, 128, 512),
    "laguna_full_chunk": (48, 8, 128, 3, 4, 64, 64, 128, None),
    "laguna_window_chunk": (72, 8, 128, 6, 4, 64, 64, 128, 512),
    "mimo_window": (64, 8, 256, 5, 64, 64, 64, 128, 128),
}
TINY = {"tiny": (4, 2, 128, 2, 4, 6, 8, 8, None), "tiny_window": (18, 2, 128, 2, 4, 6, 8, 8, 8), "tiny_sinks": (8, 2, 256, 2, 4, 6, 8, 8, 8)}
# (lanes of a key the mathematics needs, lanes of a value) where they are not the page's, and a sink a head
WIDTHS = {"mimo_window": (192, 128), "tiny_sinks": (192, 128)}
# (query heads, value lanes, rotated lanes, lanes a page stores, layers, rows, pages a row, page)
LATENT = {"glm47": (20, 512, 64, 640, 16, 64, 64, 64)}
LATENT_TINY = {"glm47": (20, 128, 32, 256, 2, 6, 12, 8)}
# (query heads, value lanes, rotated lanes, lanes a page stores, layers, rows, pages a row, page, live keys a row, chosen shares)
SPARSE = {"dots3_sparse": (128, 512, 64, 640, 3, 32, 256, 64, (4096, 8192, 16384), (0.26, 1.0))}
SPARSE_TINY = {"dots3_sparse": (8, 128, 32, 256, 2, 4, 12, 8, (40, 96), (0.26, 1.0))}
SPARSE_GAP = 0.03  # the two forms' outputs, bfloat16 entries of unit variance: they differ by the order of their sums and p's rounding


def mixes(rng, rows, maxp, page, wide):
    """``{mix: (window width, [(new tokens, keys after the step)] a row)}``."""
    import numpy as np

    longest = maxp * page

    def decode(n):
        return [(1, int(kv)) for kv in rng.integers(min(200, longest // 2), min(1500, longest), n)]

    chunk = (wide, int(min(longest, 4 * wide)))  # a prompt's fourth chunk
    few = [(0, 0)] * rows  # three live rows among dead ones
    for row, live in zip((1, rows // 2, rows - 2), decode(3)):
        few[row] = live
    # contexts as a long_decode cell's window finds them: none shorter than a seventh of the longest, a long tail up to it
    long = rng.permutation(longest * (0.146 + 0.854 * (1 - (1 - np.arange(rows) / max(1, rows - 1)) ** (1 / 2.33))))
    return {
        "decode16": (1, decode(rows)),
        "chat4": (1, few),
        "mixed": (wide, [chunk] + decode(rows - 3) + [(0, 0)] * 2),
        "decode_long": (1, [(1, max(1, int(kv))) for kv in long]),
        "chunk1": (wide, [chunk] + [(0, 0)] * (rows - 1)),
    }


def tried_constants(args):
    """Each ``--set NAME=INT,...`` as a dict of a kernel file's constants."""
    return [dict((name, int(value)) for name, value in (pair.split("=") for pair in text.split(","))) for text in args.set]


def timed(program, operands, carried, rehearse):
    """The least seconds of five runs of ``program`` and the operands as the last run left them (``carried``:
    the donated ones' places among them, which the program's later outputs fill again)."""
    best = float("inf")
    for _ in range(1 if rehearse else 5):
        t = time.perf_counter()
        acc, *pools = program(*operands)
        acc.block_until_ready()
        best = min(best, time.perf_counter() - t)
        for at, pool in zip(carried, pools):
            operands[at] = pool
    return best, operands


def latent_bench(args, model, dims, calls, peak):
    """The latent model's mixes through its kernel, each once with the file's constants and once a ``--set``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.kernels import latent_paged_attention as k
    from deepspeed_tpu.ops.transformer import latent_attention as module

    NH, Dv, rope, D, L, R, maxp, P = dims
    NP, longest = R * maxp + 1, maxp * P
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    layer = jax.random.normal(key, (1, NP, P, D), jnp.bfloat16)
    table = jnp.asarray(1 + rng.permutation(R * maxp).reshape(R, maxp), jnp.int32)  # scattered, as a pool ages
    q = jax.random.normal(jax.random.fold_in(key, 1), (R, 1, NH, Dv + rope), jnp.bfloat16)
    new = jax.random.normal(jax.random.fold_in(key, 2), (R, 1, Dv + rope), jnp.bfloat16)
    q_lens = jnp.ones((R,), jnp.int32)
    attend = functools.partial(
        module.latent_paged_attention, value_lanes=Dv, scale=(Dv + rope) ** -0.5, interpret=args.rehearse or None,
        pages_per_buffer=args.pages_per_buffer,
    )

    # contexts as the cell's window finds them, the same for every seed: none shorter than a seventh of the longest,
    # a long tail up to it (the quantiles of 0.146 + 0.854 Beta(1, 2.33)), in a shuffled order
    long = rng.permutation(longest * (0.146 + 0.854 * (1 - (1 - np.arange(R) / max(1, R - 1)) ** (1 / 2.33))))
    tried = [{}] + tried_constants(args)
    pool = jnp.concatenate([layer] * L)
    for mix, kv in {"decode64_long": long.astype(np.int64), "decode64_short": (long / 4).astype(np.int64)}.items():
        if mix not in args.mixes.split(","):
            continue
        kv_lens = jnp.asarray(kv, jnp.int32)
        # what XLA's scatter + gather makes of one call, on ONE layer's pages (the whole pool twice does not fit the chip)
        want_o, want_pool = jax.jit(functools.partial(attend, impl="xla"))(q, new, layer, 0, table, kv_lens, q_lens)
        floor, bound = k.min_seconds([(1, int(n)) for n in kv], NH, Dv, rope, peak)
        for constants in tried:

            def many(q, new, pool, table, kv_lens, q_lens):  # a function of its own a trial: jit keeps no trace of another's constants
                # CALLS layers back to back in one program, the pool carried as the layer loop carries it
                def body(i, carry):
                    acc, pool = carry
                    o, pool = attend(q, new, pool, i % L, table, kv_lens, q_lens, impl="pallas")
                    return acc + o.astype(jnp.float32), pool

                return jax.lax.fori_loop(0, calls, body, (jnp.zeros(q.shape[:3] + (Dv,), jnp.float32), pool))

            defaults = {name: getattr(module, name) for name in constants}
            for name, value in constants.items():
                setattr(module, name, value)
            try:
                form = "the parent's double buffer"  # a checkout from before the ring (--root) has no forms
                if hasattr(module, "_latent_tiles"):
                    C, CK, _, N = module._latent_tiles(NH, 1, P, D, maxp, 2, args.pages_per_buffer)
                    form = f"{'narrow' if NH < module._TILE_ROWS else 'wide'}: {N} halves of {C * P} keys, key tiles of {'/'.join(str(n * P) for n in range(CK, C + 1, CK))}"
                operands = [q, new, pool, table, kv_lens, q_lens]
                t0 = time.perf_counter()
                traced = jax.jit(many, donate_argnums=(2,)).trace(*operands)
                t1 = time.perf_counter()
                lowered = traced.lower()
                t2 = time.perf_counter()
                program = lowered.compile()
                t3 = time.perf_counter()
                got_o, got_pool = jax.jit(functools.partial(attend, impl="pallas"))(q, new, layer, 0, table, kv_lens, q_lens)
            finally:
                for name, value in defaults.items():
                    setattr(module, name, value)
            gap = float(jnp.max(jnp.abs(got_o.astype(jnp.float32) - want_o.astype(jnp.float32))))
            same_pool = bool(jnp.array_equal(got_pool[:, 1:], want_pool[:, 1:]))
            del got_pool
            best, (_, _, pool, *_) = timed(program, operands, (2,), args.rehearse)
            print(
                f"{model:14s} {mix} W=1 rows {R} pages {sum(-(-n // P) for n in kv):4d}/{R * maxp} mean context {kv.mean():.0f} "
                f"{' '.join(f'{n}={v}' for n, v in constants.items()) or 'as the file has it'}: "
                f"{best / calls * 1e6:8.1f} us a call, floor {floor * 1e6:6.1f} us ({bound}), "
                f"{100 * floor / (best / calls):5.1f}% | {form} | trace {t1 - t0:.3f} s lower {t2 - t1:.3f} s compile {t3 - t2:.2f} s | "
                f"max |o - xla| {gap:.4f} pool {'same' if same_pool else 'DIFFERS'}",
                flush=True,
            )


def sparse_bench(args, model, dims, calls, peak):
    """A sparse layer's decode rows over their chosen keys, both forms, at each length and chosen share."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models import hybrid_moe as hm
    from deepspeed_tpu.ops.transformer import sparse_latent_attention as module

    NH, Dv, rope, D, L, R, maxp, P, lengths, shares = dims
    lengths = [int(n) for n in args.live_keys.split(",")] if args.live_keys else lengths
    shares = [float(x) for x in args.shares.split(",")] if args.shares else shares
    NP, S = R * maxp + 1, maxp * P
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    pool = jax.random.normal(key, (L, NP, P, D), jnp.bfloat16)
    table = jnp.asarray(1 + rng.permutation(R * maxp).reshape(R, maxp), jnp.int32)  # scattered, as a pool ages
    q = jax.random.normal(jax.random.fold_in(key, 1), (R, NH, D), jnp.bfloat16)
    scores = jax.random.normal(jax.random.fold_in(key, 2), (R, S), jnp.float32)  # seeded: the chosen lie evenly over a row's pages
    scale = (Dv + rope) ** -0.5
    at = jnp.arange(S, dtype=jnp.int32)
    interpret = True if args.rehearse else None
    tried = [{}] + tried_constants(args)

    def gather(topk, q, scores, pool, layer, kv_lens):
        return module._chosen_entries_attention(q, scores, pool, layer, table, kv_lens, topk, Dv, scale)

    def walk(topk, q, scores, pool, layer, kv_lens):
        mask = hm.chosen_keys(scores, at[None, :] < kv_lens[:, None], topk)
        return module._walk_chosen_pages(q, mask, pool, layer, table, kv_lens, Dv, scale, interpret=interpret)

    def sort(topk, q, scores, pool, layer, kv_lens):  # the gather form's selection alone: one stable sort, a payload beside the key
        _, where = jax.lax.sort((jnp.where(at[None, :] < kv_lens[:, None], -scores, jnp.inf), jnp.broadcast_to(at, scores.shape)), dimension=1, is_stable=True, num_keys=1)
        return where[:, :1, None].astype(jnp.float32)

    def counts(topk, q, scores, pool, layer, kv_lens):  # the walk form's selection alone
        return jnp.sum(hm.chosen_keys(scores, at[None, :] < kv_lens[:, None], topk), axis=1, dtype=jnp.float32)[:, None, None]

    def sort_mask(topk, q, scores, pool, layer, kv_lens):  # the same mask from ONE sort of the scores alone: the k-th largest, then the ties by position
        live = at[None, :] < kv_lens[:, None]
        keyed = jnp.where(live, scores, -jnp.inf)
        kth = jnp.sort(keyed, axis=1)[:, S - min(topk, S)][:, None]
        above, tied = keyed > kth, keyed == kth
        mask = live & (above | (tied & (jnp.cumsum(tied, axis=1) <= topk - jnp.sum(above, axis=1, keepdims=True))))
        return jnp.sum(mask, axis=1, dtype=jnp.float32)[:, None, None]

    def us_a_call(form, topk, kv_lens):
        def many(q, scores, pool, kv_lens):  # ``calls`` layers back to back in one program; the scores move so that no selection is hoisted
            def body(i, acc):
                return acc + form(topk, q, scores + i.astype(jnp.float32) * 1e-9, pool, i % L, kv_lens).astype(jnp.float32)

            return jax.lax.fori_loop(0, calls, body, jnp.zeros((R, NH, Dv), jnp.float32))

        program = jax.jit(many).lower(q, scores, pool, kv_lens).compile()
        best = float("inf")
        for _ in range(1 if args.rehearse else 5):
            t = time.perf_counter()
            program(q, scores, pool, kv_lens).block_until_ready()
            best = min(best, time.perf_counter() - t)
        return best / calls * 1e6

    for live in lengths:
        # the longest row at ``live`` keys, the others down to three quarters of it in even steps: no two rows end in the same place of a half
        kv_lens = jnp.asarray([live - (r * (live // 4)) // max(1, R - 1) - (r > 0) * (P // 2 - 1) for r in range(R)], jnp.int32)
        walked = int(jnp.sum(kv_lens))
        floor = sum(-(-int(n) // P) for n in kv_lens) * P * D * 2 / peak["hbm_bytes_per_s"] * 1e6
        for share in shares:
            topk = min(S, max(1, int(round(share * live))))
            want = jax.jit(functools.partial(gather, topk))(q, scores, pool, 1, kv_lens).astype(jnp.float32)
            times = {}
            for constants in tried:
                defaults = {name: getattr(module, name) for name in constants}
                for name, value in constants.items():
                    setattr(module, name, value)
                try:
                    C, CK, N = module._walk_tiles(P, maxp)
                    got = jax.jit(functools.partial(walk, topk))(q, scores, pool, 1, kv_lens).astype(jnp.float32)
                    gap = float(jnp.max(jnp.abs(got - want)))
                    if not gap < SPARSE_GAP:  # held to the gather form before any timing
                        sys.exit(f"ragged_kernel_bench: the walk form is not the gather form at {live} keys, {topk} chosen: max |walk - gather| {gap}")
                    label = " ".join(f"{n}={v}" for n, v in constants.items()) or "walk"
                    times[label] = (us_a_call(walk, topk, kv_lens), f"{N} halves of {C * P} keys, key tiles of {CK * P}", gap)
                finally:
                    for name, value in defaults.items():
                        setattr(module, name, value)
            t_gather, t_sort, t_counts, t_sort_mask = (us_a_call(form, topk, kv_lens) for form in (gather, sort, counts, sort_mask))
            chosen = R * min(topk, live)
            for label, (t_walk, form, gap) in times.items():
                print(
                    f"{model:14s} decode{R} live keys {live:5d} chosen {topk:5d} ({100 * share:3.0f}%) {label}: gather {t_gather:8.1f} us a call "
                    f"(its sort {t_sort:7.1f}), walk {t_walk:8.1f} (its counts {t_counts:7.1f}; the mask by one sort {t_sort_mask:7.1f}), the walk's bytes {floor:7.1f} us ({100 * floor / (t_walk - t_counts):5.1f}% "
                    f"of the kernel's time) | {1e3 * (t_walk - t_counts) / walked:6.2f} ns a key walked, {1e3 * (t_gather - t_sort) / chosen:6.2f} ns an entry gathered | "
                    f"{form} | max |walk - gather| {gap:.4f}",
                    flush=True,
                )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--models", default="mistral7b,olmoe")
    ap.add_argument("--mixes", default="decode16,chat4,mixed,decode64_long,decode64_short,decode_long,chunk1")
    ap.add_argument("--root", default=ROOT, help="the checkout whose deepspeed_tpu is measured")
    ap.add_argument("--pages-per-buffer", type=int, default=None)
    ap.add_argument(
        "--rows-per-step", default="", metavar="INT,...",
        help="rows a grid step of the ragged kernel attends, each value a run of its own (a window model's narrow mixes take "
        "them; elsewhere the kernel keeps one row a step)",
    )
    ap.add_argument(
        "--set", action="append", default=[], metavar="NAME=INT,...",
        help="constants of the kernel's file to try in place of the file's, a run each time it is given: glm47 "
        "_NARROW_HALF_KEYS=768,_NARROW_RING=2 and dots3_sparse _WALK_HALF_KEYS=2048 (beside the file's own), the ragged models _RING_SLOTS=6 (in its place)",
    )
    ap.add_argument("--live-keys", default="", metavar="INT,...", help="dots3_sparse: the rows' live keys, in place of 4096,8192,16384")
    ap.add_argument("--shares", default="", metavar="FLOAT,...", help="dots3_sparse: the chosen shares of a row's live keys, in place of 0.26,1.0")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import files
    from benchmark.kernels import ragged_paged_attention as k
    from benchmark.kernels import windowed_paged_attention as kw
    from deepspeed_tpu.ops.transformer import decode_attention
    from deepspeed_tpu.ops.transformer.decode_attention import ragged_paged_attention
    from deepspeed_tpu.ops.transformer.paged_attention import ragged_paged_attention as front

    calls = 2 if args.rehearse else CALLS
    names = args.models.split(",")
    latent = [name for name in names if name in LATENT]
    sparse = [name for name in names if name in SPARSE]
    models = TINY if args.rehearse and not latent + sparse else {name: MODELS[name] for name in names if name not in {**LATENT, **SPARSE}}
    peak = files.load_json(files.HERE, "peaks.json")["TPU v5 lite" if args.rehearse else jax.devices()[0].device_kind]
    for model in latent:
        latent_bench(args, model, (LATENT_TINY if args.rehearse else LATENT)[model], calls, peak)
    for model in sparse:
        sparse_bench(args, model, (SPARSE_TINY if args.rehearse else SPARSE)[model], calls, peak)
    if not models:  # a --set names constants of the latent or the sparse file alone
        return
    swept = [int(n) for n in args.rows_per_step.split(",")] if args.rows_per_step else [None]
    tried = tried_constants(args) or [{}]
    defaults = {name: getattr(decode_attention, name) for constants in tried for name in constants}
    for model, (NH, NKV, D, L, R, maxp, P, wide, window) in models.items():
        dk, dv = WIDTHS.get(model, (D, D))
        sinks = jnp.linspace(-1.0, 2.0, NH, dtype=jnp.float32) if model in WIDTHS else None
        # a row's pages: its own all the way, or with a window a ring of those a chunk writes and the window's before it
        held = maxp if window is None else -(-wide // P) + -(-(window - 1) // P)
        NP = R * held + 1
        rng = np.random.default_rng(0)
        key = jax.random.PRNGKey(0)
        pools = [jax.random.normal(jax.random.fold_in(key, i), (L, NP, NKV, P, lanes), jnp.bfloat16) for i, lanes in ((1, D), (2, dv))]
        if window is None:
            table = jnp.asarray(1 + rng.permutation(R * maxp).reshape(R, maxp), jnp.int32)  # scattered, as a pool ages
        else:
            table = jnp.asarray(1 + np.arange(R)[:, None] * held + np.arange(maxp)[None, :] % held, jnp.int32)
        for mix, (W, rows) in mixes(rng, R, maxp, P, wide).items():
            if mix not in args.mixes.split(","):
                continue
            q_lens = jnp.asarray([n for n, _ in rows], jnp.int32)
            kv_lens = jnp.asarray([kv for _, kv in rows], jnp.int32)
            q, k_new, v_new = (
                jax.random.normal(jax.random.fold_in(key, 3 + i), (R, W, heads, lanes), jnp.bfloat16)
                for i, (heads, lanes) in enumerate(((NH, dk), (NKV, dk), (NKV, dv)))
            )
            floor, bound = k.min_seconds(rows, NH, NKV, D, peak) if window is None else kw.min_seconds(rows, NH, NKV, dk, dv, peak, window)
            pages = sum(-(-kw.keys_read(n, kv, window) // P) for n, kv in rows if n)
            walked = maxp if window is None else min(maxp, -(-(window - 1) // P) + -(-W // P) + 1)  # as the kernel's wrapper reckons it
            C, CK, TQ, HB = decode_attention._ragged_tiles(NKV, NH // NKV, W, P, D, walked, 2, args.pages_per_buffer)
            for constants, rows_per_step in [(constants, n) for constants in tried for n in swept]:
                # a checkout from before the block form (--root) has neither the argument nor the rule
                block = {} if rows_per_step is None else dict(rows_per_step=rows_per_step)
                attend = functools.partial(
                    ragged_paged_attention, interpret=args.rehearse, pages_per_buffer=args.pages_per_buffer, window=window, sinks=sinks, **block
                )
                for name, value in constants.items():
                    setattr(decode_attention, name, value)
                RB, PR = 1, 0
                if hasattr(decode_attention, "_ragged_block") and (args.pages_per_buffer is None or rows_per_step):
                    RB, PR = decode_attention._ragged_block(NKV, NH // NKV, W, P, D, dv, CK, 2, window, rows_per_step)
                form = f"group {NH // NKV}{'' if window is None else f' window {window}'}: "
                form += f"halves of {C} pages" if RB == 1 else f"window_rows_per_grid_step {RB}, a ring of {decode_attention._RING_SLOTS} slots of {PR} pages"
                form += f", key tiles of {CK * P}, query tiles of {TQ} rows, {HB} kv heads a tile"

                def many(q, k_new, v_new, k_pages, v_pages, table, kv_lens, q_lens):
                    # CALLS layers back to back in one program, the pools carried as the layer loop carries them
                    def body(i, carry):
                        acc, kp, vp = carry
                        o, kp, vp = attend(q, k_new, v_new, kp, vp, i % L, table, kv_lens, q_lens)
                        return acc + o.astype(jnp.float32), kp, vp

                    return jax.lax.fori_loop(0, calls, body, (jnp.zeros(q.shape[:3] + (dv,), jnp.float32), k_pages, v_pages))

                try:
                    operands = (q, k_new, v_new, *pools, table, kv_lens, q_lens)
                    t0 = time.perf_counter()
                    traced = jax.jit(many, donate_argnums=(3, 4)).trace(*operands)
                    t1 = time.perf_counter()
                    lowered = traced.lower()
                    t2 = time.perf_counter()
                    program = lowered.compile()
                    t3 = time.perf_counter()
                    got_o, got_k, got_v = jax.jit(attend)(q, k_new, v_new, *pools, 0, table, kv_lens, q_lens)
                finally:
                    for name, value in defaults.items():
                        setattr(decode_attention, name, value)
                # what XLA's write + gather makes of one call, before the pools are donated
                want_o, want_k, want_v = jax.jit(front, static_argnames=("impl", "window"))(
                    q, k_new, v_new, *pools, 0, table, kv_lens, q_lens, impl="xla", window=window, sinks=sinks
                )
                live = (jnp.arange(W)[None, :] < q_lens[:, None])[:, :, None, None]
                gap = float(jnp.max(jnp.abs(jnp.where(live, got_o.astype(jnp.float32) - want_o.astype(jnp.float32), 0))))
                same_pools = all(bool(jnp.array_equal(g[:, 1:], w[:, 1:])) for g, w in ((got_k, want_k), (got_v, want_v)))
                del want_k, want_v, got_k, got_v
                best, (_, _, _, *pools, _, _, _) = timed(program, [q, k_new, v_new, *pools, table, kv_lens, q_lens], (3, 4), args.rehearse)
                print(
                    f"{model:19s} {mix:11s} W={W:<4d} live rows {sum(1 for n, _ in rows if n):2d} pages {pages:4d}/{R * held}: "
                    f"{best / calls * 1e6:8.1f} us a call, floor {floor * 1e6:6.1f} us ({bound}), "
                    f"{100 * floor / (best / calls):5.1f}% | {form} | trace {t1 - t0:.3f} s lower {t2 - t1:.3f} s compile {t3 - t2:.2f} s | "
                    f"max |o - xla| {gap:.4f} pools {'same' if same_pools else 'DIFFER'}",
                    flush=True,
                )


if __name__ == "__main__":
    main()
