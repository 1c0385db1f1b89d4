"""The ragged paged-attention kernel alone, on the chip, at the serving cells'
shapes (``deepspeed_tpu/ops/transformer/decode_attention.py::ragged_paged_attention``):
``CALLS`` calls in one program, walking the pool's layers as a serving step's
layer loop does, for three mixes of rows

* ``decode16``: 16 decode rows holding 200-1,500 tokens (the decode-heavy cells),
* ``chat4``: 2-4 live rows of 16 (the chat cell),
* ``mixed``: a prefill chunk of the wide window among decode rows,

and prints microseconds a call against the least the chip could take
(``benchmark/kernels/ragged_paged_attention.py::min_seconds``), the seconds
the program took to trace and to lower (what every process pays at set-up,
whatever the compilation cache holds), and whether outputs and pools agree
with XLA's scatter + gather. It is how the tile sizes at the head of the
kernel were chosen.

    chiprun -- python3 tools/ragged_kernel_bench.py [--models mistral7b,olmoe] [--mixes decode16,chat4,mixed]
    python3 tools/ragged_kernel_bench.py --rehearse      # tiny, on the CPU: the control flow only
"""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CALLS = 100
# (query heads, kv heads, head size, layers, rows, pages a row, page, wide window)
MODELS = {
    "mistral7b": (32, 8, 128, 16, 16, 38, 64, 128),
    "olmoe": (16, 16, 128, 12, 16, 24, 64, 128),
    "mistral7b_tp4": (8, 2, 128, 16, 8, 38, 64, 128),
}
TINY = {"tiny": (4, 2, 128, 2, 4, 6, 8, 8)}


def mixes(rng, rows, maxp, page, wide):
    """``{mix: (window width, [(new tokens, keys after the step)] a row)}``."""
    longest = maxp * page

    def decode(n):
        return [(1, int(kv)) for kv in rng.integers(min(200, longest // 2), min(1500, longest), n)]

    chunk = (wide, int(min(longest, 4 * wide)))  # a prompt's fourth chunk
    few = [(0, 0)] * rows  # three live rows among dead ones
    for row, live in zip((1, rows // 2, rows - 2), decode(3)):
        few[row] = live
    return {
        "decode16": (1, decode(rows)),
        "chat4": (1, few),
        "mixed": (wide, [chunk] + decode(rows - 3) + [(0, 0)] * 2),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--models", default="mistral7b,olmoe")
    ap.add_argument("--mixes", default="decode16,chat4,mixed")
    ap.add_argument("--pages-per-buffer", type=int, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import files
    from benchmark.kernels import ragged_paged_attention as k
    from deepspeed_tpu.ops.transformer.decode_attention import ragged_paged_attention
    from deepspeed_tpu.ops.transformer.paged_attention import ragged_paged_attention as front

    calls = 2 if args.rehearse else CALLS
    models = TINY if args.rehearse else {name: MODELS[name] for name in args.models.split(",")}
    peak = files.load_json(files.HERE, "peaks.json")["TPU v5 lite" if args.rehearse else jax.devices()[0].device_kind]
    for model, (NH, NKV, D, L, R, maxp, P, wide) in models.items():
        NP = R * maxp + 1
        rng = np.random.default_rng(0)
        key = jax.random.PRNGKey(0)
        pools = [jax.random.normal(jax.random.fold_in(key, i), (L, NP, NKV, P, D), jnp.bfloat16) for i in (1, 2)]
        table = jnp.asarray(1 + rng.permutation(R * maxp).reshape(R, maxp), jnp.int32)  # scattered, as a pool ages
        for mix, (W, rows) in mixes(rng, R, maxp, P, wide).items():
            if mix not in args.mixes.split(","):
                continue
            q_lens = jnp.asarray([n for n, _ in rows], jnp.int32)
            kv_lens = jnp.asarray([kv for _, kv in rows], jnp.int32)
            q, k_new, v_new = (
                jax.random.normal(jax.random.fold_in(key, 3 + i), (R, W, heads, D), jnp.bfloat16)
                for i, heads in enumerate((NH, NKV, NKV))
            )

            def many(q, k_new, v_new, k_pages, v_pages, table, kv_lens, q_lens):
                # CALLS layers back to back in one program, the pools carried as the layer loop carries them
                def body(i, carry):
                    acc, kp, vp = carry
                    o, kp, vp = ragged_paged_attention(
                        q, k_new, v_new, kp, vp, i % L, table, kv_lens, q_lens,
                        interpret=args.rehearse, pages_per_buffer=args.pages_per_buffer,
                    )
                    return acc + o.astype(jnp.float32), kp, vp

                return jax.lax.fori_loop(0, calls, body, (jnp.zeros(q.shape, jnp.float32), k_pages, v_pages))

            operands = (q, k_new, v_new, *pools, table, kv_lens, q_lens)
            t0 = time.perf_counter()
            traced = jax.jit(many, donate_argnums=(3, 4)).trace(*operands)
            t1 = time.perf_counter()
            lowered = traced.lower()
            t2 = time.perf_counter()
            program = lowered.compile()
            t3 = time.perf_counter()
            # what XLA's write + gather makes of one call, before the pools are donated
            want_o, want_k, want_v = jax.jit(front, static_argnames=("impl",))(
                q, k_new, v_new, *pools, 0, table, kv_lens, q_lens, impl="xla"
            )
            got_o, got_k, got_v = jax.jit(
                lambda *a: ragged_paged_attention(*a, interpret=args.rehearse, pages_per_buffer=args.pages_per_buffer)
            )(q, k_new, v_new, *pools, 0, table, kv_lens, q_lens)
            live = (jnp.arange(W)[None, :] < q_lens[:, None])[:, :, None, None]
            gap = float(jnp.max(jnp.abs(jnp.where(live, got_o.astype(jnp.float32) - want_o.astype(jnp.float32), 0))))
            same_pools = all(bool(jnp.array_equal(g[:, 1:], w[:, 1:])) for g, w in ((got_k, want_k), (got_v, want_v)))
            del want_k, want_v, got_k, got_v
            best = float("inf")
            for _ in range(1 if args.rehearse else 5):
                t = time.perf_counter()
                acc, *pools = program(q, k_new, v_new, *pools, table, kv_lens, q_lens)
                jax.block_until_ready(acc)
                best = min(best, time.perf_counter() - t)
            floor, bound = k.min_seconds(rows, NH, NKV, D, peak)
            pages = sum(-(-kv // P) for n, kv in rows if n)
            print(
                f"{model:14s} {mix:9s} W={W:<4d} live rows {sum(1 for n, _ in rows if n):2d} pages {pages:4d}/{R * maxp}: "
                f"{best / calls * 1e6:8.1f} us a call, floor {floor * 1e6:6.1f} us ({bound}), "
                f"{100 * floor / (best / calls):5.1f}% | trace {t1 - t0:.3f} s lower {t2 - t1:.3f} s compile {t3 - t2:.2f} s | "
                f"max |o - xla| {gap:.4f} pools {'same' if same_pools else 'DIFFER'}",
                flush=True,
            )


if __name__ == "__main__":
    main()
