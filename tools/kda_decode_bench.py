"""The recurrence scope of ONE linear layer alone, on the chip, at the two
cells' shapes (``deepspeed_tpu/ops/transformer/linear_attention.py::kda_decode``:
a one-token row from its projections to the recurrence's output, in place on
the state pool and the tail pool): ``CALLS`` calls in one program, walking the
pools' layers as a serving step's layer loop does, for

* ``kimi``: 64 rows of 32 heads of 128 on 10 layers' pools (``kimi_linear_long_decode``),
* ``solar``: 64 rows of 64 heads of 128 on 3 layers' pools (``solar_open2_decode_heavy``),

each with every row live (``live64``) and with a quarter of the rows dead
(``live48``: a dead row works on the spare slot and is no byte of the floor),
and prints microseconds a call against the least the chip could take for the
live rows (``benchmark/kernels/kda_recurrence.py::min_seconds``: what
``serve.kda_state_roofline`` counts), the seconds the program took to compile,
and whether outputs, states and tails agree with the XLA form.

    chiprun -- python3 tools/kda_decode_bench.py [--models kimi,solar] [--root DIR]
    python3 tools/kda_decode_bench.py --rehearse      # tiny, on the CPU: the control flow only
"""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CALLS = 100
# (heads, head size, taps, layers, rows)
MODELS = {"kimi": (32, 128, 4, 10, 64), "solar": (64, 128, 4, 3, 64)}
TINY = {"tiny": (16, 128, 4, 2, 4)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--models", default="kimi,solar")
    ap.add_argument("--root", default=ROOT, help="the checkout whose deepspeed_tpu is measured")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import files
    from benchmark.kernels import kda_recurrence as k
    from deepspeed_tpu.ops.transformer.linear_attention import kda_decode

    calls = 2 if args.rehearse else CALLS
    impl = "pallas_interpret" if args.rehearse else "pallas"
    peak = files.load_json(files.HERE, "peaks.json")["TPU v5 lite" if args.rehearse else jax.devices()[0].device_kind]
    models = TINY if args.rehearse else {name: MODELS[name] for name in args.models.split(",")}
    for model, (H, D, K, L, R) in models.items():
        key = jax.random.PRNGKey(0)
        qkv = jax.random.normal(jax.random.fold_in(key, 1), (R, 3, H, D), jnp.bfloat16)
        log_a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (R, H, D)) - 2.0)
        beta = 2 * jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 3), (R, H)))
        taps = (jax.random.normal(jax.random.fold_in(key, 4), (K, 3, H, D)) * 0.5).astype(jnp.bfloat16)
        state = jax.random.normal(jax.random.fold_in(key, 5), (1, R + 1, H, D, D)) * 0.1
        tails = jax.random.normal(jax.random.fold_in(key, 6), (1, R + 1, K - 1, 3, H, D), jnp.bfloat16)
        slots = jnp.asarray(np.random.default_rng(0).permutation(R), jnp.int32)  # scattered, as a pool ages
        fresh = jnp.zeros((R,), bool).at[R // 2].set(True)
        for mix, dead in {f"live{R}": 0, f"live{R - R // 4}": R // 4}.items():
            live = jnp.arange(R) % 4 != 3 if dead else jnp.ones((R,), bool)
            operands = (qkv, log_a, beta, taps)
            want = jax.jit(lambda s, t: kda_decode(*operands, s, t, 0, slots, live, fresh, impl="xla"))(state, tails)
            got = jax.jit(lambda s, t: kda_decode(*operands, s, t, 0, slots, live, fresh, impl=impl))(state, tails)
            # a dead row's output is nobody's; the spare slot (the pools' last) is the dead rows' alone
            gaps = [float(jnp.max(jnp.abs(jnp.where(live[:, None, None], got[0] - want[0], 0))))]
            gaps += [float(jnp.max(jnp.abs(g[0, :R].astype(jnp.float32) - w[0, :R].astype(jnp.float32)))) for g, w in zip(got[1:], want[1:])]
            del want, got

            def many(state, tails):
                # CALLS layers back to back in one program, the pools carried as the layer loop carries them
                def body(i, carry):
                    acc, state, tails = carry
                    o, state, tails = kda_decode(*operands, state, tails, i % L, slots, live, fresh, impl=impl)
                    return acc + o, state, tails

                return jax.lax.fori_loop(0, calls, body, (jnp.zeros((R, H, D), jnp.float32), state, tails))

            pools = [jnp.concatenate([state] * L), jnp.concatenate([tails] * L)]
            t0 = time.perf_counter()
            program = jax.jit(many, donate_argnums=(0, 1)).lower(*pools).compile()
            t1 = time.perf_counter()
            best = float("inf")
            for _ in range(1 if args.rehearse else 5):
                t = time.perf_counter()
                acc, *pools = program(*pools)
                acc.block_until_ready()
                best = min(best, time.perf_counter() - t)
            del pools
            rows = [(int(alive), 0) for alive in np.asarray(live)]
            floor, bound = k.min_seconds(rows, H, D, peak, K)
            print(
                f"{model:6s} {mix:7s} rows {R} heads {H}: {best / calls * 1e6:8.1f} us a call, floor {floor * 1e6:6.1f} us ({bound}), "
                f"{100 * floor / (best / calls):5.1f}% | compile {t1 - t0:.2f} s | max |o - xla| {gaps[0]:.2e} state {gaps[1]:.2e} tails {gaps[2]:.2e}",
                flush=True,
            )


if __name__ == "__main__":
    main()
