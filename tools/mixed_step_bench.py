"""The mixed serving program alone, on the chip, at the serving cells' shapes
(``deepspeed_tpu/inference/decode.py::build_ragged_step`` at 16 rows x 128:
``paged_ragged_r16_w128``), for windows that hold

* ``1+15``: one prompt chunk of 128 among 15 decode rows (143 live tokens of
  2,048 slots: what a decode-heavy cell's mixed step mostly is),
* ``2+14``, ``4+12``: two and four chunks,
* ``16+0``: sixteen chunks, every slot live (the start of a closed loop),

and prints device milliseconds a call: ``CALLS`` calls are enqueued back to
back on the donated pools and the last result is waited for, so the host's
toll a call is hidden behind the device. The weights are seeded noise of the
configuration's shapes (``benchmark/configs/*.json``), made on the device.

It is the evidence for ``decode.token_tile``: run it on the parent's copy and
on the change's side by side (``--root``: the checkout whose ``deepspeed_tpu``
is imported), and with ``--tiles`` to try other tiles than the rule's (a
checkout without ``token_tile`` computes the whole slab and takes none).

``--profile`` also traces ``CALLS`` calls of every fill with the profiler and
prints the device's operations by their time a call (``while`` operations
span their bodies): where a mixed step's time goes, by the compiler's names.

    chiprun -- python3 tools/mixed_step_bench.py [--root DIR] [--models mistral7b,olmoe] [--fills 1+15,16+0] [--tiles 256,512] [--profile]
    python3 tools/mixed_step_bench.py --rehearse      # tiny, on the CPU: the control flow only
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CALLS = 10
FILLS = "1+15,2+14,4+12,16+0"
CONFIGS = {"mistral7b": "mistral-7b-v0.3-l16.json", "olmoe": "olmoe-1b-7b-0125-l12.json"}


def window(rng, fill: str, rows: int, width: int, longest: int, vocab: int):
    """``chunks+decodes`` -> (tokens [R, W], lengths [R], q_lens [R]): chunk
    rows first, each a prompt's later chunk, then decode rows holding
    200-1,500 tokens, then dead rows."""
    import numpy as np

    chunks, decodes = (int(n) for n in fill.split("+"))
    if chunks + decodes > rows:
        raise ValueError(f"fill {fill!r} needs more than the window's {rows} rows")
    q_lens = np.zeros(rows, np.int32)
    lengths = np.zeros(rows, np.int32)
    q_lens[:chunks] = width
    lengths[:chunks] = width * rng.integers(0, max(1, min(3, longest // width - 1)), chunks)
    q_lens[chunks : chunks + decodes] = 1
    lengths[chunks : chunks + decodes] = rng.integers(min(200, longest // 2), min(1500, longest - 1), decodes)
    return rng.integers(0, vocab, (rows, width)).astype(np.int32), lengths, q_lens


def device_ops(trace_dir: str, calls: int, top: int = 16):
    """[(ms a call, events a call, the instruction's text)] of the newest trace under ``trace_dir``."""
    import collections

    from jax.profiler import ProfileData

    from benchmark.trace_reduce import find_xplane

    total, count = collections.Counter(), collections.Counter()
    for plane in ProfileData.from_file(find_xplane(trace_dir)).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        total[ev.name] += ev.duration_ns
                        count[ev.name] += 1
    return [(ns / calls / 1e6, count[name] / calls, name) for name, ns in total.most_common(top)]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=ROOT, help="the checkout whose deepspeed_tpu is measured")
    ap.add_argument("--models", default="mistral7b,olmoe")
    ap.add_argument("--fills", default=FILLS)
    ap.add_argument("--tiles", default="", help="token tiles to try in place of decode.token_tile's, e.g. 256,512")
    ap.add_argument("--profile", action="store_true", help="trace every fill and print its operations by time")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference import decode
    from deepspeed_tpu.models import MoETransformerLM, TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig
    from deepspeed_tpu.models.moe_transformer import MoETransformerConfig, olmoe_config

    def cell(model: str):
        """(config, model class, rows, width, page, pages a row) of a serving cell."""
        if args.rehearse:
            cfg = olmoe_config("tiny", dtype="float32", flash_attention=False, remat=False, max_seq_len=512)
            return cfg, MoETransformerLM, 16, 128, 8, 64
        with open(os.path.join(ROOT, "benchmark/configs", CONFIGS[model])) as f:
            conf = json.load(f)
        paged = conf["engine"]["init_inference"]["paged_kv"]
        kwargs = {**conf["model"]["kwargs"], "max_seq_len": paged["max_seq_len"]}
        moe = "num_experts" in kwargs
        cfg = (MoETransformerConfig if moe else TransformerConfig)(**kwargs)
        return (cfg, MoETransformerLM if moe else TransformerLM, paged["max_slots"], paged["prefill_chunk"],
                paged["page_size"], paged["max_seq_len"] // paged["page_size"])

    dtype = jnp.float32 if args.rehearse else jnp.bfloat16
    calls = 2 if args.rehearse else CALLS
    rule = getattr(decode, "token_tile", None)
    tiles = [int(t) for t in args.tiles.split(",") if t] if rule is not None else []
    print(f"root {os.path.abspath(args.root)} on {jax.devices()[0].device_kind}", flush=True)
    for model in ["tiny"] if args.rehearse else args.models.split(","):
        cfg, lm, R, W, P, maxp = cell(model)
        shapes = jax.eval_shape(lambda: lm(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
        paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

        @jax.jit
        def seeded(key):
            def leaf(i, path, a):
                name = jax.tree_util.keystr(path)
                if "scale" in name:
                    return jnp.ones(a.shape, dtype)
                return (jax.random.normal(jax.random.fold_in(key, i), a.shape, jnp.float32) * 0.02).astype(dtype)

            return jax.tree_util.tree_unflatten(treedef, [leaf(i, path, a) for i, (path, a) in enumerate(paths)])

        params = seeded(jax.random.PRNGKey(0))
        n_pages = R * maxp + 1
        table = jnp.asarray(1 + np.random.default_rng(0).permutation(R * maxp).reshape(R, maxp), jnp.int32)
        for tile in tiles or [None]:
            if tile is not None:
                decode.token_tile = lambda cfg, tile=tile: tile
                decode._paged_program_cache.clear()
            used = "the slab" if rule is None else decode.token_tile(cfg)
            step = decode.build_ragged_step(cfg, R, W, P, attn_impl="xla" if args.rehearse else "auto")
            pools = [jnp.zeros((cfg.num_layers, n_pages, cfg.num_kv_heads, P, cfg.head_dim), dtype) for _ in range(2)]
            for fill in args.fills.split(","):
                rng = np.random.default_rng(1)
                tokens, lengths, q_lens = window(rng, fill, R, W, maxp * P, cfg.vocab_size)
                t0 = time.perf_counter()
                out, *pools = step(params, tokens, *pools, table, lengths, q_lens)  # compiles at the first fill
                jax.block_until_ready(out)
                first = time.perf_counter() - t0
                best = float("inf")
                for _ in range(1 if args.rehearse else 3):
                    t = time.perf_counter()
                    for _ in range(calls):
                        out, *pools = step(params, tokens, *pools, table, lengths, q_lens)
                    jax.block_until_ready(out)
                    best = min(best, (time.perf_counter() - t) / calls)
                print(
                    f"{model:10s} tile {used!s:>8} fill {fill:>5} live {int(q_lens.sum()):4d}/{R * W}: "
                    f"{best * 1e3:8.3f} ms a call (first call {first:.2f} s)",
                    flush=True,
                )
                if args.profile and not args.rehearse:  # the CPU backend writes no device plane
                    trace_dir = os.path.join(ROOT, ".benchmark_trace", "mixed_step_bench")
                    jax.profiler.start_trace(trace_dir)
                    for _ in range(calls):
                        out, *pools = step(params, tokens, *pools, table, lengths, q_lens)
                    jax.block_until_ready(out)
                    jax.profiler.stop_trace()
                    for ms, n, name in device_ops(trace_dir, calls):
                        print(f"    {ms:8.3f} ms  x{n:6.1f}  {name[:230]}", flush=True)
            del pools, step
        del params
        gc.collect()


if __name__ == "__main__":
    main()
