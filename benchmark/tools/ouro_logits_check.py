"""Ouro-2.6B at its published widths on the chip, logits against the plain
reference, outside any timed window: the benchmark's configuration whole (48
layers run four times, 16 heads of 128, the sandwich norms, the untied head of
49,152), seeded bfloat16 weights, ``--sequences`` sequences of ``--prompt`` +
``--decode`` tokens through ``decode._paged_layers`` as the server runs them
(the cell's 8 rows, the first ``--sequences`` live, on pages that are not in
walk order; the prompt in chunks of 128 through the wide program's token
tiles, then one token a step through the narrow one, each step fed the
sequence's own next token, every pass writing and reading its own 48 of the
192 cache layers; the head over the live rows alone), against ONE full forward
of ``benchmark/reference/ouro_decoder.py`` in float32. Prints the worst and
mean absolute logit difference and the teacher-forced regret of the program's
own arg-max (what ``engine.check`` reads of served tokens) beside the cell's
limits, and the same for the reference's five wrong blocks
(``ouro_decoder.WRONG``), each of which the written limits have to refuse: a
pass too few, every pass attending to pass 0's keys and values, no norm
between passes, the post-sublayer norms left out, and every weight in float8's
significand (the nearest precision below the served one).

    chiprun -- python3 benchmark/tools/ouro_logits_check.py --seed 7
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--sequences", type=int, default=2)
    ap.add_argument("--prompt", type=int, default=192)
    ap.add_argument("--decode", type=int, default=384)
    ap.add_argument("--only", default="", help="comma-separated wrong blocks to run (default: all)")
    ap.add_argument("--rehearse", action="store_true", help="the configuration's tiny rehearse sizes, on the CPU, float32")
    args = ap.parse_args()

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import files
    from benchmark.serving import seeded_weights
    from deepspeed_tpu.inference import decode
    from deepspeed_tpu.inference.kv_pool import init_paged_cache
    from deepspeed_tpu.profiling import use_compile_cache

    use_compile_cache()
    config = files.load_json(files.HERE, "configs", "ouro-2.6b.json")
    if args.rehearse:
        config = files.overlay(config, config["rehearse"])
        config["model"]["kwargs"]["dtype"] = "float32"
        args.prompt, args.decode = min(args.prompt, 40), min(args.decode, 24)
    model, shape = files.build_model(config)
    reference = files.reference_of(config)
    paged = config["engine"]["init_inference"]["paged_kv"]
    rows, page, chunk = paged["max_slots"], paged["page_size"], paged["prefill_chunk"]
    total = args.prompt + args.decode
    maxp = -(-total // page)
    cfg = model.config
    act = jnp.float32 if args.rehearse else jnp.bfloat16
    served = seeded_weights(model, args.seed, act)
    tokens = np.random.default_rng([args.seed, 1]).integers(0, shape["vocab_size"], (args.sequences, total), dtype=np.int32)
    ref = np.asarray(reference.logits(config["model"], served, tokens))
    impl = "xla" if args.rehearse else "auto"

    @functools.partial(jax.jit, donate_argnums=(2, 3))
    def forward(params, window, kp, vp, table, lengths, q_lens):
        positions = lengths[:, None] + jnp.arange(window.shape[1], dtype=jnp.int32)[None, :]
        kv_lens = jnp.where(q_lens > 0, lengths + q_lens, 0)
        x, kp, vp, _, packed = decode._paged_layers(
            cfg, params, window, kp, vp, table, positions, impl, prefill_kv_lens=kv_lens, ragged_q_lens=q_lens
        )
        if packed is not None:
            x = packed.expand(x)
        return decode._final_logits(cfg, params, x[: args.sequences]).astype(jnp.float32), kp, vp

    def served_logits():
        cache = init_paged_cache(cfg, rows * maxp + 1, page, dtype=act)
        kp, vp = cache.k_pages, cache.v_pages
        del cache
        table = np.full((rows, maxp), -1, np.int32)
        for r in range(args.sequences):
            table[r] = 1 + r + args.sequences * np.arange(maxp)  # a row's pages interleaved with the others'
        out, done = np.zeros(ref.shape, np.float32), 0
        while done < total:
            width = chunk if done < args.prompt else 1
            real = min(width, args.prompt - done) if done < args.prompt else 1
            window = np.zeros((rows, width), np.int32)
            window[: args.sequences, :real] = tokens[:, done : done + real]
            lengths, q_lens = np.zeros(rows, np.int32), np.zeros(rows, np.int32)
            lengths[: args.sequences], q_lens[: args.sequences] = done, real
            logits, kp, vp = forward(served, window, kp, vp, table, lengths, q_lens)
            out[:, done : done + real] = np.asarray(logits)[:, :real]
            done += real
        return out

    check = config["engine"]["check"]
    report = {"device": jax.devices()[0].device_kind, "sequences": args.sequences, "prompt": args.prompt, "decode": args.decode,
              "seed": args.seed, "layers": cfg.num_layers, "passes": cfg.num_loops, "logit_std": float(ref.std()),
              "cell_limits": {"logit_margin": check["logit_margin"], "mean_logit_gap": check["mean_logit_gap"]}}

    def readings(logits):
        """[worst and mean absolute difference, the mean over the decoded
        positions alone, worst and mean regret of the arg-max (what
        ``engine.check`` reads of served tokens)]."""
        diff = np.abs(logits - ref)
        regret = ref.max(-1) - np.take_along_axis(ref, logits.argmax(-1)[..., None], -1)[..., 0]
        return [float(diff.max()), float(diff.mean()), float(diff[:, args.prompt :].mean()), float(regret.max()), float(regret.mean())]

    by_cell = lambda r: r[3] > check["logit_margin"] or r[4] > check["mean_logit_gap"]  # teacher-forced regret against the cell's limits
    ours = served_logits()
    base = readings(ours)
    report["worst_abs_diff"], report["mean_abs_diff"], report["decode_mean_abs_diff"] = base[:3]
    report["served_argmax_regret_worst_mean"] = base[3:]
    report["argmax_agreement"] = float(np.mean(ours.argmax(-1) == ref.argmax(-1)))
    print(json.dumps({"ours": base}), flush=True)
    wanted = [c for c in args.only.split(",") if c] or list(reference.WRONG)
    for name in wanted:
        report[name] = readings(np.asarray(reference.logits(config["model"], served, tokens, wrong=name)))
        print(json.dumps({name: report[name]}), flush=True)
    report["wrong_blocks_refused_by_the_cells_limits"] = {name: bool(by_cell(report[name])) for name in wanted}
    ok = not by_cell(base)
    report["within_limits"] = bool(ok)
    print(json.dumps(report), flush=True)
    return 0 if (ok and all(report["wrong_blocks_refused_by_the_cells_limits"].values())) or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())
