"""OLMoE at its published widths on the chip, logits against the plain
reference, outside any timed window: ``--layers`` layers of the benchmark's
configuration (2 suffice: the layers are identical), seeded bfloat16 weights,
``--sequences`` sequences of ``--prompt`` + ``--decode`` tokens through
``decode._paged_forward``'s ragged entry as the server runs it (16 rows of
which the first ``--sequences`` are live, the prompt in chunks of 128 by the
fused Pallas kernel, then one token a step, each step fed the sequence's own
next token), against ONE full forward of ``benchmark/reference/olmoe_decoder.py``
in float32. Prints the worst and mean absolute logit difference, and the same
for three wrong blocks (7 experts a token, renormalised gates, no QK-norm),
which the written tolerance has to refuse, and for the expert stacks
rounded to float8's significand (refused too) and to int8 (reported, not
judged: it reads 1.3 times bfloat16's own error).

    chiprun -- python3 benchmark/tools/olmoe_logits_check.py --seed 7
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# the bfloat16 program against the float32 reference on the same weights (the
# configuration's seeded router, gate mass 0.75), 2 layers, logits of standard
# deviation 0.9: three seeds on the chip (PR 25, PERF.md section 6) read worst
# 0.34-0.47 and mean 0.0082-0.0086. Limits: twice the worst; 1.75 times the
# largest mean (a mean over 39 million logits: the seeds lie within 3%), which
# the expert stacks in float8's significand (0.0211-0.0241) and the mildest
# wrong block (7 of 8 experts a token, 0.031-0.033) have to exceed.
# Per-channel int8 experts read 0.0108-0.0113, 1.3 times bfloat16's own
# error, and pass: no limit that bfloat16 passes with room refuses them
WORST, MEAN = 1.0, 0.015


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--sequences", type=int, default=2)
    ap.add_argument("--prompt", type=int, default=256)
    ap.add_argument("--decode", type=int, default=128)
    ap.add_argument("--rehearse", action="store_true", help="the configuration's tiny rehearse sizes, on the CPU")
    args = ap.parse_args()

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import files
    from benchmark.serving import seeded_weights
    from deepspeed_tpu.inference import decode
    from deepspeed_tpu.profiling import use_compile_cache

    use_compile_cache()
    config = files.load_json(files.HERE, "configs", "olmoe-1b-7b-0125-l12.json")
    if args.rehearse:
        config = files.overlay(config, config["rehearse"])
    config["model"]["kwargs"]["num_layers"] = args.layers
    model, shape = files.build_model(config)
    reference = files.reference_of(config)
    paged = config["engine"]["init_inference"]["paged_kv"]
    rows, page, chunk = paged["max_slots"], paged["page_size"], paged["prefill_chunk"]
    total = args.prompt + args.decode
    maxp = -(-total // page)
    cfg = model.config
    params = seeded_weights(model, args.seed, jnp.bfloat16)
    tokens = np.random.default_rng([args.seed, 1]).integers(0, shape["vocab_size"], (args.sequences, total), dtype=np.int32)
    ref = np.asarray(reference.logits(config["model"], params, tokens))

    def forward_of(run_cfg, width):
        @jax.jit
        def forward(params, window, kp, vp, table, lengths, q_lens):
            positions = lengths[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
            kv_lens = jnp.where(q_lens > 0, lengths + q_lens, 0)
            logits, kp, vp, counts = decode._paged_forward(run_cfg, params, window, kp, vp, table, positions, None, "auto",
                                                           prefill_kv_lens=kv_lens, ragged_q_lens=q_lens)
            return logits[: args.sequences].astype(jnp.float32), kp, vp, counts

        return forward

    def served_logits(run_cfg):
        pool = (cfg.num_layers, rows * maxp + 1, cfg.num_kv_heads, page, cfg.head_dim)
        kp, vp = jnp.zeros(pool, jnp.bfloat16), jnp.zeros(pool, jnp.bfloat16)
        table = np.zeros((rows, maxp), np.int32)
        for r in range(args.sequences):
            table[r] = 1 + r * maxp + np.arange(maxp)
        forward = {w: forward_of(run_cfg, w) for w in (chunk, 1)}
        out, done, assignments = np.zeros(ref.shape, np.float32), 0, 0
        while done < total:
            width = chunk if done < args.prompt else 1
            real = min(width, args.prompt - done) if done < args.prompt else 1
            window = np.zeros((rows, width), np.int32)
            window[: args.sequences, :real] = tokens[:, done : done + real]
            lengths, q_lens = np.zeros(rows, np.int32), np.zeros(rows, np.int32)
            lengths[: args.sequences], q_lens[: args.sequences] = done, real
            logits, kp, vp, counts = forward[width](params, window, kp, vp, table, lengths, q_lens)
            out[:, done : done + real] = np.asarray(logits)[:, :real]
            assignments += int(np.asarray(counts).sum())
            done += real
        return out, assignments

    report = {"device": jax.devices()[0].device_kind, "layers": args.layers, "sequences": args.sequences, "prompt": args.prompt,
              "decode": args.decode, "seed": args.seed, "logit_std": float(ref.std()), "limits": {"worst": WORST, "mean": MEAN}}
    ours, assignments = served_logits(cfg)
    diff = np.abs(ours - ref)
    report["worst_abs_diff"], report["mean_abs_diff"] = float(diff.max()), float(diff.mean())
    report["prefill_worst_mean"] = [float(diff[:, : args.prompt].max()), float(diff[:, : args.prompt].mean())]
    report["decode_worst_mean"] = [float(diff[:, args.prompt :].max()), float(diff[:, args.prompt :].mean())]
    report["argmax_agreement"] = float(np.mean(ours.argmax(-1) == ref.argmax(-1)))
    report["assignments"], report["assignments_expected"] = assignments, args.sequences * total * cfg.moe_top_k * args.layers
    wrong = {"7_experts_a_token": dict(moe_top_k=cfg.moe_top_k - 1), "renormalised_gates": dict(moe_norm_topk_prob=True), "no_qk_norm": dict(qk_norm=None)}
    for name, change in wrong.items():
        w, _ = served_logits(dataclasses.replace(cfg, **change))
        report[name] = [float(np.abs(w - ref).max()), float(np.abs(w - ref).mean())]
    # the expert stacks rounded through a coarser type (benchmark/tools/olmoe_precision_check.py has the same two)
    def fp8(w):
        mantissa, exponent = jnp.frexp(w.astype(jnp.float32))
        return jnp.ldexp(jnp.round(mantissa * 16.0) / 16.0, exponent).astype(w.dtype)

    def int8(w):
        scale = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True) / 127.0
        return (jnp.round(w.astype(jnp.float32) / jnp.maximum(scale, 1e-30)) * scale).astype(w.dtype)

    served = params
    for name, rounding in (("experts_fp8", fp8), ("experts_int8", int8)):
        experts = {k: jax.jit(rounding)(v) for k, v in served["layers"]["moe"]["experts"].items()}
        params = {**served, "layers": {**served["layers"], "moe": {**served["layers"]["moe"], "experts": experts}}}
        w, _ = served_logits(cfg)
        report[name] = [float(np.abs(w - ref).max()), float(np.abs(w - ref).mean())]
    params = served
    ok = report["worst_abs_diff"] <= WORST and report["mean_abs_diff"] <= MEAN and assignments == report["assignments_expected"]
    report["within_limits"] = bool(ok)
    report["wrong_blocks_refused"] = bool(all(report[name][1] > MEAN for name in (*wrong, "experts_fp8")))
    print(json.dumps(report), flush=True)
    return 0 if ok and (args.rehearse or report["wrong_blocks_refused"]) else 1


if __name__ == "__main__":
    sys.exit(main())
