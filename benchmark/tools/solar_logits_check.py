"""Solar-Open2 at its published widths on the chip, logits against the plain
reference, outside any timed window: the benchmark's configuration whole (one
period: a gated GQA layer and three delta-rule layers, 40 held experts of a
router over 320, the shared expert, 1/8 of the vocabulary), seeded bfloat16
weights, ``--sequences`` sequences of ``--prompt`` + ``--decode`` tokens
through ``hybrid_decode.hybrid_forward`` as the server runs it (64 rows of
which the first ``--sequences`` are live, in slots that differ from their
rows; the prompt in chunks of 128 through the ragged kernel and the chunkwise
recurrence, then one token a step through ``kda_decode``, each step fed the
sequence's own next token), against ONE full forward of
``benchmark/reference/solar_open2_decoder.py`` in float32 (the recurrence a
plain scan). Prints the worst and mean absolute logit difference, and the
same for what the written tolerance has to refuse: every weight rounded to
float8's significand, 7 experts a token, no shared expert, no output gates, no
selection bias; and for the state kept in bfloat16 and the expert stacks alone
in float8, which the bfloat16 program's own routing noise hides. ``--isolated``
shows those two: a softmax and a linear layer of the same widths in float32
throughout, where nothing but the order of the sums separates program and
reference, with the state's relative error against ``final_states`` beside the
logits'.

    chiprun -- python3 benchmark/tools/solar_logits_check.py --seed 7
    chiprun -- python3 benchmark/tools/solar_logits_check.py --seed 7 --isolated
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# The bfloat16 program against the float32 reference on the same weights, one
# period, logits of standard deviation 1.28 (my chip runs, PR 31, four runs of
# three seeds): worst 0.71-0.79, mean 0.039-0.051. Three times OLMoE's relative
# error, and the cause is the router: the 8th and the 9th of 320 sigmoid scores
# lie 0.005 apart on average (a third of the tokens under 0.002), so the
# activations' bfloat16 rounding moves a choice in many tokens, and a moved
# choice moves that token's logits by tenths. Limits: twice the worst; 1.5
# times the largest mean, which the mildest wrong block (7 of 8 experts a
# token, 0.104-0.122), no selection bias (0.18-0.19), every weight in float8's
# significand (0.33-0.34), no output gates (0.95) and no shared expert (1.2)
# exceed. NOT told apart here: the state in bfloat16 (0.046-0.048) and the
# expert stacks alone in float8 (0.049-0.050) drown in that routing noise;
# ``--isolated`` holds them (below).
WORST, MEAN = 1.6, 0.075
# ``--isolated``: a softmax and a linear layer in float32 throughout (weights
# the same bfloat16-rounded values, matmuls at precision highest), where the
# program and the reference differ by the order of their sums alone. My chip
# runs, PR 31, published widths, 128 + 256 tokens, two seeds: mean logit difference
# 7.0e-6, the linear layer's state 3.7e-6 and 4.2e-6 off the reference's (relative, Frobenius);
# with the state kept in bfloat16 3.3e-3 and 8.1e-3; with the expert stacks in
# float8's significand 9.1e-3 and 8.7e-3. Limits 2e-4 both: 29 and 54 times the
# float32 readings, 17 and 40 times under the mildest control's
ISOLATED_MEAN, ISOLATED_STATE = 2e-4, 2e-4


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--sequences", type=int, default=2)
    ap.add_argument("--prompt", type=int, default=320)
    ap.add_argument("--decode", type=int, default=96)
    ap.add_argument("--only", default="", help="comma-separated controls to run (default: all)")
    ap.add_argument("--isolated", action="store_true", help="two layers in float32 throughout: the state's and the expert stacks' precision alone")
    ap.add_argument("--rehearse", action="store_true", help="the configuration's tiny rehearse sizes, on the CPU")
    args = ap.parse_args()

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import files
    from benchmark.serving import seeded_weights
    from deepspeed_tpu.inference import hybrid_decode
    from deepspeed_tpu.profiling import use_compile_cache

    use_compile_cache()
    config = files.load_json(files.HERE, "configs", "solar-open2-250b-l4-ep8.json")
    if args.rehearse:
        config = files.overlay(config, config["rehearse"])
        args.prompt, args.decode = min(args.prompt, 40), min(args.decode, 24)
    if args.isolated:
        config["model"]["kwargs"].update(num_layers=2, layer_types=["softmax", "linear"], dtype="float32")
    model, shape = files.build_model(config)
    reference = files.reference_of(config)
    paged = config["engine"]["init_inference"]["paged_kv"]
    rows, page, chunk = paged["max_slots"], paged["page_size"], paged["prefill_chunk"]
    total = args.prompt + args.decode
    maxp = -(-total // page)
    cfg = model.config
    served = seeded_weights(model, args.seed, jnp.bfloat16)
    tokens = np.random.default_rng([args.seed, 1]).integers(0, shape["vocab_size"], (args.sequences, total), dtype=np.int32)
    ref = np.asarray(reference.logits(config["model"], served, tokens))
    ref_states = [np.asarray(S) for S in reference.final_states(config["model"], served, tokens)]
    slot_of = [(3 * r + 5) % rows for r in range(args.sequences)]  # a row's state lies elsewhere than its row

    act = jnp.float32 if args.isolated else jnp.bfloat16

    def forward_of(run_cfg):
        @jax.jit
        def forward(params, window, kp, vp, st, cv, table, lengths, q_lens, slots):
            logits, kp, vp, st, cv, counts = hybrid_decode.hybrid_forward(
                run_cfg, params, window, kp, vp, st, cv, table, lengths, q_lens, slots, "xla" if args.isolated else "auto"
            )
            return logits[: args.sequences].astype(jnp.float32), kp, vp, st, cv, counts

        return forward

    def served_logits(run_cfg, params, state_dtype=jnp.float32):
        pages = (run_cfg.layers_of("softmax"), rows * maxp + 1, run_cfg.num_kv_heads, page, run_cfg.head_dim)
        shapes = hybrid_decode.state_shapes(run_cfg, rows)
        pools = [jnp.zeros(pages, act), jnp.zeros(pages, act), jnp.zeros(shapes.state, state_dtype), jnp.zeros(shapes.conv, act)]
        table = np.full((rows, maxp), -1, np.int32)
        slots = np.full(rows, rows, np.int32)
        for r in range(args.sequences):
            table[r], slots[r] = 1 + r * maxp + np.arange(maxp), slot_of[r]
        forward = forward_of(run_cfg)
        out, done, held = np.zeros(ref.shape, np.float32), 0, 0
        while done < total:
            width = chunk if done < args.prompt else 1
            real = min(width, args.prompt - done) if done < args.prompt else 1
            window = np.zeros((rows, width), np.int32)
            window[: args.sequences, :real] = tokens[:, done : done + real]
            lengths, q_lens = np.zeros(rows, np.int32), np.zeros(rows, np.int32)
            lengths[: args.sequences], q_lens[: args.sequences] = done, real
            logits, *pools, counts = forward(params, window, *pools, table, lengths, q_lens, slots)
            out[:, done : done + real] = np.asarray(logits)[:, :real]
            held += int(np.asarray(counts).sum())
            done += real
        # each linear layer's state of the live rows against the reference's: |S - S_ref| / |S_ref| (Frobenius)
        mine = np.asarray(pools[2][:, np.asarray(slot_of)].astype(jnp.float32))
        state_error.append([float(np.linalg.norm(mine[i] - S) / np.linalg.norm(S)) for i, S in enumerate(ref_states)])
        return out, held

    def fp8(w):
        mantissa, exponent = jnp.frexp(w.astype(jnp.float32))
        return jnp.ldexp(jnp.round(mantissa * 16.0) / 16.0, exponent).astype(w.dtype)

    def with_moe(params, **moe):
        return {**params, "periods": {**params["periods"], "moe": {**params["periods"]["moe"], **moe}}}

    def without(tree, *keys):
        return {k: v for k, v in tree.items() if k not in keys}

    state_error = []  # one entry a served_logits call, in call order
    if args.isolated:
        served = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), served)  # the same values, float32 arithmetic
        jax.config.update("jax_default_matmul_precision", "highest")
    periods = served["periods"]
    controls = {
        "state_bf16": lambda: served_logits(cfg, served, jnp.bfloat16),
        "weights_fp8": lambda: served_logits(cfg, jax.tree_util.tree_map(jax.jit(fp8), served)),
        "experts_fp8": lambda: served_logits(cfg, with_moe(served, experts=jax.tree_util.tree_map(jax.jit(fp8), periods["moe"]["experts"]))),
        "7_experts_a_token": lambda: served_logits(dataclasses.replace(cfg, moe_top_k=cfg.moe_top_k - 1), served),
        "no_shared_expert": lambda: served_logits(cfg, {**served, "periods": {**periods, "moe": without(periods["moe"], "shared")}}),
        "no_output_gates": lambda: served_logits(cfg, {**served, "periods": {
            **periods, "softmax": without(periods["softmax"], "wg"),
            "linear": {**periods["linear"], "wg_up": jnp.zeros_like(periods["linear"]["wg_up"])}}}),  # sigmoid(0): a constant 1/2
        "no_selection_bias": lambda: served_logits(cfg, with_moe(served, gate=without(periods["moe"]["gate"], "bias"))),
    }
    report = {"device": jax.devices()[0].device_kind, "sequences": args.sequences, "prompt": args.prompt, "decode": args.decode,
              "seed": args.seed, "logit_std": float(ref.std()), "limits": {"worst": WORST, "mean": MEAN}}
    def readings(logits):
        """[worst and mean absolute difference, the mean over the decoded
        positions alone, worst and mean regret of the program's own arg-max
        (what ``engine.check`` reads of served tokens)]; a control's entry
        ends with its states' relative errors, a linear layer each."""
        diff = np.abs(logits - ref)
        regret = ref.max(-1) - np.take_along_axis(ref, logits.argmax(-1)[..., None], -1)[..., 0]
        return [float(diff.max()), float(diff.mean()), float(diff[:, args.prompt :].mean()), float(regret.max()), float(regret.mean())]

    ours, held = served_logits(cfg, served)
    report["state_rel_error_by_linear_layer"] = state_error[-1]
    diff = np.abs(ours - ref)
    base = readings(ours)
    report["worst_abs_diff"], report["mean_abs_diff"], report["decode_mean_abs_diff"] = base[:3]
    report["served_argmax_regret_worst_mean"] = base[3:]
    report["prefill_worst_mean"] = [float(diff[:, : args.prompt].max()), float(diff[:, : args.prompt].mean())]
    report["mean_by_position_64"] = [float(diff[:, i : i + 64].mean()) for i in range(0, total, 64)]
    report["argmax_agreement"] = float(np.mean(ours.argmax(-1) == ref.argmax(-1)))
    routed = args.sequences * total * cfg.moe_top_k * cfg.num_layers
    report["held_assignments"], report["routed_assignments"] = held, routed
    wanted = [c for c in args.only.split(",") if c] or (["state_bf16", "experts_fp8"] if args.isolated else list(controls))
    for name in wanted:
        report[name] = readings(controls[name]()[0]) + [state_error[-1]]
        print(json.dumps({name: report[name]}), flush=True)
    if args.isolated:
        report["limits"] = {"mean": ISOLATED_MEAN, "state": ISOLATED_STATE}
        refused = lambda mean, states: mean > ISOLATED_MEAN or max(states) > ISOLATED_STATE
        ok = not refused(report["mean_abs_diff"], report["state_rel_error_by_linear_layer"]) and 0 < held < routed
        report["controls_refused"] = {name: bool(refused(report[name][1], report[name][5])) for name in wanted}
    else:
        ok = report["worst_abs_diff"] <= WORST and report["mean_abs_diff"] <= MEAN and 0 < held < routed
        report["controls_refused"] = {name: bool(report[name][1] > MEAN or report[name][0] > WORST) for name in wanted}
    report["within_limits"] = bool(ok)
    print(json.dumps(report), flush=True)
    return 0 if ok or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())
