"""Laguna-S-2.1 at its published widths on the chip, logits against the plain
reference, outside any timed window: the benchmark's configuration whole (the
leading dense layer and two periods: three full layers of 48 query heads with
the leading half of a head rotated at YaRN frequencies, six window layers of 72
with the whole head rotated at plain ones over 512 keys, 8 KV heads of 128 in
both, a sigmoid gate a head, 16 held experts of a softmax router over 256 with
a shared expert, 1/8 of the vocabulary), seeded bfloat16 weights,
``--sequences`` sequences of ``--prompt`` + ``--decode`` tokens through
``hybrid_decode.hybrid_forward`` as the server runs it (64 rows of which the
first ``--sequences`` are live, in slots that differ from their rows; the
prompt in chunks of 128 through the ragged kernel, then one token a step, each
step fed the sequence's own next token, past the 512-key window and more than
once round the window layers' ring of 640 positions), against ONE full forward
of ``benchmark/reference/laguna_decoder.py`` in float32. Prints the worst and
mean absolute logit difference, and the same for what the written tolerance has
to refuse: the gate left out; the gate a feature's (the element-wise variant,
its own seeded matrix); a window layer's 72 heads run as 48 (the first 48, in
groups of 6); plain rotary in a full layer (no YaRN); the attention factor left
out; all 128 features rotated in a full layer; the full layers' theta in a
window layer; the window one key too narrow and one too wide; the factor 2.5
left out; the chosen gates not renormalised; sigmoid scores for softmax; the
shared expert dropped; fifteen of the sixteen held experts; the expert stacks in
float8's significand (and every weight in it: the nearest precision below the
served one, which the cell's own limits have to refuse). ``--isolated`` is where
every one of them shows: the leading layer, a window layer and a full layer of
the same widths in float32 throughout, where nothing but the order of the sums
separates program and reference.

    chiprun -- python3 benchmark/tools/laguna_logits_check.py --seed 7
    chiprun -- python3 benchmark/tools/laguna_logits_check.py --seed 7 --isolated
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# The bfloat16 program against the float32 reference on the same weights, nine
# layers: limits on the worst and the mean absolute logit difference, and which
# controls they refuse, are written from the chip runs in PERF.md section 6
# (PR 45); the readings stand beside them there (seed 7: served 0.51 / 0.0215;
# the nearest control the mean refuses, fifteen of sixteen experts, 0.0359).
WORST, MEAN = 1.0, 0.03
# ``--isolated``: three layers in float32 throughout (weights the same
# bfloat16-rounded values, matmuls at precision highest). The program and the
# reference differ by the order of their sums alone; every control is a
# different function and differs by orders of magnitude more.
ISOLATED_MEAN = 2e-4


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--sequences", type=int, default=2)
    ap.add_argument("--prompt", type=int, default=640)
    ap.add_argument("--decode", type=int, default=384)
    ap.add_argument("--only", default="", help="comma-separated controls to run (default: all)")
    ap.add_argument("--isolated", action="store_true", help="three layers in float32 throughout: every control against the order of the sums alone")
    ap.add_argument("--rehearse", action="store_true", help="the configuration's tiny rehearse sizes, on the CPU, float32")
    args = ap.parse_args()

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import files
    from benchmark.serving import seeded_weights
    from deepspeed_tpu.inference import hybrid_decode
    from deepspeed_tpu.inference.kv_pool import PagePool
    from deepspeed_tpu.profiling import use_compile_cache

    use_compile_cache()
    config = files.load_json(files.HERE, "configs", "laguna-s-2.1-l9-ep16.json")
    if args.rehearse:
        config = files.overlay(config, config["rehearse"])
        args.prompt, args.decode, args.isolated = min(args.prompt, 40), min(args.decode, 24), True
    if args.isolated:
        config["model"]["kwargs"].update(num_layers=3, layer_types=["softmax", "window", "softmax"], dtype="float32")
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
    slot_of = [(3 * r + 5) % rows for r in range(args.sequences)]  # a row's ring lies elsewhere than its row
    act = jnp.float32 if args.isolated else jnp.bfloat16
    if args.isolated:
        served = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), served)  # the same values, float32 arithmetic
        jax.config.update("jax_default_matmul_precision", "highest")
    impl = "xla" if args.rehearse else "auto"

    def served_logits(run_cfg, params):
        @jax.jit
        def forward(params, window, kp, vp, st, cv, rings, table, lengths, q_lens, slots):
            logits, kp, vp, st, cv, counts, rings = hybrid_decode.hybrid_forward(
                run_cfg, params, window, kp, vp, st, cv, table, lengths, q_lens, slots, impl, window=rings
            )
            return logits[: args.sequences].astype(jnp.float32), kp, vp, st, cv, rings, counts

        pool = PagePool(run_cfg, rows * maxp + 1, page, rows, max_seq_len=maxp * page, dtype=act, prefill_chunk=chunk)
        pools = [pool.cache.k_pages, pool.cache.v_pages, pool.states.state, pool.states.conv, (pool.states.window_k, pool.states.window_v)]
        table = np.full((rows, maxp), -1, np.int32)
        slots = np.full(rows, rows, np.int32)
        for r in range(args.sequences):
            table[r], slots[r] = 1 + slot_of[r] * maxp + np.arange(maxp), slot_of[r]
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
        return out, held

    def fp8(w):
        mantissa, exponent = jnp.frexp(w.astype(jnp.float32))
        return jnp.ldexp(jnp.round(mantissa * 16.0) / 16.0, exponent).astype(w.dtype)

    def with_moe(params, **leaves):
        return {**params, "periods": {**params["periods"], "moe": {**params["periods"]["moe"], **leaves}}}

    def with_mixers(params, change):
        """``change(kind, leaves)`` on the leading layers' mixers and on each kind's stack."""
        leading = [{**lead, "mixer": change(kind, lead["mixer"])} for kind, lead in zip(cfg.layer_types, params.get("leading", ()))]
        kinds = {kind: change(kind, params["periods"][kind]) for kind in ("softmax", "window") if kind in params["periods"]}
        return {**params, "leading": leading, "periods": {**params["periods"], **kinds}}

    def without_gate(kind, leaves):
        return {k: v for k, v in leaves.items() if k != "wg_head"}

    def feature_gate(kind, leaves):
        # the element-wise variant's own matrix [H, heads x 128], drawn as init draws it
        shape = leaves["wg_head"].shape[:-1] + (cfg.heads_of(kind) * cfg.v_head_dim,)
        wg = 0.02 * jax.random.normal(jax.random.PRNGKey(args.seed + len(shape)), shape, jnp.float32)
        return {**without_gate(kind, leaves), "wg": wg.astype(leaves["wg_head"].dtype)}

    def first_48_heads(kind, leaves):
        if kind != "window":
            return leaves
        n, D = cfg.num_heads, cfg.head_dim
        return {**leaves, "wq": leaves["wq"][..., : n * D], "wg_head": leaves["wg_head"][..., :n], "wo": leaves["wo"][..., : n * D, :]}

    moe = served["periods"]["moe"]
    experts = moe["experts"]
    last = cfg.num_experts - 1
    run = lambda **change: served_logits(dataclasses.replace(cfg, **change), served)
    controls = {
        "no_gate": lambda: served_logits(dataclasses.replace(cfg, attn_head_gate=False), with_mixers(served, without_gate)),
        "gate_a_feature": lambda: served_logits(dataclasses.replace(cfg, attn_head_gate=False, attn_output_gate=True), with_mixers(served, feature_gate)),
        "window_72_heads_as_48": lambda: served_logits(dataclasses.replace(cfg, window_num_heads=cfg.num_heads), with_mixers(served, first_48_heads)),
        "full_plain_rotary": lambda: run(rope_yarn_factor=0.0),
        "no_attention_factor": lambda: run(rope_yarn_attention_factor=1.0),
        "full_all_rotated": lambda: run(rope_dim=cfg.head_dim),
        "window_theta_5e5": lambda: run(window_rope_theta=cfg.rope_theta),
        "window_511": lambda: run(window=cfg.window - 1),
        "window_513": lambda: run(window=cfg.window + 1),
        "no_routed_scaling": lambda: run(moe_routed_scaling=1.0),
        "gates_not_renormalised": lambda: run(moe_norm_topk_prob=False),
        "sigmoid_scores": lambda: run(moe_scoring="sigmoid"),
        "no_shared_expert": lambda: served_logits(cfg, with_moe(served, shared={**moe["shared"], "w_out": jnp.zeros_like(moe["shared"]["w_out"])})),
        "15_of_16_experts": lambda: served_logits(cfg, with_moe(served, experts={**experts, "w_out": experts["w_out"].at[:, :, last].set(0)})),
        "experts_fp8": lambda: served_logits(cfg, with_moe(served, experts=jax.tree_util.tree_map(jax.jit(fp8), experts))),
        # the nearest precision below the served one, for the cell's own limits (engine.check)
        "weights_fp8": lambda: served_logits(cfg, jax.tree_util.tree_map(jax.jit(fp8), served)),
    }
    limits = {"mean": ISOLATED_MEAN} if args.isolated else {"worst": WORST, "mean": MEAN}
    report = {"device": jax.devices()[0].device_kind, "sequences": args.sequences, "prompt": args.prompt, "decode": args.decode,
              "seed": args.seed, "isolated": args.isolated, "layers": cfg.num_layers, "logit_std": float(ref.std()), "limits": limits}

    def readings(logits):
        """[worst and mean absolute difference, the mean over the decoded
        positions alone, worst and mean regret of the program's own arg-max
        (what ``engine.check`` reads of served tokens)]."""
        diff = np.abs(logits - ref)
        regret = ref.max(-1) - np.take_along_axis(ref, logits.argmax(-1)[..., None], -1)[..., 0]
        return [float(diff.max()), float(diff.mean()), float(diff[:, args.prompt :].mean()), float(regret.max()), float(regret.mean())]

    refused = (lambda r: r[1] > ISOLATED_MEAN) if args.isolated else (lambda r: r[1] > MEAN or r[0] > WORST)
    ours, held = served_logits(cfg, served)
    base = readings(ours)
    report["worst_abs_diff"], report["mean_abs_diff"], report["decode_mean_abs_diff"] = base[:3]
    report["served_argmax_regret_worst_mean"] = base[3:]
    diff = np.abs(ours - ref)
    report["mean_by_position_64"] = [float(diff[:, i : i + 64].mean()) for i in range(0, total, 64)]
    report["argmax_agreement"] = float(np.mean(ours.argmax(-1) == ref.argmax(-1)))
    routed = args.sequences * total * cfg.moe_top_k * cfg.num_moe_layers
    report["held_assignments"], report["routed_assignments"] = held, routed
    print(json.dumps({"ours": base}), flush=True)
    wanted = [c for c in args.only.split(",") if c] or list(controls)
    for name in wanted:
        report[name] = readings(controls[name]()[0])
        print(json.dumps({name: report[name]}), flush=True)
    report["controls_refused"] = {name: bool(refused(report[name])) for name in wanted}
    ok = not refused(base) and 0 < held < routed
    report["within_limits"] = bool(ok)
    print(json.dumps(report), flush=True)
    return 0 if ok or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())
