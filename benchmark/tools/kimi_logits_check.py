"""Kimi-Linear-48B-A3B at its published widths on the chip, logits against the
plain reference, outside any timed window: the benchmark's configuration whole
(a leading dense layer that is a KDA one and three periods ``[KDA, KDA, MLA,
KDA]``: 10 delta-rule layers of 32 heads of 128 beside 3 latent layers of 32
heads over an entry of 512 + 64 unrotated, 32 held experts of a router over 256
and a shared one, 1/8 of the vocabulary), seeded bfloat16 weights,
``--sequences`` sequences of ``--prompt`` + ``--decode`` tokens through
``hybrid_decode.hybrid_forward`` as the server runs it (64 rows of which the
first ``--sequences`` are live, in slots that are not their rows, on pages that
are not in walk order; the prompt in chunks of 128 through ``kda_chunked`` and
the latent kernel, then one token a step through ``kda_decode`` in place, each
step fed the sequence's own next token: the ABSORBED latent form throughout),
against ONE full forward of ``benchmark/reference/kimi_linear_decoder.py`` in
float32 (the recurrence token by token, the published EXPANDED latent form).
Prints the worst and mean absolute logit difference and the regret of the
program's own arg-max (what ``engine.check`` reads of served tokens), and the
same for what the written limits have to refuse: the rotary applied to ``q_r``
and ``k_r``, the 64 shared features dropped, ``b`` doubled, the decay a head
instead of a channel (what tells KDA from a gated delta net), the factor 2.446
left out, the shared expert dropped, the LEADING layer's state or convolution
tail not carried from one step to the next, seven of a token's eight experts,
and every weight in float8's significand (the nearest precision below the
served one). ``--isolated`` is where every one of them shows: the leading
layer and one period in float32 throughout, where nothing but the order of the
sums, the chunkwise form and the absorbed product's association separates
program and reference.

    chiprun -- python3 benchmark/tools/kimi_logits_check.py --seed 7
    chiprun -- python3 benchmark/tools/kimi_logits_check.py --seed 7 --isolated
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# The bfloat16 program against the float32 reference on the same weights, 13
# layers: limits on the worst and the mean absolute logit difference; they and
# which controls they refuse are written from the chip runs in PERF.md section
# 6 (PR 49), where the readings stand beside them.
WORST, MEAN = 1.5, 0.06
# ``--isolated``: five layers in float32 throughout (weights the same
# bfloat16-rounded values, matmuls at precision highest). Every control is a
# different function and differs by orders of magnitude more.
ISOLATED_MEAN = 2e-4
ISOLATED_LAYERS = ["linear", "linear", "linear", "latent", "linear"]  # the leading layer and one period


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--sequences", type=int, default=2)
    ap.add_argument("--prompt", type=int, default=384)
    ap.add_argument("--decode", type=int, default=128)
    ap.add_argument("--only", default="", help="comma-separated controls to run (default: all)")
    ap.add_argument("--isolated", action="store_true", help="five layers in float32 throughout: every control against the order of the sums alone")
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
    from deepspeed_tpu.models import hybrid_moe as hm
    from deepspeed_tpu.profiling import use_compile_cache

    use_compile_cache()
    config = files.load_json(files.HERE, "configs", "kimi-linear-48b-a3b-l13-ep8.json")
    if args.rehearse:
        config = files.overlay(config, config["rehearse"])
        args.prompt, args.decode, args.isolated = min(args.prompt, 40), min(args.decode, 24), True
    if args.isolated:
        config["model"]["kwargs"].update(num_layers=len(ISOLATED_LAYERS), layer_types=ISOLATED_LAYERS, dtype="float32")
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
    act = jnp.float32 if args.isolated else jnp.bfloat16
    if args.isolated:
        served = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), served)  # the same values, float32 arithmetic
        jax.config.update("jax_default_matmul_precision", "highest")
    impl = "xla" if args.rehearse else "auto"
    published = {"latent_project": hm.latent_project, "linear_inputs": hm.linear_inputs}

    def no_shared_features(cfg, p, h, positions):
        """``hm.latent_project`` with the 64 features beside the latent dropped from every score."""
        q_nope, q_r, entry = published["latent_project"](cfg, p, h, positions)
        return q_nope, jnp.zeros_like(q_r), entry

    def decay_a_head(cfg, p, h):
        """``hm.linear_inputs`` with ONE decay a head (its channels' mean log decay): a gated delta net."""
        qkv, log_a, beta = published["linear_inputs"](cfg, p, h)
        heads = log_a.reshape(log_a.shape[:-1] + (cfg.linear_num_heads, cfg.linear_head_dim))
        return qkv, jnp.broadcast_to(heads.mean(-1, keepdims=True), heads.shape).reshape(log_a.shape), beta

    def served_logits(run_cfg, params, patch=None, forget=None):
        """``patch``: {name of a function of ``hm``: its stand-in} (the step
        reaches both through the module: traced below, restored after);
        ``forget``: 2 or 3, the pool (state, convolution tails) whose entry 0,
        the LEADING layer's, is zeroed after every step."""
        for name, fn in (patch or {}).items():
            setattr(hm, name, fn)

        @jax.jit
        def forward(params, window, kp, vp, st, cv, latent, table, lengths, q_lens, slots):
            logits, kp, vp, st, cv, counts, latent = hybrid_decode.hybrid_forward(
                run_cfg, params, window, kp, vp, st, cv, table, lengths, q_lens, slots, impl, latent=latent
            )
            return logits[: args.sequences].astype(jnp.float32), kp, vp, st, cv, latent, counts

        pool = PagePool(run_cfg, rows * maxp + 1, page, rows, max_seq_len=maxp * page, dtype=act, prefill_chunk=chunk)
        pools = [pool.cache.k_pages, pool.cache.v_pages, pool.states.state, pool.states.conv, pool.states.latent]
        table = np.full((rows, maxp), -1, np.int32)
        slots = np.full(rows, rows, np.int32)
        for r in range(args.sequences):
            # a row's pages interleaved with the others', so that page ids are not in walk order
            table[r], slots[r] = 1 + r + args.sequences * np.arange(maxp), (3 * r + 5) % rows
        out, done, held = np.zeros(ref.shape, np.float32), 0, 0
        try:
            while done < total:
                width = chunk if done < args.prompt else 1
                real = min(width, args.prompt - done) if done < args.prompt else 1
                window = np.zeros((rows, width), np.int32)
                window[: args.sequences, :real] = tokens[:, done : done + real]
                lengths, q_lens = np.zeros(rows, np.int32), np.zeros(rows, np.int32)
                lengths[: args.sequences], q_lens[: args.sequences] = done, real
                logits, *pools, counts = forward(params, window, *pools, table, lengths, q_lens, slots)
                if forget is not None:
                    pools[forget] = pools[forget].at[0].set(0)
                out[:, done : done + real] = np.asarray(logits)[:, :real]
                held += int(np.asarray(counts).sum())
                done += real
        finally:
            for name, fn in published.items():
                setattr(hm, name, fn)
        return out, held

    def fp8(w):
        mantissa, exponent = jnp.frexp(w.astype(jnp.float32))
        return jnp.ldexp(jnp.round(mantissa * 16.0) / 16.0, exponent).astype(w.dtype)

    moe = served["periods"]["moe"]
    assert cfg.layer_types[0] == "linear" and cfg.leading_dense_layers == 1  # entry 0 of the state store is the leading layer's
    controls = {
        "rotary_on_q_r_and_k_r": lambda: served_logits(dataclasses.replace(cfg, position="rope"), served),
        "shared_features_dropped": lambda: served_logits(cfg, served, {"latent_project": no_shared_features}),
        "b_doubled": lambda: served_logits(dataclasses.replace(cfg, linear_allow_neg_eigval=True), served),
        "decay_a_head": lambda: served_logits(cfg, served, {"linear_inputs": decay_a_head}),
        "no_factor_2.446": lambda: served_logits(dataclasses.replace(cfg, moe_routed_scaling=1.0), served),
        "no_shared_expert": lambda: served_logits(cfg, {**served, "periods": {**served["periods"], "moe": {k: v for k, v in moe.items() if k != "shared"}}}),
        "leading_state_not_carried": lambda: served_logits(cfg, served, forget=2),
        "leading_conv_tail_not_carried": lambda: served_logits(cfg, served, forget=3),
        "7_of_8_experts": lambda: served_logits(dataclasses.replace(cfg, moe_top_k=cfg.moe_top_k - 1), served),
        # the nearest precision below the served one, for the cell's own limits (engine.check): LAST, and in place, because
        # a second copy of 6.9 GB of weights does not fit beside the first and the pools (the served weights are gone after it)
        "weights_fp8": lambda: served_logits(cfg, jax.tree_util.tree_map(jax.jit(fp8, donate_argnums=0), served)),
    }
    limits = {"mean": ISOLATED_MEAN} if args.isolated else {"worst": WORST, "mean": MEAN}
    check = config["engine"]["check"]
    report = {"device": jax.devices()[0].device_kind, "sequences": args.sequences, "prompt": args.prompt, "decode": args.decode,
              "seed": args.seed, "isolated": args.isolated, "layers": cfg.num_layers, "logit_std": float(ref.std()), "limits": limits,
              "cell_limits": {"logit_margin": check["logit_margin"], "mean_logit_gap": check["mean_logit_gap"]}}

    def readings(logits):
        """[worst and mean absolute difference, the mean over the decoded
        positions alone, worst and mean regret of the program's own arg-max
        (what ``engine.check`` reads of served tokens)]."""
        diff = np.abs(logits - ref)
        regret = ref.max(-1) - np.take_along_axis(ref, logits.argmax(-1)[..., None], -1)[..., 0]
        return [float(diff.max()), float(diff.mean()), float(diff[:, args.prompt :].mean()), float(regret.max()), float(regret.mean())]

    refused = (lambda r: r[1] > ISOLATED_MEAN) if args.isolated else (lambda r: r[1] > MEAN or r[0] > WORST)
    by_cell = lambda r: r[3] > check["logit_margin"] or r[4] > check["mean_logit_gap"]  # teacher-forced regret against the cell's limits
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
    wanted.sort(key=lambda name: name == "weights_fp8")  # it consumes the served weights
    for name in wanted:
        report[name] = readings(controls[name]()[0])
        print(json.dumps({name: report[name]}), flush=True)
    report["controls_refused"] = {name: bool(refused(report[name])) for name in wanted}
    report["controls_refused_by_the_cells_limits"] = {name: bool(by_cell(report[name])) for name in wanted}
    ok = not refused(base) and 0 < held < routed
    report["within_limits"] = bool(ok)
    print(json.dumps(report), flush=True)
    return 0 if ok or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())
