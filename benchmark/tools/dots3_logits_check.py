"""dots3-note-prev at its published widths on the chip, logits against the
plain reference, outside any timed window: the benchmark's configuration whole
(a leading dense layer and two periods of ``full, window x 3``: three full
latent layers of 128 heads that attend the 2,048 keys their indexer chooses,
six window layers of 64 heads over a ring of latents of rank 1,024, a gate a
head on all nine, 16 held experts of a router over 256 and a shared one, 1/8
of the vocabulary), seeded bfloat16 weights, ``--sequences`` sequences of
``--prompt`` + ``--decode`` tokens through ``hybrid_decode.hybrid_forward`` as
the server runs it (32 rows of which the first ``--sequences`` are live, on
pages that are not in walk order; the prompt in chunks of 512, each query
token's own selection under a masked walk, then one token a step through the
sort and the gather of chosen entries, each step fed the sequence's own next
token: the ABSORBED form throughout), against ONE full forward of
``benchmark/reference/dots3_note_decoder.py`` in float32 (the published
EXPANDED form, an ``argsort`` a query). The default sizes put every decoded
token and a third of the prompt PAST ``index_topk``: where the selection is the
identity a wrong indexer hides. Prints the worst and mean absolute logit
difference and the regret of the program's own arg-max (what ``engine.check``
reads of served tokens), and the same for what the written limits have to
refuse: the selection off (all keys), the most recent 2,048 in place of the
top 2,048, the ReLU left out of the index, the indexer's rotary off, a window
of 512, the rescale of the low ranks left out, the gates left out, fifteen of
the sixteen held experts, and every weight in float8's significand (the
nearest precision below the served one). ``--isolated`` is where every one of
them shows: the leading layer, a routed full layer and a window layer in float32
throughout, where
nothing but the order of the sums and the absorbed product's association
separates program and reference (and a near-tie at the 2,048th score is
resolved the same way by both).

    chiprun -- python3 benchmark/tools/dots3_logits_check.py --seed 7
    chiprun -- python3 benchmark/tools/dots3_logits_check.py --seed 7 --isolated
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# The bfloat16 program against the float32 reference on the same weights, 9
# layers: limits on the worst and the mean absolute logit difference; they and
# which controls they refuse are written from the chip runs in PERF.md section
# 6 (PR 66), where the readings stand beside them.
WORST, MEAN = 6.5, 0.08
# ``--isolated``: three layers in float32 throughout (weights the same
# bfloat16-rounded values, matmuls at precision highest). Every control is a
# different function and differs by orders of magnitude more.
ISOLATED_MEAN = 2e-4


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--sequences", type=int, default=2)
    ap.add_argument("--prompt", type=int, default=3072)
    ap.add_argument("--decode", type=int, default=512)
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
    from deepspeed_tpu.models import hybrid_moe as hm
    from deepspeed_tpu.ops.transformer import sparse_latent_attention as sla
    from deepspeed_tpu.profiling import use_compile_cache

    use_compile_cache()
    config = files.load_json(files.HERE, "configs", "dots3-note-prev-l9-ep16.json")
    if args.rehearse:
        config = files.overlay(config, config["rehearse"])
        args.prompt, args.decode, args.isolated = min(args.prompt, 40), min(args.decode, 24), True
    if args.isolated:
        kw = config["model"]["kwargs"]
        kw.update(num_layers=3, layer_types=kw["layer_types"][:3], dtype="float32")  # full (dense FFN), full, window: 5.6 GB in float32
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
    published = {"index_scores": hm.index_scores, "index_queries": hm.index_queries, "index_key": hm.index_key, "paged": sla.paged_index_scores}

    def served_logits(run_cfg, params, **patched):
        """The sequences through ``hybrid_forward``; ``patched``: functions the step reaches through their modules, traced below and restored after."""
        for name, fn in patched.items():
            setattr(sla if name == "paged" else hm, "paged_index_scores" if name == "paged" else name, fn)

        @jax.jit
        def forward(params, window, kp, vp, st, cv, latent, index, rings, table, lengths, q_lens, slots):
            logits, kp, vp, st, cv, counts, latent, index, rings = hybrid_decode.hybrid_forward(
                run_cfg, params, window, kp, vp, st, cv, table, lengths, q_lens, slots, "xla" if args.rehearse else "auto", latent=latent, index=index, latent_rings=rings
            )
            return logits[: args.sequences].astype(jnp.float32), kp, vp, st, cv, latent, index, rings, counts

        pool = PagePool(run_cfg, rows * maxp + 1, page, rows, max_seq_len=maxp * page, dtype=act, prefill_chunk=chunk)
        states = pool.states
        pools = [pool.cache.k_pages, pool.cache.v_pages, states.state, states.conv, states.latent, states.index, states.window_latent]
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
                out[:, done : done + real] = np.asarray(logits)[:, :real]
                held += int(np.asarray(counts).sum())
                done += real
        finally:
            hm.index_scores, hm.index_queries, hm.index_key, sla.paged_index_scores = (published[k] for k in ("index_scores", "index_queries", "index_key", "paged"))
        return out, held

    def fp8(w):
        mantissa, exponent = jnp.frexp(w.astype(jnp.float32))
        return jnp.ldexp(jnp.round(mantissa * 16.0) / 16.0, exponent).astype(w.dtype)

    def with_moe(params, **leaves):
        return {**params, "periods": {**params["periods"], "moe": {**params["periods"]["moe"], **leaves}}}

    def without_gates(params):
        drop = lambda mixer: {k: v for k, v in mixer.items() if k != "wg_head"}
        periods = {k: (drop(v) if k in hm.LATENT_KINDS else v) for k, v in params["periods"].items()}
        return {**params, "periods": periods, "leading": [{**layer, "mixer": drop(layer["mixer"])} for layer in params["leading"]]}

    def newest_first(qi, wi, index, layer, page_table, kv_lens):
        """In place of the indexer's scores: a key's position, so that the top 2,048 are the most recent 2,048."""
        _, per, n, _ = sla._blocks(page_table, index.shape[2])
        positions = per * n * index.shape[2]
        return jnp.broadcast_to(jnp.arange(positions, dtype=jnp.float32), qi.shape[:2] + (positions,))

    def scores_without_relu(q, w, k):
        return jnp.sum(jnp.einsum("...tjd,...sd->...tjs", q, k.astype(q.dtype), preferred_element_type=jnp.float32) * w[..., None], axis=-2)

    def unrotated_queries(cfg, p, c_q, h, positions):
        IH, ID = cfg.index_num_heads, cfg.index_head_dim
        return hm.qmatmul(c_q, p["wi_qb"]).reshape(c_q.shape[:-1] + (IH, ID)), hm.qmatmul(h, p["wi_w"]).astype(jnp.float32) * float(IH ** -0.5 * ID ** -0.5)

    def unrotated_key(cfg, p, h, positions):
        from deepspeed_tpu.models.transformer import _norm

        return _norm(hm.qmatmul(h, p["wi_k"]), p["wi_k_norm_scale"], p["wi_k_norm_bias"], "layernorm", cfg.norm_eps)

    moe = served["periods"]["moe"]
    last = cfg.num_experts - 1
    controls = {
        "all_keys": lambda: served_logits(dataclasses.replace(cfg, index_topk=1 << 20), served),
        "recent_2048": lambda: served_logits(cfg, served, paged=newest_first),
        "no_relu": lambda: served_logits(cfg, served, index_scores=scores_without_relu),
        "no_index_rotary": lambda: served_logits(cfg, served, index_queries=unrotated_queries, index_key=unrotated_key),
        "window_512": lambda: served_logits(dataclasses.replace(cfg, window=cfg.window - 1), served),
        "no_rescale": lambda: served_logits(dataclasses.replace(cfg, latent_lora_rescale=False), served),
        "no_gate": lambda: served_logits(cfg, without_gates(served)),
        "15_of_16_experts": lambda: served_logits(cfg, with_moe(served, experts={**moe["experts"], "w_out": moe["experts"]["w_out"].at[:, :, last].set(0)})),
        # the nearest precision below the served one, for the cell's own limits (engine.check). Run LAST and in place, leaf by
        # leaf: a second copy of 9.2 GB of weights does not fit beside the first (RESOURCE_EXHAUSTED, PR 66's second chip call)
        "weights_fp8": lambda: served_logits(cfg, jax.tree_util.tree_map(jax.jit(fp8, donate_argnums=0), served)),
    }
    limits = {"mean": ISOLATED_MEAN} if args.isolated else {"worst": WORST, "mean": MEAN}
    check = config["engine"]["check"]
    report = {"device": jax.devices()[0].device_kind, "sequences": args.sequences, "prompt": args.prompt, "decode": args.decode,
              "seed": args.seed, "isolated": args.isolated, "layers": cfg.num_layers, "index_topk": cfg.index_topk, "window": cfg.window,
              "logit_std": float(ref.std()), "limits": limits,
              "cell_limits": {"logit_margin": check["logit_margin"], "mean_logit_gap": check["mean_logit_gap"]}}
    past = min(cfg.index_topk, total - 1)  # positions from here on have more keys than the selection keeps

    def readings(logits):
        """[worst and mean absolute difference, the mean over the positions
        past ``index_topk`` alone, worst and mean regret of the program's own
        arg-max there (what ``engine.check`` reads of served tokens)]."""
        diff = np.abs(logits - ref)
        regret = (ref.max(-1) - np.take_along_axis(ref, logits.argmax(-1)[..., None], -1)[..., 0])[:, past:]
        return [float(diff.max()), float(diff.mean()), float(diff[:, past:].mean()), float(regret.max()), float(regret.mean())]

    refused = (lambda r: r[1] > ISOLATED_MEAN) if args.isolated else (lambda r: r[1] > MEAN or r[0] > WORST)
    by_cell = lambda r: r[3] > check["logit_margin"] or r[4] > check["mean_logit_gap"]  # teacher-forced regret against the cell's limits
    ours, held = served_logits(cfg, served)
    base = readings(ours)
    report["worst_abs_diff"], report["mean_abs_diff"], report["past_topk_mean_abs_diff"] = base[:3]
    report["served_argmax_regret_worst_mean"] = base[3:]
    diff = np.abs(ours - ref)
    step = max(total // 14, 1)
    report["mean_by_position"] = [float(diff[:, i : i + step].mean()) for i in range(0, total, step)]
    report["argmax_agreement"] = float(np.mean(ours.argmax(-1) == ref.argmax(-1)))
    routed = args.sequences * total * cfg.moe_top_k * cfg.num_moe_layers
    report["held_assignments"], report["routed_assignments"] = held, routed
    print(json.dumps({"ours": base}), flush=True)
    wanted = sorted([c for c in args.only.split(",") if c] or list(controls), key=lambda c: c == "weights_fp8")  # it consumes the weights
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
