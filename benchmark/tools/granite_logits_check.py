"""granite-4.0-h-micro at its published widths on the chip, logits against the
plain reference, outside any timed window: the benchmark's configuration whole
(all 40 layers: four periods of nine Mamba-2 layers of 64 heads of 64 over a
state of 128 around one NoPE GQA layer of 32 heads of 64 over 8, a dense FFN of
8,192 in every layer, the tied table of 100,352 rows, the four multipliers),
seeded bfloat16 weights, ``--sequences`` sequences of ``--prompt`` + ``--decode``
tokens through ``hybrid_decode``'s layers as the server runs them (``ROWS``
rows, not the cell's 64: the tool's steps lend and take back their pools, and
two stores of 65 slots x 36 layers x 2 MB beside 6.4 GB of weights are no chip's;
the first ``--sequences`` rows live, in slots that are not their rows, on
pages that are not in walk order; the prompt in chunks of 128 through
``ssd_chunked`` from the carried state and tail, then one token a step through
``ssd_decode`` in place, each step fed the sequence's own next token; the head
over the live rows alone: 64 x 128 slots x 100,352 logits are no array a chip
holds), against ONE full forward of
``benchmark/reference/granite_hybrid_decoder.py`` in float32 (the recurrence
token by token, full causal attention). Prints the worst and mean absolute
logit difference and the regret of the program's own arg-max (what
``engine.check`` reads of served tokens) beside their limits, and the same for
what the written limits have to refuse: the softmax scale ``64^-0.5`` for 1/64,
the convolution's bias dropped, the gate behind the norm, ``D x`` dropped, the
residual multiplier on one branch only, the state or the convolution tail not
carried from one step to the next, the state rounded to bfloat16 after every
step, and every weight in float8's significand (the nearest precision below
the served one). ``--isolated`` is where every one of them shows: one period of
ten in float32 throughout, where nothing but the order of the sums and the
chunk form separates program and reference.

    chiprun -- python3 benchmark/tools/granite_logits_check.py --seed 7
    chiprun -- python3 benchmark/tools/granite_logits_check.py --seed 7 --isolated
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# The bfloat16 program against the float32 reference on the same weights, 40
# layers: limits on the worst and the mean absolute logit difference (logits of
# standard deviation 0.057: a table drawn at 0.01, the head divided by 8); they and which controls they
# refuse are written from the chip runs in PERF.md section 6 (PR 52), where the
# readings stand beside them.
WORST, MEAN = 0.05, 0.005
# ``--isolated``: ten layers in float32 throughout (weights the same
# bfloat16-rounded values, matmuls at precision highest). Every control is a
# different function and differs by orders of magnitude more.
ISOLATED_MEAN = 1e-5
ISOLATED_LAYERS = ["ssm"] * 5 + ["softmax"] + ["ssm"] * 4  # one period, as published
ROWS = 8  # of the served program's rows: a state store of 9 slots, 0.68 GB


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--sequences", type=int, default=2)
    ap.add_argument("--prompt", type=int, default=384)
    ap.add_argument("--decode", type=int, default=128)
    ap.add_argument("--only", default="", help="comma-separated controls to run (default: all)")
    ap.add_argument("--isolated", action="store_true", help="one period in float32 throughout: every control against the order of the sums alone")
    ap.add_argument("--rehearse", action="store_true", help="the configuration's tiny rehearse sizes, on the CPU, float32")
    args = ap.parse_args()

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import files
    from benchmark.serving import seeded_weights
    from deepspeed_tpu.inference import decode, hybrid_decode
    from deepspeed_tpu.inference.kv_pool import PagePool, StateStore
    from deepspeed_tpu.models import hybrid_moe as hm
    from deepspeed_tpu.profiling import use_compile_cache

    use_compile_cache()
    config = files.load_json(files.HERE, "configs", "granite-4.0-h-micro.json")
    if args.rehearse:
        config = files.overlay(config, config["rehearse"])
        args.prompt, args.decode, args.isolated = min(args.prompt, 40), min(args.decode, 24), True
    if args.isolated:
        config["model"]["kwargs"].update(num_layers=len(ISOLATED_LAYERS), layer_types=ISOLATED_LAYERS, dtype="float32")
    model, shape = files.build_model(config)
    reference = files.reference_of(config)
    paged = config["engine"]["init_inference"]["paged_kv"]
    rows, page, chunk = min(ROWS, paged["max_slots"]), paged["page_size"], paged["prefill_chunk"]
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
    published = {"ssm_output": hm.ssm_output}

    def gate_behind_the_norm(cfg, p, z, y):
        """``hm.ssm_output`` with the gate applied AFTER the norm (the other Mamba-2 variant)."""
        normed = hm._norm(y.astype(jnp.float32), p["o_norm_scale"], None, "rmsnorm", cfg.norm_eps)
        return hm.qmatmul((normed * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype), p["wo"])

    def served_logits(run_cfg, params, patch=None, forget=None, round_state=False):
        """``patch``: {name of a function of ``hm``: its stand-in} (the step
        reaches them through the module: traced below, restored after);
        ``forget``: "state" or "conv", the pool that is zeroed after every
        step; ``round_state``: the state rounded to bfloat16 after every step."""
        for name, fn in (patch or {}).items():
            setattr(hm, name, fn)

        @functools.partial(jax.jit, donate_argnums=(2, 3, 4, 5))
        def forward(params, window, kp, vp, st, cv, table, lengths, q_lens, slots):
            x, kp, vp, store, _, packed = hybrid_decode._hybrid_layers(
                run_cfg, params, window, kp, vp, StateStore(st, cv), table, lengths, q_lens, slots, impl
            )
            live = packed.expand(x)[: args.sequences]  # the head over the live rows alone
            return decode._final_logits(run_cfg, params, live).astype(jnp.float32), kp, vp, store.state, store.conv

        pool = PagePool(run_cfg, rows * maxp + 1, page, rows, max_seq_len=maxp * page, dtype=act, prefill_chunk=chunk)
        pools = [pool.cache.k_pages, pool.cache.v_pages, pool.states.state, pool.states.conv]
        del pool
        table = np.full((rows, maxp), -1, np.int32)
        slots = np.full(rows, rows, np.int32)
        for r in range(args.sequences):
            # a row's pages interleaved with the others', so that page ids are not in walk order
            table[r], slots[r] = 1 + r + args.sequences * np.arange(maxp), (3 * r + 5) % rows
        out, done = np.zeros(ref.shape, np.float32), 0
        try:
            while done < total:
                width = chunk if done < args.prompt else 1
                real = min(width, args.prompt - done) if done < args.prompt else 1
                window = np.zeros((rows, width), np.int32)
                window[: args.sequences, :real] = tokens[:, done : done + real]
                lengths, q_lens = np.zeros(rows, np.int32), np.zeros(rows, np.int32)
                lengths[: args.sequences], q_lens[: args.sequences] = done, real
                logits, *pools = forward(params, window, *pools, table, lengths, q_lens, slots)
                if forget is not None:
                    at = 2 if forget == "state" else 3
                    pools[at] = jnp.zeros_like(pools[at])
                if round_state:
                    pools[2] = pools[2].astype(jnp.bfloat16).astype(jnp.float32)
                out[:, done : done + real] = np.asarray(logits)[:, :real]
                done += real
        finally:
            for name, fn in published.items():
                setattr(hm, name, fn)
        return out

    def fp8(w):
        mantissa, exponent = jnp.frexp(w.astype(jnp.float32))
        return jnp.ldexp(jnp.round(mantissa * 16.0) / 16.0, exponent).astype(w.dtype)

    def without(leaf):
        """The served weights with one leaf of every state-space layer zeroed (a bias, ``D``)."""
        ssm = served["periods"]["ssm"]
        return {**served, "periods": {**served["periods"], "ssm": {**ssm, leaf: jnp.zeros_like(ssm[leaf])}}}

    controls = {
        "softmax_scale_rsqrt_64": lambda: served_logits(dataclasses.replace(cfg, attn_softmax_scale=cfg.head_dim ** -0.5), served),
        "conv_bias_dropped": lambda: served_logits(cfg, without("conv_b")),
        "gate_behind_the_norm": lambda: served_logits(cfg, served, {"ssm_output": gate_behind_the_norm}),
        "D_dropped": lambda: served_logits(cfg, without("D")),
        "no_logits_scaling": lambda: served_logits(dataclasses.replace(cfg, logits_scaling=1.0), served),
        "no_residual_multiplier": lambda: served_logits(dataclasses.replace(cfg, residual_multiplier=1.0), served),
        "state_not_carried": lambda: served_logits(cfg, served, forget="state"),
        "conv_tail_not_carried": lambda: served_logits(cfg, served, forget="conv"),
        "state_bfloat16": lambda: served_logits(cfg, served, round_state=True),
        # the nearest precision below the served one, for the cell's own limits (engine.check): LAST, and in place, because
        # a second copy of 6.4 GB of weights does not fit beside the first and the pools (the served weights are gone after it)
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
    ours = served_logits(cfg, served)
    base = readings(ours)
    report["worst_abs_diff"], report["mean_abs_diff"], report["decode_mean_abs_diff"] = base[:3]
    report["served_argmax_regret_worst_mean"] = base[3:]
    diff = np.abs(ours - ref)
    report["mean_by_position_64"] = [float(diff[:, i : i + 64].mean()) for i in range(0, total, 64)]
    report["argmax_agreement"] = float(np.mean(ours.argmax(-1) == ref.argmax(-1)))
    print(json.dumps({"ours": base}), flush=True)
    wanted = [c for c in args.only.split(",") if c] or list(controls)
    wanted.sort(key=lambda name: name == "weights_fp8")  # it consumes the served weights
    for name in wanted:
        report[name] = readings(controls[name]())
        print(json.dumps({name: report[name]}), flush=True)
    report["controls_refused"] = {name: bool(refused(report[name])) for name in wanted}
    report["controls_refused_by_the_cells_limits"] = {name: bool(by_cell(report[name])) for name in wanted}
    ok = not refused(base)
    report["within_limits"] = bool(ok)
    print(json.dumps(report), flush=True)
    return 0 if ok or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())
