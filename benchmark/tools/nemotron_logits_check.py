"""NVIDIA-Nemotron-3-Nano-30B-A3B at its published widths on the chip, logits
against the plain reference, outside any timed window: the benchmark's
configuration (published blocks 0-15, ``MEMEM*EMEMEM*EME``: seven Mamba-2 blocks
of 64 heads of 64 over a state of 128 with EIGHT groups of ``B`` and ``C``, two
NoPE GQA blocks of 32 heads over 2 of 128, seven expert blocks of 64 held
two-matrix ``relu2`` experts of 1,856 behind a sigmoid router of 128 and a
shared expert of 3,712, 65,536 rows of the vocabulary), seeded bfloat16 weights,
``--sequences`` sequences of ``--prompt`` + ``--decode`` tokens through
``hybrid_decode``'s blocks as the server runs them (``ROWS`` rows; the first
``--sequences`` rows live, in slots that are not their rows, on pages that are
not in walk order; the prompt in chunks of 128 through ``ssd_chunked`` from the
carried state and tail, then one token a step through ``ssd_decode`` in place,
each step fed the token the program itself chose the step before, greedily, as
a served stream is made; the head over the live rows alone), against a full
forward of ``benchmark/reference/nemotron_h_decoder.py`` over that stream in
float32 (the recurrence token by token, full causal attention, every held
expert over all tokens behind a mask). Prints the worst and mean absolute
logit difference beside their limits and, under ``cell_check``, what the
harness's own comparison (``benchmark/serving.py::ServeSession.check_streams``,
the code that decides a run's ``correct``, under the configuration's own
``engine.check`` limits) says of the stream, and the same for what the written
limits have to refuse, each judged on its own stream: group
0's ``B`` and ``C`` for every head, the gated norm over all 4,096 features, the
gate behind the norm, ``relu`` for ``relu^2``, a SwiGLU-shaped expert, the
factor 2.5 left out, the selection bias left out, the shared expert dropped, a
rotary applied, the state or the convolution tail not carried from one step to
the next, the state rounded to bfloat16 after every step, and every weight in
float8's significand (the nearest precision below the served one).
``--isolated`` is where every one of them shows, a bfloat16 state too, which
the router's band would hide: six blocks ``MEM*EM`` in float32 throughout at
the published widths, where nothing but the order of the sums, the chunk form
and the sorted rows separates program and reference.

    chiprun -- python3 benchmark/tools/nemotron_logits_check.py --seed 7
    chiprun -- python3 benchmark/tools/nemotron_logits_check.py --seed 7 --isolated
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

# The bfloat16 program against the float32 reference on the same weights, 16
# blocks: limits on the worst and the mean absolute logit difference (logits of
# standard deviation ~1.0: a head drawn at 0.02 over 2,688 normed features); they
# and which controls they refuse are written from the chip runs in PERF.md
# section 6 (PR 59), where the readings stand beside them. The band is the
# router's: a choice near a tie moved by bfloat16 moves 2.5 / 6 of an expert's term.
WORST, MEAN = 4.0, 0.15
# ``--isolated``: six blocks in float32 throughout (weights the same
# bfloat16-rounded values, matmuls at precision highest). Every control is a
# different function and differs by orders of magnitude more.
ISOLATED_MEAN = 2e-4
ISOLATED_LAYERS = ["ssm", "ffn", "ssm", "softmax", "ffn", "ssm"]  # MEM*EM: every kind of block, a state behind a state
ROWS = 8  # of the served program's rows: a state store of 9 slots, 0.13 GB


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--sequences", type=int, default=2)
    ap.add_argument("--prompt", type=int, default=384)
    ap.add_argument("--decode", type=int, default=128)
    ap.add_argument("--only", default="", help="comma-separated controls to run (default: all)")
    ap.add_argument("--isolated", action="store_true", help="six blocks (MEM*EM) in float32 throughout: every control against the order of the sums alone")
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
    config = files.load_json(files.HERE, "configs", "nemotron-3-nano-30b-a3b-l16-ep2.json")
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
    act = jnp.float32 if args.isolated else jnp.bfloat16
    true_weights = served  # what the reference reads, whatever a control does to the program's
    if args.isolated:
        served = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), served)  # the same values, float32 arithmetic
        jax.config.update("jax_default_matmul_precision", "highest")
    impl = "xla" if args.rehearse else "auto"
    from deepspeed_tpu.moe import experts as moe_experts

    published = {"ssm_output": hm.ssm_output, "_pointwise_activation": moe_experts._pointwise_activation}
    home = {"ssm_output": hm, "_pointwise_activation": moe_experts}  # the module each patched function is reached through

    def gate_behind_the_norm(cfg, p, z, y):
        """``hm.ssm_output`` with the gate applied AFTER the grouped norm (the other Mamba-2 variant)."""
        by_group = (cfg.ssm_groups, cfg.ssm_inner // cfg.ssm_groups)
        normed = hm._norm(y.astype(jnp.float32).reshape(y.shape[:-1] + by_group), p["o_norm_scale"].reshape(by_group), None, "rmsnorm", cfg.norm_eps)
        return hm.qmatmul((normed.reshape(y.shape) * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype), p["wo"])

    def one_norm_over_all_features(cfg, p, z, y):
        """``hm.ssm_output`` with ONE RMSNorm over all ``d_inner`` features (one group's rule)."""
        gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        return hm.qmatmul(hm._norm(gated, p["o_norm_scale"], None, "rmsnorm", cfg.norm_eps).astype(z.dtype), p["wo"])

    def served_logits(run_cfg, params, patch=None, forget=None, round_state=False):
        """(logits [sequences, total, V], the stream [sequences, total]: the
        prompt's tokens, then the program's own greedy choices).
        ``patch``: {name of a function of ``hm`` or of ``moe/experts.py``
        (``home``): its stand-in} (the step reaches them through the module:
        traced below, restored after);
        ``forget``: "state" or "conv", the pool that is zeroed after every
        step; ``round_state``: the state rounded to bfloat16 after every step."""
        for name, fn in (patch or {}).items():
            setattr(home[name], name, fn)

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
        out, stream, done = np.zeros((args.sequences, total, shape["vocab_size"]), np.float32), tokens.copy(), 0
        try:
            while done < total:
                width = chunk if done < args.prompt else 1
                real = min(width, args.prompt - done) if done < args.prompt else 1
                window = np.zeros((rows, width), np.int32)
                window[: args.sequences, :real] = stream[:, done : done + real]
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
                if args.prompt <= done < total:  # the next step's token is this step's choice
                    stream[:, done] = out[:, done - 1].argmax(-1)
        finally:
            for name, fn in published.items():
                setattr(home[name], name, fn)
        return out, stream

    def fp8(w):
        mantissa, exponent = jnp.frexp(w.astype(jnp.float32))
        return jnp.ldexp(jnp.round(mantissa * 16.0) / 16.0, exponent).astype(w.dtype)

    def with_stack(kind, **leaves):
        """The served weights with leaves of one kind's stacks (``ssm``, ``moe``) replaced."""
        return {**served, "periods": {**served["periods"], kind: {**served["periods"][kind], **leaves}}}

    def group_0_for_every_head():
        """Every group's ``B`` and ``C`` channels made group 0's, projection, taps and bias alike: the depthwise
        convolution then hands every head group 0's, in the chunk form and in the decode kernel."""
        ssm, inner, N, G = served["periods"]["ssm"], cfg.ssm_inner, cfg.ssm_state, cfg.ssm_groups

        def copied(a):
            x, B, C = a[..., :inner], a[..., inner : inner + N], a[..., inner + G * N : inner + G * N + N]
            return jnp.concatenate([x] + [B] * G + [C] * G, axis=-1)

        return with_stack("ssm", **{leaf: copied(ssm[leaf]) for leaf in ("w_xbc", "conv_w", "conv_b")})

    moe = served["periods"]["moe"]
    controls = {
        "group_0_for_every_head": lambda: served_logits(cfg, group_0_for_every_head()),
        "one_norm_over_all_features": lambda: served_logits(cfg, served, {"ssm_output": one_norm_over_all_features}),
        "gate_behind_the_norm": lambda: served_logits(cfg, served, {"ssm_output": gate_behind_the_norm}),
        "relu_for_relu2": lambda: served_logits(dataclasses.replace(cfg, activation="relu"), served),
        # (silu(h W) * (h W)) W_out, the gate a copy of the up projection: silu(u) u in relu(u)^2's place, on the same two matrices
        "swiglu_shaped_expert": lambda: served_logits(cfg, served, {"_pointwise_activation": lambda u, activation: jax.nn.silu(u) * u}),
        "no_routed_scaling": lambda: served_logits(dataclasses.replace(cfg, moe_routed_scaling=1.0), served),
        "no_selection_bias": lambda: served_logits(cfg, with_stack("moe", gate={**moe["gate"], "bias": jnp.zeros_like(moe["gate"]["bias"])})),
        "shared_expert_dropped": lambda: served_logits(cfg, with_stack("moe", shared={**moe["shared"], "w_out": jnp.zeros_like(moe["shared"]["w_out"])})),
        "rotary_applied": lambda: served_logits(dataclasses.replace(cfg, position="rope"), served),
        "state_not_carried": lambda: served_logits(cfg, served, forget="state"),
        "conv_tail_not_carried": lambda: served_logits(cfg, served, forget="conv"),
        "state_bfloat16": lambda: served_logits(cfg, served, round_state=True),
        # the nearest precision below the served one, for the cell's own limits (engine.check): LAST, and in place, because
        # a second copy of 10.6 GB of weights does not fit beside the first and the pools (the served weights are gone after it,
        # and ``reference_weights`` draws them again from the seed for the reference's forward over the float8 program's stream)
        "weights_fp8": lambda: served_logits(cfg, jax.tree_util.tree_map(jax.jit(fp8, donate_argnums=0), served)),
    }
    limits = {"mean": ISOLATED_MEAN} if args.isolated else {"worst": WORST, "mean": MEAN}
    check = config["engine"]["check"]
    report = {"device": jax.devices()[0].device_kind, "sequences": args.sequences, "prompt": args.prompt, "decode": args.decode,
              "seed": args.seed, "isolated": args.isolated, "layers": cfg.num_layers, "limits": limits,
              "cell_limits": {"logit_margin": check["logit_margin"], "mean_logit_gap": check["mean_logit_gap"]}, "cell_check": {}}

    def reference_weights():
        """The seeded weights the reference reads: drawn again from the seed once ``weights_fp8`` has consumed them."""
        nonlocal true_weights
        if jax.tree_util.tree_leaves(true_weights)[0].is_deleted():
            true_weights = seeded_weights(model, args.seed, jnp.bfloat16)
        return true_weights

    def cell_check(stream, ref):
        """The harness's own verdict on ``stream`` as served streams: ``ServeSession.check_streams`` itself under the
        configuration's ``engine.check`` limits (its sample and context are this tool's sequences and their length),
        handed the reference's logits of the stream, which ``readings`` needs too (by row: it draws the sample's order)."""
        from benchmark.serving import ServeSession
        from types import SimpleNamespace as NS

        records = [NS(rejected=False, req=NS(prompt=row[: args.prompt]), stream=lambda row=row: row, ok=lambda: True) for row in stream]
        session = NS(check={**check, "sample": len(records), "max_context": total}, model_section=config["model"], params=None,
                     reference=NS(logits=lambda model, params, rows: jnp.asarray(ref[[int(np.flatnonzero((stream == row).all(-1))[0]) for row in rows]])))
        return ServeSession.check_streams(session, records, args.seed)

    def readings(name, run):
        """[worst and mean absolute difference from the reference over the
        run's own stream, the mean over the decoded positions alone, the
        worst and the mean gap of a served token as the harness reads them]."""
        logits, stream = run()
        ref = np.asarray(reference.logits(config["model"], reference_weights(), stream))
        diff = np.abs(logits - ref)
        verdict = report["cell_check"][name] = cell_check(stream, ref)
        if name == "ours":
            report["logit_std"] = float(ref.std())
            report["mean_by_position_64"] = [float(diff[:, i : i + 64].mean()) for i in range(0, total, 64)]
            report["argmax_agreement"] = float(np.mean(logits.argmax(-1) == ref.argmax(-1)))
        return [float(diff.max()), float(diff.mean()), float(diff[:, args.prompt :].mean()), verdict["worst_logit_gap"], verdict["mean_logit_gap"]]

    refused = (lambda r: r[1] > ISOLATED_MEAN) if args.isolated else (lambda r: r[1] > MEAN or r[0] > WORST)
    base = readings("ours", lambda: served_logits(cfg, served))
    report["worst_abs_diff"], report["mean_abs_diff"], report["decode_mean_abs_diff"] = base[:3]
    report["served_token_gap_worst_mean"] = base[3:]
    print(json.dumps({"ours": base}), flush=True)
    wanted = [c for c in args.only.split(",") if c] or list(controls)
    wanted.sort(key=lambda name: name == "weights_fp8")  # it consumes the served weights
    for name in wanted:
        report[name] = readings(name, controls[name])
        print(json.dumps({name: report[name]}), flush=True)
    report["controls_refused"] = {name: bool(refused(report[name])) for name in wanted}
    report["controls_refused_by_the_cells_limits"] = {name: not report["cell_check"][name]["correct"] for name in wanted}
    ok = not refused(base)
    report["within_limits"] = bool(ok)
    print(json.dumps(report), flush=True)
    return 0 if ok or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())
