"""LFM2-24B-A2B at its published widths and its FULL depth on the chip, logits
against the plain reference, outside any timed window: the benchmark's
configuration (all 40 layers: 30 gated short convolutions, 10 rotary GQA
layers with a head norm on q and k, two leading dense layers, 8 of 64 experts
held in each of the 38 routed layers, the whole vocabulary), seeded bfloat16
weights, ``--sequences`` sequences of ``--prompt`` + ``--decode`` tokens
through ``hybrid_decode``'s layers as the server runs them (``ROWS`` rows; the
first ``--sequences`` rows live, in slots that are not their rows, on pages
that are not in walk order; the prompt in chunks of 128 through the
convolution's chunk form and the tail hand-over, then one token a step from
the stored tail, each step fed the token the program itself chose the step
before, greedily, as a served stream is made; the head over the live rows
alone), against a full forward of ``benchmark/reference/lfm2_moe_decoder.py``
over that stream in float32. Prints the worst and mean absolute logit
difference and, under ``cell_check``, what the harness's own comparison
(``benchmark/serving.py::ServeSession.check_streams``, the code that decides a
run's ``correct``, under the configuration's own ``engine.check`` limits) says
of the stream, and the same for what the written limits have to refuse, each
judged on its own stream: the tail not shifted (a decode row keeps its oldest
product), ``B`` and ``C`` swapped, the head norm left out, the two trailing
layers left out, the tail not carried from one step to the next, and every
weight in float8's significand (the nearest precision below the served one).

    chiprun -- python3 benchmark/tools/lfm2_logits_check.py --seed 7
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

ROWS = 8  # of the served program's rows: a tail store of 9 slots


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--sequences", type=int, default=2)
    ap.add_argument("--prompt", type=int, default=384)
    ap.add_argument("--decode", type=int, default=128)
    ap.add_argument("--only", default="", help="comma-separated controls to run (default: all)")
    ap.add_argument("--rehearse", action="store_true", help="the configuration's tiny rehearse sizes, on the CPU")
    args = ap.parse_args()

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import files
    from benchmark.serving import ServeSession, seeded_weights
    from deepspeed_tpu.inference import decode, hybrid_decode
    from deepspeed_tpu.inference.kv_pool import PagePool, StateStore
    from deepspeed_tpu.models import hybrid_moe as hm
    from deepspeed_tpu.profiling import use_compile_cache

    use_compile_cache()
    config = files.load_json(files.HERE, "configs", "lfm2-24b-a2b-ep8.json")
    if args.rehearse:
        config = files.overlay(config, config["rehearse"])
        args.prompt, args.decode = min(args.prompt, 40), min(args.decode, 24)
    model, shape = files.build_model(config)
    reference = files.reference_of(config)
    paged = config["engine"]["init_inference"]["paged_kv"]
    rows, page, chunk = min(ROWS, paged["max_slots"]), paged["page_size"], paged["prefill_chunk"]
    total = args.prompt + args.decode
    maxp = -(-total // page)
    cfg = model.config
    served = seeded_weights(model, args.seed, jnp.bfloat16)
    tokens = np.random.default_rng([args.seed, 1]).integers(0, shape["vocab_size"], (args.sequences, total), dtype=np.int32)
    true_weights = served  # what the reference reads, whatever a control does to the program's
    impl = "xla" if args.rehearse else "auto"
    published = {"shifted_tail": hm.shifted_tail, "conv_inputs": hm.conv_inputs}

    def swapped(p, h):
        """``hm.conv_inputs`` with ``B`` and ``C`` in each other's place: ``u = C * x~``, the output gate ``B``."""
        b, c, x = jnp.split(hm.qmatmul(h, p["w_in"]), 3, axis=-1)
        return c * x, b

    def not_shifted(tail, u):
        """``hm.shifted_tail`` that keeps the OLDEST entry and overwrites the newest."""
        return jnp.concatenate([tail[..., :-1, :], u[..., None, :]], axis=-2)

    def served_logits(run_cfg, params, patch=None, forget=False):
        """(logits [sequences, total, V], the stream [sequences, total]: the
        prompt's tokens, then the program's own greedy choices). ``patch``:
        {name of a function of ``hm``: its stand-in} (the step reaches them
        through the module: traced below, restored after); ``forget``: the
        tail store zeroed after every step."""
        for name, fn in (patch or {}).items():
            setattr(hm, name, fn)

        @functools.partial(jax.jit, donate_argnums=(2, 3, 4))
        def forward(params, window, kp, vp, cv, table, lengths, q_lens, slots):
            x, kp, vp, store, _, packed = hybrid_decode._hybrid_layers(
                run_cfg, params, window, kp, vp, StateStore(None, cv), table, lengths, q_lens, slots, impl
            )
            live = packed.expand(x)[: args.sequences]  # the head over the live rows alone
            return decode._final_logits(run_cfg, params, live).astype(jnp.float32), kp, vp, store.conv

        pool = PagePool(run_cfg, rows * maxp + 1, page, rows, max_seq_len=maxp * page, dtype=jnp.bfloat16, prefill_chunk=chunk)
        pools = [pool.cache.k_pages, pool.cache.v_pages, pool.states.conv]
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
                if forget:
                    pools[2] = jnp.zeros_like(pools[2])
                out[:, done : done + real] = np.asarray(logits)[:, :real]
                done += real
                if args.prompt <= done < total:  # the next step's token is this step's choice
                    stream[:, done] = out[:, done - 1].argmax(-1)
        finally:
            for name, fn in published.items():
                setattr(hm, name, fn)
        return out, stream

    def fp8(w):
        mantissa, exponent = jnp.frexp(w.astype(jnp.float32))
        return jnp.ldexp(jnp.round(mantissa * 16.0) / 16.0, exponent).astype(w.dtype)

    whole_periods = cfg.num_layers - len(cfg.remainder)
    controls = {
        "tail_not_shifted": lambda: served_logits(cfg, served, {"shifted_tail": not_shifted}),
        "b_and_c_swapped": lambda: served_logits(cfg, served, {"conv_inputs": swapped}),
        "head_norm_left_out": lambda: served_logits(dataclasses.replace(cfg, qk_norm=None), served),
        "trailing_layers_left_out": lambda: served_logits(
            dataclasses.replace(cfg, num_layers=whole_periods, layer_types=cfg.layer_types[:whole_periods]), served),
        "tail_not_carried": lambda: served_logits(cfg, served, forget=True),
        # the nearest precision below the served one: LAST, and in place (the served weights are gone after it, and
        # ``reference_weights`` draws them again from the seed for the reference's forward over the float8 program's stream)
        "weights_fp8": lambda: served_logits(cfg, jax.tree_util.tree_map(jax.jit(fp8, donate_argnums=0), served)),
    }
    check = config["engine"]["check"]
    report = {"device": jax.devices()[0].device_kind, "sequences": args.sequences, "prompt": args.prompt, "decode": args.decode,
              "seed": args.seed, "layers": cfg.num_layers, "period": list(cfg.period), "periods": cfg.num_periods, "remainder": list(cfg.remainder),
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
        handed the reference's logits of the stream (by row: it draws the sample's order)."""
        from types import SimpleNamespace as NS

        records = [NS(rejected=False, req=NS(prompt=row[: args.prompt]), stream=lambda row=row: row, ok=lambda: True) for row in stream]
        session = NS(check={**check, "sample": len(records), "max_context": total}, model_section=config["model"], params=None,
                     reference=NS(logits=lambda model, params, rows: jnp.asarray(ref[[int(np.flatnonzero((stream == row).all(-1))[0]) for row in rows]])))
        return ServeSession.check_streams(session, records, args.seed)

    def readings(name, run):
        """[worst and mean absolute difference from the reference over the run's own stream, the mean over the decoded
        positions alone, the worst and the mean gap of a served token as the harness reads them]."""
        logits, stream = run()
        ref = np.asarray(reference.logits(config["model"], reference_weights(), stream))
        diff = np.abs(logits - ref)
        verdict = report["cell_check"][name] = cell_check(stream, ref)
        if name == "ours":
            report["logit_std"] = float(ref.std())
            report["mean_by_position_64"] = [float(diff[:, i : i + 64].mean()) for i in range(0, total, 64)]
            report["argmax_agreement"] = float(np.mean(logits.argmax(-1) == ref.argmax(-1)))
            # how far the current token's own row of the TIED table stands over the rest, in the logits' standard deviations
            own = np.take_along_axis(ref[:, :-1], stream[:, :-1, None], axis=-1)[..., 0]
            report["own_row_stands_over_std"] = float(((own - ref[:, :-1].mean(-1)) / ref[:, :-1].std(-1)).mean())
        return [float(diff.max()), float(diff.mean()), float(diff[:, args.prompt :].mean()), verdict["worst_logit_gap"], verdict["mean_logit_gap"]]

    base = readings("ours", lambda: served_logits(cfg, served))
    report["worst_abs_diff"], report["mean_abs_diff"], report["decode_mean_abs_diff"] = base[:3]
    report["served_token_gap_worst_mean"] = base[3:]
    print(json.dumps({"ours": base}), flush=True)
    wanted = [c for c in args.only.split(",") if c] or list(controls)
    wanted.sort(key=lambda name: name == "weights_fp8")  # it consumes the served weights
    for name in wanted:
        report[name] = readings(name, controls[name])
        print(json.dumps({name: report[name]}), flush=True)
    report["controls_refused_by_the_cells_limits"] = {name: not report["cell_check"][name]["correct"] for name in wanted}
    ok = report["cell_check"]["ours"]["correct"]
    report["within_limits"] = bool(ok)
    print(json.dumps(report), flush=True)
    return 0 if ok or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())
