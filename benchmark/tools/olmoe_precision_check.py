"""The reading that sets the OLMoE cell's ``check`` limits from below: the
same server, weights and check as the cell's, with weights rounded through a
coarser type first: ``--experts fp8`` (the significand of float8 e4m3, 4
bits against bfloat16's 8, ideally scaled: plain arithmetic, since a convert
to float8 and back proved a no-op in the compiled program), ``int8`` (per
output channel, as ``compression/int8.py`` does) or ``bf16`` (as served, the
control); the expert stacks alone, or with ``--all`` every floating leaf
(embedding, attention, router, norms and head too). ``--requests`` requests of the decode-heavy mix's
lengths are served to their end, then the weights are made again from the
seed, unrounded, and the cell's own check (``ServeSession.check_streams``)
compares the served tokens with the float32 reference on them. Only one copy
of the 10.5 GB tree is alive at a time. A coarser type than the
configuration states has to come out as not correct.

    chiprun -- python3 benchmark/tools/olmoe_precision_check.py --experts fp8 --seed 11
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--experts", choices=("bf16", "fp8", "int8"), required=True)
    ap.add_argument("--all", action="store_true", help="round every floating leaf, not the expert stacks alone")
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rehearse", action="store_true", help="the configuration's tiny rehearse sizes, on the CPU")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import files, loadgen
    from benchmark.serving import Served, ServeSession, seeded_weights
    from deepspeed_tpu.profiling import use_compile_cache

    use_compile_cache()
    spec = files.load_json(ROOT, "BENCHMARK.json")
    cell = files.load_cell(spec, "olmoe_decode_heavy", args.rehearse)
    config, mix = cell["config_file"], cell["traffic_file"]

    def rounded(w):
        if args.experts == "fp8":
            mantissa, exponent = jnp.frexp(w.astype(jnp.float32))  # |mantissa| in [0.5, 1): 4 bits of it are sixteenths
            return jnp.ldexp(jnp.round(mantissa * 16.0) / 16.0, exponent).astype(w.dtype)
        if w.ndim < 2:
            return w  # int8 is a matrix format
        scale = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True) / 127.0  # per output channel
        return (jnp.round(w.astype(jnp.float32) / jnp.maximum(scale, 1e-30)) * scale).astype(w.dtype)

    session = ServeSession(config, args.seed)
    rounding_moved = 0.0  # mean relative change of a slab of one expert matrix: proof that the rounding took
    if args.experts != "bf16":
        round_in_place = jax.jit(rounded, donate_argnums=0)  # one leaf at a time
        params = session.params
        slab = lambda tree: np.asarray(tree["layers"]["moe"]["experts"]["w_gate"][0, 0, :64, :128].astype(jnp.float32))
        before = slab(params)
        if args.all:
            params = jax.tree_util.tree_map(lambda a: round_in_place(a) if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
        else:
            experts = params["layers"]["moe"]["experts"]
            for name in sorted(experts):
                experts[name] = round_in_place(experts[name])
            del experts
        rounding_moved = float(np.abs(slab(params) - before).mean() / np.abs(before).mean())
        session.params = None
        session.engine.set_params(params)
        del params  # the served tree has to be free before the unrounded one is made
    session.warm_up(args.seed)
    supply = loadgen.request_stream(mix, args.requests, session.shape["vocab_size"], args.seed)
    for _ in range(args.requests):
        session.submit(Served(req=next(supply), due=time.perf_counter()))
    while session.server.has_work():
        session.step()
    records, check_cfg, model_section, reference = session.records, session.check, session.model_section, session.reference
    model, _ = files.build_model(config)
    # drop the served tree, then make the unrounded weights again for the reference
    session.engine = session.server = session.params = None
    del session
    gc.collect()
    dtype = jnp.bfloat16 if config["engine"]["init_inference"]["dtype"] == "bf16" else jnp.dtype(config["engine"]["init_inference"]["dtype"])
    holder = types.SimpleNamespace(check=check_cfg, reference=reference, model_section=model_section,
                                   params=seeded_weights(model, args.seed, dtype))
    out = ServeSession.check_streams(holder, records, args.seed)
    out.update(experts=args.experts, every_leaf=args.all, seed=args.seed, requests=args.requests, device=jax.devices()[0].device_kind,
               tokens_served=int(sum(len(r.stamps) for r in records)), rounding_moved=rounding_moved)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
