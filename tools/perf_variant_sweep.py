"""Config-1 perf variants on the live TPU: (micro, flash[, scan]) combos.

Usage: python tools/perf_variant_sweep.py "8,1" "16,1" "12,0" "8,1,0"
Third field: scan_layers (default 1); 0 = unrolled Python layer loop.
Each timed window ends in ``block_until_ready`` on the step's outputs.
Persistent compile cache on (``use_compile_cache``), so reruns skip
compiles.
"""
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax

import deepspeed_tpu as ds
import deepspeed_tpu.parallel.mesh as mesh_mod
from deepspeed_tpu.models import TransformerLM, gpt2_config
from deepspeed_tpu.profiling import use_compile_cache

use_compile_cache()

combos = [tuple(int(x) for x in a.split(",")) for a in sys.argv[1:]] or [(8, 1), (16, 1)]
combos = [c if len(c) == 3 else (*c, 1) for c in combos]
seq = 1024
PEAK = 197e12

for micro, flash, scan in combos:
    mesh_mod.reset_topology()
    mcfg = gpt2_config("125m", max_seq_len=seq, remat=False, flash_attention=bool(flash), scan_layers=bool(scan))
    engine, _, _, _ = ds.initialize(
        model=TransformerLM(mcfg),
        config={
            "train_micro_batch_size_per_gpu": micro,
            "optimizer": {"type": "adam", "params": {"lr": 3e-4, "weight_decay": 0.01}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 1},
            "gradient_clipping": 1.0,
            "steps_per_print": 10_000,
        },
        dist_init_required=False,
    )
    rs = np.random.RandomState(0)
    toks = rs.randint(0, mcfg.vocab_size, (micro, seq + 1)).astype(np.int32)
    batch = {"input_ids": toks[:, :-1], "labels": toks[:, 1:]}
    placed = engine._place_batch(batch)

    def drain():
        jax.block_until_ready(engine.get_params())

    try:
        for _ in range(3):
            loss = engine(placed)
            engine.backward(loss)
            engine.step()
        drain()
        steps = 20
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine(placed)
            engine.backward(loss)
            engine.step()
        drain()
        dt = time.perf_counter() - t0
        tps = steps * micro * seq / dt
        n = engine.num_parameters()
        mfu = tps * (6 * n + 12 * mcfg.num_layers * mcfg.hidden_size * seq) / PEAK
        print(
            f"micro={micro} flash={flash} scan={scan}: {tps:,.0f} tok/s/chip  mfu={mfu:.4f}  "
            f"vs_ns={mfu / 0.40:.4f}  ({dt:.3f}s / {steps} steps)",
            flush=True,
        )
    except Exception as e:
        print(f"micro={micro} flash={flash} scan={scan}: FAILED {type(e).__name__}: {str(e)[:160]}", flush=True)
