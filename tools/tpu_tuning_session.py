"""Hardware autotuning session — run on a machine with a TPU.

    timeout 1500 python tools/tpu_tuning_session.py

Tunes (zero stage × micro batch) for a GPT-2-small-class model on the real
chip with reference-style isolated subprocess trials (a hung trial or an
HBM OOM fails one trial, not the session) and records the session under
``autotuning_results_tpu/`` (session_summary.json + best_config.json). No
hardware tuning session has been run or recorded yet. The parent stays off
the chip (a chip belongs to one process): the trial children are the only
processes that initialise a backend, one at a time.

This file doubles as the ``--script`` contract for the trial children:
``model_factory`` / ``batch_factory`` / ``base_config`` below.
"""

import numpy as np


def model_factory():
    from deepspeed_tpu.models import TransformerLM, gpt2_config

    return TransformerLM(gpt2_config("125m", max_seq_len=512, remat=False))


def batch_factory(n):
    rs = np.random.RandomState(0)
    toks = rs.randint(0, 50257, (max(n, 1), 513)).astype(np.int32)
    return {"input_ids": toks[:, :-1], "labels": toks[:, 1:]}


base_config = {
    "train_micro_batch_size_per_gpu": 4,
    "optimizer": {"type": "adam", "params": {"lr": 3e-4}},
    "bf16": {"enabled": True},
    "gradient_clipping": 1.0,
    "steps_per_print": 10_000,
}


def main():
    import json
    import os

    from deepspeed_tpu.autotuning.autotuner import Autotuner

    tuner = Autotuner(
        model_factory,
        base_config,
        batch_factory,
        micro_batches=[4, 8, 12],
        stages=[1, 2],
        trial_steps=10,
        warmup_steps=3,
        isolation="subprocess",
        user_script=os.path.abspath(__file__),
        trial_timeout_s=420.0,
        session_dir="autotuning_results_tpu",
    )
    best = tuner.tune()
    print(json.dumps(best, indent=2, default=str) if best else "no feasible config")


if __name__ == "__main__":
    main()
