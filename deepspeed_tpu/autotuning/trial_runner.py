"""One autotuning trial in an isolated process.

Counterpart of the reference's per-experiment launch
(``deepspeed/autotuning/scheduler.py`` ``run_job`` — each experiment runs as
its own ``deepspeed`` launch with DS_AUTOTUNING env and a result file). On
one TPU host the isolation is a subprocess: a trial that OOMs HBM or takes
the XLA runtime down kills only itself, the sweep continues, and the parent
enforces a hard timeout.

Usage (spawned by ``scheduler.SubprocessTrialRunner``)::

    python -m deepspeed_tpu.autotuning.trial_runner \
        --script user_tuning.py --config exp.json --out result.json

``--script`` must define ``model_factory``, ``batch_factory`` and
``base_config`` (the same contract as ``deepspeed --autotuning``).
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--script", required=True)
    p.add_argument("--config", required=True, help="path to the trial config json")
    p.add_argument("--out", required=True, help="path to write the result json")
    p.add_argument("--trial-steps", type=int, default=5)
    p.add_argument("--warmup-steps", type=int, default=2)
    args = p.parse_args(argv)

    from deepspeed_tpu.autotuning.autotuner import Autotuner, load_user_script

    namespace = load_user_script(args.script)
    with open(args.config) as f:
        config = json.load(f)

    tuner = Autotuner(
        namespace["model_factory"],
        namespace["base_config"],
        namespace["batch_factory"],
        trial_steps=args.trial_steps,
        warmup_steps=args.warmup_steps,
    )
    result = tuner.run_trial(config)
    if result is None:
        return 1
    with open(args.out, "w") as f:
        json.dump(result, f, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
