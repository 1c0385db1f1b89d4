"""Autotuner.

Counterpart of the reference's ``Autotuner``
(``deepspeed/autotuning/autotuner.py:42``): profile the model, derive which
ZeRO stages fit memory (``get_instantiation_memory_required_per_gpu``
reference :278), generate a candidate-config grid, run short trials, pick
the best by throughput/latency (``autotuning_metric``).

TPU deltas: trials run in-process by default (one jit cache per trial; the
reference schedules separate jobs because CUDA state is poisoned per
process — XLA recompiles cleanly), with ``isolation="subprocess"`` for
hardware sessions (reference ``scheduler.run_job`` parity: a killable
process per experiment so an OOM or a hung trial fails one trial, not the
sweep). Memory feasibility uses the analytic ZeRO estimator plus the
compiled step's own memory analysis when available.
"""

from __future__ import annotations

import itertools
import json
import os
import random as _random
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from deepspeed_tpu.runtime.zero.partition import estimate_zero_memory
from deepspeed_tpu.utils.logging import logger

DEFAULT_MICRO_BATCHES = [1, 2, 4, 8, 16]
DEFAULT_STAGES = [0, 1, 2, 3]

AUTOTUNING_METRIC_THROUGHPUT = "throughput"
AUTOTUNING_METRIC_LATENCY = "latency"


class BaseTuner:
    """(reference autotuning/tuner/base_tuner.py)"""

    def __init__(self, exps: List[Dict]):
        self.all_exps = list(exps)

    def next_batch(self, sample_size: int) -> List[Dict]:
        raise NotImplementedError

    def has_next(self) -> bool:
        return bool(self.all_exps)


class GridSearchTuner(BaseTuner):
    """Exhaustive order (reference tuner/index_based_tuner.py)."""

    def next_batch(self, sample_size: int) -> List[Dict]:
        batch = self.all_exps[:sample_size]
        self.all_exps = self.all_exps[sample_size:]
        return batch


class RandomTuner(BaseTuner):
    """Random order (reference tuner/index_based_tuner.py RandomTuner)."""

    def __init__(self, exps: List[Dict], seed: int = 0):
        super().__init__(exps)
        _random.Random(seed).shuffle(self.all_exps)

    def next_batch(self, sample_size: int) -> List[Dict]:
        batch = self.all_exps[:sample_size]
        self.all_exps = self.all_exps[sample_size:]
        return batch


class ModelBasedTuner(BaseTuner):
    """Cost-model-guided order (reference tuner/model_based_tuner.py):
    candidates sorted by predicted per-chip memory headroom (larger micro
    batches first among feasible — the throughput prior)."""

    def __init__(self, exps: List[Dict], hbm_bytes: int, n_params: int, dp: int):
        def score(exp):
            zc = exp["zero_optimization"]["stage"]
            mem = estimate_zero_memory(n_params, zc, dp)["total_bytes"]
            headroom = hbm_bytes - mem
            return (headroom < 0, -exp["train_micro_batch_size_per_gpu"], zc)

        super().__init__(sorted(exps, key=score))

    def next_batch(self, sample_size: int) -> List[Dict]:
        batch = self.all_exps[:sample_size]
        self.all_exps = self.all_exps[sample_size:]
        return batch


class Autotuner:
    def __init__(
        self,
        model_factory: Callable[[], Any],
        base_config: Dict,
        batch_factory: Callable[[int], Any],
        micro_batches: Optional[List[int]] = None,
        stages: Optional[List[int]] = None,
        metric: str = AUTOTUNING_METRIC_THROUGHPUT,
        tuner_type: str = "gridsearch",
        trial_steps: int = 5,
        warmup_steps: int = 2,
        max_trials: int = 50,
        hbm_bytes: int = 16 * 2**30,
        isolation: str = "in_process",
        user_script: Optional[str] = None,
        trial_timeout_s: float = 600.0,
        session_dir: Optional[str] = None,
        trial_env: Optional[Dict[str, str]] = None,
        num_devices: Optional[int] = None,
    ):
        if isolation not in ("in_process", "subprocess"):
            raise ValueError(f"isolation={isolation!r} (want in_process|subprocess)")
        if isolation == "subprocess" and not user_script:
            raise ValueError(
                "subprocess isolation needs user_script (the file defining "
                "model_factory/batch_factory/base_config for the child)"
            )
        self.model_factory = model_factory
        self.base_config = dict(base_config)
        self.batch_factory = batch_factory
        self.micro_batches = micro_batches or DEFAULT_MICRO_BATCHES
        self.stages = stages or DEFAULT_STAGES
        self.metric = metric
        self.tuner_type = tuner_type
        self.trial_steps = trial_steps
        self.warmup_steps = warmup_steps
        self.max_trials = max_trials
        self.hbm_bytes = hbm_bytes
        self.isolation = isolation
        self.user_script = user_script
        self.trial_timeout_s = trial_timeout_s
        self.session_dir = session_dir
        self.trial_env = trial_env
        self.num_devices = num_devices
        self._model_info: Optional[Dict[str, Any]] = None
        self.results: List[Dict] = []

    # --- model info (reference model_info_profile_run :663) ---------------
    def model_info(self) -> Dict[str, Any]:
        """Parameter count via ``eval_shape`` with a ShapeDtypeStruct rng —
        fully abstract, so NO backend is initialized: in subprocess mode the
        parent must never claim the chip the trial children need. Memoized:
        generate_experiments and the model-based tuner both consult it."""
        if getattr(self, "_model_info", None) is not None:
            return self._model_info
        import jax
        import jax.numpy as jnp

        model = self.model_factory()
        batch = self.batch_factory(1)
        rng_shape = jax.ShapeDtypeStruct((2,), jnp.uint32)
        shapes = jax.eval_shape(
            lambda r, b: model.init(r, b) if hasattr(model, "init") else model[0](r, b),
            rng_shape,
            batch,
        )
        n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
        self._model_info = {"num_params": n}
        return self._model_info

    def _device_count(self) -> int:
        """dp width for the memory gate. In-process: the live backend.
        Subprocess mode: probe in a child that exits before the first trial
        starts — ``jax.devices()`` in the parent would hold the chip
        against the trial children (a chip belongs to one process)."""
        if self.num_devices:
            return self.num_devices
        if self.isolation == "subprocess":
            import subprocess
            import sys

            # probe under the SAME env the trial children get — a cpu-forced
            # harness run must not gate memory on the hardware device count
            env = dict(os.environ)
            if self.trial_env:
                env.update(self.trial_env)
            try:
                out = subprocess.run(
                    [sys.executable, "-c", "import jax; print(len(jax.devices()))"],
                    capture_output=True,
                    timeout=120,
                    text=True,
                    env=env,
                )
                self.num_devices = max(1, int(out.stdout.strip().splitlines()[-1]))
            except Exception:
                logger.warning("device-count probe failed; memory-gating for 1 device")
                self.num_devices = 1
            return self.num_devices
        import jax

        self.num_devices = len(jax.devices())
        return self.num_devices

    # --- candidate grid ---------------------------------------------------
    def generate_experiments(self) -> List[Dict]:
        """(stage, micro) sweep with the per-stage tuning templates applied
        (reference ``config_templates/``), memory-gated per candidate."""
        from deepspeed_tpu.autotuning.config_templates import candidate_configs

        info = self.model_info()
        n_params = info["num_params"]
        dp = self._device_count()
        exps = []
        for cfg in candidate_configs(self.base_config, self.stages, self.micro_batches):
            stage = cfg["zero_optimization"]["stage"]
            mem = estimate_zero_memory(n_params, stage, dp)["total_bytes"]
            if mem > self.hbm_bytes:
                logger.debug(f"skip stage={stage} (needs {mem/2**30:.1f} GiB)")
                continue
            exps.append(cfg)
        return exps

    def _make_tuner(self, exps: List[Dict]) -> BaseTuner:
        if self.tuner_type == "random":
            return RandomTuner(exps)
        if self.tuner_type == "model_based":
            info = self.model_info()
            return ModelBasedTuner(
                exps, self.hbm_bytes, info["num_params"], self._device_count()
            )
        return GridSearchTuner(exps)

    # --- trials -----------------------------------------------------------
    def run_trial(self, config: Dict) -> Optional[Dict]:
        import jax

        import deepspeed_tpu as ds
        import deepspeed_tpu.parallel.mesh as mesh_mod

        mesh_mod.reset_topology()
        micro = config["train_micro_batch_size_per_gpu"]
        try:
            engine, _, _, _ = ds.initialize(
                model=self.model_factory(), config=config, dist_init_required=False
            )
            batch = self.batch_factory(micro * engine.data_parallel_world_size())
            for _ in range(self.warmup_steps):
                loss = engine(batch)
                engine.backward(loss)
                engine.step()
            jax.device_get(loss)
            t0 = time.perf_counter()
            for _ in range(self.trial_steps):
                loss = engine(batch)
                engine.backward(loss)
                engine.step()
            jax.device_get(loss)
            dt = (time.perf_counter() - t0) / self.trial_steps
        except Exception as e:
            logger.warning(f"trial failed for {config.get('zero_optimization')}, mb={micro}: {e}")
            return None
        samples_per_sec = micro * engine.data_parallel_world_size() / dt
        return {
            "config": config,
            "latency_s": dt,
            "throughput_samples_per_s": samples_per_sec,
        }

    def _trial_fn(self):
        """Per-experiment executor: in-process (fast; harness/CI) or the
        reference-style isolated subprocess (hardware sessions — an OOM or
        a hung trial fails one experiment, not the sweep)."""
        if self.isolation == "subprocess":
            from deepspeed_tpu.autotuning.scheduler import SubprocessTrialRunner

            log_path = (
                os.path.join(self.session_dir, "trials.log") if self.session_dir else None
            )
            return SubprocessTrialRunner(
                self.user_script,
                trial_steps=self.trial_steps,
                warmup_steps=self.warmup_steps,
                timeout_s=self.trial_timeout_s,
                env=self.trial_env,
                log_path=log_path,
            )
        return self.run_trial

    def _record_session(self) -> None:
        """Persist the tuning session (reference writes per-exp dirs under
        ``autotuning_exps/``): one summary json + the best config."""
        if not self.session_dir:
            return
        os.makedirs(self.session_dir, exist_ok=True)
        with open(os.path.join(self.session_dir, "session_summary.json"), "w") as f:
            json.dump(self.scheduler.summary(), f, indent=2, default=str)

    def tune(self) -> Optional[Dict]:
        from deepspeed_tpu.autotuning.scheduler import ResourceManager

        if self.session_dir:
            os.makedirs(self.session_dir, exist_ok=True)
        exps = self.generate_experiments()
        logger.info(f"autotuning over {len(exps)} candidate configs")
        tuner = self._make_tuner(exps)
        # the scheduler owns execution/status; the tuner owns the visit order
        self.scheduler = ResourceManager(self._trial_fn(), num_slots=1)
        trials = 0
        while tuner.has_next() and trials < self.max_trials:
            batch = tuner.next_batch(1)
            self.scheduler.schedule_all(batch)
            trials += len(batch)
        for exp in self.scheduler.run():
            if exp.result is not None:
                self.results.append(exp.result)
        self._record_session()
        if not self.results:
            return None
        if self.metric == AUTOTUNING_METRIC_LATENCY:
            best = min(self.results, key=lambda r: r["latency_s"])
        else:
            best = max(self.results, key=lambda r: r["throughput_samples_per_s"])
        logger.info(
            f"autotuning best: stage={best['config']['zero_optimization']['stage']} "
            f"micro={best['config']['train_micro_batch_size_per_gpu']} "
            f"({best['throughput_samples_per_s']:.1f} samples/s)"
        )
        if self.session_dir:
            with open(os.path.join(self.session_dir, "best_config.json"), "w") as f:
                json.dump(best, f, indent=2, default=str)
        return best


def load_user_script(path: str) -> Dict[str, Any]:
    """Exec the tuning user script and validate its contract — shared by the
    CLI entry and the subprocess trial runner so both fail with the same
    diagnostic instead of a bare KeyError."""
    namespace: Dict[str, Any] = {}
    with open(path) as f:
        code = f.read()
    exec(compile(code, path, "exec"), namespace)  # noqa: S102
    required = ("model_factory", "batch_factory", "base_config")
    if not all(k in namespace for k in required):
        raise RuntimeError(
            f"autotuning requires the script to define {required} "
            "(see deepspeed_tpu.autotuning.Autotuner)"
        )
    return namespace


def run_autotuning(args) -> int:
    """CLI entry (reference runner.py:360): the user script is expected to
    define ``model_factory``/``batch_factory``/``base_config``; exec it and
    tune."""
    namespace = load_user_script(args.user_script)
    # session dir: the ds config's autotuning.results_dir when set
    # (reference AUTOTUNING_RESULTS_DIR), else ./autotuning_results
    session_dir = (
        (namespace["base_config"].get("autotuning") or {}).get("results_dir")
        or "autotuning_results"
    )
    tuner = Autotuner(
        namespace["model_factory"],
        namespace["base_config"],
        namespace["batch_factory"],
        # CLI sessions are hardware sessions: reference-style isolated
        # trials + a persisted session record
        isolation="subprocess",
        user_script=args.user_script,
        session_dir=session_dir,
    )
    best = tuner.tune()
    if best is None:
        print("autotuning: no feasible config found")
        return 1
    import json

    print(json.dumps(best["config"], indent=2, default=str))
    return 0
