"""Experiment scheduler over a resource pool (reference:
``deepspeed/autotuning/scheduler.py`` ``ResourceManager``).

The reference schedules tuning experiments across reserved node groups via
ssh; here a resource is any experiment-executor slot (on one TPU host:
usually 1 — trials share the chip serially; in a pod: one slot per slice).
Experiments carry QUEUED → RUNNING → DONE/FAILED state, results collect as
they finish, and the caller's tuner drains the queue in arrival order.

``SubprocessTrialRunner`` is the hardware-session executor (reference
``run_job``'s per-experiment launch): each trial runs in its own killable
process, so an HBM OOM or a hung trial fails ONE experiment,
not the sweep.
"""

from __future__ import annotations

import enum
import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional


class ExpStatus(str, enum.Enum):
    QUEUED = "queued"
    RUNNING = "running"
    DONE = "done"
    FAILED = "failed"


class Experiment:
    _next_id = 0

    def __init__(self, config: Dict):
        Experiment._next_id += 1
        self.exp_id = Experiment._next_id
        self.config = config
        self.status = ExpStatus.QUEUED
        self.result: Optional[Dict] = None
        self.error: Optional[str] = None
        self.start_time: Optional[float] = None
        self.end_time: Optional[float] = None


class SubprocessTrialRunner:
    """Callable trial executor that spawns ``trial_runner`` per experiment.

    ``user_script`` follows the ``deepspeed --autotuning`` contract
    (defines model_factory / batch_factory / base_config). ``timeout_s``
    kills the whole process group — a stalled trial must not eat the
    session. ``env`` overrides
    the child environment (e.g. JAX_PLATFORMS=cpu for harness tests)."""

    def __init__(
        self,
        user_script: str,
        trial_steps: int = 5,
        warmup_steps: int = 2,
        timeout_s: float = 600.0,
        env: Optional[Dict[str, str]] = None,
        log_path: Optional[str] = None,
    ):
        self.user_script = user_script
        self.trial_steps = trial_steps
        self.warmup_steps = warmup_steps
        self.timeout_s = timeout_s
        self.env = env
        self.log_path = log_path or os.devnull

    def __call__(self, config: Dict) -> Optional[Dict]:
        with tempfile.TemporaryDirectory(prefix="ds_tune_") as tmp:
            cfg_path = os.path.join(tmp, "exp.json")
            out_path = os.path.join(tmp, "result.json")
            with open(cfg_path, "w") as f:
                json.dump(config, f, default=str)
            cmd = [
                sys.executable,
                "-m",
                "deepspeed_tpu.autotuning.trial_runner",
                "--script",
                self.user_script,
                "--config",
                cfg_path,
                "--out",
                out_path,
                "--trial-steps",
                str(self.trial_steps),
                "--warmup-steps",
                str(self.warmup_steps),
            ]
            env = dict(os.environ)
            if self.env:
                env.update(self.env)
            with open(self.log_path, "ab") as log:
                proc = subprocess.Popen(
                    cmd, stdout=log, stderr=subprocess.STDOUT, start_new_session=True, env=env
                )
                try:
                    proc.wait(timeout=self.timeout_s)
                except subprocess.TimeoutExpired:
                    try:
                        os.killpg(os.getpgid(proc.pid), 9)
                    except (ProcessLookupError, PermissionError):
                        proc.kill()
                    proc.wait()
            # the result file, not the rc, is the success signal — checked
            # on the timeout path too: a child that wrote it and then hung
            # in backend teardown still measured something
            if not os.path.exists(out_path):
                return None
            try:
                with open(out_path) as f:
                    return json.load(f)
            except Exception:
                return None


class ResourceManager:
    """Run experiments over ``num_slots`` executor slots.

    ``run_fn(config) -> result_dict | None`` executes one experiment (the
    autotuner's ``run_trial``); exceptions / None mark the experiment
    FAILED. With one slot this is the single-host serial flow; more slots
    round-robin (a pod-slice pool would pass per-slice executors)."""

    def __init__(self, run_fn: Callable[[Dict], Optional[Dict]], num_slots: int = 1):
        self.run_fn = run_fn
        self.num_slots = max(1, num_slots)
        self.experiments: List[Experiment] = []

    def schedule(self, config: Dict) -> Experiment:
        exp = Experiment(config)
        self.experiments.append(exp)
        return exp

    def schedule_all(self, configs: List[Dict]) -> List[Experiment]:
        return [self.schedule(c) for c in configs]

    def _run_one(self, exp: Experiment) -> None:
        exp.status = ExpStatus.RUNNING
        exp.start_time = time.perf_counter()
        try:
            result = self.run_fn(exp.config)
        except Exception as e:  # an exploding trial must not kill the sweep
            exp.status = ExpStatus.FAILED
            exp.error = f"{type(e).__name__}: {e}"
            exp.end_time = time.perf_counter()
            return
        exp.end_time = time.perf_counter()
        if result is None:
            exp.status = ExpStatus.FAILED
        else:
            exp.status = ExpStatus.DONE
            exp.result = result

    def run(self) -> List[Experiment]:
        """Drain the queue. With >1 slot, experiments run concurrently in a
        thread pool (each slot's executor owns its device resources)."""
        queued = [e for e in self.experiments if e.status == ExpStatus.QUEUED]
        if self.num_slots == 1:
            for exp in queued:
                self._run_one(exp)
        else:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(max_workers=self.num_slots) as pool:
                list(pool.map(self._run_one, queued))
        return self.experiments

    # --- reporting -------------------------------------------------------
    def finished(self) -> List[Experiment]:
        return [e for e in self.experiments if e.status in (ExpStatus.DONE, ExpStatus.FAILED)]

    def successful(self) -> List[Experiment]:
        return [e for e in self.experiments if e.status == ExpStatus.DONE]

    def best(self, key: Callable[[Dict], Any], maximize: bool = True) -> Optional[Experiment]:
        done = self.successful()
        if not done:
            return None
        pick = max if maximize else min
        return pick(done, key=lambda e: key(e.result))

    def summary(self) -> List[Dict]:
        return [
            {
                "exp_id": e.exp_id,
                "status": e.status.value,
                "stage": e.config.get("zero_optimization", {}).get("stage"),
                "micro_batch": e.config.get("train_micro_batch_size_per_gpu"),
                "result": e.result,
                "error": e.error,
                "elapsed_s": (
                    (e.end_time - e.start_time)
                    if e.start_time is not None and e.end_time is not None
                    else None
                ),
            }
            for e in self.experiments
        ]
