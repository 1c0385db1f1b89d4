"""Autotuner tests (reference: ``tests/unit/autotuning/test_autotuning.py``)."""

from __future__ import annotations

import numpy as np
import pytest

from deepspeed_tpu.autotuning import Autotuner, GridSearchTuner, RandomTuner
from tests.unit.simple_model import SimpleModel


def _batch_factory(n):
    rs = np.random.RandomState(0)
    return (rs.randn(n, 16).astype(np.float32), rs.randn(n, 16).astype(np.float32))


BASE = {
    "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
    "steps_per_print": 1000,
}


def _tuner(**kw):
    return Autotuner(
        model_factory=lambda: SimpleModel(hidden_dim=16),
        base_config=BASE,
        batch_factory=_batch_factory,
        micro_batches=kw.pop("micro_batches", [1, 2]),
        stages=kw.pop("stages", [0, 1]),
        trial_steps=2,
        warmup_steps=1,
        **kw,
    )


class TestTuners:
    def test_grid_exhausts_in_order(self):
        exps = [{"i": i} for i in range(5)]
        t = GridSearchTuner(exps)
        seen = []
        while t.has_next():
            seen += t.next_batch(2)
        assert [e["i"] for e in seen] == [0, 1, 2, 3, 4]

    def test_random_is_permutation(self):
        exps = [{"i": i} for i in range(10)]
        t = RandomTuner(exps, seed=1)
        seen = []
        while t.has_next():
            seen += t.next_batch(3)
        assert sorted(e["i"] for e in seen) == list(range(10))


class TestAutotuner:
    def test_model_info(self):
        info = _tuner().model_info()
        assert info["num_params"] == 2 * 16 * 16

    def test_generate_experiments_grid(self):
        exps = _tuner().generate_experiments()
        assert len(exps) == 4  # 2 stages × 2 micro batches
        combos = {
            (e["zero_optimization"]["stage"], e["train_micro_batch_size_per_gpu"])
            for e in exps
        }
        assert combos == {(0, 1), (0, 2), (1, 1), (1, 2)}

    def test_memory_filter(self):
        t = _tuner(hbm_bytes=10)  # nothing fits in 10 bytes
        assert t.generate_experiments() == []

    def test_tune_end_to_end(self):
        best = _tuner().tune()
        assert best is not None
        assert best["throughput_samples_per_s"] > 0
        assert best["config"]["zero_optimization"]["stage"] in (0, 1)


class TestConfigTemplates:
    def test_templates_per_stage(self):
        from deepspeed_tpu.autotuning import STAGE_TEMPLATES, template_for_stage

        assert set(STAGE_TEMPLATES) == {0, 1, 2, 3}
        t3 = template_for_stage(3)
        assert t3["zero_optimization"]["overlap_comm"] is True
        t3["zero_optimization"]["stage"] = 99  # copies, not shared state
        assert STAGE_TEMPLATES[3]["zero_optimization"]["stage"] == 3

    def test_user_values_win_over_template(self):
        from deepspeed_tpu.autotuning import candidate_configs

        base = {
            "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"reduce_bucket_size": 123},
        }
        cfgs = candidate_configs(base, stages=[2], micro_batches=[1, 4])
        assert len(cfgs) == 2
        for cfg in cfgs:
            assert cfg["zero_optimization"]["stage"] == 2
            assert cfg["zero_optimization"]["reduce_bucket_size"] == 123  # user wins
            assert cfg["zero_optimization"]["reduce_scatter"] is True  # template fills
            assert cfg["optimizer"]["params"]["lr"] == 1e-3
        assert [c["train_micro_batch_size_per_gpu"] for c in cfgs] == [1, 4]


class TestResourceManager:
    def test_schedules_and_tracks_status(self):
        from deepspeed_tpu.autotuning import ExpStatus, ResourceManager

        def run_fn(cfg):
            if cfg.get("boom"):
                raise RuntimeError("exploded")
            if cfg.get("none"):
                return None
            return {"throughput": cfg["id"] * 10}

        rm = ResourceManager(run_fn)
        rm.schedule_all([{"id": 1}, {"id": 3}, {"boom": True}, {"none": True}])
        rm.run()
        statuses = [e.status for e in rm.experiments]
        assert statuses == [
            ExpStatus.DONE,
            ExpStatus.DONE,
            ExpStatus.FAILED,
            ExpStatus.FAILED,
        ]
        assert "exploded" in rm.experiments[2].error
        best = rm.best(key=lambda r: r["throughput"])
        assert best.config["id"] == 3
        summary = rm.summary()
        assert len(summary) == 4 and summary[0]["status"] == "done"

    def test_multi_slot_pool(self):
        from deepspeed_tpu.autotuning import ResourceManager

        import threading

        seen = set()

        def run_fn(cfg):
            seen.add(threading.get_ident())
            return {"v": cfg["id"]}

        rm = ResourceManager(run_fn, num_slots=3)
        rm.schedule_all([{"id": i} for i in range(6)])
        rm.run()
        assert len(rm.successful()) == 6


class TestSubprocessTrials:
    """Reference scheduler.run_job parity: isolated per-experiment
    processes with timeout + a persisted session record."""

    USER_SCRIPT = '''
import numpy as np
from tests.unit.simple_model import SimpleModel

def model_factory():
    return SimpleModel(hidden_dim=16)

def batch_factory(n):
    rs = np.random.RandomState(0)
    return (rs.randn(max(n, 8), 16).astype(np.float32),
            rs.randn(max(n, 8), 16).astype(np.float32))

base_config = {
    "train_micro_batch_size_per_gpu": 1,
    "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
    "steps_per_print": 10000,
}
'''

    def _write_script(self, tmp_path):
        import os

        script = tmp_path / "user_tuning.py"
        script.write_text(self.USER_SCRIPT)
        return str(script)

    def _cpu_env(self):
        import os

        repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
        return {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "PYTHONPATH": repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
        }

    def test_subprocess_trial_runs(self, tmp_path):
        from deepspeed_tpu.autotuning.scheduler import SubprocessTrialRunner

        runner = SubprocessTrialRunner(
            self._write_script(tmp_path),
            trial_steps=2,
            warmup_steps=1,
            timeout_s=300,
            env=self._cpu_env(),
            log_path=str(tmp_path / "trial.log"),
        )
        config = {
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "steps_per_print": 10000,
        }
        result = runner(config)
        assert result is not None, (tmp_path / "trial.log").read_text()[-2000:]
        assert result["throughput_samples_per_s"] > 0

    def test_timeout_kills_trial(self, tmp_path):
        from deepspeed_tpu.autotuning.scheduler import SubprocessTrialRunner

        script = tmp_path / "hang.py"
        script.write_text("import time\ntime.sleep(600)\n")
        runner = SubprocessTrialRunner(str(script), timeout_s=3, env=self._cpu_env())
        assert runner({"train_micro_batch_size_per_gpu": 1}) is None

    def test_session_record(self, tmp_path):
        import json

        from deepspeed_tpu.autotuning.autotuner import Autotuner
        from tests.unit.simple_model import SimpleModel
        import numpy as np
        import deepspeed_tpu.parallel.mesh as mesh_mod

        mesh_mod.reset_topology()

        def batch_factory(n):
            rs = np.random.RandomState(0)
            return (rs.randn(max(n, 8), 16).astype(np.float32),
                    rs.randn(max(n, 8), 16).astype(np.float32))

        tuner = Autotuner(
            lambda: SimpleModel(hidden_dim=16),
            {
                "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
                "steps_per_print": 10000,
            },
            batch_factory,
            micro_batches=[1],
            stages=[0, 1],
            trial_steps=2,
            warmup_steps=1,
            session_dir=str(tmp_path / "session"),
        )
        best = tuner.tune()
        assert best is not None
        summary = json.loads((tmp_path / "session" / "session_summary.json").read_text())
        assert len(summary) == 2
        assert all(row["status"] in ("done", "failed") for row in summary)
        best_rec = json.loads((tmp_path / "session" / "best_config.json").read_text())
        assert best_rec["throughput_samples_per_s"] > 0

    def test_subprocess_requires_script(self):
        import pytest

        from deepspeed_tpu.autotuning.autotuner import Autotuner

        with pytest.raises(ValueError, match="user_script"):
            Autotuner(lambda: None, {}, lambda n: None, isolation="subprocess")
