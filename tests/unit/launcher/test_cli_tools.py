"""bin/ CLI smoke tests (reference: bin/ds_report env report, bin/ds_bench
collective sweep, bin/ds_elastic batch explorer): each tool runs on the CPU
mesh and prints its contract."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def _run(args, timeout=240):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    return subprocess.run(
        [sys.executable] + args,
        env=env, cwd=REPO, capture_output=True, text=True, timeout=timeout,
    )


def test_ds_report_prints_environment():
    r = _run([os.path.join(REPO, "bin", "ds_report")])
    assert r.returncode == 0, r.stdout + r.stderr
    out = r.stdout.lower()
    assert "jax" in out
    assert "op" in out or "builder" in out or "native" in out


def test_ds_elastic_explores_batch_sizes(tmp_path):
    import json

    cfg = tmp_path / "elastic.json"
    cfg.write_text(json.dumps({
        "train_micro_batch_size_per_gpu": 1,
        "elasticity": {
            "enabled": True,
            "max_train_batch_size": 64,
            "micro_batch_sizes": [2, 4],
            "min_gpus": 1, "max_gpus": 8,
            "min_time": 0, "version": 0.1,
        },
    }))
    r = _run([os.path.join(REPO, "bin", "ds_elastic"), "-c", str(cfg), "-w", "4"])
    assert r.returncode == 0, r.stdout + r.stderr
    out = r.stdout.lower()
    assert "batch size" in out and "micro batch" in out, r.stdout


def test_ds_bench_runs_collective_sweep():
    r = _run([os.path.join(REPO, "bin", "ds_bench"), "--sizes-mb", "1", "--trials", "2"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "all_reduce" in r.stdout.lower() or "allreduce" in r.stdout.lower() or "bytes" in r.stdout.lower()
