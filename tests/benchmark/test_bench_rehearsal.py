"""Every cell end to end on the CPU at the tiny sizes of each file's
``rehearse`` block, through the benchmark's one command, in a process of its
own (one process per cell, as on the chip). The four-chip cell runs on four
virtual devices. A rehearsal prints counts and no metric."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)


def run(args, **env):
    full = dict(os.environ, **env)
    return subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py")] + args, cwd=ROOT, env=full,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("cell, trace", [(w["name"], i % 2) for i, w in enumerate(SPEC["workloads"])])
def test_cell_rehearses(cell, trace):
    done = run(["--workload", cell, "--seed", "5", "--seconds", "3", "--trace", str(trace), "--rehearse"])
    assert done.returncode == 0, (done.stdout[-2000:], done.stderr[-1500:])
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed" and last["correct"] is True
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    assert "metrics" not in last  # a CPU run never prints a device metric
    chips = next(w["chips"] for w in SPEC["workloads"] if w["name"] == cell)
    assert last["device"]["count"] == chips


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    done = run(["--workload", SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1", "--trace", "0"], JAX_PLATFORMS="cpu")
    assert done.returncode != 0
    assert not any(line.startswith("{") and '"metrics"' in line for line in done.stdout.splitlines())


def test_knee_sweep_tool_rehearses():
    # two rates x two seeds at tiny sizes: a line a window, a line a rate, then the knee and the cell's rate
    tool = os.path.join(ROOT, "benchmark", "tools", "knee_sweep.py")
    done = subprocess.run([sys.executable, tool, "--workload", "mistral7b_chat_steady", "--rates", "2,4", "--seeds", "2", "--seconds", "3", "--rehearse"],
                          cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, (done.stdout[-2000:], done.stderr[-1500:])
    lines = [json.loads(line) for line in done.stdout.splitlines() if line.startswith("{")]
    windows, rates, knee = lines[:4], lines[4:6], lines[6]
    assert [(w["rate_rps"], w["seed"]) for w in windows] == [(2.0, 100), (4.0, 101), (2.0, 110), (4.0, 111)]
    assert all(w["failed"] == 0 and {"queue_end", "running_end"} <= set(w) for w in windows)
    assert [r["windows"] for r in rates] == [2, 2] and all(isinstance(r["sustained"], bool) for r in rates)
    assert set(knee) == {"knee_rps", "cell_rate_rps"}
