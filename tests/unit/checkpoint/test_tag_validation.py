"""Checkpoint tag validation across REAL processes (reference:
tests/unit/checkpoint/test_tag_validation.py; engine.py:2944 all-gathers
the tag and asserts equality, config checkpoint.tag_validation
Warn/Fail/Ignore)."""

import os
import socket
import subprocess
import sys
import time

import pytest

_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")

import numpy as np
import deepspeed_tpu as ds
from deepspeed_tpu import comm as dist
from tests.unit.simple_model import SimpleModel, random_dataloader

mode = os.environ["TAG_MODE"]          # Warn | Fail | Ignore
mismatch = os.environ["TAG_MISMATCH"] == "1"
ckpt_dir = os.environ["TAG_CKPT_DIR"]

ds.init_distributed()
rank = dist.get_rank()
engine, *_ = ds.initialize(model=SimpleModel(), config={
    "train_micro_batch_size_per_gpu": 8,
    "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
    "bf16": {"enabled": True},
    "checkpoint": {"tag_validation": mode},
})
batch = next(random_dataloader(total_samples=8, batch_size=8))
loss = engine(batch); engine.backward(loss); engine.step()

tag = f"tag_rank{rank}" if mismatch else "tag_same"
try:
    engine.save_checkpoint(ckpt_dir, tag=tag)
    # warn mode normalizes mismatched tags to rank 0's so the collective
    # save stays coherent — the latest file must name THAT tag
    with open(os.path.join(ckpt_dir, "latest")) as f:
        saved_tag = f.read().strip()
    expect = "tag_rank0" if mismatch else "tag_same"
    assert saved_tag == expect, (saved_tag, expect)
    print(f"RANK{rank} SAVED", flush=True)
except RuntimeError as e:
    assert "mismatch" in str(e), e
    print(f"RANK{rank} REJECTED", flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


_RANK_LIMIT_S = 60


def _spawn_ranks(worker, tmp_path, marker, **env_extra):
    """Two ranks of ``worker`` with one deadline for both: a rank that has
    not ended by then is killed (and its peer with it) and the failure names
    it, beside every rank's output. Each rank must exit 0 having printed
    ``RANK<n> <marker>``."""
    port = _free_port()
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update(
            MASTER_ADDR="127.0.0.1",
            MASTER_PORT=str(port),
            RANK=str(rank),
            WORLD_SIZE="2",
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=1",
            **env_extra,
        )
        # output to a file: a pipe nobody reads while the peer is waited for
        # fills, and the rank blocks in its own logging
        with open(tmp_path / f"rank{rank}.log", "w") as log:
            procs.append(
                subprocess.Popen(
                    [sys.executable, "-c", worker],
                    env=env, stdout=log, stderr=subprocess.STDOUT, cwd=repo,
                )
            )
    deadline = time.monotonic() + _RANK_LIMIT_S
    lost = []
    for rank, p in enumerate(procs):
        try:
            p.wait(timeout=max(0.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            lost.append(rank)
    for p in procs:
        if p.poll() is None:
            p.kill()
            p.wait()
    outs = [(tmp_path / f"rank{rank}.log").read_text() for rank in range(2)]
    assert not lost, f"rank(s) {lost} still running after {_RANK_LIMIT_S} s:\n" + "\n".join(
        f"--- rank {rank} (rc {p.returncode}) ---\n{out[-2500:]}"
        for rank, (p, out) in enumerate(zip(procs, outs))
    )
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank}:\n{out[-2500:]}"
        assert f"RANK{rank} {marker}" in out, out[-2500:]


def _run(mode, mismatch, marker, tmp_path):
    _spawn_ranks(
        _WORKER,
        tmp_path,
        marker,
        TAG_MODE=mode,
        TAG_MISMATCH="1" if mismatch else "0",
        TAG_CKPT_DIR=str(tmp_path / f"ck_{mode}"),
    )


_ROUNDTRIP_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")

import numpy as np
import deepspeed_tpu as ds
from deepspeed_tpu import comm as dist
from tests.unit.simple_model import SimpleModel, random_dataloader

ds.init_distributed()
rank = dist.get_rank()
engine, *_ = ds.initialize(model=SimpleModel(), config={
    "train_micro_batch_size_per_gpu": 8,
    "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
    "bf16": {"enabled": True},
    "zero_optimization": {"stage": 2},
})
batch = next(random_dataloader(total_samples=8, batch_size=8))
for _ in range(2):
    loss = engine(batch); engine.backward(loss); engine.step()
engine.save_checkpoint(os.environ["TAG_CKPT_DIR"])
# reload: orbax hands back GLOBAL arrays across both processes; the load
# path must reshard them without a local device_put
engine.load_checkpoint(os.environ["TAG_CKPT_DIR"])
loss = engine(batch); engine.backward(loss); engine.step()  # still trains
assert np.isfinite(float(jax.device_get(loss)))
print(f"RANK{rank} ROUNDTRIP", flush=True)
"""


def test_cross_process_zero2_checkpoint_roundtrip(tmp_path):
    """Two real processes: ZeRO-2 save -> load -> continue training (the
    multi-process global-array load path)."""
    _spawn_ranks(_ROUNDTRIP_WORKER, tmp_path, "ROUNDTRIP", TAG_CKPT_DIR=str(tmp_path / "ck_rt"))


@pytest.mark.parametrize("mode", ["Warn", "Ignore"])
def test_matching_tags_save(mode, tmp_path):
    _run(mode, mismatch=False, marker="SAVED", tmp_path=tmp_path)


def test_mismatched_tags_fail_mode_raises(tmp_path):
    _run("Fail", mismatch=True, marker="REJECTED", tmp_path=tmp_path)


def test_mismatched_tags_warn_mode_saves(tmp_path):
    _run("Warn", mismatch=True, marker="SAVED", tmp_path=tmp_path)
