"""True multi-process distributed runtime test.

The rest of the suite exercises multi-CHIP semantics on a virtual mesh in
one process; this is the multi-HOST leg — the reference's
distributed-in-one-box strategy applied to the actual rendezvous
(``deepspeed.init_distributed`` → ``jax.distributed.initialize``) and a
cross-process collective, with 2 real OS processes coordinating over TCP
(SURVEY §4; reference ``tests/unit/common.py`` ``DistributedExec``).
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys

import pytest

_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")

import deepspeed_tpu as ds
from deepspeed_tpu import comm as dist

ds.init_distributed()  # rendezvous from MASTER_ADDR/RANK/WORLD_SIZE envs
assert dist.is_initialized()
rank, world = dist.get_rank(), dist.get_world_size()
assert world == 2, world

import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
import numpy as np

# one device per process; a global psum must cross the process boundary
mesh = Mesh(np.asarray(jax.devices()).reshape(-1), ("data",))
local = jnp.full((4,), float(rank + 1))
arr = jax.make_array_from_single_device_arrays(
    (2 * 4,), NamedSharding(mesh, P("data")),
    [jax.device_put(local, jax.local_devices()[0])],
)
total = jax.jit(lambda x: x.sum(), out_shardings=NamedSharding(mesh, P()))(arr)
expected = 4.0 * 1 + 4.0 * 2
got = float(jax.device_get(total.addressable_shards[0].data))
assert got == expected, (got, expected)

# facade control-plane ops across the real process boundary -----------------
# all_to_all_single: rank r sends chunk i to rank i
x = np.arange(4.0) + 10.0 * rank  # rank0: [0..3], rank1: [10..13]
out = dist.all_to_all_single(None, x)
exp = np.concatenate([np.arange(2.0) + 10.0 * s for s in range(2)]) + 2.0 * rank
np.testing.assert_array_equal(out, exp)

# dtype-preserving coalesced all-reduce (f32 + int64 flag together)
ra, rb = dist.all_reduce_coalesced([np.arange(3, dtype=np.float32), np.array([rank], np.int64)])
np.testing.assert_array_equal(ra, 2 * np.arange(3, dtype=np.float32))
assert rb.dtype == np.int64 and int(rb[0]) == 1

# cooperative p2p: both ranks isend then irecv (the torch nonblocking order)
peer = 1 - rank
dist.isend(np.full((2,), float(rank)), dst=peer)
w = dist.irecv(None, src=peer)
got_p2p = w.wait()
np.testing.assert_array_equal(got_p2p, np.full((2,), float(peer)))

print(f"RANK{rank} OK", flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_rendezvous_and_psum(tmp_path):
    port = _free_port()
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
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _WORKER],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
                cwd=os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))),
            )
        )
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=150)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append(out)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        assert f"RANK{rank} OK" in out
