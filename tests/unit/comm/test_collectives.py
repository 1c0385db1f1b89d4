"""In-mesh collective tests (reference: tests/unit/comm/test_dist.py)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from deepspeed_tpu.comm import collectives
from deepspeed_tpu import comm as dist
import os


@pytest.fixture
def mesh(eight_devices):
    return Mesh(np.asarray(eight_devices), ("data",))


def _smap(mesh, fn, in_specs, out_specs):
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs, check_vma=False, out_specs=out_specs))


def test_psum(mesh):
    x = jnp.arange(8.0)
    out = _smap(mesh, lambda v: collectives.psum(v, "data"), P("data"), P("data"))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 28.0))


def test_all_gather(mesh):
    x = jnp.arange(8.0)
    out = _smap(mesh, lambda v: collectives.all_gather(v, "data"), P("data"), P())(x)
    # each shard gathers the full array; out_specs=P() verifies replication
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0))


def test_reduce_scatter(mesh):
    # every shard holds the full vector [0..7]; each ends with its 1/8 slice
    # of the 8-way sum
    x = jnp.tile(jnp.arange(8.0), 8)  # [64] sharded -> local [8] = 0..7
    out = _smap(mesh, lambda v: collectives.reduce_scatter(v, "data"), P("data"), P("data"))(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0) * 8)


def test_all_to_all_is_resharding(mesh):
    # all_to_all moves a row-sharded matrix to column-sharded WITHOUT
    # changing its content (this is exactly the Ulysses seq<->head swap)
    x = jnp.arange(64.0).reshape(8, 8)
    fn = _smap(
        mesh,
        lambda v: collectives.all_to_all(v, "data", split_axis=1, concat_axis=0),
        P("data", None),
        P(None, "data"),
    )
    out = fn(x)
    assert out.shape == (8, 8)
    np.testing.assert_allclose(np.asarray(out), np.arange(64.0).reshape(8, 8))
    # and the output really is column-sharded now
    assert "data" in str(out.sharding.spec[1])


def test_ring_shift(mesh):
    x = jnp.arange(8.0)
    out = _smap(mesh, lambda v: collectives.ring_shift(v, "data", shift=1), P("data"), P("data"))(x)
    np.testing.assert_allclose(np.asarray(out), np.roll(np.arange(8.0), 1))


def test_quantized_reduce_scatter_close_to_exact(mesh):
    rs = np.random.RandomState(0)
    data = rs.randn(8, 1024).astype(np.float32)

    def body(v):
        return collectives.quantized_reduce_scatter(v[0], "data", n_shards=8, block=128)

    fn = _smap(mesh, body, P("data", None), P("data"))
    out = np.asarray(fn(jnp.asarray(data)))  # global [8 * 128]
    exact = data.sum(axis=0)  # [1024]; shard s holds slice s of the reduction
    rel_rms = np.sqrt(np.mean((out - exact) ** 2)) / np.sqrt(np.mean(exact**2))
    assert rel_rms < 0.02, f"quantization error too large: {rel_rms}"


def test_eager_control_plane_single_process():
    from deepspeed_tpu import comm as dist

    assert dist.get_world_size() == 1
    out = dist.all_reduce(np.array([1.0, 2.0]))
    np.testing.assert_allclose(out, [1.0, 2.0])
    gathered = dist.all_gather_object({"rank": dist.get_rank()})
    assert gathered == [{"rank": 0}]
    dist.barrier()  # no-op, must not raise


class TestFacadeSurface:
    """The full torch.distributed-shaped surface (reference comm/comm.py) —
    single-process semantics; the multi-process rendezvous is exercised by
    test_multiprocess.py."""

    def test_reduce_gather_single(self):
        out = dist.reduce(np.arange(4.0), dst=0)
        np.testing.assert_array_equal(out, np.arange(4.0))
        lst = []
        g = dist.gather(np.arange(3), gather_list=lst, dst=0)
        assert g.shape == (1, 3)
        assert len(lst) == 1

    def test_into_tensor_forms(self):
        x = np.arange(6.0)
        out = dist.all_gather_into_tensor(np.zeros(6), x)
        np.testing.assert_array_equal(out, x)
        rs = dist.reduce_scatter_tensor(np.zeros(6), x)
        np.testing.assert_array_equal(rs, x)
        np.testing.assert_array_equal(dist.allgather_fn(np.zeros(6), x), x)
        np.testing.assert_array_equal(dist.reduce_scatter_fn(np.zeros(6), x), x)

    def test_all_to_all_single_identity_at_world1(self):
        x = np.arange(8.0).reshape(4, 2)
        out = dist.all_to_all_single(None, x)
        np.testing.assert_array_equal(out, x)
        outs = dist.all_to_all([], [x])
        np.testing.assert_array_equal(outs[0], x)

    def test_coalesced(self):
        a, b = np.arange(3.0), np.ones((2, 2))
        ra, rb = dist.all_reduce_coalesced([a, b])
        np.testing.assert_array_equal(ra, a)
        np.testing.assert_array_equal(rb, b)
        per = dist.all_gather_coalesced([a, b])
        assert len(per) == 2 and len(per[0]) == 1
        np.testing.assert_array_equal(per[0][0], a)

    def test_p2p_cooperative_single(self):
        got = dist.recv(None, src=0)
        assert got is None or isinstance(got, np.ndarray)
        w = dist.isend(np.arange(2), dst=0)
        assert w.is_completed()
        w2 = dist.irecv(None, src=0)
        w2.wait()

    def test_misc_probes(self):
        assert dist.is_available()
        assert dist.get_world_group().size == dist.get_world_size()
        dist.monitored_barrier(timeout=1.0)
        assert dist.in_aml() in (True, False)
        np.testing.assert_array_equal(
            dist.inference_all_reduce(np.arange(3.0)), np.arange(3.0)
        )

    def test_env_patches(self, monkeypatch):
        monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "0")
        monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "1")
        monkeypatch.setenv("OMPI_COMM_WORLD_LOCAL_SIZE", "1")
        for var in ("RANK", "WORLD_SIZE", "LOCAL_RANK", "MASTER_ADDR", "MASTER_PORT"):
            monkeypatch.delenv(var, raising=False)
        dist.patch_aml_env_for_torch_nccl_backend(verbose=False)
        assert os.environ["RANK"] == "0"
        assert "MASTER_ADDR" in os.environ
        dist.patch_aws_sm_env_for_torch_nccl_backend(verbose=False)
        assert os.environ["WORLD_SIZE"] == "1"
