"""Sequence-parallelism tests.

The reference has NO unit test for deepspeed/sequence (SURVEY §4); these
cover the gap: all-to-all roundtrip, Ulysses == local attention, ring ==
full attention (values and grads), and end-to-end TransformerLM parity.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.parallel.mesh import initialize_topology
from deepspeed_tpu.runtime.config import MeshConfig
from deepspeed_tpu.sequence import DistributedAttention, ring_attention, seq_all_to_all


def _ref_attention(q, k, v, causal=True):
    T = q.shape[1]
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("btnd,bsnd->bnts", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bnts,bsnd->btnd", p, v)


def _qkv(key, B=2, T=16, N=4, D=8, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return tuple(jax.random.normal(ks[i], (B, T, N, D), dtype) for i in range(3))


def test_seq_all_to_all_roundtrip(eight_devices):
    topo = initialize_topology(MeshConfig(sequence=4))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, 4))
    spec = P(None, "sequence", None, None)

    @jax.jit
    def roundtrip(x):
        def body(xl):
            y = seq_all_to_all(xl, scatter_idx=2, gather_idx=1)
            assert y.shape == (2, 8, 1, 4)  # full seq, head shard
            return seq_all_to_all(y, scatter_idx=1, gather_idx=2)

        return shard_map(body, mesh=topo.mesh, in_specs=(spec,), out_specs=spec)(x)

    np.testing.assert_allclose(roundtrip(x), x, rtol=1e-6)


@pytest.mark.parametrize("seq", [2, 4])
def test_ulysses_matches_local(eight_devices, seq):
    topo = initialize_topology(MeshConfig(sequence=seq))
    q, k, v = _qkv(jax.random.PRNGKey(1))
    expect = _ref_attention(q, k, v)

    dist_attn = DistributedAttention(lambda q, k, v: _ref_attention(q, k, v), topo.mesh)
    shard = NamedSharding(topo.mesh, P(None, "sequence", None, None))
    qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
    got = jax.jit(dist_attn)(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_full(eight_devices, causal):
    topo = initialize_topology(MeshConfig(sequence=4))
    q, k, v = _qkv(jax.random.PRNGKey(2))
    expect = _ref_attention(q, k, v, causal=causal)
    shard = NamedSharding(topo.mesh, P(None, "sequence", None, None))
    qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
    got = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh=topo.mesh, causal=causal))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5)


def test_ring_grads_match(eight_devices):
    topo = initialize_topology(MeshConfig(sequence=4))
    q, k, v = _qkv(jax.random.PRNGKey(3), T=8)

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(_ref_attention(q, k, v)))

    def loss_ring(q, k, v):
        return jnp.sum(jnp.square(ring_attention(q, k, v, mesh=topo.mesh, causal=True)))

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("mode", ["ulysses", "ring"])
def test_transformer_sp_parity(eight_devices, mode):
    """Same tokens, same seed: SP loss == non-SP loss."""
    from deepspeed_tpu.models.config import TransformerConfig
    from deepspeed_tpu.models.transformer import TransformerLM

    def run(sp):
        import deepspeed_tpu.parallel.mesh as mesh_mod

        mesh_mod.reset_topology()
        initialize_topology(MeshConfig(sequence=4 if sp else 1))
        cfg = TransformerConfig(
            vocab_size=64,
            hidden_size=32,
            num_layers=2,
            num_heads=4,
            max_seq_len=32,
            dtype="float32",
            flash_attention=False,
            position="rope",
            norm="rmsnorm",
            activation="swiglu",
            use_bias=False,
            sequence_parallel=sp,
            sequence_parallel_mode=mode,
            attn_dropout=0.0,
            hidden_dropout=0.0,
        )
        model = TransformerLM(cfg)
        rng = jax.random.PRNGKey(0)
        tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 16), 0, 64)
        batch = {"input_ids": tokens, "labels": tokens}
        params = model.init(rng, batch)
        return jax.jit(lambda p: model.apply(p, batch, train=False))(params)

    base = run(False)
    sp = run(True)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(base), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mode", ["ulysses", "ring"])
def test_engine_sp_training(eight_devices, mode):
    """End-to-end: ZeRO over seq×data group (ref engine.py:1111) trains."""
    import deepspeed_tpu as ds
    import deepspeed_tpu.parallel.mesh as mesh_mod
    from deepspeed_tpu.models.config import TransformerConfig
    from deepspeed_tpu.models.transformer import TransformerLM

    mesh_mod.reset_topology()
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "mesh": {"sequence": 2, "data": 4},
    }
    model = TransformerLM(
        TransformerConfig(
            vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
            max_seq_len=32, dtype="float32", flash_attention=False,
            position="rope", norm="rmsnorm", use_bias=False,
            sequence_parallel=True, sequence_parallel_mode=mode,
        )
    )
    engine, *_ = ds.initialize(model=model, config=cfg)
    tokens = np.random.randint(0, 64, (8, 16))
    batch = {"input_ids": tokens, "labels": tokens}
    losses = []
    for _ in range(6):
        loss = engine(batch)
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    assert losses[-1] < losses[0], f"loss did not drop: {losses}"


def test_ring_gqa(eight_devices):
    """Ring with grouped kv heads (kv stays at NKV through the ppermute)."""
    topo = initialize_topology(MeshConfig(sequence=4))
    key = jax.random.PRNGKey(9)
    ks = jax.random.split(key, 3)
    B, T, NH, NKV, D = 2, 16, 8, 2, 8
    q = jax.random.normal(ks[0], (B, T, NH, D))
    k = jax.random.normal(ks[1], (B, T, NKV, D))
    v = jax.random.normal(ks[2], (B, T, NKV, D))
    k_full = jnp.repeat(k, NH // NKV, axis=2)
    v_full = jnp.repeat(v, NH // NKV, axis=2)
    expect = _ref_attention(q, k_full, v_full)
    got = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh=topo.mesh, causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5)


def test_bad_sp_mode_raises(eight_devices):
    from deepspeed_tpu.models.config import TransformerConfig

    with pytest.raises(ValueError, match="sequence_parallel_mode"):
        TransformerConfig(sequence_parallel_mode="Ring")
