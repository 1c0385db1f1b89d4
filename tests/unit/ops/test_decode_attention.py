"""Ragged decode-attention kernel tests (interpret mode on CPU).

Reference analog: ``tests/unit/ops/transformer/inference`` softmax_context
numerics — the fused single-token cache attention must match the dense
masked computation, including ragged per-batch lengths and GQA grouping.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer.decode_attention import decode_attention


def _dense_ref(q, k_cache, v_cache, kv_len, scale):
    B, NH, D = q.shape
    S, NKV = k_cache.shape[1], k_cache.shape[2]
    if NKV != NH:
        k_cache = np.repeat(k_cache, NH // NKV, axis=2)
        v_cache = np.repeat(v_cache, NH // NKV, axis=2)
    scores = np.einsum("bnd,bsnd->bns", q, k_cache).astype(np.float64) * scale
    lens = np.broadcast_to(np.asarray(kv_len), (B,))
    for b in range(B):
        scores[b, :, lens[b] :] = -1e30
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    return np.einsum("bns,bsnd->bnd", probs, v_cache)


@pytest.mark.parametrize("nkv", [8, 2])  # MHA and GQA grouping
def test_matches_dense(nkv):
    B, NH, D, S = 3, 8, 64, 512
    rs = np.random.RandomState(0)
    q = rs.randn(B, NH, D).astype(np.float32)
    k = rs.randn(B, S, nkv, D).astype(np.float32)
    v = rs.randn(B, S, nkv, D).astype(np.float32)
    lens = np.array([1, 200, 512], np.int32)  # ragged, incl. edges
    out = decode_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), lens, block_k=128)
    ref = _dense_ref(q, k, v, lens, 1.0 / np.sqrt(D))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_scalar_length_and_custom_scale():
    B, NH, D, S = 2, 4, 32, 256
    rs = np.random.RandomState(1)
    q = rs.randn(B, NH, D).astype(np.float32)
    k = rs.randn(B, S, NH, D).astype(np.float32)
    v = rs.randn(B, S, NH, D).astype(np.float32)
    out = decode_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), 77, scale=1.0)
    ref = _dense_ref(q, k, v, 77, 1.0)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_dead_blocks_are_skipped_semantics():
    """Values in cache slots past kv_len must not affect the output."""
    B, NH, D, S = 1, 4, 32, 512
    rs = np.random.RandomState(2)
    q = rs.randn(B, NH, D).astype(np.float32)
    k = rs.randn(B, S, NH, D).astype(np.float32)
    v = rs.randn(B, S, NH, D).astype(np.float32)
    out1 = decode_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), 100, block_k=128)
    k2, v2 = k.copy(), v.copy()
    k2[:, 100:] = 1e6  # garbage beyond the live prefix
    v2[:, 100:] = -1e6
    out2 = decode_attention(jnp.asarray(q), jnp.asarray(k2), jnp.asarray(v2), 100, block_k=128)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


def test_rejects_bad_shapes():
    q = jnp.zeros((1, 6, 8))
    kv = jnp.zeros((1, 256, 4, 8))
    with pytest.raises(ValueError, match="multiple"):
        decode_attention(q, kv, kv, 10)


class TestDeepSpeedTransformerLayer:
    def test_layer_runs_and_matches_model_family(self):
        import deepspeed_tpu as ds
        import jax
        import jax.numpy as jnp

        cfg = ds.DeepSpeedTransformerConfig(hidden_size=32, heads=4, pre_layer_norm=True)
        layer = ds.DeepSpeedTransformerLayer(cfg)
        params = layer.init(jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 32), jnp.float32)
        out = layer(params, x, train=False)
        assert out.shape == (2, 8, 32)
        assert np.isfinite(np.asarray(out)).all()
        # post-LN (BERT) variant
        cfg2 = ds.DeepSpeedTransformerConfig(hidden_size=32, heads=4, pre_layer_norm=False)
        layer2 = ds.DeepSpeedTransformerLayer(cfg2)
        params2 = layer2.init(jax.random.PRNGKey(1))
        out2 = layer2(params2, x, train=False)
        assert out2.shape == (2, 8, 32)
        assert not np.allclose(np.asarray(out), np.asarray(out2))

    def test_mask_rejected(self):
        import deepspeed_tpu as ds
        import jax
        import jax.numpy as jnp
        import pytest

        layer = ds.DeepSpeedTransformerLayer(
            ds.DeepSpeedTransformerConfig(hidden_size=16, heads=2)
        )
        params = layer.init(jax.random.PRNGKey(0))
        with pytest.raises(NotImplementedError, match="mask"):
            layer(params, jnp.zeros((1, 4, 16)), attention_mask=jnp.ones((1, 4)))

    def test_on_device_context(self):
        import deepspeed_tpu as ds
        import jax
        import jax.numpy as jnp

        with ds.OnDevice(device="cpu"):
            x = jnp.ones((2, 2))
        assert x.devices()  # placed somewhere valid
        with ds.OnDevice(device="meta"):
            shapes = jax.eval_shape(lambda: jnp.zeros((4, 4)))
        assert shapes.shape == (4, 4)


def _paged(q, pool_k, pool_v, table, lens):
    """One token a row (``q_lens == 1``) through the ragged page-table kernel
    on a per-layer pool: the kernel takes every layer's pool and a layer
    index, so the pool rides as layer 1 under a layer of garbage that a wrong
    index would read. The kernel also writes each row's newest key and value
    (position ``len - 1``); it is handed the ones the pool holds there, so
    no page but the trash page 0 may change."""
    from deepspeed_tpu.ops.transformer.decode_attention import ragged_paged_attention

    pool_k, pool_v, table = np.asarray(pool_k), np.asarray(pool_v), np.asarray(table)
    lens = np.broadcast_to(np.asarray(lens, np.int32), (len(q),))
    last, rows = lens - 1, np.arange(len(q))
    pid = table[rows, last // pool_k.shape[2]]
    newest = lambda pool: jnp.asarray(pool[pid, :, last % pool.shape[2]][:, None])  # [B, 1, NKV, D]
    stack = lambda pool: jnp.stack([jnp.full(pool.shape, 1e6, pool.dtype), jnp.asarray(pool)])
    out, k2, v2 = ragged_paged_attention(
        jnp.asarray(q)[:, None], newest(pool_k), newest(pool_v), stack(pool_k), stack(pool_v), 1,
        jnp.asarray(table), jnp.asarray(lens), jnp.ones_like(lens), interpret=True,
    )
    for new, old in ((k2, pool_k), (v2, pool_v)):
        np.testing.assert_array_equal(np.asarray(new[1, 1:]), old[1:])
        assert (np.asarray(new[0]) == 1e6).all()
    return out[:, 0]


class TestPagedDecodeAttention:
    """Decode rows of the ragged kernel (one new token on a live prefix)."""

    def _pages_from_contiguous(self, k, v, page):
        """Scatter a contiguous [B,S,NKV,D] cache into a shared page pool
        with a per-sequence page table."""
        B, S, NKV, D = k.shape
        per = S // page
        pool_k = np.zeros((B * per + 1, NKV, page, D), np.float32)
        pool_v = np.zeros_like(pool_k)
        table = np.zeros((B, per), np.int32)
        nxt = 1  # page 0 stays unused (garbage detector)
        for b in range(B):
            for pi in range(per):
                pool_k[nxt] = k[b, pi * page : (pi + 1) * page].transpose(1, 0, 2)
                pool_v[nxt] = v[b, pi * page : (pi + 1) * page].transpose(1, 0, 2)
                table[b, pi] = nxt
                nxt += 1
        return pool_k, pool_v, table

    @pytest.mark.parametrize("nkv", [4, 2])
    def test_matches_contiguous_kernel(self, nkv):
        B, NH, D, S, page = 2, 4, 32, 512, 128
        rs = np.random.RandomState(0)
        q = rs.randn(B, NH, D).astype(np.float32)
        k = rs.randn(B, S, nkv, D).astype(np.float32)
        v = rs.randn(B, S, nkv, D).astype(np.float32)
        lens = np.array([130, 512], np.int32)
        pool_k, pool_v, table = self._pages_from_contiguous(k, v, page)
        out = _paged(q, pool_k, pool_v, table, lens)
        ref = _dense_ref(q, k, v, lens, 1.0 / np.sqrt(D))
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)

    def test_shared_prefix_pages(self):
        """Two sequences sharing their first page (prefix sharing — the
        memory win paging exists for) must read identical prefix content."""
        NH, D, page = 4, 32, 128
        rs = np.random.RandomState(1)
        pool_k = rs.randn(4, NH, page, D).astype(np.float32)
        pool_v = rs.randn(4, NH, page, D).astype(np.float32)
        q = rs.randn(2, NH, D).astype(np.float32)
        # both sequences point at page 1 first, then diverge (2 vs 3)
        table = np.array([[1, 2], [1, 3]], np.int32)
        lens = np.array([256, 256], np.int32)
        out = _paged(q, pool_k, pool_v, table, lens)
        # dense reference: reconstruct each sequence's contiguous cache
        for b in range(2):
            kb = np.concatenate(
                [pool_k[table[b, i]].transpose(1, 0, 2) for i in range(2)], axis=0
            )[None]
            vb = np.concatenate(
                [pool_v[table[b, i]].transpose(1, 0, 2) for i in range(2)], axis=0
            )[None]
            ref = _dense_ref(q[b : b + 1], kb, vb, np.array([256]), 1.0 / np.sqrt(D))
            np.testing.assert_allclose(np.asarray(out)[b : b + 1], ref, rtol=2e-5, atol=2e-5)

    def test_unused_pool_pages_ignored(self):
        NH, D, page = 2, 32, 128
        rs = np.random.RandomState(2)
        pool_k = rs.randn(3, NH, page, D).astype(np.float32)
        pool_v = rs.randn(3, NH, page, D).astype(np.float32)
        q = rs.randn(1, NH, D).astype(np.float32)
        table = np.array([[1, 2]], np.int32)
        out1 = _paged(q, pool_k, pool_v, table, np.array([200]))
        pool_k2 = pool_k.copy()
        pool_k2[0] = 1e6  # garbage in the unused page
        # and garbage past len inside the last live page's tail
        out2 = _paged(q, pool_k2, pool_v, table, np.array([200]))
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)

    def test_padding_slots_with_sentinel_ids(self):
        """Serving stacks pad page tables with -1 (or ids >= NP) past the
        live length; the index map must clamp those fetches in-range rather
        than read out of bounds, and their scores are masked anyway."""
        NH, D, page = 2, 32, 128
        rs = np.random.RandomState(3)
        pool_k = rs.randn(5, NH, page, D).astype(np.float32)
        pool_v = rs.randn(5, NH, page, D).astype(np.float32)
        q = rs.randn(2, NH, D).astype(np.float32)
        lens = np.array([130, 256], np.int32)
        valid = np.array([[1, 2, 0, 0], [3, 4, 0, 0]], np.int32)
        padded = np.array([[1, 2, -1, 99], [3, 4, -1, -1]], np.int32)
        out_valid = _paged(q, pool_k, pool_v, valid, lens)
        out_padded = _paged(q, pool_k, pool_v, padded, lens)
        np.testing.assert_allclose(
            np.asarray(out_valid), np.asarray(out_padded), rtol=1e-6
        )
