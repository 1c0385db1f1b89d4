"""Pallas flash attention (TPU).

TPU-native replacement for the reference's fused attention kernels
(``csrc/transformer/softmax_kernels.cu`` + strided-batch-gemm training path
and ``csrc/transformer/inference/csrc/softmax.cu`` softmax_context): one
fused kernel that never materializes the [T, T] score matrix in HBM.

Layout: q/k/v as [BN, T, D] (batch*heads flattened into the leading grid
dim). Online-softmax forward with running (m, l) in VMEM scratch over the kv
grid dimension; the log-sum-exp is saved as a residual and the backward pass
recomputes probabilities blockwise (standard FlashAttention-2 scheme: one
kernel for dq accumulating over kv blocks, one for dk/dv accumulating over q
blocks).

Causal blocks above the diagonal are skipped via ``pl.when`` — with the kv
grid dimension marked "arbitrary" the skipped iterations cost only control
flow, halving work for causal attention.

The lse/delta residuals are stored lanes-broadcast as [BN, T, 128] f32 (the
layout jax's own TPU flash kernels use for l/m residuals): Mosaic requires
the last dim to tile to 128, so the broadcast buys tileability at T*512B of
HBM per (b, n) row per residual — real but small next to activations, and
only alive between fwd and bwd of one layer.

The three ``pallas_call``s are named (``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``): the name is the custom call's instruction name in the
compiled HLO and a component of its op name stack, which is how a profiler
trace finds each kernel (``benchmark/op_scopes.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator import on_tpu

NEG_INF = -1e30


def _maybe_when(cond, fn):
    """Run ``fn`` under pl.when for traced conds, directly for static True."""
    if cond is True:
        fn()
    else:
        pl.when(cond)(fn)


def _causal_mask(s, qi, ki, blk_q, blk_k):
    rows = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(rows >= cols, s, NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *, scale, blk_q, blk_k, nk, causal):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def _compute():
        # operands stay in their native dtype (bf16 in training): the MXU
        # multiplies bf16 at full rate and accumulates fp32 via
        # preferred_element_type; an explicit fp32 cast here would force
        # 1/8-rate fp32 MXU passes (measured 20 vs 197 TFLOP/s on v5e).
        # Softmax math runs fp32 on the VPU either way.
        q = q_ref[0]  # [blk_q, D]
        k = k_ref[0]  # [blk_k, D]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        s = s * scale
        if causal:
            s = _causal_mask(s, qi, ki, blk_q, blk_k)
        m_prev = m_s[:, :1]
        l_prev = l_s[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    _maybe_when((ki * blk_k <= qi * blk_q + blk_q - 1) if causal else True, _compute)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_s[:, :1]
        safe_l = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc_s[...] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = (m_s[...] + jnp.log(safe_l)).astype(lse_ref.dtype)  # lanes identical


def _block_specs(order):
    """q/k block index maps given which of (q, k) is the outer grid dim."""

    def q_map(b, x, y):
        qi = x if order == "q_outer" else y
        return (b, qi, 0)

    def k_map(b, x, y):
        ki = y if order == "q_outer" else x
        return (b, ki, 0)

    return q_map, k_map


def _flash_fwd(q, k, v, scale, causal, blk_q, blk_k, interpret):
    BN, T, D = q.shape
    nq, nk = T // blk_q, T // blk_k
    kernel = functools.partial(
        _fwd_kernel, scale=scale, blk_q=blk_q, blk_k=blk_k, nk=nk, causal=causal
    )
    q_map, k_map = _block_specs("q_outer")
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    o, lse = pl.pallas_call(
        kernel,
        grid=(BN, nq, nk),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_k, D), k_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_k, D), k_map, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, D), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_q, 128), lambda b, qi, ki: (b, qi, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BN, T, D), q.dtype),
            jax.ShapeDtypeStruct((BN, T, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, 128), jnp.float32),
            pltpu.VMEM((blk_q, 128), jnp.float32),
            pltpu.VMEM((blk_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
        **params,
    )(q, k, v)
    return o, lse[:, :, 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_s, *, scale, blk_q, blk_k, nk, causal):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    def _compute():
        # native-dtype operands + fp32 accumulation (see _fwd_kernel note)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, blk_q, blk_k)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_s[...] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    _maybe_when((ki * blk_k <= qi * blk_q + blk_q - 1) if causal else True, _compute)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_s, dv_s, *, scale, blk_q, blk_k, nq, causal):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def _compute():
        # native-dtype operands + fp32 accumulation (see _fwd_kernel note)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, blk_q, blk_k)
        p = jnp.exp(s - lse)  # [blk_q, blk_k]
        p_lo = p.astype(do.dtype)
        dv_s[...] += jax.lax.dot_general(p_lo, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_s[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    _maybe_when((qi * blk_q + blk_q - 1 >= ki * blk_k) if causal else True, _compute)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _flash_bwd(res, g, scale, causal, blk_q, blk_k, interpret):
    q, k, v, o, lse = res
    BN, T, D = q.shape
    nq, nk = T // blk_q, T // blk_k
    do = g
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [BN, T]
    # lanes-broadcast residual layout: [BN, T, 128] satisfies the (8, 128)
    # Mosaic tile; ~T*512B of HBM per (b, n) row, negligible vs q/k/v
    lse = jnp.broadcast_to(lse[:, :, None], (BN, T, 128))
    delta = jnp.broadcast_to(delta[:, :, None], (BN, T, 128))
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )

    q_map, k_map = _block_specs("q_outer")
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, blk_q=blk_q, blk_k=blk_k, nk=nk, causal=causal),
        grid=(BN, nq, nk),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_k, D), k_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_k, D), k_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_q, D), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_q, 128), lambda b, qi, ki: (b, qi, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_q, 128), lambda b, qi, ki: (b, qi, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, blk_q, D), q_map, memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((BN, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, D), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
        **params,
    )(q, k, v, do, lse, delta)

    q_map2, k_map2 = _block_specs("k_outer")
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, blk_q=blk_q, blk_k=blk_k, nq=nq, causal=causal),
        grid=(BN, nk, nq),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), q_map2, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_k, D), k_map2, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_k, D), k_map2, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_q, D), q_map2, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_q, 128), lambda b, ki, qi: (b, qi, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_q, 128), lambda b, ki, qi: (b, qi, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_k, D), k_map2, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_k, D), k_map2, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BN, T, D), k.dtype),
            jax.ShapeDtypeStruct((BN, T, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, D), jnp.float32),
            pltpu.VMEM((blk_k, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
        **params,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, scale, causal, blk_q, blk_k, interpret):
    o, _ = _flash_fwd(q, k, v, scale, causal, blk_q, blk_k, interpret)
    return o


def _flash_core_fwd(q, k, v, scale, causal, blk_q, blk_k, interpret):
    o, lse = _flash_fwd(q, k, v, scale, causal, blk_q, blk_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_core_bwd(scale, causal, blk_q, blk_k, interpret, res, g):
    return _flash_bwd(res, g, scale, causal, blk_q, blk_k, interpret)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
):
    """Fused attention over [B, T, N, D] (heads-last layout like the model).

    GQA inputs (fewer kv heads) must be pre-expanded by the caller. The
    sequence is padded up to the block size; padded kv columns sit above the
    causal diagonal of every real row, and padded q rows are sliced off on
    return.
    """
    B, T, N, D = q.shape
    assert k.shape == v.shape == (B, T, N, D), "flash_attention requires equal q/kv heads"
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    if interpret is None:
        interpret = not on_tpu()

    import math

    blk_q = min(block_q, T)
    blk_k = min(block_k, T)
    # both block sizes must divide the padded length or grid truncation would
    # silently drop trailing blocks
    pad = (-T) % math.lcm(blk_q, blk_k)
    if pad and not causal:
        raise ValueError("non-causal flash attention requires T divisible by the block sizes")
    padded_T = T + pad
    assert padded_T % blk_q == 0 and padded_T % blk_k == 0
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    def to_bn(x):
        return x.transpose(0, 2, 1, 3).reshape(B * N, padded_T, D)

    o = _flash_core(to_bn(q), to_bn(k), to_bn(v), float(scale), causal, blk_q, blk_k, interpret)
    o = o.reshape(B, N, padded_T, D).transpose(0, 2, 1, 3)
    if pad:
        o = o[:, :T]
    return o
