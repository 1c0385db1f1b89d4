"""Flash-attention numerics vs einsum reference (reference analog:
tests/unit/ops/transformer/). Runs the Pallas kernel in interpret mode on the
CPU mesh; the same code lowers to Mosaic on TPU."""

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer.flash_attention import flash_attention


def ref_attn(q, k, v, causal=True):
    D = q.shape[-1]
    s = jnp.einsum("btnd,bsnd->bnts", q, k).astype(jnp.float32) / np.sqrt(D)
    if causal:
        T = q.shape[1]
        m = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(m[None, None], s, -1e30)
    return jnp.einsum("bnts,bsnd->btnd", jax.nn.softmax(s, axis=-1).astype(v.dtype), v)


def _qkv(B=2, T=256, N=4, D=64, seed=0):
    rs = np.random.RandomState(seed)
    return [jnp.asarray(rs.randn(B, T, N, D), jnp.float32) for _ in range(3)]


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = _qkv()
    o1 = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    o2 = ref_attn(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


def test_gradients_match_reference():
    q, k, v = _qkv()

    def l_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=128, block_k=128) ** 2)

    def l_ref(q, k, v):
        return jnp.sum(ref_attn(q, k, v) ** 2)

    g1 = jax.grad(l_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(l_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_padded_sequence():
    q, k, v = _qkv(T=200)  # not a multiple of the block
    o1 = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    o2 = ref_attn(q, k, v)
    assert o1.shape == q.shape
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


def test_single_block():
    q, k, v = _qkv(T=64)
    o1 = flash_attention(q, k, v, causal=True)
    o2 = ref_attn(q, k, v)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


def test_uneven_blocks():
    q, k, v = _qkv(T=384)
    o1 = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    o2 = ref_attn(q, k, v)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


def test_model_uses_flash_when_enabled():
    from deepspeed_tpu.models import TransformerLM, llama_config

    cfg_on = llama_config("tiny", num_layers=2, flash_attention=True, remat=False)
    cfg_off = llama_config("tiny", num_layers=2, flash_attention=False, remat=False)
    rs = np.random.RandomState(0)
    toks = rs.randint(0, cfg_on.vocab_size, (2, 128)).astype(np.int32)
    m_on, m_off = TransformerLM(cfg_on), TransformerLM(cfg_off)
    params = m_on.init(jax.random.PRNGKey(0), toks)
    l_on = m_on.apply(params, (toks, toks), train=True)
    l_off = m_off.apply(params, (toks, toks), train=True)
    np.testing.assert_allclose(float(l_on), float(l_off), rtol=1e-3)


# ---------------------------------------------------------------------------
# what one grid step holds (PR 33): heads a step, the walk's three forms
# (unrolled, rolled, streaming), the lane-dense row statistics
# ---------------------------------------------------------------------------
fa = sys.modules[flash_attention.__module__]  # the module: the package rebinds its name to the function

_HOLDS_ALL = fa._VMEM_BUDGET
_STREAMS = 1  # bytes: no head's sequence fits, so every side streams a block a step
_UNROLLS = fa._UNROLL_PAIRS
_ROLLS = 0  # pairs of blocks a walk may unroll: none, so a whole head a step walks rolled


def _check(B, T, N, D, *, causal=True, dtype=jnp.float32, blocks=(128, 128), budget=_HOLDS_ALL, pairs=_UNROLLS, seed=0):
    """Forward and the three gradients against the einsum reference (float32,
    on the same inputs)."""
    q, k, v = (x.astype(dtype) for x in _qkv(B, T, N, D, seed))
    w = jnp.asarray(np.random.RandomState(seed + 1).randn(B, T, N, D), jnp.float32)  # a cotangent with no symmetry

    def flash(q, k, v):
        o = fa._flash_attention(q, k, v, causal, None, *blocks, True, budget, pairs)
        return jnp.sum(o.astype(jnp.float32) * w), o

    def ref(q, k, v):
        o = ref_attn(*(x.astype(jnp.float32) for x in (q, k, v)), causal=causal)
        return jnp.sum(o * w), o

    (_, o1), g1 = jax.value_and_grad(flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, o2), g2 = jax.value_and_grad(ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    assert o1.shape == q.shape and o1.dtype == dtype
    exact = dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(o1, np.float32), np.asarray(o2), atol=2e-5 if exact else 2e-2)
    for name, a, b in zip("qkv", g1, g2):
        assert a.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=5e-4 if exact else 6e-2, err_msg=f"d{name}"
        )


@pytest.mark.parametrize(
    "case",
    [
        # (B, T, N, D), then what differs from the defaults of _check
        ((2, 200, 4, 64), {}),  # T not a multiple of the block: padded
        ((1, 128, 4, 64), {}),  # T of one block
        ((1, 64, 2, 64), {"blocks": (512, 512)}),  # shorter than a lane tile: padded up to it
        ((2, 512, 4, 64), {}),  # 4 x 4 blocks, several heads a step: the walk unrolled by the lowering
        ((2, 512, 4, 64), {"pairs": _ROLLS}),  # the same walked rolled
        ((1, 640, 2, 64), {}),  # 5 x 5 blocks, more than the walk unrolls: rolled by the shape
        ((1, 512, 2, 64), {"blocks": (256, 128), "pairs": _ROLLS}),  # rolled, the diagonal crossing two key blocks
        ((1, 256, 2, 128), {"causal": False, "pairs": _ROLLS}),  # rolled, no diagonal, D 128
        ((1, 384, 3, 64), {"dtype": jnp.bfloat16, "pairs": _ROLLS}),  # rolled, bf16, 3 heads a step
        ((2, 512, 4, 64), {"budget": _STREAMS}),  # the same through the streaming grid
        ((1, 512, 2, 64), {"budget": 1_600_000}),  # between: outer blocks on the grid, the walked side whole (forward, dq); dkv streams
        ((1, 512, 2, 64), {"blocks": (128, 256), "budget": _STREAMS}),  # streaming, a key block of two query blocks
        ((1, 512, 2, 64), {"blocks": (256, 128)}),  # a query block of two key blocks: the diagonal crosses two
        ((1, 384, 6, 64), {"blocks": (128, 256)}),  # 6 heads: the preferred 8 a step does not divide them
        ((1, 256, 25, 64), {}),  # 25 heads (GPT-2 XL): 5 a step
        ((1, 256, 4, 128), {}),  # D 128
        ((1, 384, 2, 128), {"budget": _STREAMS, "blocks": (256, 128)}),  # D 128, streaming, uneven blocks
        ((2, 256, 4, 64), {"causal": False}),
        ((1, 384, 2, 64), {"causal": False, "budget": _STREAMS}),
        ((1, 384, 2, 64), {"causal": False, "blocks": (512, 512)}),  # no block of whole lanes under 384 but 128 and 384
        ((2, 256, 4, 64), {"dtype": jnp.bfloat16}),
        ((1, 512, 2, 128), {"dtype": jnp.bfloat16, "budget": _STREAMS}),
    ],
    ids=lambda case: "-".join(map(str, case[0])) + "".join(f"-{k}={getattr(v, '__name__', v)}" for k, v in case[1].items()),
)
def test_shapes_a_grid_step_may_hold(case):
    shape, how = case
    _check(*shape, **how)


def test_plan_follows_shapes_and_budget():
    """Heads a step and the form of the walk come from T, D, the dtype and the
    VMEM budget alone."""
    plan = functools.partial(fa._plan, blk_outer=256, blk_walked=512, tensors_outer=2, tensors_walked=2)
    held = plan(96, 1024, 64, 2, vmem_budget=_HOLDS_ALL)
    assert not held.streams and (held.outer, held.walked) == (1024, 1024) and 96 % held.heads == 0 and held.heads > 1
    assert plan(200, 1024, 64, 2, vmem_budget=_HOLDS_ALL).heads == held.heads  # both cells' head counts
    assert plan(6, 1024, 64, 2, vmem_budget=_HOLDS_ALL).heads == 6
    assert plan(25, 1024, 64, 2, vmem_budget=_HOLDS_ALL).heads == 5
    assert plan(7, 1024, 64, 2, vmem_budget=_HOLDS_ALL).heads == 7
    long = plan(8, 32768, 128, 2, vmem_budget=_HOLDS_ALL)  # 8 MB a tensor a head: streams
    assert long.streams and long.outer == 256 and long.walked % 512 == 0 and 32768 % long.walked == 0
    assert long.walked > 512  # as many walked blocks a step as the budget holds
    tiny = plan(8, 1024, 64, 2, vmem_budget=_STREAMS)
    assert tiny.streams and (tiny.heads, tiny.outer, tiny.walked) == (1, 256, 512)


def test_row_statistics_are_stored_once_and_lane_dense():
    """The forward's residual is f32[BN, 1, T], and nothing in the backward
    program is a [BN, T, 128] broadcast of it."""
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(2, 256, 4, 64))
    loss = lambda q, k, v: flash_attention(q, k, v).astype(jnp.float32).sum()
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    from deepspeed_tpu.analysis import iter_eqns

    shapes = {tuple(var.aval.shape) for eqn in iter_eqns(jaxpr) for var in eqn.outvars if hasattr(var.aval, "shape")}
    assert (8, 1, 256) in shapes
    assert not any(len(s) == 3 and s[0] == 8 and s[1:] == (256, 128) for s in shapes), shapes


@pytest.mark.parametrize(
    "walk, lengths",
    [("unrolled", (512, 1024)), ("rolled", (2048, 8192)), ("streams", (512, 2048))],
    ids=["unrolled", "rolled", "streams"],
)
def test_kernel_jaxprs_do_not_grow_with_length_heads_or_heads_a_step(walk, lengths):
    """What a process pays at set-up for a training program is the Python
    tracing and the lowering of these three bodies, which no compilation cache
    holds (PR 26 was refused for a body unrolled in Python). Heads, outer
    blocks and walked blocks are ``fori_loop``s traced once, in each of the
    walk's three forms (unrolled by the lowering where a head has few blocks,
    rolled, streaming), so the jaxpr of forward + backward is as long for a
    short T as for a long one, for 4 heads as for 200, for 1 head a step as
    for 8, and short."""
    from deepspeed_tpu.analysis import iter_eqns

    def equations(BN=4, T=lengths[0]):
        shape = jax.ShapeDtypeStruct((BN, T, 64), jnp.bfloat16)
        budget = _STREAMS if walk == "streams" else _HOLDS_ALL

        def loss(q, k, v):
            return fa._flash_core(q, k, v, fa._How(0.125, True, 256, 256, False, budget, _UNROLLS)).astype(jnp.float32).sum()

        eqns = list(iter_eqns(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(shape, shape, shape)))
        # the three bodies, their loops' and branches' included; and whether the lowering unrolls the loops over blocks
        unrolls = any(e.primitive.name == "scan" and e.params["length"] > 1 and e.params["unroll"] == e.params["length"] for e in eqns)
        return len(eqns), unrolls

    base = equations()
    assert base == equations(T=lengths[1]) == equations(BN=200) == equations(BN=1) == equations(BN=8)
    assert base[1] == (walk == "unrolled")
    assert base[0] < 700, base


@pytest.mark.parametrize("BN", [96, 200], ids=["gpt2_125m", "gpt2_xl_a_chip"])
def test_both_cells_walk_unrolled(BN):
    """At the training cells' per-head shape (T 1,024, D 64, bf16; 96 and 200
    heads a chip) a head is held whole, several heads a step, and its walk is 2 x 2
    blocks of 512: unrolled, the form the kernel bench found 1.7 times faster."""
    from deepspeed_tpu.analysis import iter_eqns

    assert (1024 // fa._BLOCK_Q) * (1024 // fa._BLOCK_K) <= fa._UNROLL_PAIRS
    shape = jax.ShapeDtypeStruct((1, 1024, BN, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, interpret=False).astype(jnp.float32).sum()

    eqns = list(iter_eqns(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(shape, shape, shape)))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    grids = [e.params["grid_mapping"].grid for e in calls]
    assert len(grids) == 3 and all(g[1:] == (1, 1) and BN % g[0] == 0 and g[0] <= BN // 4 for g in grids), grids  # whole heads, several a step
    walks = [e for e in eqns if e.primitive.name == "scan" and e.params["length"] == 2]
    assert walks and all(e.params["unroll"] == 2 for e in walks)  # outer and walked blocks: unrolled by the lowering


def test_flash_kernel_bench_rehearses():
    """``tools/flash_kernel_bench.py --rehearse``: the tool's control flow, tiny, on the CPU: one line a tried set of constants."""
    import pathlib
    import subprocess

    tool = pathlib.Path(__file__).parents[3] / "tools" / "flash_kernel_bench.py"
    done = subprocess.run(
        [sys.executable, str(tool), "--rehearse", "--set", "_BLOCK_Q=128,_BLOCK_K=128", "--set", "_UNROLL_PAIRS=0"],
        capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [line for line in done.stdout.splitlines() if "ms a call" in line]
    assert [line.split()[1].rstrip(":") for line in lines] == ["_BLOCK_Q=128,_BLOCK_K=128", "_UNROLL_PAIRS=0"]
    assert all("trace" in line and "lower" in line for line in lines)
