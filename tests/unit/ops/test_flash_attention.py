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
# what one grid step holds (PR 33; PR 60: a block of lanes of the model's own
# [B, T, N * D]), the walk's three forms (unrolled, rolled, streaming), the
# lane-dense row statistics
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
        ((2, 512, 4, 64), {}),  # 4 x 4 blocks, two heads a step: the walk unrolled by the lowering
        ((2, 512, 4, 64), {"pairs": _ROLLS}),  # the same walked rolled
        ((1, 640, 2, 64), {}),  # 5 x 5 blocks, more than the walk unrolls: rolled by the shape
        ((1, 512, 2, 64), {"blocks": (256, 128), "pairs": _ROLLS}),  # rolled, the diagonal crossing two key blocks
        ((1, 256, 2, 128), {"causal": False, "pairs": _ROLLS}),  # rolled, no diagonal, D 128
        ((1, 384, 3, 64), {"dtype": jnp.bfloat16, "pairs": _ROLLS}),  # rolled, bf16, 3 heads: the second lane tile holds one
        ((2, 512, 4, 64), {"budget": _STREAMS}),  # the same through the streaming grid
        ((1, 512, 2, 64), {"budget": 1_600_000}),  # between: outer blocks on the grid, the walked side whole (forward, dq); dkv streams
        ((1, 512, 2, 64), {"blocks": (128, 256), "budget": _STREAMS}),  # streaming, a key block of two query blocks
        ((1, 512, 2, 64), {"blocks": (256, 128)}),  # a query block of two key blocks: the diagonal crosses two
        ((1, 384, 6, 64), {"blocks": (128, 256)}),  # 6 heads: three lane tiles
        ((1, 256, 25, 64), {}),  # 25 heads (GPT-2 XL): 12.5 lane tiles
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


_WALKS = {"unrolled": {}, "rolled": {"pairs": _ROLLS}, "streams": {"budget": _STREAMS}}


@pytest.mark.parametrize(
    "N, D, T, walk",
    [(N, D, 256, "unrolled") for N in (2, 3, 5, 12) for D in (64, 128, 96)]
    + [(2, 64, 200, "unrolled"), (3, 96, 200, "unrolled"), (5, 128, 200, "unrolled"), (12, 64, 200, "unrolled")]  # padded up to the block
    + [(3, 64, 384, "rolled"), (3, 64, 384, "streams"), (5, 64, 200, "streams"), (2, 128, 384, "rolled"), (3, 96, 384, "streams"), (5, 96, 200, "rolled")],
    ids=lambda x: str(x),
)
def test_parity_in_the_models_layout(N, D, T, walk):
    """Outputs and the three gradients against the plain reference, the kernels
    addressing q, k, v, o as ``[B, T, N * D]``: two heads of 64 a lane tile (an
    odd count leaves the last tile one head and lanes that are not there), a
    head of 128 a block of its own, heads of 96 through the head-major entry;
    T a multiple of the block and padded up to it; every form of the walk."""
    _check(2 if N == 3 else 1, T, N, D, **_WALKS[walk])


@pytest.mark.parametrize(
    "heads, head_dim, layout, steps",
    [
        (12, 64, ("lanes", 2, False), 2),  # gpt2_125m_zero1_train: 6 lane tiles, 3 a grid step
        (25, 64, ("lanes", 2, True), 13),  # gpt2_xl_zero3_dp4_train: 12.5 lane tiles; 13 has no divisor, one a grid step
        (2, 64, ("lanes", 2, False), 1),
        (3, 64, ("lanes", 2, True), 1),
        (5, 64, ("lanes", 2, True), 1),
        (1, 64, ("lanes", 1, False), 1),  # fewer lanes than a tile: the block is the operand's full width
        (5, 32, ("lanes", 4, True), 1),
        (2, 128, ("lanes", 1, False), 1),
        (3, 128, ("lanes", 1, False), 1),
        (5, 128, ("lanes", 1, False), 1),
        (12, 128, ("lanes", 1, False), 2),  # six heads a step
        (4, 256, ("lanes", 1, False), 1),
        (2, 96, ("head_major", 1, False), 1),
        (3, 96, ("head_major", 1, False), 1),
        (5, 96, ("head_major", 1, False), 1),
        (12, 96, ("head_major", 1, False), 1),
        (20, 80, ("head_major", 1, False), 1),
    ],
    ids=lambda x: "x".join(map(str, x)) if isinstance(x, tuple) else str(x),
)
def test_operand_layout_follows_heads_and_width(heads, head_dim, layout, steps):
    """One pure function of the shape chooses the layout; ``_flash_attention``
    calls it, and the call it makes has the grid that answer means: a batch
    row's heads in ``steps`` grid steps of up to eight, lane tile beside lane
    tile, every step as many (every head a row of its own through the
    head-major entry)."""
    assert tuple(fa.operand_layout(heads, head_dim)) == layout
    path = layout[0]
    shape = jax.ShapeDtypeStruct((2, 256, heads, head_dim), jnp.bfloat16)
    from deepspeed_tpu.analysis import iter_eqns

    jaxpr = jax.make_jaxpr(lambda q, k, v: flash_attention(q, k, v, interpret=False))(shape, shape, shape)
    eqns = list(iter_eqns(jaxpr))
    (call,) = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (2 if path == "lanes" else 2 * heads, steps, 1, 1)
    at_the_door = [e for e in eqns if e.primitive.name == "transpose" and e.outvars[0].aval.ndim == 4]  # the kernels' own are of a block
    assert len(at_the_door) == (4 if path == "head_major" else 0)


def test_engine_records_the_operand_layout_once():
    """The ops have no tracer: the engine says once, where it builds the step,
    which layout training's attention gets (heads a chip, their width, the
    kernels' answer), so a fall back to the transposing entry shows in a run's
    events."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM, llama_config
    from deepspeed_tpu.parallel import mesh as mesh_mod

    def events(**model):
        mesh_mod.reset_topology()
        cfg = llama_config("tiny", num_layers=1, hidden_size=128, max_seq_len=128, vocab_size=64, remat=False, **model)
        config = {"train_micro_batch_size_per_gpu": 1, "optimizer": {"type": "adam", "params": {"lr": 1e-3}}, "steps_per_print": 10_000}
        engine, *_ = ds.initialize(model=TransformerLM(cfg), config=config)
        toks = np.zeros((8, 128), np.int32)
        engine({"input_ids": toks, "labels": toks})
        return [s for s in engine.tracer.spans() if s["name"] == "flash.operand_layout"]

    from deepspeed_tpu.models.transformer import flash_operand_layout

    (event,) = events(num_heads=2, flash_attention=True)
    assert event["ph"] == "i"
    assert event["attrs"] == {"heads_on_a_chip": 2, "head_dim": 64, "path": "lanes", "heads_per_lane_tile": 2, "edge_tile": False}
    topo = mesh_mod.get_topology()
    assert event["attrs"] == flash_operand_layout(llama_config("tiny", hidden_size=128, num_heads=2, flash_attention=True), topo)
    # nothing to say of a model whose training does not enter the kernels
    assert flash_operand_layout(llama_config("tiny", hidden_size=128, num_heads=2, flash_attention=False), topo) is None
    assert flash_operand_layout(llama_config("tiny", hidden_size=128, num_heads=2, attn_dropout=0.1), topo) is None
    mesh_mod.reset_topology()


def test_plan_follows_shapes_and_budget():
    """The form of the walk comes from T, the lanes of a block, the dtype and
    the VMEM budget alone."""
    plan = functools.partial(fa._plan, blk_outer=256, blk_walked=512, tensors_outer=2, tensors_walked=2)
    held = plan(1024, 128, 2, 2, vmem_budget=_HOLDS_ALL)  # both cells: two heads of 64, bf16
    assert not held.streams and (held.outer, held.walked) == (1024, 1024)
    assert plan(1024, 64, 1, 2, vmem_budget=_HOLDS_ALL) == held  # a row of 64 lanes takes a whole tile in VMEM
    long = plan(32768, 128, 1, 2, vmem_budget=_HOLDS_ALL)  # 8 MB a tensor a block: streams
    assert long.streams and long.outer == 256 and long.walked % 512 == 0 and 32768 % long.walked == 0
    assert long.walked > 512  # as many walked blocks a step as the budget holds
    tiny = plan(1024, 128, 2, 2, vmem_budget=_STREAMS)
    assert tiny.streams and (tiny.outer, tiny.walked) == (256, 512)


def test_row_statistics_are_stored_once_and_lane_dense():
    """The forward's residual is f32[B, N, 1, T], and nothing in the backward
    program is a [.., T, 128] broadcast of it."""
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(2, 256, 4, 64))
    loss = lambda q, k, v: flash_attention(q, k, v).astype(jnp.float32).sum()
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    from deepspeed_tpu.analysis import iter_eqns

    shapes = {tuple(var.aval.shape) for eqn in iter_eqns(jaxpr) for var in eqn.outvars if hasattr(var.aval, "shape")}
    assert (2, 4, 1, 256) in shapes
    assert not any(len(s) >= 3 and s[-2:] == (256, 128) and np.prod(s[:-2]) == 8 for s in shapes), shapes


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
    short T as for a long one, for 8 heads (one grid step's) as for 200, and
    short; an odd count (a last lane tile of one head) adds the selects that
    keep the lanes that are not there out of a contraction, once."""
    from deepspeed_tpu.analysis import iter_eqns

    def equations(N=8, T=lengths[0]):
        shape = jax.ShapeDtypeStruct((1, T, N * 64), jnp.bfloat16)
        budget = _STREAMS if walk == "streams" else _HOLDS_ALL

        def loss(q, k, v):
            return fa._flash_core(q, k, v, fa._How(0.125, True, 256, 256, False, budget, _UNROLLS, 64)).astype(jnp.float32).sum()

        eqns = list(iter_eqns(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(shape, shape, shape)))
        # the three bodies, their loops' and branches' included; and whether the lowering unrolls the loops over blocks
        unrolls = any(e.primitive.name == "scan" and e.params["length"] > 1 and e.params["unroll"] == e.params["length"] for e in eqns)
        return len(eqns), unrolls

    base = equations()
    assert base == equations(N=200) == equations(N=16) == equations(N=32)
    # a step of one lane tile (two heads in all, or a sequence too long to hold four tiles of) has no tile to find: a little shorter
    for one_tile in (equations(N=2), equations(T=lengths[1])):
        assert one_tile[1] == base[1] and base[0] - 40 < one_tile[0] <= base[0], (base, one_tile)
    odd = equations(N=25)  # the same bodies, and the selects that keep the lanes past the last head out of a contraction
    assert odd == equations(N=9) and odd[1] == base[1] and base[0] < odd[0] < base[0] + 150, (base, odd)
    assert base[1] == (walk == "unrolled")
    assert base[0] < 800, base


@pytest.mark.parametrize("N", [12, 25], ids=["gpt2_125m", "gpt2_xl_a_chip"])
def test_both_cells_walk_unrolled(N):
    """At the training cells' shape a chip (8 sequences of T 1,024, 12 and 25
    heads of 64, bf16) a grid step holds three lane tiles of a sequence whole
    or one, two heads each (XL's thirteenth tile one), and a head's walk is 2 x 2
    blocks of 512: unrolled, the form the kernel bench found 1.7 times faster."""
    from deepspeed_tpu.analysis import iter_eqns

    assert (1024 // fa._BLOCK_Q) * (1024 // fa._BLOCK_K) <= fa._UNROLL_PAIRS
    shape = jax.ShapeDtypeStruct((8, 1024, N, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, interpret=False).astype(jnp.float32).sum()

    eqns = list(iter_eqns(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(shape, shape, shape)))
    calls = {e.params["name"] if "name" in e.params else e.params["metadata"]["name"]: e for e in eqns if e.primitive.name == "pallas_call"}
    assert sorted(calls) == ["flash_bwd_delta", "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    grids = {name: e.params["grid_mapping"].grid for name, e in calls.items()}
    steps = {12: 2, 25: 13}[N]  # 6 lane tiles as 2 steps of 3; 13 one a step (every step holds a divisor of the tiles)
    assert all(g[:2] == (8, steps) and set(g[2:]) == {1} for g in grids.values()), grids  # lane tiles of a whole sequence a step
    walks = [e for e in eqns if e.primitive.name == "scan" and e.params["length"] == 2]
    rolled = [e for e in walks if e.params["unroll"] != 2]
    assert len(walks) > len(rolled)  # outer and walked blocks: unrolled by the lowering
    assert not rolled
    # the walk over a step's six heads stays rolled, one body for them all (a loop to a traced bound where the last tile holds one)
    heads_walks = [e for e in eqns if e.primitive.name == "scan" and e.params["length"] == 6]
    assert [e.params["unroll"] for e in heads_walks] == ([1, 1, 1] if N == 12 else [])


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
