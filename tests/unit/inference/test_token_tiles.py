"""The mixed serving step computes its live tokens only (ISSUE 30).

A ragged window wider than one token tile (``decode.token_tile``) is packed,
its token-wise work runs tile by tile over the live tokens, and only the
attention kernel sees the ``[R, W]`` slab (``decode._paged_layers``). The
oracle is the same program with the rule turned off, which computes the whole
slab as every narrow window still does: greedy tokens, logits, pages and
routing counts must agree at every fill. The narrow, verify-width and
multi-step programs hold no tile loop at all, the mixed program's size does not
follow the window's, and the scheduler counts what the program runs.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.compression.int8 import quantize_params_int8
from deepspeed_tpu.inference import decode
from deepspeed_tpu.inference.scheduler import PagedServer
from deepspeed_tpu.inference.tp import TPServing, serving_mesh
from deepspeed_tpu.models import MoETransformerLM, TransformerLM
from deepspeed_tpu.models.config import TransformerConfig
from deepspeed_tpu.models.moe_transformer import olmoe_config
from deepspeed_tpu.profiling.tracer import Tracer

ROWS, WIDTH, PAGE, MAXP = 16, 128, 16, 24  # the serving cells' window; 384 positions a row
DENSE = dict(
    vocab_size=128, hidden_size=32, num_layers=2, num_heads=4, num_kv_heads=2, intermediate_size=64,
    max_seq_len=PAGE * MAXP, norm="rmsnorm", position="rope", activation="swiglu", use_bias=False,
    tie_embeddings=False, flash_attention=False, dtype="float32",
)
# (new tokens, tokens already cached) a row; rows past the list are dead
DECODE = [(1, 10 + 7 * i) for i in range(15)]
FILLS = {
    "one_chunk_15_decode": [(128, 0)] + DECODE,  # 143 live: one tile, its tail dead
    "partial_last_tile": [(128, 128), (128, 0), (128, 0), (128, 128), (37, 256)] + DECODE[:9],  # 558: a second tile of 46
    "exactly_one_tile": [(128, 0)] * 3 + [(114, 128)] + DECODE[:12],  # 512
    "several_tiles": [(128, 0)] * 9 + DECODE[:7],  # 1,159: three tiles
    "all_rows_a_whole_chunk": [(128, 128 * (i % 2)) for i in range(16)],  # 2,048: no dead slot
    "dead_rows_between_live": [(0, 0), (128, 0), (0, 0), (1, 40), (0, 0), (0, 0), (90, 128), (1, 3), (0, 0)],
    "verify_row_in_a_mixed_step": [(128, 0), (4, 30), (1, 9), (3, 77)],  # pending + 3 and + 2 drafts
}


@pytest.fixture(scope="module")
def dense():
    cfg = TransformerConfig(**DENSE)
    return cfg, TransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


@pytest.fixture(scope="module")
def olmoe():
    cfg = olmoe_config("tiny", dtype="float32", flash_attention=False, remat=False, max_seq_len=PAGE * MAXP)
    return cfg, MoETransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


@pytest.fixture
def whole_slab(monkeypatch):
    """Inside ``with whole_slab():`` no window is tiled: the oracle."""

    @contextlib.contextmanager
    def off():
        with monkeypatch.context() as m:
            m.setattr(decode, "token_tile", lambda cfg: 0)
            decode._paged_program_cache.clear()
            yield
        decode._paged_program_cache.clear()

    return off


def window(cfg, fill, seed=0):
    """(tokens, pools, table, lengths, q_lens) of one step, the pools holding
    what earlier steps would have left; a verify row's drafts are its window's
    seeded tokens."""
    rng = np.random.default_rng(seed)
    q_lens, lengths = np.zeros(ROWS, np.int32), np.zeros(ROWS, np.int32)
    for r, (new, cached) in enumerate(fill):
        q_lens[r], lengths[r] = new, cached
    tokens = rng.integers(0, cfg.vocab_size, (ROWS, WIDTH)).astype(np.int32)
    shape = (cfg.num_layers, ROWS * MAXP + 1, cfg.num_kv_heads, PAGE, cfg.head_dim)
    # what earlier steps left in the pages: every row reads its own
    pools = [jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.3 for _ in range(2)]
    table = np.where(q_lens[:, None] > 0, 1 + np.arange(ROWS * MAXP).reshape(ROWS, MAXP), -1).astype(np.int32)
    return tokens, pools, table, lengths, q_lens


def run_step(cfg, params, fill, tp=None):
    tokens, pools, table, lengths, q_lens = window(cfg, fill)
    step = decode.build_ragged_step(cfg, ROWS, WIDTH, PAGE, attn_impl="xla", tp=tp)
    if tp is not None:
        pools = [jax.device_put(p, tp.kv_sharding) for p in pools]
    out, kp, vp = step(params, tokens, *pools, table, lengths, q_lens)
    return np.asarray(out), np.asarray(kp), np.asarray(vp), q_lens


def assert_same_step(got, want):
    out, kp, vp, q_lens = got
    ref, ref_kp, ref_vp, _ = want
    live = np.arange(WIDTH)[None, :] < q_lens[:, None]
    np.testing.assert_array_equal(out[:ROWS, 1:][live], ref[:ROWS, 1:][live])  # the greedy token after every live slot
    np.testing.assert_array_equal(out[:ROWS, 0], ref[:ROWS, 0])  # accepted drafts
    np.testing.assert_array_equal(out[ROWS:], ref[ROWS:])  # an MoE model's routing counts
    np.testing.assert_allclose(kp[:, 1:], ref_kp[:, 1:], atol=1e-5)  # every page but the trash page
    np.testing.assert_allclose(vp[:, 1:], ref_vp[:, 1:], atol=1e-5)


@pytest.mark.parametrize("fill", sorted(FILLS))
def test_mixed_step_equals_the_whole_slab_dense(dense, whole_slab, fill):
    cfg, params = dense
    assert ROWS * WIDTH > decode.token_tile(cfg)
    with whole_slab():
        want = run_step(cfg, params, FILLS[fill])
    assert_same_step(run_step(cfg, params, FILLS[fill]), want)


@pytest.mark.parametrize("fill", ["one_chunk_15_decode", "partial_last_tile", "several_tiles", "all_rows_a_whole_chunk",
                                  "dead_rows_between_live"])
def test_mixed_step_equals_the_whole_slab_olmoe(olmoe, whole_slab, fill):
    """The routed FFN tile by tile: the same experts for every token, the
    same counts (``moe_assignments``, ``moe_experts_hit``,
    ``moe_max_expert_load``: the packed result's last rows)."""
    cfg, params = olmoe
    assert ROWS * WIDTH > decode.token_tile(cfg) >= decode.DENSE_TOKEN_TILE
    with whole_slab():
        want = run_step(cfg, params, FILLS[fill])
    got = run_step(cfg, params, FILLS[fill])
    assert_same_step(got, want)
    live_tokens = sum(new for new, _ in FILLS[fill])
    assert got[0][ROWS, 0] == live_tokens * cfg.moe_top_k * cfg.num_layers  # moe_assignments


LAYOUTS = {
    "gpt2_learned_positions_biases_tied_head": dict(
        position="learned", norm="layernorm", activation="gelu", use_bias=True, tie_embeddings=True, num_kv_heads=4
    ),
    "parallel_residual": dict(parallel_residual=True),
}


@pytest.mark.parametrize("int8", [False, True], ids=["fp", "int8_weights"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_mixed_step_equals_the_whole_slab_other_layouts(whole_slab, layout, int8):
    """The same body for every model the ragged programs serve: learned
    positions (packed with the tokens), biases, a tied head, parallel
    residuals, and int8 weights whose codes and scales are indexed out of
    their stacks together."""
    cfg = TransformerConfig(**{**DENSE, **LAYOUTS[layout]})
    params = TransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    if int8:
        params = quantize_params_int8(params)
    with whole_slab():
        want = run_step(cfg, params, FILLS["several_tiles"])
    assert_same_step(run_step(cfg, params, FILLS["several_tiles"]), want)


@pytest.mark.parametrize("model", ["dense", "olmoe"])
@pytest.mark.parametrize("fill", ["one_chunk_15_decode", "several_tiles"])
def test_paged_forward_logits_equal_the_whole_slab(request, whole_slab, model, fill):
    """The entry ``olmoe_logits_check.py`` and ``test_olmoe.py`` call: the
    same positional arguments, ``[B, T, V]`` logits, per-layer counts."""
    cfg, params = request.getfixturevalue(model)
    tokens, pools, table, lengths, q_lens = window(cfg, FILLS[fill], seed=3)

    def forward():
        positions = lengths[:, None] + np.arange(WIDTH, dtype=np.int32)[None, :]
        kv_lens = np.where(q_lens > 0, lengths + q_lens, 0).astype(np.int32)
        fn = jax.jit(lambda params, *a: decode._paged_forward(
            cfg, params, *a, None, "xla", prefill_kv_lens=kv_lens, ragged_q_lens=q_lens))
        logits, _, _, counts = fn(params, tokens, *pools, table, positions)
        return np.asarray(logits), None if counts is None else np.asarray(counts)

    with whole_slab():
        want, want_counts = forward()
    got, counts = forward()
    live = np.arange(WIDTH)[None, :] < q_lens[:, None]
    assert got.shape == (ROWS, WIDTH, cfg.vocab_size)
    np.testing.assert_allclose(got[live], want[live], atol=2e-5, rtol=0)
    if model == "olmoe":
        np.testing.assert_array_equal(counts, want_counts)
        assert counts.shape == (cfg.num_layers, cfg.num_experts)


@pytest.mark.parametrize("fill", ["one_chunk_15_decode", "several_tiles"])
def test_mixed_step_under_shard_map_equals_one_chip(whole_slab, fill):
    """TP serving: the tile loops inside ``shard_map``, the row-parallel
    all-reduces and the vocab-sharded arg-max inside loops whose trip count
    every shard computes from the same replicated ``q_lens``."""
    cfg = TransformerConfig(**{**DENSE, "num_kv_heads": 4})
    params = TransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    with whole_slab():
        want = run_step(cfg, params, FILLS[fill])
    tp = TPServing(mesh=serving_mesh(2))
    sharded = tp.shard_params(cfg, params)
    assert tp.head_sharded
    assert_same_step(run_step(cfg, sharded, FILLS[fill], tp=tp), want)


# --- the way round is static; the mixed program does not grow -----------------


def loops_of(jaxpr, depth=0):
    """(primitive, nesting depth) of every loop in a jaxpr, inner jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        is_loop = eqn.primitive.name in ("scan", "while")
        if is_loop:
            found.append((eqn.primitive.name, depth))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += loops_of(sub, depth + is_loop)
    return found


def count_eqns(jaxpr):
    return sum(1 + sum(count_eqns(sub) for sub in jax.core.jaxprs_in_params(eqn.params)) for eqn in jaxpr.eqns)


def step_jaxpr(cfg, params, rows, width):
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    pool = jax.ShapeDtypeStruct((cfg.num_layers, rows * MAXP + 1, cfg.num_kv_heads, PAGE, cfg.head_dim), jnp.float32)
    step = decode.build_ragged_step(cfg, rows, width, PAGE, attn_impl="xla")
    return step.trace(params, S(rows, width), pool, pool, S(rows, MAXP), S(rows), S(rows)).jaxpr.jaxpr


@pytest.mark.parametrize("model", ["dense", "olmoe"])
@pytest.mark.parametrize("width", [1, 4])
def test_narrow_and_verify_programs_hold_no_loop_but_the_layer_scan(request, model, width):
    cfg, params = request.getfixturevalue(model)
    assert loops_of(step_jaxpr(cfg, params, ROWS, width)) == [("scan", 0)]


@pytest.mark.parametrize("model", ["dense", "olmoe"])
def test_mixed_program_runs_its_tiles_in_loops_traced_once(request, model):
    """Two tile loops inside the layer scan and the head's after it, each a
    ``while`` whose trip count is data; as many equations at four times the
    slots (``R W / tile`` of 4 and of 16 for the dense rule)."""
    cfg, params = request.getfixturevalue(model)
    jaxpr = step_jaxpr(cfg, params, ROWS, WIDTH)
    assert loops_of(jaxpr) == [("scan", 0), ("while", 1), ("while", 1), ("while", 0)]
    assert count_eqns(jaxpr) == count_eqns(step_jaxpr(cfg, params, 4 * ROWS, WIDTH))


def test_the_tile_rule():
    dense = TransformerConfig(**DENSE)
    assert decode.token_tile(dense) == decode.DENSE_TOKEN_TILE == 512
    assert decode.token_tile(olmoe_config()) == 1024  # 128 rows an expert at 8 of 64
    capacity = olmoe_config("tiny", moe_top_k=2, moe_drop_tokens=True)
    assert decode.token_tile(capacity) == 0  # capacity is a function of the slot count: never tiled
    assert decode.token_tiles(capacity, 16, 128, 143) == 0
    assert [decode.token_tiles(dense, 16, 128, n) for n in (0, 1, 143, 512, 513, 2048)] == [0, 1, 1, 1, 2, 4]
    assert decode.token_tiles(dense, 16, 1, 16) == 0 and decode.token_tiles(dense, 4, 128, 200) == 0  # the way round


# --- the scheduler's counter ---------------------------------------------------


def test_pack_span_and_serve_stats_count_live_tokens_and_tiles(dense):
    cfg, params = dense
    tracer = Tracer()
    server = PagedServer(cfg, params, page_size=PAGE, max_slots=ROWS, prefill_chunk=WIDTH, attn_impl="xla",
                         dtype=jnp.float32, tracer=tracer)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32) for n in (130, 128, 40, 300, 200, 128)]
    budgets = [3, 2, 6, 2, 2, 4]
    outs = server.serve(prompts, max_new_tokens=budgets)
    for prompt, n, out in zip(prompts, budgets, outs):
        np.testing.assert_array_equal(out, np.asarray(decode.generate(cfg, params, prompt[None], n))[0])
    packs = [s["attrs"] for s in tracer.spans() if s["name"] == "serve.pack"]
    mixed = [a for a in packs if a["width"] == WIDTH]
    assert mixed and all(a["token_tiles"] == -(-a["live_tokens"] // decode.token_tile(cfg)) for a in mixed)
    assert mixed[0]["live_tokens"] == 5 * 128 + 40 and mixed[0]["token_tiles"] == 2  # every prompt's first chunk
    assert all(a["token_tiles"] == 0 and a["live_tokens"] == a["rows"] for a in packs if a["width"] == 1)
    stats = server.serve_stats()
    assert stats["mixed_steps"] == len(mixed) == 3
    assert stats["mixed_live_tokens"] == sum(a["live_tokens"] for a in mixed) >= sum(p.size for p in prompts)
    assert stats["mixed_token_tiles"] == sum(a["token_tiles"] for a in mixed)
    assert stats["mixed_tiles_per_step"] == stats["mixed_token_tiles"] / 3 > 1
    assert stats["mixed_tokens_per_step"] == stats["mixed_live_tokens"] / 3


def test_mixed_step_bench_rehearses():
    """``tools/mixed_step_bench.py --rehearse``: the tool's control flow, tiny, on the CPU."""
    import pathlib
    import subprocess
    import sys

    tool = pathlib.Path(__file__).parents[3] / "tools" / "mixed_step_bench.py"
    done = subprocess.run(
        [sys.executable, str(tool), "--rehearse", "--fills", "1+15,16+0", "--tiles", "512,1024"],
        capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [line for line in done.stdout.splitlines() if "ms a call" in line]
    assert [(line.split()[2], line.split()[4]) for line in lines] == [
        ("512", "1+15"), ("512", "16+0"), ("1024", "1+15"), ("1024", "16+0")
    ]
