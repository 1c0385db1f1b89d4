"""The main path's Pallas kernels, compiled by the TPU compiler for a v5e.

The chip is described, not attached (``on-chip-measurement`` guide §2.3):
nothing runs, but the compiler refuses here what it would refuse on the
chip — a slice not aligned to the tiling, too much VMEM — which interpret
mode never shows. Every kernel is entered with ``interpret=False``: left to
itself it reads ``jax.default_backend()`` (the CPU here) and would lower the
interpreter instead of the kernel. Shapes are the ones ``chip_smoke.py``
runs: GPT-2 125M training (B8 T1024 N12 D64) and llama-1b serving (32 q
heads over 4 kv heads, D64, page 64, 8 slots, windows 1 and 128); the ragged
kernel also at the benchmark's serving cells' (heads of 128: the kernel that
walks a row's live pages).

The tests after the kernels compile whole ragged serving steps at the shapes
of the benchmark's cells (Mistral's first) and read what the compiler made of
the pools. The file's last test compiles training's pipelined ZeRO-3 layer scan
at GPT-2 XL's widths for the four described chips and reads where a layer's
gradient is written.
"""

import collections
import functools
import json
import os
import pathlib
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.analysis.hlo import parse_computations, parse_input_output_aliases
from deepspeed_tpu.inference import decode
from deepspeed_tpu.models import MoETransformerLM, TransformerLM
from deepspeed_tpu.models.config import TransformerConfig
from deepspeed_tpu.models.moe_transformer import MoETransformerConfig
from deepspeed_tpu.moe.grouped_matmul import grouped_matmul
from deepspeed_tpu.ops.sparse_attention.pallas_block_sparse import pallas_block_sparse_attention
from deepspeed_tpu.ops.sparse_attention.sparsity_config import BSLongformerSparsityConfig
from deepspeed_tpu.ops.transformer.decode_attention import (
    decode_attention,
    ragged_paged_attention,
)
from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

BF16 = jnp.bfloat16
I32 = jnp.int32


@pytest.fixture(scope="module")
def v5e_2x2():
    """A described ``v5e:2x2``. A compile for it is written to the
    persistent cache but can never be read back without a chip, so the
    cache is off for this module (the guide's advice)."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def v5e(v5e_2x2):
    """One described v5e device."""
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _flash_fwd(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=False)


def _bwd(fwd):
    """dq, dk, dv of a scalar of ``fwd``: the kernel's backward programs."""

    def bwd(q, k, v):
        loss = lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    return bwd


def _ragged(q, k_new, v_new, k_pages, v_pages, table, kv_lens, q_lens):
    return ragged_paged_attention(
        q, k_new, v_new, k_pages, v_pages, 1, table, kv_lens, q_lens, interpret=False
    )


def _latent(q, new, pages, table, kv_lens, q_lens):
    from deepspeed_tpu.ops.transformer.latent_attention import latent_paged_attention

    return latent_paged_attention(q, new, pages, 3, table, kv_lens, q_lens, value_lanes=512, scale=1 / 16, impl="pallas", interpret=False)


def _dense_decode(q, k_cache, v_cache, kv_lens):
    return decode_attention(q, k_cache, v_cache, kv_lens, interpret=False)


_SPARSE_T, _SPARSE_BLOCK = 8192, 64  # tests/perf/block_sparse_bench.py's shape


def _sparse_layout():
    cfg = BSLongformerSparsityConfig(num_heads=8, block=_SPARSE_BLOCK)
    return np.asarray(cfg.make_layout(_SPARSE_T))[:1]


def _sparse_fwd(q, k, v):
    return pallas_block_sparse_attention(
        q, k, v, _sparse_layout(), _SPARSE_BLOCK, causal=True, interpret=False
    )


# the server's pool: two layers of 8 slots x (2048 / 64) pages + the trash page
_PAGES = ((2, 257, 4, 64, 64), BF16)
_TABLE = ((8, 32), I32)
_LENS = ((8,), I32)
_TRAIN_QKV = [((8, 1024, 12, 64), BF16)] * 3
_TRAIN_XL_QKV = [((8, 1024, 25, 64), BF16)] * 3  # a chip's share of gpt2_xl_zero3_dp4_train
_SPARSE_QKV = [((1, 8, _SPARSE_T, 64), BF16)] * 3

def _grouped(x, w, sizes):
    return grouped_matmul(x, w, sizes, out_dtype=jnp.float32, impl="pallas")


CASES = {
    "flash_fwd_gpt2_125m": (_flash_fwd, _TRAIN_QKV),
    "flash_bwd_gpt2_125m": (_bwd(_flash_fwd), _TRAIN_QKV),
    "flash_fwd_gpt2_xl": (_flash_fwd, _TRAIN_XL_QKV),
    "flash_bwd_gpt2_xl": (_bwd(_flash_fwd), _TRAIN_XL_QKV),
    # heads of 128 at T 4,096 (tools/flash_kernel_bench.py's last shape): what a grid step holds is cut to the VMEM budget
    "flash_bwd_d128_t4096": (_bwd(_flash_fwd), [((1, 4096, 32, 128), BF16)] * 3),
    "ragged_w1_llama_1b": (
        _ragged,
        [((8, 1, 32, 64), BF16)] + [((8, 1, 4, 64), BF16)] * 2 + [_PAGES, _PAGES, _TABLE, _LENS, _LENS],
    ),
    "ragged_w128_llama_1b": (
        _ragged,
        [((8, 128, 32, 64), BF16)] + [((8, 128, 4, 64), BF16)] * 2 + [_PAGES, _PAGES, _TABLE, _LENS, _LENS],
    ),
    **{
        # the benchmark's serving cells: 16 rows over the Mistral pool (32 query heads on 8 kv heads) and the
        # OLMoE pool (16 on 16), and a tensor-parallel shard of Mistral's (8 rows, 8 on 2), both widths
        f"ragged_w{width}_{cell}": (
            _ragged,
            [((rows, width, heads, 128), BF16)] + [((rows, width, kv_heads, 128), BF16)] * 2
            + [((layers, rows * maxp + 1, kv_heads, 64, 128), BF16)] * 2 + [((rows, maxp), I32)] + [((rows,), I32)] * 2,
        )
        for cell, (rows, heads, kv_heads, layers, maxp) in {
            "mistral7b": (16, 32, 8, 16, 38), "olmoe": (16, 16, 16, 12, 24), "mistral7b_tp4_shard": (8, 8, 2, 16, 38),
        }.items()
        for width in (1, 128)
    },
    **{
        # GLM-4.7-Flash's latent kernel at the published shapes: 20 heads over one entry of 576 (512 of them the
        # value) in pages of 64 x 640 lanes, 16 layers of 4,097 pages; 64 decode rows, and the wide window's 4 chunk rows
        f"latent_w{width}_glm47": (
            _latent,
            [((rows, width, 20, 576), BF16), ((rows, width, 576), BF16), ((16, 4097, 64, 640), BF16), ((rows, 64), I32)]
            + [((rows,), I32)] * 2,
        )
        for rows, width in ((64, 1), (4, 128))
    },
    "dense_decode_llama_1b": (
        _dense_decode,
        [((8, 32, 64), BF16), ((8, 2048, 4, 64), BF16), ((8, 2048, 4, 64), BF16), _LENS],
    ),
    # OLMoE's expert matmuls: 64 experts of 2048 x 1024, a narrow step's 128
    # assignments and a mixed step's 16,384
    "moe_grouped_up_narrow": (_grouped, [((128, 2048), BF16), ((64, 2048, 1024), BF16), ((64,), I32)]),
    "moe_grouped_down_mixed": (_grouped, [((16384, 1024), BF16), ((64, 1024, 2048), BF16), ((64,), I32)]),
    # Solar's and MiMo's gate/up calls, where K is in two tiles: a narrow step's 512 assignment rows over the
    # 40 (16) held experts of one layer of the stack (the body slices the held row block by a dynamic K step)
    "moe_grouped_up_narrow_solar": (_grouped, [((512, 4096), BF16), ((160, 4096, 1280), BF16), ((40,), I32)]),
    "moe_grouped_up_narrow_mimo": (_grouped, [((512, 4096), BF16), ((96, 4096, 2048), BF16), ((16,), I32)]),
    "block_sparse_fwd_8k": (_sparse_fwd, _SPARSE_QKV),
    "block_sparse_bwd_8k": (_bwd(_sparse_fwd), _SPARSE_QKV),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(v5e, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e) for shape, dtype in shapes]
    compiled = jax.jit(fn, donate_argnums=(2,) if fn is _latent else ()).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel is not in the program"
    if fn is _latent:
        # donated, the pool goes in and comes out in one buffer (a bitcast to the stack of pages and back): no other
        # instruction makes an array of its size, and the one custom call reads the page ONCE (one pool operand)
        assert 2 in parse_input_output_aliases(text)
        made = [line for line in text.splitlines() if re.search(r"= bf16\[(16,4097|65552),64,640\]", line)]
        assert made and all(re.search(r" (parameter|bitcast|get-tuple-element)\(", line) for line in made), made
        (call,) = re.findall(r"custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\"", text)
        assert len(call.split(",")) == 5, call  # table, lengths, q lengths, the row operand, the pool
        if name == "latent_w1_glm47":
            # the narrow form: what the kernel may hold (its vmem_limit_bytes less the compiler's 24 MiB) is the ring
            # of three halves of 24 pages, and the row's queries, output and statistics on top
            (kernel,) = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
            (limit,) = re.findall(r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', kernel)
            ring = 3 * 24 * 64 * 640 * 2
            assert ring < int(limit) - (24 << 20) < ring + (1 << 18), limit
    if fn is _grouped:
        # ONE s32 operand in front (the packed visits): the benchmark's readers tell the ragged attention
        # kernel by its three, and jax.lax.ragged_dot's own lowering opens with five
        shape_of = dict(re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = (\S+)", text, flags=re.M))
        (operands,) = re.findall(r"%moe_grouped_matmul[\w.-]* = .*? custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\"", text)
        kinds = [shape_of[o].split("[")[0] for o in re.findall(r"%([\w.-]+)", operands)]
        assert kinds == ["s32", "bf16", "bf16"], kinds


# Laguna-S-2.1's two kinds of attention layer at the published shapes: 8 KV heads of 128 under 48 query heads (groups
# of 6, every key, three layers of 4,097 pages) and under 72 (groups of 9, a window of 512 keys on six layers' rings of
# 10 pages a slot); the narrow program's 64 rows of one token and a wide window's trip of 4 rows of 128
LAGUNA_KERNEL = {
    f"{kind}_w{width}": (heads, window, layers, pages, rows, width)
    for kind, (heads, window, layers, pages) in {"full": (48, None, 3, 4097), "window": (72, 512, 6, 641)}.items()
    for rows, width in ((64, 1), (4, 128))
}


@pytest.mark.parametrize("name", sorted(LAGUNA_KERNEL))
def test_ragged_kernel_compiles_at_groups_of_six_and_nine_with_its_pools_in_place(v5e, name):
    """No accepted model's group is anything but a power of two; the kernel
    finds a query row's window slot by ``row // group`` on a vector and cuts
    128 x 9 rows into tiles of 128 that end inside a group. Mosaic takes both:
    the call compiles for a v5e, it is the live-pages kernel (three ``s32``
    operands in front), and the donated pools come out in the buffers they went in."""
    heads, window, layers, pages, rows, width = LAGUNA_KERNEL[name]

    def call(q, k_new, v_new, k_pages, v_pages, table, kv_lens, q_lens):
        return ragged_paged_attention(q, k_new, v_new, k_pages, v_pages, 1, table, kv_lens, q_lens, interpret=False, window=window)

    shapes = (
        [((rows, width, heads, 128), BF16)] + [((rows, width, 8, 128), BF16)] * 2 + [((layers, pages, 8, 64, 128), BF16)] * 2
        + [((rows, 64), I32)] + [((rows,), I32)] * 2
    )
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e) for shape, dtype in shapes]
    text = jax.jit(call, donate_argnums=(3, 4)).lower(*args).compile().as_text()
    assert {3, 4} <= parse_input_output_aliases(text)
    shape_of = dict(re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = (\S+)", text, flags=re.M))
    (operands,) = re.findall(r"custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\"", text)
    kinds = [shape_of[o].split("[")[0] for o in re.findall(r"%([\w.-]+)", operands)]
    assert kinds == ["s32"] * 3 + ["bf16"] * 3, kinds  # table, lengths, q lengths; the row operand, the two pools
    made = [line for line in text.splitlines() if re.search(rf"= bf16\[({layers},{pages}|{layers * pages}),8,64,128\]", line)]
    assert made and all(re.search(r" (parameter|bitcast|get-tuple-element|custom-call)\(", line) for line in made), made


# The two window cells' narrow programs: 64 rows of one token under Laguna-S-2.1's window layers (72 query heads over
# 8 KV heads of 128, 512 keys on rings of 10 pages) and MiMo-V2.5's (64 over 8, keys of 192 in pages of 256 lanes,
# values of 128, a sink a head, 128 keys on rings of 4 pages)
WINDOW_DECODE = {
    "laguna": dict(heads=72, dq=128, lanes=128, window=512, layers=6, pages=641, sinks=False),
    "mimo": dict(heads=64, dq=192, lanes=256, window=128, layers=5, pages=257, sinks=True),
}


def _window_decode(name, window, rows=64, width=1):
    """The call and its abstract arguments (no sharding yet)."""
    c = WINDOW_DECODE[name]

    def call(q, k_new, v_new, k_pages, v_pages, table, kv_lens, q_lens, sinks):
        return ragged_paged_attention(
            q, k_new, v_new, k_pages, v_pages, 1, table, kv_lens, q_lens, interpret=False, window=window,
            sinks=sinks if c["sinks"] and window else None,
        )

    shapes = (
        [((rows, width, c["heads"], c["dq"]), BF16), ((rows, width, 8, c["dq"]), BF16), ((rows, width, 8, 128), BF16)]
        + [((c["layers"], c["pages"], 8, 64, c["lanes"]), BF16), ((c["layers"], c["pages"], 8, 64, 128), BF16)]
        + [((rows, 64), I32)] + [((rows,), I32)] * 2 + [((c["heads"],), jnp.float32)]
    )
    return call, shapes


def _kernel_grid_and_equations(call, shapes):
    from deepspeed_tpu.analysis import iter_eqns

    jaxpr = jax.make_jaxpr(call)(*[jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in shapes])
    (grid,) = [eqn.params["grid_mapping"].grid for eqn in iter_eqns(jaxpr) if eqn.primitive.name == "pallas_call"]
    return grid, sum(1 for _ in iter_eqns(jaxpr))


@pytest.mark.parametrize("name", sorted(WINDOW_DECODE))
def test_window_decode_rows_go_a_block_a_grid_step(v5e, name):
    """A window layer's 64 one-token rows are attended ``RB`` a grid step
    (``_ragged_block``: the grid is ``ceil(64 / RB)`` steps, not 65: no step
    that only fetches), and Mosaic takes the block form for a v5e at both
    cells' shapes: a ring of rows' walks in VMEM, slabs of 16 rows fetched and
    written back at a dynamic offset of a page, the pools in place."""
    from deepspeed_tpu.ops.transformer import decode_attention

    c = WINDOW_DECODE[name]
    call, shapes = _window_decode(name, c["window"])
    _, CK, _, _ = decode_attention._ragged_tiles(8, c["heads"] // 8, 1, 64, c["lanes"], -(-(c["window"] - 1) // 64) + 2, 2)
    RB, _ = decode_attention._ragged_block(8, c["heads"] // 8, 1, 64, c["lanes"], 128, CK, 2, c["window"])
    assert RB > 1
    grid, _ = _kernel_grid_and_equations(call, shapes)
    assert grid == (-(-64 // RB),)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e) for shape, dtype in shapes]
    text = jax.jit(call, donate_argnums=(3, 4)).lower(*args).compile().as_text()
    assert {3, 4} <= parse_input_output_aliases(text)
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("rows,width,equations", [(64, 1, 266), (4, 128, 278)])
def test_a_row_a_grid_step_where_no_window_bounds_the_walk(rows, width, equations):
    """Every full layer, and a window layer's prefill chunks: one row a grid
    step, and with no window the body every other model traces, equation for
    equation what it was before the block form existed (PR 50's counts)."""
    call, shapes = _window_decode("laguna", None, rows, width)
    assert _kernel_grid_and_equations(call, shapes) == ((rows + 1,), equations)
    if width > 1:
        call, shapes = _window_decode("laguna", 512, rows, width)
        assert _kernel_grid_and_equations(call, shapes)[0] == (rows + 1,)


def test_flash_backward_is_what_the_benchmark_reads(v5e):
    """The compiled forward + backward of the 125M cell's attention: the row
    statistics stay lane-dense (no ``f32[96,1024,128]`` anywhere: they were
    stored and re-broadcast so until PR 33), and each of the three custom
    calls has the operands and results by which the benchmark's readers tell
    the kernels apart in a trace (``benchmark/kernels/flash_attention.py``)."""
    from benchmark.kernels import flash_attention as read
    from benchmark.trace_reduce import _LAYOUT

    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e) for shape, dtype in _TRAIN_QKV]
    from jax._src.lib import xla_client

    # an instruction as a trace event names it: with its operands' shapes
    as_traced = xla_client._xla.HloPrintOptions.short_parsable()
    as_traced.print_operand_shape = as_traced.print_percent = True
    (module,) = jax.jit(_bwd(_flash_fwd)).lower(*args).compile().runtime_executable().hlo_modules()
    text = module.to_string(as_traced)
    assert "f32[96,1024,128]" not in text and "f32[8,12,1024,128]" not in text
    assert "f32[8,12,1,1024]" in text
    calls = [_LAYOUT.sub("", line) for line in text.splitlines() if "custom-call(" in line and "tpu_custom_call" in line]
    found = {kind: [c for c in calls if re.search(pattern, c)] for kind, pattern in read.EVENTS.items()}
    assert {kind: len(c) for kind, c in found.items()} == {"forward": 1, "backward_dq": 1, "backward_dkv": 1}, calls
    for kind, name in (("forward", "flash_fwd"), ("backward_dq", "flash_bwd_dq"), ("backward_dkv", "flash_bwd_dkv")):
        assert name in found[kind][0].split(" = ")[0], found[kind][0][:120]


@pytest.mark.parametrize("heads, remat", [(12, False), (25, True)], ids=["gpt2_125m", "gpt2_xl_a_chip"])
def test_no_copy_of_q_k_v_or_o_stands_beside_a_flash_call(v5e, heads, remat):
    """A scan of two layers, forward + backward, of what stands around
    training's attention (the three projections, the flash kernels, ``wo``,
    the residual) at both cells' shapes a chip (8 x 1,024 tokens, 12 and 25
    heads of 64; XL's layer rematerialised as its cell's is): the kernels take
    q, k, v and ``do`` and give o, dq, dk, dv as ``[B, T, N * D]``, where the
    matrix products leave and take them, so the compiled program holds no
    ``copy`` or ``transpose`` of a tensor of that size in any shape or layout.
    Until PR 60 the kernels were entered head-major and this program held 12
    of them a layer at either shape (``bf16[8,12,1024,64]``, ``[8,1024,25,64]``,
    ``[8,25,1024,64]``; 9 and 14 in the cells' own compiled steps, 35.2 ms of
    GPT-2 XL's 880 ms). The backward's ``delta`` is a kernel for the same
    reason: a reduction of XLA's over a head's lanes takes ``do`` and the
    saved ``o`` with ``T`` minor, and each is then copied for the kernels."""
    B, T, D, L = 8, 1024, 64, 2
    H = heads * D

    def layer(x, w):
        q, k, v = ((x @ w[i]).reshape(B, T, heads, D) for i in range(3))
        return x + _flash_fwd(q, k, v).reshape(B, T, H) @ w[3], None

    def loss(x, ws):
        return jax.lax.scan(jax.checkpoint(layer) if remat else layer, x, ws)[0].astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((B, T, H), BF16, sharding=v5e)
    ws = jax.ShapeDtypeStruct((L, 4, H, H), BF16, sharding=v5e)
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, ws).compile().as_text()
    for kernel, calls in {"flash_fwd": 2 if remat else 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1}.items():
        assert len(re.findall(rf"%{kernel}[\w.]* = .* custom-call\(", text)) == calls, kernel
    moved = [
        m.group(0)
        for m in re.finditer(r"%[\w.$-]+ = \w+\[([\d,]+)\]\S* (?:copy|transpose)\(", text)
        if np.prod([int(d) for d in m.group(1).split(",")]) == B * T * H
    ]
    assert not moved, moved


_MISTRAL_CELL = pathlib.Path(__file__).parents[3] / "benchmark/configs/mistral-7b-v0.3-l16.json"
_PLUMBING = {"parameter", "tuple", "get-tuple-element", "while", "bitcast"}  # no bytes move
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.$-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.$-]+) = (.+?) ([a-z][a-z0-9-]*)\((.*)$")


def _computations(text):
    """``{computation: [(is ROOT, name, result as written, opcode, the rest of the line)]}``
    of a compiled program's text.
    (``analysis.hlo.parse_computations`` does not read a tiled layout,
    ``{1,0:T(8,128)(2,1)}``, and drops such a line: most of a TPU program.)"""
    computations, body = {}, None
    for line in text.splitlines():
        header = _COMPUTATION.match(line)
        if header:
            body = computations.setdefault(header.group(1), [])
        elif body is not None and (m := _INSTRUCTION.match(line)):
            body.append((line.lstrip().startswith("ROOT "), *m.groups()))
    return computations


def _executed(text):
    """``(name, opcode, result as written)`` of every instruction of a
    compiled program's text that runs as itself: the entry's and the loop
    bodies'. What stands inside a fusion is part of it and writes nothing out."""
    computations = _computations(text)
    fused = {
        callee for body in computations.values() for _, _, _, opcode, rest in body if opcode == "fusion"
        for callee in re.findall(r"calls=%([\w.$-]+)", rest)
    }
    return [
        (name, opcode, result) for computation, body in computations.items() if computation not in fused
        for _, name, result, opcode, _ in body
    ]


def _mixer_matrices_written_out(text, matrices, but=()):
    """The instructions that WRITE a mixer's matrix: a result whose two minor
    dimensions are one of ``matrices`` (a layer's, a period's ``[1, count,
    ...]`` or the stack's, in any layout), outside parameters, plumbing,
    kernels and XLA's own asynchronous prefetches into fast memory
    (``slice-start`` / ``copy-start`` and their ``-done``, which keep the
    layout). A projection reads its matrix where the parameters lie, by a
    slice fused into the matmul; a materialised period slice or a copy to the
    layout a folded head split asks for shows here (PERF.md, PR 46: 170 MB
    three times a trip of Laguna's scan, 101 MB seven times a step in MiMo, a
    layer's wq / wk / wv once a layer in Mistral). ``but``: whole shapes that
    are something else in this program (its packed activations)."""
    found = {}
    for name, opcode, result in _executed(text):
        if opcode in _PLUMBING or opcode == "custom-call" or opcode.endswith(("-start", "-done")):
            continue
        for dims in re.findall(r"bf16\[([\d,]+)\]", result):
            shape = tuple(int(d) for d in dims.split(","))
            if shape[-2:] in matrices and shape not in but:
                found[name] = f"{opcode} {result}"
    return found


def _attention_matrices(cfg, kinds=(None,)):
    """``[H, NH D]``, ``[H, NKV D]``, ``[H, NKV Dv]`` and ``[NH Dv, H]`` of a
    config's attention layers, a uniform model's or each of ``kinds``'s, and
    each the other way round (a re-laid matrix may be written as either)."""
    H, D, Dv = cfg.hidden_size, cfg.head_dim, getattr(cfg, "v_head_dim", None) or cfg.head_dim
    out = set()
    for kind in kinds:
        NH, NKV = (cfg.num_heads, cfg.num_kv_heads) if kind is None else (cfg.heads_of(kind), cfg.kv_heads_of(kind))
        out |= {(H, NH * D), (H, NKV * D), (H, NKV * Dv), (NH * Dv, H)}
    return out | {(b, a) for a, b in out}


def _slab_sized_fills(text, slots, lanes=512):
    """The instructions of a wide program that cost what its ``slots`` (rows
    x width: 64 x 128) cost and not what its live tokens do: a ``broadcast``
    or a ``copy`` that runs as itself and writes ``[slots, >= lanes]`` whole
    (a layer's token buffer zero-filled, or moved to another layout: 134-403
    MB a layer), and a ``scatter`` into one, fused or not, which goes row by
    row (PERF.md section 6, PR 47: three such ops a latent layer were 10.4%
    of GLM-4.7-Flash's cell). ``hybrid_decode.unfilled`` buffers are
    ``custom-call``s that allocate and write nothing; a tile or a chunk row
    goes in by ``dynamic-update-slice``, in place."""
    slab = re.compile(rf"(?:bf16|f32)\[{slots},(\d+)\]")
    wide = lambda result: any(int(n) >= lanes for n in slab.findall(result))
    found = {name: f"{opcode} {result}" for name, opcode, result in _executed(text) if opcode in ("broadcast", "copy") and wide(result)}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and m.group(3) == "scatter" and wide(m.group(2)):
            found[m.group(1)] = f"scatter {m.group(2)}"
    return found


# (kernel calls, temporaries in bytes) of the hybrid configurations' NARROW programs at PR 46: PR 47 rewrote the
# wide window's buffers in lines the two programs share, and the narrow text kept every instruction (compared
# with names, metadata and the kernels' payloads taken out). A PR that means to change a narrow program says so here.
# PR 50 meant to change Solar's: ``kda_decode`` takes a linear layer's rows from the projections on, so the 134 MB
# ``f32[64,8,4,128,8]`` operand stack, the gathered tails and the convolution's float32 copies are gone from the
# temporaries (224,022,016 before), with the same 16 kernel calls. The three others trace no line of a linear layer.
# PR 51 meant to change MiMo's and Laguna's: their window layers' one-token rows go a block of 8 a grid step of the
# ragged kernel (``_ragged_block_kernel``), the same 25 and 17 kernel calls (6,880,256 and 5,070,848 bytes before).
# PR 64 meant to change all four: a routed layer's plan is the ``moe_route_plan`` kernel (``moe/route_plan.py``: one
# more call a routed layer body: 16, 25, 5 and 17 before) and the sorts' pairs and the token-major copy of the
# gathered rows are gone from the temporaries (5,343,232 / 6,279,680 / 2,549,248 / 4,812,800 before).
# PR 65 meant to change all four: the routed layers' two ways between token order and expert order are the
# ``moe_dispatch_rows`` and ``moe_combine_rows`` calls (``moe/live_rows.py``: two more calls a routed layer body: 20, 31, 6
# and 21 before) where two gathers, a mask and a sum of k slabs stood (4,667,904 / 5,338,112 / 2,483,200 / 4,328,960 bytes
# of temporaries before; Laguna's grow from 4.3 to 11.3 MB, which the test beside it bounds at 50).
NARROW_PROGRAMS = {"solar": (28, 3_812_352), "mimo": (43, 4_970_496), "glm": (8, 2_510_336), "laguna": (29, 11_251_200)}


def _narrow_program(text, memory):
    return text.count('custom_call_target="tpu_custom_call"'), memory.temp_size_in_bytes


_KDA_CALL = re.compile(r"%kda_decode[\w.-]* = \((.*?)\) custom-call\(.*? operand_layout_constraints=\{(.*?)\}, output_to_operand_aliasing=\{(.*?)\}, frontend")


def _assert_kda_decode_takes_rows_as_they_lie(text, tail_pool: str):
    """Every ``kda_decode`` call of a compiled step: no operand and no result
    with fewer than 128 numbers on its minor axis (the parent's kernel took
    decay, k, k b and q as ``f32[rows, groups, 4, 128, 8]``, 8 heads on the
    lanes: 16 times its numbers in HBM), no such array anywhere in the text,
    and the convolution tails' pool ``tail_pool`` an operand AND a result,
    aliased like the state and in the layout it arrived in, so the gather
    and the scatter of the tails are gone. Returns the calls."""
    calls = _KDA_CALL.findall(text)
    shape = re.compile(r"\w+\[([\d,]*)\]")
    for results, operands, aliasing in calls:
        for dims in shape.findall(results) + shape.findall(operands):
            assert int(dims.split(",")[-1]) >= 128, (dims, operands)
        assert tail_pool in operands and tail_pool in results, (tail_pool, operands)
        assert aliasing == "{1}: (4, {}), {2}: (5, {})", aliasing  # the state pool and the tail pool, in to out
    assert not re.search(r"f32\[[\d,]*128,8\]", text)
    # no copy of the tail pool to another layout (the parent's narrow text: ``copy bf16[3,65,3,24576]`` at the entry and
    # at the exit, 28.8 MB each, around the gathers and scatters). Whether the compiler keeps a whole pool in fast
    # memory through a scan (``copy-start`` / ``slice-start`` into ``S(1)``) is its memory-space assignment's choice,
    # program by program, and no layout: PERF.md section 6, PR 50
    moved = re.findall(rf"= {re.escape(tail_pool)}\S* copy\(", text)
    assert not moved, moved
    return calls


def _compiled_mistral_step(v5e, monkeypatch, width):
    """``build_ragged_step`` at the Mistral cells' shapes (16 rows, 609 pages
    of 64), compiled for the described chip: (config, the cell's ``paged_kv``,
    the abstract params and pool it was lowered with, the executable)."""
    monkeypatch.setattr(
        sys.modules["deepspeed_tpu.ops.transformer.decode_attention"], "on_tpu", lambda: True
    )
    conf = json.loads(_MISTRAL_CELL.read_text())
    paged = conf["engine"]["init_inference"]["paged_kv"]
    cfg = TransformerConfig(**{**conf["model"]["kwargs"], "max_seq_len": paged["max_seq_len"]})
    rows, page = paged["max_slots"], paged["page_size"]
    maxp = paged["max_seq_len"] // page

    def on_v5e(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.eval_shape(
        lambda: TransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), I32))
    )
    params = jax.tree_util.tree_map(lambda a: on_v5e(a.shape, BF16), params)
    pool = on_v5e((cfg.num_layers, rows * maxp + 1, cfg.num_kv_heads, page, cfg.head_dim), BF16)
    step = decode.build_ragged_step(cfg, rows, width, page, attn_impl="pallas")
    compiled = step.lower(
        params, on_v5e((rows, width), I32), pool, pool, on_v5e((rows, maxp), I32),
        on_v5e((rows,), I32), on_v5e((rows,), I32),
    ).compile()
    return cfg, paged, params, pool, compiled


@pytest.mark.parametrize("width", [1, 128])
def test_ragged_step_keeps_the_pool_in_one_buffer(v5e, monkeypatch, width):
    """``build_ragged_step`` at the Mistral cells' shapes (16 rows, 609 pages
    of 64, the narrow and the mixed width): the donated pools are aliased to
    the outputs, nothing but parameters, tuple plumbing, bitcasts and the
    ``ragged_paged_attention`` kernel has a result of the pool's shape, of
    one layer's slice of it or of any other view of that many pages, and the
    program's temporaries are smaller than one pool. The layer loop carries the pools and the fused kernel is the
    only operation on them; a slice, a scatter or a layout copy of the pool
    would show here."""
    cfg, paged, params, pool, compiled = _compiled_mistral_step(v5e, monkeypatch, width)
    page, n_pages = paged["page_size"], pool.shape[1]
    assert (n_pages, width in (1, paged["prefill_chunk"])) == (609, True)
    text = compiled.as_text()

    first_pool = len(jax.tree_util.tree_leaves(params)) + 1  # after the params and the tokens
    assert {first_pool, first_pool + 1} <= parse_input_output_aliases(text)
    # [..., NKV, P, D] with a layer's pages or more in front: the stack, a layer of it, a view
    pages_of = re.compile(rf"\[([\d,]+),{cfg.num_kv_heads},{page},{cfg.head_dim}\]")
    held = {
        instr.name: instr.op
        for computation in parse_computations(text)[0].values() for instr in computation
        if any(
            np.prod([int(d) for d in dims.split(",")]) >= n_pages
            for dims in pages_of.findall(instr.shape_str)
        )
    }
    assert any(name.startswith("ragged_paged_attention") for name in held), held
    strangers = {
        name: opcode for name, opcode in held.items()
        if opcode not in _PLUMBING
        and not (opcode == "custom-call" and name.startswith("ragged_paged_attention"))
    }
    assert not strangers, f"pool-shaped results outside the kernel: {strangers}"
    pool_bytes = int(np.prod(pool.shape)) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes
    # nor is a layer's wq, wk, wv or wo written out on its way to its matmul (once a layer in the narrow program, before PR 46)
    assert not _mixer_matrices_written_out(text, _attention_matrices(cfg))


def test_mixed_step_computes_token_tiles_not_the_slab(v5e, monkeypatch):
    """``paged_ragged_r16_w128`` at the Mistral cells' shapes computes its
    token-wise work in tiles of ``decode.token_tile`` packed tokens: the
    compiled program holds one ``tpu_custom_call`` (the layer loop's one
    ragged kernel), no instruction whose result is the slab's MLP
    intermediate (``[16,128,14336]`` or ``[2048,14336]``) but the tile's, and
    its temporaries are smaller than one weight matrix: neither a layer's
    slices nor a stack in another layout are copied on their way into the tile
    loops (both were, in this PR's first drafts: 470 MB and 768 MB a layer)."""
    cfg, paged, _, _, compiled = _compiled_mistral_step(v5e, monkeypatch, 128)
    rows, width, inner = paged["max_slots"], paged["prefill_chunk"], cfg.intermediate_size
    tile = decode.token_tile(cfg)
    assert (width, rows * width > tile) == (128, True)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"[{tile},{inner}]" in text, "the MLP is not computed a tile at a time"
    for slab in (f"[{rows},{width},{inner}]", f"[{rows * width},{inner}]"):
        assert slab not in text, f"an instruction of the whole slab's shape {slab}"
    assert compiled.memory_analysis().temp_size_in_bytes < cfg.hidden_size * inner * 2


def _kernel_operands(text, kernel: str):
    """The element types of a named Mosaic call's operands in a compiled program's text."""
    shape_of = dict(re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = (\S+)", text, flags=re.M))
    (operands,) = re.findall(rf"%{kernel}[\w.-]* = .*? custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\"", text)
    return [shape_of[o].split("[")[0] for o in re.findall(r"%([\w.-]+)", operands)]


@pytest.mark.parametrize("rows", [64, 512], ids=["narrow", "token_tile"])
def test_a_routed_layers_scope_is_three_calls_and_no_gather_of_every_row(v5e, monkeypatch, rows):
    """``routed_ffn`` as Laguna's serving step calls it (256 experts, 10 a
    token, 16 held, hidden 3,072; a narrow step's 64 rows and a mixed step's
    tile of 512), compiled for a v5e: the ``moe_route`` scope holds the plan's
    call, ``moe_dispatch_rows`` and ``moe_combine_rows`` (``moe/live_rows.py``)
    and no gather at all, and nowhere in the program is there an array of the
    gather form's shapes, ``f32[k, S, H]`` or a gathered ``[S k, H]``. Each new
    call opens with ONE ``s32`` operand (the ragged attention kernel's readers
    count a call that opens with three)."""
    from deepspeed_tpu.moe import routed_ffn

    for module in ("deepspeed_tpu.moe.route_plan", "deepspeed_tpu.moe.grouped_matmul"):
        monkeypatch.setattr(sys.modules[module], "on_tpu", lambda: True)
    width, k, held, hidden, inner = 256, 10, (0, 16), 3072, 1024

    def on_v5e(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def layer(experts, tokens, logits, live):
        return routed_ffn.routed_ffn(experts, tokens, logits, k=k, activation="swiglu", norm_topk_prob=True, live=live, held=held)[:2]

    experts = {"w_gate": on_v5e((16, hidden, inner), BF16), "w_up": on_v5e((16, hidden, inner), BF16), "w_out": on_v5e((16, inner, hidden), BF16)}
    text = jax.jit(layer).lower(experts, on_v5e((rows, hidden), BF16), on_v5e((rows, width), jnp.float32), on_v5e((rows,), bool)).compile().as_text()
    in_scope = [line for line in text.splitlines() if "/moe_route/" in line]
    calls = re.findall(r"moe_route/(\w+)/pallas_call", "\n".join(line for line in in_scope if 'custom_call_target="tpu_custom_call"' in line))
    assert calls == ["moe_route_plan", "moe_dispatch_rows", "moe_combine_rows"], calls
    assert not [line for line in in_scope if re.search(r" gather\(", line)], "a gather in the moe_route scope"
    assert f"f32[{k},{rows},{hidden}]" not in text
    assert not re.findall(rf"= (?:bf16|f32)\[{rows * k},{hidden}\]\S* gather\(", text)
    assert _kernel_operands(text, "moe_dispatch_rows") == ["s32", "bf16", "s32"]
    assert _kernel_operands(text, "moe_combine_rows") == ["s32", "f32", "f32", "s32"]


@pytest.mark.parametrize(
    "rows, k, groups, hidden, dtype",
    [(8, 8, 64, 2048, BF16), (24, 4, 8, 2048, BF16), (520, 4, 8, 2048, BF16), (1000, 8, 64, 2048, BF16), (512, 8, 16, 4096, BF16), (512, 4, 8, 2048, jnp.float32)],
    ids=["8_olmoe", "24_lfm2", "520_lfm2", "1000_olmoe", "mimo_token_tile", "float32_token_tile"],
)
def test_the_live_rows_calls_compile_at_the_sizes_the_plan_admits(v5e, rows, k, groups, hidden, dtype):
    """``moe_dispatch_rows`` and ``moe_combine_rows`` pass Mosaic wherever
    ``moe_route_plan`` does: the fewest tokens, a size that fills no block of
    128 rows, a ragged last token block, the widest token tile (512 x 4,096:
    four column slabs) and float32 tokens (three bfloat16 parts), with no
    ``vmem_limit_bytes`` asked for."""
    from deepspeed_tpu.moe import live_rows
    from deepspeed_tpu.moe.route_plan import RoutePlan, kernel_fits

    assert kernel_fits(rows, 64, k)

    def on_v5e(shape, kind):
        return jax.ShapeDtypeStruct(shape, kind, sharding=v5e)

    per_choice, per_row = (k, rows), (rows * k,)
    plan = RoutePlan(on_v5e(per_choice, jnp.float32), *(on_v5e(per_choice, I32),) * 3, on_v5e((groups,), I32), on_v5e(per_row, I32), on_v5e(per_row, I32), on_v5e(per_row, jnp.float32))

    def both(tokens, out_rows, plan):
        return live_rows.dispatch(tokens, plan, impl="live_rows"), live_rows.combine(out_rows, plan, dtype, masked=True, impl="live_rows")

    text = jax.jit(both).lower(on_v5e((rows, hidden), dtype), on_v5e((rows * k, hidden), jnp.float32), plan).compile().as_text()
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(name for line in kernels for name in re.findall(r"/(moe_\w+_rows)/pallas_call", line)) == ["moe_combine_rows", "moe_dispatch_rows"]
    assert not any("vmem_limit_bytes" in line or '"scoped_memory_configs":[{' in line for line in kernels)


def test_a_routed_layer_compiles_under_a_highest_default_precision(v5e, monkeypatch):
    """The routed models' ``--isolated`` logits tools run float32 with
    ``jax_default_matmul_precision`` ``highest``; a kernel's product of two
    bfloat16 arrays that leaves its precision to that default is refused by
    Mosaic there ("Bad lhs type": ``laguna_logits_check.py --isolated`` on the
    chip, PR 65, in the plan's kernel as PR 64 left it). The three calls of a
    routed layer say ``DEFAULT`` themselves (``route_plan.bf16_dot``)."""
    from deepspeed_tpu.moe import routed_ffn

    for module in ("deepspeed_tpu.moe.route_plan", "deepspeed_tpu.moe.grouped_matmul"):
        monkeypatch.setattr(sys.modules[module], "on_tpu", lambda: True)

    def on_v5e(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def layer(experts, tokens, logits, live):
        return routed_ffn.routed_ffn(experts, tokens, logits, k=4, activation="swiglu", norm_topk_prob=True, live=live, held=(0, 8))[:2]

    experts = {"w_gate": on_v5e((8, 256, 128)), "w_up": on_v5e((8, 256, 128)), "w_out": on_v5e((8, 128, 256))}
    with jax.default_matmul_precision("highest"):
        text = jax.jit(layer).lower(experts, on_v5e((64, 256)), on_v5e((64, 64)), on_v5e((64,), bool)).compile().as_text()
    assert sorted(re.findall(r"moe_route/(\w+)/pallas_call", text))[:1] == ["moe_combine_rows"] and "moe_route_plan" in text and "moe_dispatch_rows" in text


def _sparse_decode_layer(dtype, table_pages=256):
    """``sparse_latent_attention``'s T = 1 call at the dots3 cell's shape (32 rows, 128 heads, entries of 576 in pages
    of 640 lanes, a table of 256 pages of 64, ``index_topk`` 2,048, three layers of pages) and its abstract operands."""
    from deepspeed_tpu.ops.transformer.sparse_latent_attention import sparse_latent_attention

    R, NH, D, lanes, IH, ID, NP, P, MAXP = 32, 128, 576, 640, 64, 128, 2048, 64, table_pages

    def layer(q, qi, wi, new, new_index, latent, index, table, kv_lens, q_lens):
        return sparse_latent_attention(q, qi, wi, new, new_index, latent, index, jnp.int32(1), table, kv_lens, q_lens, topk=2048, value_lanes=512, scale=192**-0.5)

    shapes = [
        ((R, 1, NH, D), dtype), ((R, 1, IH, ID), dtype), ((R, 1, IH), jnp.float32), ((R, 1, D), dtype), ((R, 1, ID), dtype),
        ((3, NP, P, lanes), dtype), ((3, NP, P, ID), dtype), ((R, MAXP), I32), ((R,), I32), ((R,), I32),
    ]
    return layer, shapes


def test_a_sparse_layers_decode_row_walks_its_pages_in_one_call_and_gathers_no_chosen_entry(v5e, monkeypatch):
    """The sparse layer's T = 1 call at the cell's shape, compiled for a v5e:
    the table's 16,384 positions are 8 x ``index_topk``, so the form is the
    walk; the ``sparse_attend`` scope holds ONE Mosaic call,
    ``sparse_latent_attention``, and no gather; nowhere in the program is
    there the gather form's ``[65536, 640]`` or ``[32, 2048, 640]`` buffer;
    the selection's scope is there and holds no sort."""
    from deepspeed_tpu.ops.transformer import sparse_latent_attention as sla

    monkeypatch.setattr(sla, "on_tpu", lambda: True)
    assert sla.decode_form(256 * 64, 2048)["form"] == "walk"
    layer, shapes = _sparse_decode_layer(BF16)
    text = jax.jit(layer).lower(*(jax.ShapeDtypeStruct(shape, dtype, sharding=v5e) for shape, dtype in shapes)).compile().as_text()
    in_scope = [line for line in text.splitlines() if "/sparse_attend/" in line]
    calls = [line for line in in_scope if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and "sparse_attend/sparse_latent_attention/pallas_call" in calls[0], calls
    assert not [line for line in in_scope if re.search(r" gather\(", line)], "a gather in the sparse_attend scope"
    assert "[65536,640]" not in text and "[32,2048,640]" not in text
    select = [line for line in text.splitlines() if "/sparse_select/" in line]
    assert select and not [line for line in select if re.search(r" sort\(", line)]
    assert [line for line in text.splitlines() if "/sparse_index_scores/" in line]


@pytest.mark.parametrize("pages, form", [(256, "walk"), (384, "gather")], ids=["walk", "gather"])
def test_a_sparse_layers_decode_row_compiles_under_a_highest_default_precision(v5e, monkeypatch, pages, form):
    """The float32 ``--isolated`` logits run (``jax_default_matmul_precision``
    ``highest``, a float32 pool): the kernel compiles there (its float32
    products take the process's precision, its bfloat16 ones would name
    ``DEFAULT``), so the form rule asks nothing of the pool's dtype; a table
    past ``WALK_MAX_MULTIPLE x index_topk`` positions takes the gather form,
    which holds no Mosaic call."""
    from deepspeed_tpu.ops.transformer import sparse_latent_attention as sla

    monkeypatch.setattr(sla, "on_tpu", lambda: True)
    layer, shapes = _sparse_decode_layer(jnp.float32, pages)
    assert sla.decode_form(pages * 64, 2048)["form"] == form
    with jax.default_matmul_precision("highest"):
        lowered = jax.jit(layer).lower(*(jax.ShapeDtypeStruct(shape, dtype, sharding=v5e) for shape, dtype in shapes))
        if form == "gather":  # plain XLA, as the parent's: nothing of Mosaic's to compile (its sort alone compiles for half a minute)
            assert "tpu_custom_call" not in lowered.as_text()
            return
        text = lowered.compile().as_text()
    calls = [line for line in text.splitlines() if "/sparse_attend/" in line and 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, calls


_OLMOE_CELL = pathlib.Path(__file__).parents[3] / "benchmark/configs/olmoe-1b-7b-0125-l12.json"


@pytest.mark.parametrize("width", [1, 128])
def test_olmoe_ragged_step_fits_and_names_its_kernels(v5e, monkeypatch, width):
    """``build_ragged_step`` at the OLMoE cell's shapes (12 layers, 64 experts
    of 1,024, top-8, 16 rows, 385 pages of 64): it compiles for a v5e, the
    pools stay aliased, weights + pools + temporaries fit the chip, and the
    expert matmuls are the ``moe_grouped_matmul`` kernel. Only the ragged
    attention kernel may open with three ``s32`` operands: the benchmark's
    accepted readers tell it by that signature
    (``benchmark/kernels/ragged_paged_attention.py``), and ``jax.lax.ragged_dot``'s
    own lowering, which opens with five, would be counted as one."""
    for module in ("deepspeed_tpu.ops.transformer.decode_attention", "deepspeed_tpu.moe.grouped_matmul", "deepspeed_tpu.moe.route_plan"):
        monkeypatch.setattr(sys.modules[module], "on_tpu", lambda: True)
    conf = json.loads(_OLMOE_CELL.read_text())
    paged = conf["engine"]["init_inference"]["paged_kv"]
    cfg = MoETransformerConfig(**conf["model"]["kwargs"])
    rows, page = paged["max_slots"], paged["page_size"]
    maxp = paged["max_seq_len"] // page
    n_pages = rows * maxp + 1

    def on_v5e(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.eval_shape(lambda: MoETransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), I32)))
    params = jax.tree_util.tree_map(lambda a: on_v5e(a.shape, BF16), params)
    pool = on_v5e((cfg.num_layers, n_pages, cfg.num_kv_heads, page, cfg.head_dim), BF16)
    step = decode.build_ragged_step(cfg, rows, width, page, attn_impl="pallas")
    compiled = step.lower(
        params, on_v5e((rows, width), I32), pool, pool, on_v5e((rows, maxp), I32),
        on_v5e((rows,), I32), on_v5e((rows,), I32),
    ).compile()
    text = compiled.as_text()
    first_pool = len(jax.tree_util.tree_leaves(params)) + 1
    assert {first_pool, first_pool + 1} <= parse_input_output_aliases(text)
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.5e9
    # the compiled text names a call's operands without their types: look them up
    shape_of = dict(re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = (\S+)", text, flags=re.M))
    kernels = dict(re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = .*? custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\"", text, flags=re.M))
    opens_with_three_s32 = [
        name for name, operands in kernels.items()
        if all(shape_of[o].startswith("s32[") for o in re.findall(r"%([\w.-]+)", operands)[:3])
    ]
    assert len(opens_with_three_s32) == 1 and opens_with_three_s32[0].startswith("ragged_paged_attention"), kernels
    assert sum(name.startswith("moe_grouped_matmul") for name in kernels) == 3, kernels
    # the expert stacks reach the kernel as they lie (every layer's, seen as one stack), not as a layer's copy:
    # the kernel's weight operand has L x E matrices and the temporaries hold no 268 MB matrix stack of one layer
    for name, operands in kernels.items():
        if name.startswith("moe_grouped_matmul"):
            weights = shape_of[re.findall(r"%([\w.-]+)", operands)[2]]
            assert weights.startswith(f"bf16[{cfg.num_layers * cfg.num_experts},"), (name, weights)
    assert memory.temp_size_in_bytes < 0.5e9
    # the step's one result: 16 rows of width + 1, and the three rows of routing counts
    assert re.search(rf"s32\[{rows + decode.MOE_STAT_ROWS},{width + 1}\]", text)


@pytest.mark.parametrize("donated", [True, False], ids=["pools_donated", "pools_returned_undonated"])
def test_kda_decode_alone_compiles_whoever_owns_its_pools(v5e, donated):
    """``kda_decode`` at Kimi's shapes as a program's only operation, its two
    pools donated (the serving step's way) and not (the logits tools' way,
    whose ``hybrid_forward`` returns the pools it was lent). The tail pool
    pinned to HBM through the result's memory space compiles inside the
    serving step and nowhere else: XLA refuses the first of these programs
    and aborts the process in the second."""
    from deepspeed_tpu.ops.transformer.linear_attention import kda_decode

    R, H, D, K, L = 64, 32, 128, 4, 10
    on = lambda shape, dtype=BF16: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
    program = jax.jit(functools.partial(kda_decode, impl="pallas"), donate_argnums=(4, 5) if donated else ())
    compiled = program.lower(
        on((R, 3, H, D)), on((R, H, D), jnp.float32), on((R, H), jnp.float32), on((K, 3, H, D)),
        on((L, R + 1, H, D, D), jnp.float32), on((L, R + 1, K - 1, 3, H, D)), on((), I32), on((R,), I32), on((R,), bool), on((R,), bool),
    ).compile()
    assert len(_KDA_CALL.findall(compiled.as_text())) == 1  # (a pool that is not donated is copied first: the caller's choice)


_SOLAR_CELL = pathlib.Path(__file__).parents[3] / "benchmark/configs/solar-open2-250b-l4-ep8.json"


@pytest.mark.parametrize("width", [1, 128])
def test_solar_open2_ragged_step_fits_and_keeps_its_four_pools_in_place(v5e, monkeypatch, width):
    """``build_ragged_step`` at the Solar-Open2 cell's shapes (one period:
    a gated GQA layer of 64 query heads over 8, three delta-rule layers of 64
    heads of 128, 40 held experts of 1,280 of a router over 320, 64 rows,
    1,537 pages of 64, a state store of 65 slots): it compiles for a v5e, the
    pages AND the state store stay aliased in to out, weights + pools +
    temporaries fit the chip, and the recurrence of one-token rows is the
    ``kda_decode`` kernel, which must not open with three ``s32`` operands
    (the ragged attention kernel's signature for the accepted readers), takes
    its rows with 128 channels on the lanes and has the tail pool aliased in
    to out beside the state."""
    from deepspeed_tpu.inference import hybrid_decode
    from deepspeed_tpu.inference.kv_pool import StateStore
    from deepspeed_tpu.models.hybrid_moe import HybridMoEConfig, HybridMoETransformerLM

    for module in ("deepspeed_tpu.ops.transformer.decode_attention", "deepspeed_tpu.moe.grouped_matmul", "deepspeed_tpu.moe.route_plan",
                   "deepspeed_tpu.ops.transformer.linear_attention"):
        monkeypatch.setattr(sys.modules[module], "on_tpu", lambda: True)
    conf = json.loads(_SOLAR_CELL.read_text())
    paged = conf["engine"]["init_inference"]["paged_kv"]
    cfg = HybridMoEConfig(**conf["model"]["kwargs"])
    rows, page = paged["max_slots"], paged["page_size"]
    maxp = paged["max_seq_len"] // page

    def on_v5e(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.eval_shape(lambda: HybridMoETransformerLM(cfg).init(jax.random.PRNGKey(0), None))
    params = jax.tree_util.tree_map(lambda a: on_v5e(a.shape, BF16), params)
    pool = on_v5e((cfg.layers_of("softmax"), rows * maxp + 1, cfg.num_kv_heads, page, cfg.head_dim), BF16)
    shapes = hybrid_decode.state_shapes(cfg, rows)
    assert shapes.state == (3, 65, 64, 128, 128)
    step = decode.build_ragged_step(cfg, rows, width, page, attn_impl="pallas")
    store = StateStore(on_v5e(shapes.state, jnp.float32), on_v5e(shapes.conv, BF16))
    compiled = step.lower(
        params, on_v5e((rows, width), I32), pool, pool, store,
        on_v5e((rows, maxp), I32), on_v5e((rows,), I32), on_v5e((rows,), I32), on_v5e((rows,), I32),
    ).compile()
    text = compiled.as_text()
    first_pool = len(jax.tree_util.tree_leaves(params)) + 1
    assert {first_pool + i for i in range(4)} <= parse_input_output_aliases(text)
    memory = compiled.memory_analysis()
    print(f"solar w{width}: arguments {memory.argument_size_in_bytes / 1e9:.2f} GB, temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB")
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.5e9
    shape_of = dict(re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = (\S+)", text, flags=re.M))
    kernels = dict(re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = .*? custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\"", text, flags=re.M))
    opens_with_three_s32 = [
        name for name, operands in kernels.items()
        if all(shape_of[o].startswith("s32[") for o in re.findall(r"%([\w.-]+)", operands)[:3])
    ]
    # the one softmax layer's ragged kernel: a wide window calls it at width 1 for its one-token rows and at
    # the window's width, a few rows a trip, for its chunk rows (hybrid_decode.wide_attention)
    assert len(opens_with_three_s32) == (1 if width == 1 else 2), opens_with_three_s32
    assert any(name.startswith("kda_decode") for name in kernels), sorted(kernels)
    assert sum(name.startswith("moe_grouped_matmul") for name in kernels) >= 3
    assert len(_assert_kda_decode_takes_rows_as_they_lie(text, "bf16[3,65,3,3,64,128]")) == 3
    if width == 1:
        assert _narrow_program(text, memory) == NARROW_PROGRAMS["solar"]
        assert memory.temp_size_in_bytes < 0.10e9
    else:
        # nothing fills, copies or scatters into a 64 x 128-slot token buffer (a linear layer's qkv [8192, 24576], its
        # log_a f32 [8192, 8192] and its [64, 128, 8192] output, the full layer's q and out [8192, 8192], before)
        assert not _slab_sized_fills(text, rows * width)
        assert memory.temp_size_in_bytes < 1.15e9  # 1.108 GB (1.672 before the kernel took the rows' convolution): the chunk rows' kda_chunked


_MIMO_CELL = pathlib.Path(__file__).parents[3] / "benchmark/configs/mimo-v2.5-l7-ep16.json"


@pytest.mark.parametrize("width", [1, 128])
def test_mimo_v2_ragged_step_fits_and_every_attention_layer_walks_live_pages(v5e, monkeypatch, width):
    """``build_ragged_step`` at the MiMo-V2.5 cell's shapes (a leading dense
    layer and one period: two full layers of 64 query heads over 4 KV heads,
    five window layers over 8 with sinks, keys of 192 stored at 256 lanes,
    values of 128, 16 held experts of 2,048 of a router over 256, 64 rows,
    4,097 pages of 64, rings of 4 pages a slot): it compiles for a v5e, the
    pages AND the rings stay aliased in to out, weights + pools + temporaries
    fit the chip, and all seven attention calls are the live-pages kernel (three
    ``s32`` operands in front: table, lengths, q lengths), none the grid
    fallback, which a 192-wide page would take."""
    from deepspeed_tpu.inference import hybrid_decode
    from deepspeed_tpu.inference.kv_pool import StateStore, key_lanes, window_ring_pages
    from deepspeed_tpu.models.hybrid_moe import HybridMoEConfig, HybridMoETransformerLM
    from deepspeed_tpu.ops.transformer import decode_attention

    for module in ("deepspeed_tpu.ops.transformer.decode_attention", "deepspeed_tpu.moe.grouped_matmul", "deepspeed_tpu.moe.route_plan"):
        monkeypatch.setattr(sys.modules[module], "on_tpu", lambda: True)
    monkeypatch.setattr(decode_attention, "_ragged_by_grid", None)  # reaching it would raise
    conf = json.loads(_MIMO_CELL.read_text())
    paged = conf["engine"]["init_inference"]["paged_kv"]
    cfg = HybridMoEConfig(**conf["model"]["kwargs"])
    rows, page = paged["max_slots"], paged["page_size"]
    maxp = paged["max_seq_len"] // page
    ring = window_ring_pages(cfg.window, page, paged["prefill_chunk"])
    assert ring == 4 and key_lanes(cfg.head_dim) == 256

    def on_v5e(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.eval_shape(lambda: HybridMoETransformerLM(cfg).init(jax.random.PRNGKey(0), None))
    params = jax.tree_util.tree_map(lambda a: on_v5e(a.shape, BF16), params)
    k_pool = on_v5e((2, rows * maxp + 1, 4, page, 256), BF16)
    v_pool = on_v5e((2, rows * maxp + 1, 4, page, 128), BF16)
    shapes = hybrid_decode.state_shapes(cfg, rows)
    wk, wv = hybrid_decode.window_shapes(cfg, rows, page, ring)
    assert wk == (5, 257, 8, 64, 256) and wv == (5, 257, 8, 64, 128)
    store = StateStore(on_v5e(shapes.state, jnp.float32), on_v5e(shapes.conv, BF16), on_v5e(wk, BF16), on_v5e(wv, BF16))
    step = decode.build_ragged_step(cfg, rows, width, page, attn_impl="pallas")
    compiled = step.lower(
        params, on_v5e((rows, width), I32), k_pool, v_pool, store,
        on_v5e((rows, maxp), I32), on_v5e((rows,), I32), on_v5e((rows,), I32), on_v5e((rows,), I32),
    ).compile()
    text = compiled.as_text()
    first_pool = len(jax.tree_util.tree_leaves(params)) + 1
    aliased = parse_input_output_aliases(text)
    assert {first_pool, first_pool + 1, first_pool + 4, first_pool + 5} <= aliased, aliased  # pages and rings (state, conv: empty)
    memory = compiled.memory_analysis()
    print(f"mimo w{width}: arguments {memory.argument_size_in_bytes / 1e9:.2f} GB, temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB")
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.5e9
    shape_of = dict(re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = (\S+)", text, flags=re.M))
    kernels = dict(re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = .*? custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\"", text, flags=re.M))
    opens_with_three_s32 = [
        name for name, operands in kernels.items()
        if all(shape_of[o].startswith("s32[") for o in re.findall(r"%([\w.-]+)", operands)[:3])
    ]
    assert len(opens_with_three_s32) == (7 if width == 1 else 14), opens_with_three_s32  # a wide window: two calls a layer
    # the 64 x 128 window is never laid out: no operand or result of its size (64 x 128 x 64 heads x 256 lanes)
    assert not re.search(r"bf16\[64,128,(12288|16384)\]|bf16\[64,[48],128,(8|16),256\]", text)
    assert sum(name.startswith("moe_grouped_matmul") for name in kernels) >= 3
    # every projection reads its matrix where the parameters lie: no period's slice (five window layers' [12288, 4096],
    # 101 MB seven times a narrow step before PR 46) and no copy to another layout is written out
    # (the wide program's packed tokens, 64 x 128 of 4096, happen to be as many as wo's rows, and a tile of them as wv's columns)
    activations = {(rows * width, cfg.hidden_size), (decode.token_tile(cfg), cfg.hidden_size)}
    assert not _mixer_matrices_written_out(text, _attention_matrices(cfg, ("softmax", "window")), but=activations)
    if width == 1:
        assert memory.temp_size_in_bytes < 0.05e9  # 0.411 GB before
        assert _narrow_program(text, memory) == NARROW_PROGRAMS["mimo"]
    else:
        assert not _slab_sized_fills(text, rows * width)  # 7 x q [8192, 12288], 7 x out [8192, 8192] and 14 scatters into one, before
        assert memory.temp_size_in_bytes < 0.63e9  # 0.647 GB before


_GLM_CELL = pathlib.Path(__file__).parents[3] / "benchmark/configs/glm-4.7-flash-l16-ep8.json"


@pytest.mark.parametrize("width", [1, 128])
def test_glm47_flash_ragged_step_fits_and_keeps_one_latent_pool_in_place(v5e, monkeypatch, width):
    """``build_ragged_step`` at the GLM-4.7-Flash cell's shapes (a leading
    dense layer and 15 routed ones, every one a latent layer of 20 heads over
    an entry of 576 stored at 640 lanes, 8 held experts of 1,536 of a router
    over 64 and a shared one, 64 rows, 4,097 pages of 64): it compiles for a
    v5e, the ONE latent pool stays aliased in to out, weights + pool +
    temporaries fit the chip, the latent kernel is called once a layer of the
    program text (twice in the wide program: the one-token rows, the chunk
    rows) and the accepted ragged kernel not at all, and the 64 x 128 window
    is never laid out."""
    from deepspeed_tpu.inference import hybrid_decode
    from deepspeed_tpu.inference.kv_pool import StateStore, key_lanes
    from deepspeed_tpu.models.hybrid_moe import HybridMoEConfig, HybridMoETransformerLM

    for module in ("deepspeed_tpu.ops.transformer.decode_attention", "deepspeed_tpu.ops.transformer.latent_attention",
                   "deepspeed_tpu.moe.grouped_matmul", "deepspeed_tpu.moe.route_plan"):
        __import__(module)
        monkeypatch.setattr(sys.modules[module], "on_tpu", lambda: True)
    conf = json.loads(_GLM_CELL.read_text())
    paged = conf["engine"]["init_inference"]["paged_kv"]
    cfg = HybridMoEConfig(**conf["model"]["kwargs"])
    rows, page = paged["max_slots"], paged["page_size"]
    maxp = paged["max_seq_len"] // page
    assert key_lanes(cfg.latent_width) == 640 and cfg.layers_of("latent") == 16

    def on_v5e(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.eval_shape(lambda: HybridMoETransformerLM(cfg).init(jax.random.PRNGKey(0), None))
    params = jax.tree_util.tree_map(lambda a: on_v5e(a.shape, BF16), params)
    no_kv = on_v5e((0, rows * maxp + 1, 20, page, 256), BF16)  # no softmax layer: no K, no V
    shapes = hybrid_decode.state_shapes(cfg, rows)
    latent = on_v5e((16, rows * maxp + 1, page, 640), BF16)
    store = StateStore(on_v5e(shapes.state, jnp.float32), on_v5e(shapes.conv, BF16), None, None, latent)
    step = decode.build_ragged_step(cfg, rows, width, page, attn_impl="pallas")
    compiled = step.lower(
        params, on_v5e((rows, width), I32), no_kv, no_kv, store,
        on_v5e((rows, maxp), I32), on_v5e((rows,), I32), on_v5e((rows,), I32), on_v5e((rows,), I32),
    ).compile()
    text = compiled.as_text()
    first_pool = len(jax.tree_util.tree_leaves(params)) + 1
    assert first_pool + 4 in parse_input_output_aliases(text)  # k, v, state, conv (all empty), then the latent pool
    memory = compiled.memory_analysis()
    print(f"glm w{width}: arguments {memory.argument_size_in_bytes / 1e9:.2f} GB, temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB")
    assert 8.8e9 < memory.argument_size_in_bytes < 9.0e9  # 3.53 GB of weights, 5.37 GB of latent pages
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.5e9
    kernels = re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = .*? custom-call\(", text, flags=re.M)
    latent_calls = [name for name in kernels if name.startswith("latent_paged_attention")]
    assert len(latent_calls) == (2 if width == 1 else 4), kernels  # the leading layer's and the scan body's
    assert not any(name.startswith("ragged_paged_attention") for name in kernels), kernels
    assert sum(name.startswith("moe_grouped_matmul") for name in kernels) >= 3
    # no copy of the pool, and the 64 x 128 window of 20 heads x 640 lanes is never laid out
    assert not re.search(r"= bf16\[16,4097,64,640\]\S* (copy|fusion)\(", text)
    assert not re.search(r"bf16\[64,128,(12800|20,640)\]|bf16\[64,2560,640\]", text)
    if width == 1:
        assert _narrow_program(text, memory) == NARROW_PROGRAMS["glm"]
    else:
        # q [8192, 11520] and out [8192, 10240] zero-filled a layer and the chunk rows scattered into out: 10.4% of the cell, before
        assert not _slab_sized_fills(text, rows * width)
        assert memory.temp_size_in_bytes < 0.665e9  # 0.669 GB before


_LAGUNA_CELL = pathlib.Path(__file__).parents[3] / "benchmark/configs/laguna-s-2.1-l9-ep16.json"


@pytest.mark.parametrize("width", [1, 128])
def test_laguna_step_reads_its_mixers_matrices_where_they_lie(v5e, monkeypatch, width):
    """``build_ragged_step`` at the Laguna-S-2.1 cell's shapes, both
    programs (a leading dense layer and TWO periods of a full layer of 48
    query heads and three window layers of 72 over 8 KV heads, 64 rows, 4,097
    pages of 64, rings of 10 pages a slot): the scan over periods reaches a
    layer's weights by ONE slice a leaf (``hybrid_decode.layer_of``) and
    keeps the head split behind a barrier, so no period's slice of ``wq`` /
    ``wo`` (``[1, 3, 3072, 9216]``, 170 MB) and no copy of one to another
    layout is written out, and the program's temporaries are the
    activations' (0.346 GB before PR 46: a fifth of the cell's device time).
    The wide program's are its 64 x 128-slot token buffers (q and out
    ``[8192, 9216]`` / ``[8192, 6144]``, k and v ``[8192, 1024]``), which
    nothing fills, copies or scatters into (PR 47)."""
    from deepspeed_tpu.inference import hybrid_decode
    from deepspeed_tpu.inference.kv_pool import StateStore, key_lanes, window_ring_pages
    from deepspeed_tpu.models.hybrid_moe import HybridMoEConfig, HybridMoETransformerLM

    for module in ("deepspeed_tpu.ops.transformer.decode_attention", "deepspeed_tpu.moe.grouped_matmul", "deepspeed_tpu.moe.route_plan"):
        monkeypatch.setattr(sys.modules[module], "on_tpu", lambda: True)
    conf = json.loads(_LAGUNA_CELL.read_text())
    paged = conf["engine"]["init_inference"]["paged_kv"]
    cfg = HybridMoEConfig(**conf["model"]["kwargs"])
    rows, page = paged["max_slots"], paged["page_size"]
    maxp = paged["max_seq_len"] // page
    ring = window_ring_pages(cfg.window, page, paged["prefill_chunk"])
    assert (cfg.num_periods, cfg.period.count("window"), cfg.heads_of("window"), ring) == (2, 3, 72, 10)

    def on_v5e(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.eval_shape(lambda: HybridMoETransformerLM(cfg).init(jax.random.PRNGKey(0), None))
    params = jax.tree_util.tree_map(lambda a: on_v5e(a.shape), params)
    k_pool = on_v5e((cfg.layers_of("softmax"), rows * maxp + 1, cfg.num_kv_heads, page, key_lanes(cfg.head_dim)))
    shapes = hybrid_decode.state_shapes(cfg, rows)
    rings = (on_v5e(shape) for shape in hybrid_decode.window_shapes(cfg, rows, page, ring))
    store = StateStore(on_v5e(shapes.state, jnp.float32), on_v5e(shapes.conv), *rings)
    step = decode.build_ragged_step(cfg, rows, width, page, attn_impl="pallas")
    compiled = step.lower(
        params, on_v5e((rows, width), I32), k_pool, k_pool, store,
        on_v5e((rows, maxp), I32), on_v5e((rows,), I32), on_v5e((rows,), I32), on_v5e((rows,), I32),
    ).compile()
    text = compiled.as_text()
    matrices = _attention_matrices(cfg, ("softmax", "window"))
    assert (3072, 9216) in matrices and (9216, 3072) in matrices
    assert any(opcode == "while" for _, opcode, _ in _executed(text))  # the scan over periods is a loop: its body is read
    # (the wide program's packed tokens, 64 x 128 of 3072 or a tile of them, are no matrix)
    activations = {(rows * width, cfg.hidden_size), (decode.token_tile(cfg), cfg.hidden_size)}
    assert not _mixer_matrices_written_out(text, matrices, but=activations)
    memory = compiled.memory_analysis()
    print(f"laguna w{width}: arguments {memory.argument_size_in_bytes / 1e9:.2f} GB, temporaries {memory.temp_size_in_bytes / 1e9:.3f} GB")
    if width == 1:
        assert memory.temp_size_in_bytes < 0.05e9
        assert _narrow_program(text, memory) == NARROW_PROGRAMS["laguna"]
    else:
        assert not _slab_sized_fills(text, rows * width)
        assert memory.temp_size_in_bytes < 0.60e9  # 0.592 GB before: the same buffers, allocated and not filled


_KIMI_CELL = pathlib.Path(__file__).parents[3] / "benchmark/configs/kimi-linear-48b-a3b-l13-ep8.json"


@pytest.mark.parametrize("width", [1, 128])
def test_kimi_linear_step_fits_with_state_and_latent_pages_in_place(v5e, monkeypatch, width):
    """``build_ragged_step`` at the Kimi-Linear cell's shapes, both programs
    (a leading dense layer that is a KDA one and three periods ``[KDA, KDA,
    MLA, KDA]``: 10 delta-rule layers of 32 heads of 128 on a state store of 65
    slots, 3 latent layers of 32 heads over an unrotated entry of 576 stored
    at 640 lanes on 4,097 pages of 64, a query with no low rank, 32 held
    experts of 1,024 of a router over 256 and a shared one, 64 rows): it
    compiles for a v5e; the state, the convolution tails and the ONE latent
    pool stay aliased in to out; ``kda_decode`` runs at 32 heads in the
    leading layer and in the scan's body, on rows as the projections leave
    them and in place on both of its pools, the latent kernel once a latent
    layer of the text (twice in the wide program) and the accepted ragged
    kernel not at all; weights + pools are the configuration file's 9.3 GB and
    the temporaries fit beside them; no slice of a mixer's matrix is written
    out and nothing fills a 64 x 128-slot buffer."""
    from deepspeed_tpu.inference import hybrid_decode
    from deepspeed_tpu.inference.kv_pool import StateStore, key_lanes
    from deepspeed_tpu.models.hybrid_moe import HybridMoEConfig, HybridMoETransformerLM

    for module in ("deepspeed_tpu.ops.transformer.decode_attention", "deepspeed_tpu.ops.transformer.latent_attention",
                   "deepspeed_tpu.moe.grouped_matmul", "deepspeed_tpu.moe.route_plan", "deepspeed_tpu.ops.transformer.linear_attention"):
        __import__(module)
        monkeypatch.setattr(sys.modules[module], "on_tpu", lambda: True)
    conf = json.loads(_KIMI_CELL.read_text())
    paged = conf["engine"]["init_inference"]["paged_kv"]
    cfg = HybridMoEConfig(**conf["model"]["kwargs"])
    rows, page = paged["max_slots"], paged["page_size"]
    maxp = paged["max_seq_len"] // page
    assert (cfg.num_periods, cfg.period, cfg.leading_of("linear"), cfg.q_lora_rank, cfg.position) == (3, ("linear", "linear", "latent", "linear"), 1, 0, "none")

    def on_v5e(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.eval_shape(lambda: HybridMoETransformerLM(cfg).init(jax.random.PRNGKey(0), None))
    params = jax.tree_util.tree_map(lambda a: on_v5e(a.shape), params)
    no_kv = on_v5e((0, rows * maxp + 1, cfg.num_kv_heads, page, key_lanes(cfg.head_dim)))  # no softmax layer: no K, no V
    shapes = hybrid_decode.state_shapes(cfg, rows)
    assert shapes.state == (10, 65, 32, 128, 128) and shapes.conv == (10, 65, 3, 3, 32, 128)
    latent = on_v5e((3, rows * maxp + 1, page, key_lanes(cfg.latent_width)))
    store = StateStore(on_v5e(shapes.state, jnp.float32), on_v5e(shapes.conv), None, None, latent)
    step = decode.build_ragged_step(cfg, rows, width, page, attn_impl="pallas")
    compiled = step.lower(
        params, on_v5e((rows, width), I32), no_kv, no_kv, store,
        on_v5e((rows, maxp), I32), on_v5e((rows,), I32), on_v5e((rows,), I32), on_v5e((rows,), I32),
    ).compile()
    text = compiled.as_text()
    first_pool = len(jax.tree_util.tree_leaves(params)) + 1
    assert {first_pool + 2, first_pool + 3, first_pool + 4} <= parse_input_output_aliases(text)  # k, v (empty), then state, conv, latent
    memory = compiled.memory_analysis()
    print(f"kimi w{width}: arguments {memory.argument_size_in_bytes / 1e9:.2f} GB, temporaries {memory.temp_size_in_bytes / 1e9:.3f} GB")
    assert 9.25e9 < memory.argument_size_in_bytes < 9.40e9  # 6.90 GB of weights, 1.41 GB of state and tails, 1.01 GB of latent pages
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.5e9
    kernels = re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = .*? custom-call\(", text, flags=re.M)
    assert sum(name.startswith("kda_decode") for name in kernels) == 4, kernels  # the leading layer's and the body's three
    assert len(_assert_kda_decode_takes_rows_as_they_lie(text, "bf16[10,65,3,3,32,128]")) == 4
    assert sum(name.startswith("latent_paged_attention") for name in kernels) == (1 if width == 1 else 2), kernels
    assert not any(name.startswith("ragged_paged_attention") for name in kernels), kernels
    assert sum(name.startswith("moe_grouped_matmul") for name in kernels) >= 3
    # the state's operand is the whole store at 32 heads: [layers, slots, 32, 128, 128] float32, never copied (a chunk
    # row's new state goes in by a dynamic-update-slice fusion, in place)
    assert not re.search(r"= f32\[10,65,32,128,128\]\S* copy\(", text)
    assert not re.search(r"= bf16\[3,4097,64,640\]\S* (copy|fusion)\(", text)
    H = cfg.hidden_size
    wq = {(H, 32 * 192), (32 * 192, H)}  # a latent layer's query matrix, no low rank in front of it: 28 MB
    if width == 1:
        # no layer's slice of a mixer's matrix is written out, in either layout (a KDA layer's Wq Wk Wv Wo and a latent
        # layer's Wo are [2304, 4096] or its transpose). Without the barrier behind ``h Wq`` (hm.latent_project) the
        # compiler folds the head split into the matmul, writes the layer's Wq out and copies it head-major: 0.138 GB
        assert not _mixer_matrices_written_out(text, wq | {(H, 4096), (4096, H)})
        assert memory.temp_size_in_bytes < 0.03e9  # 0.0052 GB (0.091 with the kernel's operand stack and the gathered tails)
    else:
        # (the wide program stages each linear layer's and each latent layer's Wo [4096, 2304] in fast memory by a fusion
        # inside its tile loops, as Solar-Open2's does: PERF.md section 7)
        assert not _mixer_matrices_written_out(text, wq)
        assert not _slab_sized_fills(text, rows * width)
        assert memory.temp_size_in_bytes < 0.95e9  # 0.902 GB (1.388 before): the chunk rows' kda_chunked and the 64 x 128-slot buffers, unfilled


@pytest.mark.parametrize("donated", [True, False], ids=["pools_donated", "pools_returned_undonated"])
def test_ssd_decode_alone_compiles_whoever_owns_its_pools(v5e, donated):
    """``ssd_decode`` at granite-4.0-h-micro's shapes (64 rows, 64 heads of 64
    over a state of 128, 4,352 convolved channels, 36 layers' pools of 65
    slots) as a program's only operation, its two pools donated (the serving
    step's way) and not (the logits tool's way): one kernel call, a whole
    row's 2 MB of state a grid step."""
    from deepspeed_tpu.ops.transformer.state_space import ssd_decode

    R, NH, P, N, K, L = 64, 64, 64, 128, 4, 36
    C = NH * P + 2 * N
    on = lambda shape, dtype=BF16: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
    program = jax.jit(functools.partial(ssd_decode, impl="pallas"), donate_argnums=(6, 7) if donated else ())
    compiled = program.lower(
        on((R, C)), on((R, NH), jnp.float32), on((K, C)), on((C,)), on((NH,), jnp.float32), on((NH,), jnp.float32),
        on((L, R + 1, NH, P, N), jnp.float32), on((L, R + 1, K - 1, 48, 128)), on((), I32), on((R,), I32), on((R,), bool), on((R,), bool),
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%ssd_decode[\w.-]* = .*? custom-call\(", text)) == 1
    if donated:
        assert {6, 7} <= parse_input_output_aliases(text)


_GRANITE_CELL = pathlib.Path(__file__).parents[3] / "benchmark/configs/granite-4.0-h-micro.json"


@pytest.mark.parametrize("width", [1, 128])
def test_granite_hybrid_step_fits_whole_with_its_state_in_place(v5e, monkeypatch, width):
    """``build_ragged_step`` at the granite-4.0-h-micro cell's shapes, both
    programs (ALL 40 layers: four periods of nine Mamba-2 layers around one
    attention layer, 36 states of 64 x 64 x 128 float32 a row on a store of 65
    slots, heads of 64 on 1,537 pages of 64, a dense FFN of 8,192 out of the
    period's stacks, the tied table of 100,352 rows whole, 64 rows): it
    compiles for a v5e; K, V, the state and the convolution tails stay
    aliased in to out; ``ssd_decode`` runs nine times in the scan's body, the
    ragged kernel once (twice in the wide program); no routing rows ride on
    the step's result; weights + pools + temporaries fit a chip. Two KV heads
    of 64 share a page's 128 lanes (``kv_pool.heads_per_group``), so the call
    is the kernel that walks a row's live pages (the grid fallback, which
    pages of 64 lanes took, is out of reach), and the pools keep the default
    layout: the program holds no copy of either (both, in and out, 1.61 GB of
    temporaries a step, before)."""
    from deepspeed_tpu.inference import hybrid_decode
    from deepspeed_tpu.inference.kv_pool import StateStore, heads_per_group, page_shapes
    from deepspeed_tpu.models.hybrid_moe import HybridMoEConfig, HybridMoETransformerLM
    from deepspeed_tpu.ops.transformer import decode_attention

    for module in ("deepspeed_tpu.ops.transformer.decode_attention", "deepspeed_tpu.ops.transformer.state_space"):
        __import__(module)
        monkeypatch.setattr(sys.modules[module], "on_tpu", lambda: True)
    monkeypatch.setattr(decode_attention, "_ragged_by_grid", None)  # reaching it would raise
    conf = json.loads(_GRANITE_CELL.read_text())
    paged = conf["engine"]["init_inference"]["paged_kv"]
    cfg = HybridMoEConfig(**conf["model"]["kwargs"])
    rows, page = paged["max_slots"], paged["page_size"]
    maxp = paged["max_seq_len"] // page
    assert (cfg.num_periods, cfg.period.count("ssm"), cfg.period.count("softmax"), cfg.num_experts, cfg.state_kind) == (4, 9, 1, 0, "ssm")

    def on_v5e(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.eval_shape(lambda: HybridMoETransformerLM(cfg).init(jax.random.PRNGKey(0), None))
    params = jax.tree_util.tree_map(lambda a: on_v5e(a.shape), params)
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) == 3_191_396_096  # 6.38 GB in bfloat16
    f = heads_per_group(cfg.head_dim, cfg.v_head_dim, cfg.num_kv_heads)
    k_shape, v_shape = page_shapes(4, rows * maxp + 1, cfg.num_kv_heads, page, cfg.head_dim, cfg.v_head_dim, f)
    assert f == 2 and k_shape == v_shape == (4, 1537, 4, 64, 128)
    kv = on_v5e(k_shape)
    shapes = hybrid_decode.state_shapes(cfg, rows)
    assert shapes.state == (36, 65, 64, 64, 128) and shapes.conv == (36, 65, 3, 48, 128)
    store = StateStore(on_v5e(shapes.state, jnp.float32), on_v5e(shapes.conv), None, None, None)
    step = decode.build_ragged_step(cfg, rows, width, page, attn_impl="pallas")
    compiled = step.lower(
        params, on_v5e((rows, width), I32), kv, kv, store,
        on_v5e((rows, maxp), I32), on_v5e((rows,), I32), on_v5e((rows,), I32), on_v5e((rows,), I32),
    ).compile()
    text = compiled.as_text()
    first_pool = len(jax.tree_util.tree_leaves(params)) + 1
    assert {first_pool, first_pool + 1, first_pool + 2, first_pool + 3} <= parse_input_output_aliases(text)  # k, v, state, conv
    memory = compiled.memory_analysis()
    print(f"granite w{width}: arguments {memory.argument_size_in_bytes / 1e9:.2f} GB, temporaries {memory.temp_size_in_bytes / 1e9:.3f} GB")
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.5e9
    kernels = re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = .*? custom-call\(", text, flags=re.M)
    assert sum(name.startswith("ssd_decode") for name in kernels) == 9, kernels  # a period's nine, in the scan's body
    assert sum(name.startswith("ragged_paged_attention") for name in kernels) == (1 if width == 1 else 2), kernels
    assert not re.search(r"= f32\[36,65,64,64,128\]\S* copy\(", text)  # the state store is never copied
    assert not re.search(r"= bf16\[4,1537,\S* copy\(", text)  # nor are the pages
    assert memory.temp_size_in_bytes < 0.5e9  # 0.007 / 0.172 GB (1.618 / 1.680 with the pools' four copies, before)
    assert re.search(rf"s32\[{rows},{width + 1}\]", text) and not re.search(rf"s32\[{rows + decode.MOE_STAT_ROWS},{width + 1}\]", text)


_OURO_CELL = pathlib.Path(__file__).parents[3] / "benchmark/configs/ouro-2.6b.json"


@pytest.mark.parametrize("width", [1, 128])
def test_ouro_looped_step_fits_whole_with_192_cache_layers_in_place(v5e, monkeypatch, width):
    """``build_ragged_step`` at the Ouro cell's shapes (8 rows, 73 pages of 64,
    the published heads of 128, all 48 layers run four times): the pools are
    ``[192, 73, 16, 64, 128]`` (``cache_layers``), donated and aliased to the
    outputs; nothing but parameters, plumbing and the ragged kernel has a
    result of a pool's shape, of a PASS's 48 layers of it or of one layer's
    pages (a slice handed to a pass, a carry a loop does not alias, would be
    3.7 GB or 0.9 GB a step); no layer's matrix and no weight stack is
    written out on the way to its matmul, in any of the four passes; the
    program holds one kernel call a pass (48 executions each: 192 a step);
    and weights + pools + the program's temporaries fit the chip."""
    from deepspeed_tpu.models.config import cache_layers

    monkeypatch.setattr(sys.modules["deepspeed_tpu.ops.transformer.decode_attention"], "on_tpu", lambda: True)
    conf = json.loads(_OURO_CELL.read_text())
    paged = conf["engine"]["init_inference"]["paged_kv"]
    cfg = TransformerConfig(**conf["model"]["kwargs"])
    rows, page = paged["max_slots"], paged["page_size"]
    maxp = paged["max_seq_len"] // page
    n_pages = rows * maxp + 1

    def on_v5e(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.eval_shape(lambda: TransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), I32)))
    params = jax.tree_util.tree_map(lambda a: on_v5e(a.shape, BF16), params)
    pool = on_v5e((cache_layers(cfg), n_pages, cfg.num_kv_heads, page, cfg.head_dim), BF16)
    assert pool.shape == (192, 73, 16, 64, 128)
    step = decode.build_ragged_step(cfg, rows, width, page, attn_impl="pallas")
    compiled = step.lower(
        params, on_v5e((rows, width), I32), pool, pool, on_v5e((rows, maxp), I32), on_v5e((rows,), I32), on_v5e((rows,), I32)
    ).compile()
    decode._paged_program_cache.clear()
    text = compiled.as_text()

    # after the params and the tokens; the exit gate's two leaves are no operand: the timed path holds no gate arithmetic
    assert "exit_gate" in params
    first_pool = len(jax.tree_util.tree_leaves(params)) - 2 + 1
    assert {first_pool, first_pool + 1} <= parse_input_output_aliases(text)
    pages_of = re.compile(rf"\[([\d,]+),{cfg.num_kv_heads},{page},{cfg.head_dim}\]")
    strangers = {
        name: f"{opcode} {result}" for name, opcode, result in _executed(text)
        if opcode not in _PLUMBING and not (opcode == "custom-call" and name.startswith("ragged_paged_attention"))
        and any(np.prod([int(d) for d in dims.split(",")]) >= n_pages for dims in pages_of.findall(result))
    }
    assert not strangers, f"pool-shaped results outside the kernel: {strangers}"
    kernels = [name for name, opcode, _ in _executed(text) if opcode == "custom-call" and name.startswith("ragged_paged_attention")]
    assert len(kernels) == cfg.num_loops  # one in each pass's scan body
    I = cfg.intermediate_size
    matrices = _attention_matrices(cfg) | {(cfg.hidden_size, I), (I, cfg.hidden_size)}
    assert not _mixer_matrices_written_out(text, matrices)
    memory = compiled.memory_analysis()
    weights = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params)) * 2
    pools = 2 * int(np.prod(pool.shape)) * 2
    assert (round(weights / 1e9, 2), round(pools / 1e9, 2)) == (5.34, 7.35)
    assert memory.temp_size_in_bytes < 0.6e9 and weights + pools + memory.temp_size_in_bytes < 15.75 * 2**30
    print(f"ouro w{width}: temp {memory.temp_size_in_bytes:,} B, resident {weights + pools:,} B")


_XL_CELL = pathlib.Path(__file__).parents[3] / "benchmark/configs/gpt2-xl.json"


def _loop_bodies(text):
    """``{while body: [(name, opcode, result as written)]}`` of a compiled
    program's text, a fusion's opcode written ``fusion:<its root's opcode>``."""
    computations = _computations(text)
    root_of = {c: next((opcode for root, _, _, opcode, _ in body if root), None) for c, body in computations.items()}
    loops = {
        callee for body in computations.values() for _, _, _, opcode, rest in body if opcode == "while"
        for callee in re.findall(r"body=%([\w.$-]+)", rest)
    }
    return {
        loop: [
            (name, opcode if opcode != "fusion" else "fusion:" + str(root_of[re.search(r"calls=%([\w.$-]+)", rest).group(1)]), result)
            for _, name, result, opcode, rest in computations[loop]
        ]
        for loop in loops
    }


def test_zero3_layer_pipeline_writes_a_layers_gradient_where_it_lies(v5e_2x2, monkeypatch):
    """The pipelined ZeRO-3 forward and backward of ``TransformerLM`` at
    GPT-2 XL's widths (48 layers, H 1,600, I 6,400, 25 heads of 64, remat,
    flash attention under ``shard_map``; a short sequence and a small table:
    neither is in the layer scan's parameters) for the four chips of a
    described ``v5e:2x2``, the ``gpt2_xl_zero3_dp4_train`` cell's plan (stage
    3 and nothing else: one layer of lookahead, the in-loop reduction).
    Inside a loop body nothing but a ``dynamic-update-slice`` (a fusion rooted
    in one, for the matrices) has a result of a stacked leaf's shape, whole or
    cut four ways: the forward stacks what the backward needs and the backward
    writes each layer's gradient, one slice a layer, in place. A stack the
    scan closes over gets a whole-stack accumulator in the backward carry
    instead: a zero ``broadcast`` of every stack, an update of one layer of it
    and a ``select_add`` fusion over all 48 layers, once a layer (until PR 55:
    16 of them, 2 x 53.8 ms of a 1,275 ms step for ``w_in`` and ``w_out``
    alone).

    Since PR 62 neither loop holds a collective of a matrix on the core.
    The lookahead gathers each of the six matrices by direct sends, a chip's
    shard to every other chip (``chips - 1`` ``collective-permute``s a
    matrix, assembled by one ``concatenate`` in a branch a chip), and the
    backward sums each matrix's gradient the same way, a chip's blocks of
    its partial sum to the chips that keep them (``OverlapPlan.matmul``):
    no ``all-gather`` in a loop, no fused ``all-reduce-scatter`` anywhere in
    the loops, and the one ``all-reduce`` the compiler combines the ten
    vectors' into carries no matrix. (Until PR 62: five matrices as fused
    reduce-scatters with a halo exchange behind each, ``w_in`` in the
    vectors' all-reduce, three of the six gathers synchronous; until PR 58
    all sixteen leaves through one ``[4, chunk]`` bucket, which the compiler
    built in loops of its own inside the backward's.)"""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.parallel.mesh import MeshConfig, initialize_topology
    from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
    from deepspeed_tpu.runtime.zero.overlap import build_overlap_plan, overlap_scope, step_compiler_options
    from deepspeed_tpu.runtime.zero.partition import ZeroPartitioner

    module = "deepspeed_tpu.ops.transformer.flash_attention"
    __import__(module)
    monkeypatch.setattr(sys.modules[module], "on_tpu", lambda: True)
    conf = json.loads(_XL_CELL.read_text())
    chips, seq = conf["engine"]["ds_config"]["mesh"]["data"], 128
    cfg = TransformerConfig(**{**conf["model"]["kwargs"], "vocab_size": 1024, "max_seq_len": seq})
    L, H, I = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    assert (L, H, I, cfg.num_heads, cfg.remat, cfg.flash_attention, chips) == (48, 1600, 6400, 25, True, True, 4)
    topo = initialize_topology(MeshConfig(data=chips), devices=v5e_2x2.devices[:chips])  # conftest resets it
    model = TransformerLM(cfg)
    tokens = jnp.zeros((chips, seq), I32)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), {"input_ids": tokens, "labels": tokens}))
    zero = DeepSpeedZeroConfig(**conf["engine"]["ds_config"]["zero_optimization"])
    partitioner = ZeroPartitioner(zero, topo, model.tp_partition_rules(shapes))
    param_specs, grad_specs = partitioner.param_specs(shapes), partitioner.grad_accum_specs(shapes)
    plan = build_overlap_plan(zero, topo, shapes["layers"], param_specs["layers"], grad_specs["layers"], L)
    assert (plan.prefetch_enabled, plan.depth, plan.reduce_enabled) == (True, 1, True)

    def on_mesh(spec):
        return NamedSharding(topo.mesh, spec)

    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    params = jax.tree_util.tree_map(
        lambda a, spec: jax.ShapeDtypeStruct(a.shape, BF16, sharding=on_mesh(spec)), shapes, param_specs, is_leaf=is_spec
    )
    batch = jax.ShapeDtypeStruct(tokens.shape, I32, sharding=on_mesh(P("data", None)))

    def loss(params, batch):
        with overlap_scope(plan):
            return model.apply(params, batch, rngs={"dropout": jax.random.PRNGKey(0)}, train=True)

    compiled = jax.jit(
        jax.grad(loss), out_shardings=jax.tree_util.tree_map(on_mesh, grad_specs, is_leaf=is_spec)
    ).lower(params, {"input_ids": batch, "labels": batch}).compile(step_compiler_options(plan, True))
    text = compiled.as_text()

    stacks = {a.shape for a in jax.tree_util.tree_leaves(shapes["layers"])}
    assert {(L, H, I), (L, I, H), (L, H, H), (L, H)} <= stacks
    cut = {s[:d] + (s[d] // chips,) + s[d + 1:] for s in stacks for d in range(1, len(s)) if s[d] % chips == 0}
    of_a_stack = re.compile("|".join(rf"^\w+\[{','.join(map(str, s))}\]" for s in stacks | cut))
    moves = {"dynamic-update-slice", "fusion:dynamic-update-slice"}
    bodies = _loop_bodies(text)
    # the forward's and the backward's layer loop and no other: the compiler builds no bucket in loops of its own
    assert len(bodies) == 2, sorted(bodies)
    written = {
        loop: [(name, opcode, result.split("{")[0]) for name, opcode, result in body
               if of_a_stack.match(result) and opcode not in _PLUMBING]
        for loop, body in bodies.items()
    }
    # the vectors' stacks (150 KB) may be fetched ahead into fast memory: a copy of no account
    strangers = [
        w for ws in written.values() for w in ws
        if w[1] not in moves and not (w[1] in ("copy-start", "copy-done") and w[2].count(",") == 1)
    ]
    assert not strangers, strangers
    # the layer loops are there and write the matrices: forward (six gathered, for the backward) and backward (six gradients)
    assert sorted(sum(opcode == "fusion:dynamic-update-slice" for _, opcode, _ in ws) for ws in written.values() if ws) == [6, 6], written

    # no [world, chunk] view of a matrix anywhere: not flat, not in rows, not a chip's row of it
    matrices = {(H, I), (I, H), (H, H)}
    flat = {n for h, w in matrices for n in (h * w, h * w // chips)}
    views = re.compile(r"^\w+\[(?:1,)?(?:%d,)?(?:%s)\]" % (chips, "|".join(map(str, sorted(flat)))))
    assert not [(name, result) for name, _, result in _executed(text) if views.match(result)]
    # neither loop moves a matrix on the core: no gather, no fused reduce-scatter, no matrix in an all-reduce;
    # a chip's shard (forward) or block (backward) of every matrix goes to each other chip by a collective-permute
    shard = {f"bf16[{h // chips},{w}]" for h, w in matrices} | {f"bf16[{h},{w // chips}]" for h, w in matrices}
    for body in bodies.values():
        opcodes = [opcode for _, opcode, _ in body]
        assert "all-gather" not in opcodes and "fusion:all-reduce" not in opcodes and "reduce-scatter" not in opcodes
        reduced = [piece for _, opcode, result in body if opcode == "all-reduce" for piece in re.findall(r"\w+\[[\d,]*\]", result)]
        assert all(piece.count(",") == 0 for piece in reduced), reduced  # vectors alone
        sent = [re.match(r"\((\w+\[[\d,]*\])", result).group(1) for _, opcode, result in body if opcode == "collective-permute-start"]
        sent = [piece for piece in sent if "," in piece]  # a vector's are the compiler's own
        assert len(sent) == 6 * (chips - 1) and set(sent) <= shard, sorted(collections.Counter(sent).items())
    computations = _computations(text)
    assert not [name for loop in bodies for _, name, _, _, rest in computations[loop] if "calls=%all-reduce-scatter" in rest]
    opcodes = [opcode for _, opcode, _ in _executed(text)]
    collectives = {k: sum(o in (k, k + "-start") for o in opcodes) for k in ("all-gather", "all-reduce", "reduce-scatter", "collective-permute")}
    # outside the loops: the prologue's sends (18), the embedding's and the head's own
    assert collectives["collective-permute"] >= 3 * 6 * (chips - 1) and collectives["reduce-scatter"] == 0, collectives
