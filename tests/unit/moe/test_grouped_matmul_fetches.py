"""What the grouped matmul's grid FETCHES, checked on the CPU.

Pallas's pipeline copies a block whenever its index differs from the grid
step before, and nothing else decides what the kernel reads from HBM. So the
kernel's traffic is a function of its three index maps
(``grouped_matmul._x_index`` / ``_w_index`` / ``_o_index``) over ``_visits``'
metadata, and this file walks the grid in order and counts it, at the shapes
of the benchmark's three MoE serving cells. Until PR 37 a dead visit of a
call whose K is tiled alternated between an expert's two K blocks and so
re-read its whole matrix: ``PARENT`` holds those maps, as the reference for
OLMoE's shapes (which must not move) and to show that the count sees the
fault.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import grouped_matmul as gm

BF16 = 2

# the maps as they stood until PR 37 (K blocks of ``tk`` for rows and weights alike, every visit walking them)
PARENT = {
    "x": lambda n, v, kk, meta, *, V, k_tiles: (meta[V + v], kk),
    "w": lambda n, v, kk, meta, *, V, k_tiles: (meta[v], kk, n),
    "o": lambda n, v, kk, meta, *, V, k_tiles: (meta[V + v], n),
}
CHANGE = {"x": gm._x_index, "w": gm._w_index, "o": gm._o_index}


def sizes_in_the_first_rows(groups: int, hit: int, live: int, seed: int = 0):
    """``live`` rows over ``hit`` of ``groups`` groups, a row each at least:
    sorted by group they fill the window's first rows, as the held
    assignments of a serving step do."""
    rng = np.random.default_rng(seed)
    chosen = rng.choice(groups, hit, replace=False)
    sizes = np.zeros(groups, np.int32)
    sizes[chosen] = 1
    np.add.at(sizes, chosen[rng.integers(0, hit, live - hit)], 1)
    return sizes


def walk(maps, m: int, k: int, n: int, sizes, offset: int = 0, parent_blocks: bool = False):
    """The grid of ``grouped_matmul`` for ``[m, k] x [G, k, n]`` walked in
    order: for rows, weights and output the bytes each step copies (a
    block's, where its index differs from the step before) and the block
    indices themselves, which steps are dead, and the grid's sizes."""
    tm, tk, tn = gm._tiles(m, k, n, BF16)
    tiles_m, k_tiles, G = -(-m // tm), k // tk, len(sizes)
    V = tiles_m + G - 1
    meta = np.asarray(gm._visits(jnp.asarray(sizes), jnp.int32(offset), tiles_m, tm))
    nn, vv, kk = (a.reshape(-1) for a in np.meshgrid(np.arange(n // tn), np.arange(V), np.arange(k_tiles), indexing="ij"))
    block_bytes = {"x": tm * (tk if parent_blocks else k) * BF16, "w": tk * tn * BF16, "o": tm * tn * BF16}
    copied, indices = {}, {}
    for name, index in maps.items():
        at = indices[name] = np.stack([np.broadcast_to(np.asarray(i), nn.shape) for i in index(nn, vv, kk, meta, V=V, k_tiles=k_tiles)], axis=1)
        moved = np.ones(len(at), bool)
        moved[1:] = (at[1:] != at[:-1]).any(axis=1)
        copied[name] = moved * block_bytes[name]
    return copied, indices, vv >= meta[4 * V], dict(tm=tm, tk=tk, tn=tn, k_tiles=k_tiles, V=V, live_visits=int(meta[4 * V]))


def tiles_touched(sizes, tm: int):
    ends = np.cumsum(sizes)
    starts = ends - sizes
    return np.where(sizes > 0, (ends - 1) // tm - starts // tm + 1, 0)


# (rows, K, N, groups, groups hit, live rows): a narrow and a mixed window of each cell's gate/up and down calls
SOLAR_UP, SOLAR_DOWN = (4096, 1280, 40), (1280, 4096, 40)
MIMO_UP, MIMO_DOWN = (4096, 2048, 16), (2048, 4096, 16)
TILED = {
    "solar_up_narrow": (512, *SOLAR_UP, 26, 59),
    "solar_up_mixed": (4096, *SOLAR_UP, 40, 190),
    "solar_down_narrow": (512, *SOLAR_DOWN, 26, 59),
    "solar_down_mixed": (4096, *SOLAR_DOWN, 40, 190),
    "mimo_up_narrow": (512, *MIMO_UP, 14, 33),
    "mimo_up_mixed": (4096, *MIMO_UP, 16, 99),
    "mimo_down_narrow": (512, *MIMO_DOWN, 14, 33),
    "mimo_down_mixed": (4096, *MIMO_DOWN, 16, 99),
}
OLMOE = {
    "olmoe_up_narrow": (128, 2048, 1024, 64, 56, 128),
    "olmoe_down_narrow": (128, 1024, 2048, 64, 56, 128),
    "olmoe_up_mixed": (8192, 2048, 1024, 64, 64, 1144),
    "olmoe_down_mixed": (8192, 1024, 2048, 64, 64, 1144),
}


@pytest.mark.parametrize("case", sorted(TILED))
def test_a_call_reads_the_hit_experts_once_and_a_dead_step_nothing(case):
    m, k, n, groups, hit, live = TILED[case]
    sizes = sizes_in_the_first_rows(groups, hit, live)
    copied, _, dead, grid = walk(CHANGE, m, k, n, sizes, offset=groups)
    assert dead.any() and not dead.all()
    for name in ("x", "w", "o"):
        assert copied[name][dead].sum() == 0, f"a dead step copies {name}"
    # a group's matrix once for every row tile it touches; where K is one block, two visits in a row of one
    # group (a group across a tile's edge) share the block
    touches = tiles_touched(sizes, grid["tm"]) if grid["k_tiles"] > 1 else (sizes > 0)
    assert touches.sum() >= hit
    assert copied["w"].sum() == touches.sum() * k * n * BF16
    # the rows of a tile once while its visits pass, however many groups and K blocks meet them: the live rows
    # lie in the window's first tiles, and a window whose rows are all in tile 0 reads them once for the whole call
    row_tiles = (live - 1) // grid["tm"] + 1
    assert copied["x"].sum() == (1 if row_tiles == 1 else (n // grid["tn"]) * row_tiles) * grid["tm"] * k * BF16


@pytest.mark.parametrize("case", ["solar_up_narrow", "solar_up_mixed", "mimo_up_narrow", "mimo_up_mixed"])
def test_the_count_sees_what_the_maps_before_pr37_read(case):
    """K in two blocks: a dead visit of the old maps alternates between
    them, and every visit of the grid reads a whole matrix."""
    m, k, n, groups, hit, live = TILED[case]
    sizes = sizes_in_the_first_rows(groups, hit, live)
    copied, _, dead, grid = walk(PARENT, m, k, n, sizes, offset=groups, parent_blocks=True)
    assert grid["k_tiles"] == 2
    assert copied["w"][dead].sum() > 0
    assert copied["w"].sum() == grid["V"] * k * n * BF16
    fixed, *_ = walk(CHANGE, m, k, n, sizes, offset=groups)
    assert fixed["w"].sum() == grid["live_visits"] * k * n * BF16 < copied["w"].sum()


@pytest.mark.parametrize("case", sorted(OLMOE))
def test_olmoes_shapes_keep_the_plan_they_had(case):
    """One K block: the maps evaluate as before, index for index."""
    m, k, n, groups, hit, live = OLMOE[case]
    sizes = sizes_in_the_first_rows(groups, hit, live)
    assert gm._tiles(m, k, n, BF16)[1:] == (k, n)  # an expert's whole matrix is one block
    was, at_was, _, _ = walk(PARENT, m, k, n, sizes, offset=groups, parent_blocks=True)
    now, at_now, dead, _ = walk(CHANGE, m, k, n, sizes, offset=groups)
    for name in ("x", "w", "o"):
        np.testing.assert_array_equal(at_now[name], at_was[name])
        np.testing.assert_array_equal(now[name], was[name])
        assert now[name][dead].sum() == 0


def test_a_window_without_a_row_reads_one_block_a_pass():
    """No live visit at all: every step is dead and holds the first step's blocks."""
    copied, _, dead, grid = walk(CHANGE, 512, 4096, 1280, np.zeros(40, np.int32))
    assert dead.all() and grid["k_tiles"] > 1
    assert copied["w"].sum() == (1280 // grid["tn"]) * grid["tk"] * grid["tn"] * BF16
