"""Operations and bytes of the two attention kernels against hand counts,
and the event signatures they are found by."""

import re

import pytest

from benchmark.kernels import flash_attention as flash
from benchmark.kernels import ragged_paged_attention as ragged
from benchmark.trace_reduce import _LAYOUT

PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_flash_forward_by_hand():
    # one head, T = 4, D = 2: 10 causal pairs; S and PV are 2 products of 2*D ops a pair
    ops, moved = flash.ops_and_bytes("forward", bn=1, t=4, d=2)
    assert ops == 2 * (2 * 2) * 10
    assert moved == 4 * (4 * 2 * 2) + 4 * 4  # q k v o in bf16, one f32 statistic a row


@pytest.mark.parametrize("kind, products, tensors, stats", [("backward_dq", 3, 5, 2), ("backward_dkv", 4, 6, 2)])
def test_flash_backward_by_hand(kind, products, tensors, stats):
    ops, moved = flash.ops_and_bytes(kind, bn=3, t=8, d=4)
    assert ops == 3 * products * (2 * 4) * 36
    assert moved == 3 * (tensors * 8 * 4 * 2 + stats * 8 * 4)


def test_flash_forward_at_the_cell_size_is_bound_by_compute():
    # GPT-2 125M cell: 8 x 12 heads, T 1024, D 64: 0.68 us of math against 0.64 us of bytes a head
    seconds, bound = flash.min_seconds("forward", bn=96, t=1024, d=64, peak=PEAK)
    assert bound == "compute"
    assert seconds == pytest.approx(96 * 4 * 64 * (1024 * 1025 // 2) / 197e12)
    assert seconds == pytest.approx(65.5e-6, rel=0.01)


def test_ragged_by_hand():
    # a decode row (1 new token over 10 keys) and a 3-token chunk ending at 5 keys
    # (its queries see 3, 4, 5 keys = 12 pairs); 4 heads over 2 kv heads, D = 8
    rows = [(1, 10), (3, 5)]
    ops, moved = ragged.ops_and_bytes(rows, heads=4, kv_heads=2, d=8)
    assert ops == 4 * 8 * (10 + 12) * 4
    assert moved == (2 * 10 * 2 * 8 + 2 * 1 * 4 * 8) * 2 + (2 * 5 * 2 * 8 + 2 * 3 * 4 * 8) * 2
    assert ragged.ops_and_bytes([], 4, 2, 8) == (0, 0)


def test_decode_rows_are_bound_by_memory():
    rows = [(1, 1000)] * 16
    seconds, bound = ragged.min_seconds(rows, heads=32, kv_heads=8, d=128, peak=PEAK)
    assert bound == "memory"
    assert seconds == pytest.approx(16 * (2 * 1000 * 8 * 128 + 2 * 32 * 128) * 2 / 819e9)


EVENTS_SEEN = {  # event names of the PR 22 traces, cut after the operands
    "forward": '%closed_call.71 = (bf16[96,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, f32[96,1024,128]{2,1,0:T(8,128)}) custom-call(bf16[96,1024,64]{2,1,0:T(8,128)(2,1)S(1)} %bitcast.569, bf16[96,1024,64]{2,1,0:T(8,128)(2,1)S(1)} %bitcast.565, bf16[96,1024,64]{2,1,0:T(8,128)(2,1)S(1)} %bitcast.567), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[96,1024,64]{2,1,0}}',
    "backward_dkv": '%closed_call.72 = (bf16[96,1024,64]{2,1,0:T(8,128)(2,1)}, bf16[96,1024,64]{2,1,0:T(8,128)(2,1)}) custom-call(bf16[96,1024,64]{2,1,0:T(8,128)(2,1)} %dynamic-slice_bitcast_fusion.20, bf16[96,1024,64]{2,1,0} %dynamic-slice_bitcast_fusion.21, bf16[96,1024,64]{2,1,0} %dynamic-slice_bitcast_fusion.22, bf16[96,1024,64]{2,1,0} %bitcast.575, f32[96,1024,128]{2,1,0:T(8,128)S(1)} %broadcast_in_dim.410, f32[96,1024,128]{2,1,0:T(8,128)S(1)} %broadcast_in_dim.412), custom_call_target="tpu_custom_call"',
    "backward_dq": '%closed_call.73 = bf16[96,1024,64]{2,1,0:T(8,128)(2,1)} custom-call(bf16[96,1024,64]{2,1,0:T(8,128)(2,1)} %dynamic-slice_bitcast_fusion.20, bf16[96,1024,64]{2,1,0} %dynamic-slice_bitcast_fusion.21, bf16[96,1024,64]{2,1,0} %dynamic-slice_bitcast_fusion.22, bf16[96,1024,64]{2,1,0} %bitcast.575, f32[96,1024,128]{2,1,0} %broadcast_in_dim.410, f32[96,1024,128]{2,1,0} %broadcast_in_dim.412), custom_call_target="tpu_custom_call"',
    "ragged": '%closed_call.12 = bf16[16,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} custom-call(s32[16,38]{1,0:T(8,128)S(1)} %copy-done.1, s32[16]{0:T(128)S(1)} %copy-done.14, s32[16]{0:T(128)S(1)} %copy-done.13, bf16[16,8,4,128]{3,2,1,0} %pad_maximum_fusion.2, bf16[609,8,64,128]{3,2,1,0} %copy.76, bf16[609,8,64,128]{3,2,1,0} %copy.79), custom_call_target="tpu_custom_call"',
    "none": "%fusion.498 = (bf16[3072]{0}, bf16[8,1024,3072]{2,1,0}) fusion(bf16[12,8,1024,3072]{3,2,1,0} %get-tuple-element.3167), kind=kOutput",
}


@pytest.mark.parametrize("seen", sorted(EVENTS_SEEN))
def test_each_kernel_event_matches_its_own_signature_only(seen):
    text = _LAYOUT.sub("", EVENTS_SEEN[seen])
    patterns = {**flash.EVENTS, **ragged.EVENTS}
    assert [k for k, p in patterns.items() if re.search(p, text)] == ([] if seen == "none" else [seen])
