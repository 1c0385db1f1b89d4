"""What PR 34 added to the benchmark for MiMo-V2.5: the configuration file
against the catalog row's published keys, the reference's independence, the
windowed kernel's operations and bytes on hand-worked cases, the five new
readers (``mimo.`` entries until PR 44, ``serve.`` since) on a synthetic trace (device events with the name stacks the
program's scopes give them, ``serve.settle`` spans with the program's
attributes) and where their scope is absent, the traffic file against the
engine's ``max_seq_len``, and the cell's rehearsal. Pins neither a count of
cells nor a position in a list."""

import ast
import json
import os
import subprocess
import sys

import pytest

from benchmark import files, op_scopes, program_spans
from benchmark import trace_reduce as tr
from benchmark.kernels import windowed_paged_attention as wpa
from tests.benchmark.spec_lookup import readers_of

HERE = os.path.dirname(__file__)
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
NAME, CELL_NAME = "mimo-v2.5-l7-ep16", "mimo_v25_long_decode"
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
CELL = {"name": "a_serving_cell", "config": {"engine": {"kind": "serve"}}, "peak": PEAK}
NEW_READERS = ["window_attn_time_share", "full_attn_time_share", "window_attn_roofline", "full_attn_roofline", "held_experts_hit_share"]
# config.json of XiaomiMiMo/MiMo-V2.5 as the model-configs catalog holds it
PUBLISHED = {
    "attention_bias": False, "attention_chunk_size": 128, "attention_value_scale": 0.707, "attention_projection_layout": "fused_qkv",
    "add_full_attention_sink_bias": False, "add_swa_attention_sink_bias": True, "swa_num_key_value_heads": 8,
    "swa_num_attention_heads": 64, "swa_head_dim": 192, "swa_v_head_dim": 128, "head_dim": 192, "hidden_act": "silu",
    "hidden_size": 4096, "hybrid_block_size": None,
    "hybrid_layer_pattern": [0] + [1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7,
    "intermediate_size": 16384, "layernorm_epsilon": 1e-05, "max_position_embeddings": 1048576, "model_type": "mimo_v2",
    "moe_intermediate_size": 2048, "moe_layer_freq": [0] + [1] * 47, "n_group": 1, "n_routed_experts": 256, "n_shared_experts": None,
    "norm_topk_prob": True, "num_attention_heads": 64, "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
    "partial_rotary_factor": 0.334, "rope_scaling": {"rope_type": "default", "type": "default"}, "rope_theta": 10000000,
    "routed_scaling_factor": None, "scoring_func": "sigmoid", "sliding_window": 128, "sliding_window_size": 128,
    "swa_rope_theta": 10000, "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 152576,
}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def reader(name):
    return files.load_module("layer_metrics", name)


# --- the configuration ---------------------------------------------------------


def test_configuration_holds_the_published_keys_with_three_cuts_and_no_width_among_them():
    body = load("benchmark", "configs", NAME + ".json")
    entry = next(c for c in load("BENCHMARK.json")["configs"] if c["name"] == NAME)
    assert entry["reduced"] == body["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert body["source"] == entry["source"] == "https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json"
    differs = {k for k, v in PUBLISHED.items() if k not in body or body[k] != v}
    assert differs == set(body["reduced"])
    assert (body["num_hidden_layers"], body["n_routed_experts"], body["vocab_size"]) == (7, 16, 19072)
    # the published counts stand beside the cuts, and the floors of a model_config PR hold
    assert (body["published"]["num_hidden_layers"], body["published"]["n_routed_experts"], body["published"]["vocab_size"]) == (48, 256, 152576)
    assert body["n_routed_experts"] >= 8 and body["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    kwargs = body["model"]["kwargs"]
    # the leading dense layer counted once, then one whole period of the published pattern: layers 6-11, 5:1
    assert kwargs["layer_types"] == ["softmax"] + ["window"] * 5 + ["softmax"] and kwargs["leading_dense_layers"] == 1
    assert [0 if t == "softmax" else 1 for t in kwargs["layer_types"][1:]] == PUBLISHED["hybrid_layer_pattern"][6:12]
    assert len(kwargs["layer_types"]) - 1 >= 4
    assert (kwargs["moe_router_experts"], kwargs["num_experts"], kwargs["moe_expert_share"], kwargs["moe_top_k"]) == (256, 16, [0, 16], 8)
    assert (kwargs["head_dim"], kwargs["v_head_dim"], kwargs["rope_dim"]) == (192, 128, int(0.334 * 192))
    assert (kwargs["position"], kwargs["moe_scoring"], kwargs["moe_select_bias"], kwargs["moe_shared_experts"]) == ("rope", "sigmoid", True, 0)
    assert {"value_scale", "window_edge", "sink", "softmax_scale", "rotary", "selection_bias", "left_out"} <= set(body["assumed"])
    assert "sixteen v5e chips share each layer" in body["deployment"]
    check = body["engine"]["check"]
    assert check["max_context"] == 2048 and check["sample"] == 4


def test_the_traffic_fills_the_engines_max_seq_len_and_the_cell_is_in_its_lists():
    spec = load("BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == CELL_NAME)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "long_decode", 1) and len(cell["why"]) <= 200
    mix = load("benchmark", "traffic", "long_decode.json")
    paged = load("benchmark", "configs", NAME + ".json")["engine"]["init_inference"]["paged_kv"]
    assert mix["kind"] == "closed_loop" and mix["clients"] == "max_slots" and paged["max_slots"] == 64
    assert (mix["prompt_len"]["min"], mix["prompt_len"]["max"], mix["output_len"]["min"], mix["output_len"]["max"]) == (256, 1024, 1536, 3072)
    assert paged["max_seq_len"] == mix["prompt_len"]["max"] + mix["output_len"]["max"] == 4096
    # every prompt is shorter than the check's context, so every sampled stream has served tokens inside it
    assert mix["prompt_len"]["max"] < load("benchmark", "configs", NAME + ".json")["engine"]["check"]["max_context"]
    rehearse = files.load_cell(spec, CELL_NAME, rehearse=True)
    r_paged, r_mix = rehearse["config_file"]["engine"]["init_inference"]["paged_kv"], rehearse["traffic_file"]
    assert r_paged["max_seq_len"] == r_mix["prompt_len"]["max"] + r_mix["output_len"]["max"] == 96
    assert CELL_NAME in next(m for m in spec["end_to_end"] if m["name"] == "serve_tokens_per_s")["workloads"]
    family = readers_of(spec, CELL_NAME)
    assert set(NEW_READERS) <= set(family) and all(m["moves"] == "serve_tokens_per_s" for m in family.values())
    # what reckons one head layout for every layer, or every layer as routed, is not asked of this cell
    assert not set(family) & {"ragged_attn_time_share", "ragged_attn_roofline", "ragged_kernel_call_us", "softmax_attn_time_share", "experts_hit_share"}


def test_the_adapter_builds_the_programs_model_and_says_both_head_layouts():
    body = load("benchmark", "configs", NAME + ".json")
    model, shape = files.build_model(files.overlay(body, body["rehearse"]))
    assert type(model).__mro__[1].__name__ == "HybridMoETransformerLM"
    assert (shape["num_layers"], shape["num_moe_layers"], shape["num_full_layers"], shape["num_window_layers"]) == (7, 6, 2, 5)
    assert (shape["num_experts"], shape["router_experts"], shape["window"]) == (4, 16, 8)
    _, full = files.build_model(body)
    assert (full["num_experts"], full["router_experts"], full["window"], full["full_kv_heads"], full["window_kv_heads"]) == (16, 256, 128, 4, 8)
    assert (full["qk_head_dim"], full["v_head_dim"], full["vocab_size"]) == (192, 128, 19072)


def test_the_reference_imports_nothing_of_the_program_and_refuses_another_block():
    path = os.path.join(ROOT, "benchmark", "reference", "mimo_v2_decoder.py")
    with open(path) as f:
        source = f.read()
    tree = ast.parse(source)
    imported = {n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)} | {a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names}
    assert not any(name.startswith(("deepspeed_tpu", "benchmark")) for name in imported), imported
    for stated in ("ASSUMED", "LEFT OUT", "vision and audio towers", "multi-token-prediction", "i - 128 < j <= i", "0.707", "rotate-half"):
        assert stated in source, stated
    ref = files.load_module("reference", "mimo_v2_decoder")
    body = load("benchmark", "configs", NAME + ".json")
    assert ref.arch_of(body["model"])["window"] == 128
    solar = load("benchmark", "configs", "solar-open2-250b-l4-ep8.json")
    with pytest.raises((ValueError, KeyError)):
        ref.arch_of(solar["model"])
    no_sinks = {"kwargs": {**body["model"]["kwargs"], "window_sinks": False}}
    with pytest.raises(ValueError, match="does not describe"):
        ref.arch_of(no_sinks)


# --- operations and bytes --------------------------------------------------------


def test_windowed_attention_ops_and_bytes_by_hand():
    # a decode row deep in a long context: one query, 128 keys seen of 2,000
    assert wpa.pairs(1, 2000, 128) == 128 and wpa.keys_read(1, 2000, 128) == 128
    assert wpa.pairs(1, 2000, None) == 2000 and wpa.keys_read(1, 2000, None) == 2000
    # a row shorter than the window sees all it has
    assert wpa.pairs(1, 50, 128) == 50 and wpa.keys_read(1, 50, 128) == 50
    # a chunk of 128 that ends at 640: each query sees 128 keys; the chunk reads 128 + 127 keys
    assert wpa.pairs(128, 640, 128) == 128 * 128 and wpa.keys_read(128, 640, 128) == 255
    # the first chunk: causal, 1 + 2 + ... + 128
    assert wpa.pairs(128, 128, 128) == 128 * 129 // 2 == wpa.pairs(128, 128, None)
    # a chunk that straddles the window's edge: queries at 100..131 of a window of 128
    assert wpa.pairs(32, 132, 128) == sum(min(p + 1, 128) for p in range(100, 132))
    # one step: 2 decode rows and a dead one, window layer of 8 KV heads, 64 query heads, 192 / 128
    rows = [(1, 2000), (1, 50), (0, 0)]
    ops, moved = wpa.ops_and_bytes(rows, 64, 8, 192, 128, 128)
    assert ops == 2 * (192 + 128) * (128 + 50) * 64
    assert moved == ((128 + 50) * 8 * (192 + 128) + 2 * 64 * (192 + 128)) * 2
    seconds, bound = wpa.min_seconds(rows, 64, 8, 192, 128, PEAK, 128)
    assert bound == "memory" and seconds == pytest.approx(moved / 819e9)
    # the same rows in a full layer of 4 KV heads read every key
    _, full = wpa.ops_and_bytes(rows, 64, 4, 192, 128, None)
    assert full == (2050 * 4 * 320 + 2 * 64 * 320) * 2


# --- the readers on a synthetic trace --------------------------------------------

WINDOW_KERNEL = 'custom-call(s32[64,64] %a, s32[64] %b, s32[64] %c, bf16[64,8,10,256] %x, f32[8,8,128] %s), custom_call_target="tpu_custom_call"'
FULL_KERNEL = 'custom-call(s32[64,64] %a, s32[64] %b, s32[64] %c, bf16[64,4,18,256] %x), custom_call_target="tpu_custom_call"'
STACKS = {
    WINDOW_KERNEL: "jit(paged_ragged_r64_w1)/jit(main)/while/body/window_attention/ragged_paged_attention/pallas_call:",
    FULL_KERNEL: "jit(paged_ragged_r64_w1)/jit(main)/while/body/attention/ragged_paged_attention/pallas_call:",
    "fusion.window_proj": "jit(paged_ragged_r64_w1)/jit(main)/while/body/window_attention/dot_general:",
    "fusion.full_proj": "jit(paged_ragged_r64_w1)/jit(main)/attention/dot_general:",
    "fusion.experts": "jit(paged_ragged_r64_w1)/jit(main)/while/body/mlp/moe_experts/dot_general:",
}
MODEL = {"num_heads": 64, "qk_head_dim": 192, "v_head_dim": 128, "window": 128, "num_window_layers": 5, "num_full_layers": 2,
         "window_kv_heads": 8, "full_kv_heads": 4, "num_moe_layers": 6, "num_experts": 16, "num_layers": 7}
ROWS = [(1, 2000)] * 60 + [(1, 50)] * 4


def synthetic(monkeypatch, stacks=STACKS, spans=()):
    """One traced step: five window-layer kernel calls of 100 us, two
    full-layer calls of 600 us, projections and an expert matmul; 3.2 ms busy."""
    t, events = 0.0, []
    for name, n, us in ((WINDOW_KERNEL, 5, 100), (FULL_KERNEL, 2, 600), ("fusion.window_proj", 5, 60), ("fusion.full_proj", 2, 60), ("fusion.experts", 6, 180)):
        for _ in range(n):
            events.append(tr.Event(name, t, t + us * 1e-6))
            t += us * 1e-6
    dev = tr.DeviceTrace(0, events, events, [], [], [(0.0, t)])
    trace = tr.ReducedTrace(0.0, t, [dev], [])
    names = op_scopes.OpNames({"/device:TPU:0": {name: [{op_scopes.NAME_STACK: stack}] for name, stack in stacks.items()}})
    monkeypatch.setattr(op_scopes, "of_cell", lambda cell: names)
    monkeypatch.setattr(program_spans, "of_cell", lambda trace, cell: list(spans))
    return trace, t


def test_the_five_readers_on_a_synthetic_trace(monkeypatch):
    settle = [program_spans.Span("serve.settle", 0.0, 1e-4, "t", {"moe_experts_hit": hit, "moe_assignments": 30}) for hit in (80, 86)]
    trace, busy = synthetic(monkeypatch, spans=settle)
    counters = {"model": MODEL, "rows_log": [{"mixed": False, "rows": ROWS}]}
    assert reader("window_attn_time_share").value(trace, counters, CELL) == pytest.approx(100 * (5 * 100 + 5 * 60) * 1e-6 / busy)
    assert reader("full_attn_time_share").value(trace, counters, CELL) == pytest.approx(100 * (2 * 600 + 2 * 60) * 1e-6 / busy)
    least_window = wpa.min_seconds(ROWS, 64, 8, 192, 128, PEAK, 128)[0]
    least_full = wpa.min_seconds(ROWS, 64, 4, 192, 128, PEAK, None)[0]
    assert reader("window_attn_roofline").value(trace, counters, CELL) == pytest.approx(100 * 5 * least_window / 500e-6)
    assert reader("full_attn_roofline").value(trace, counters, CELL) == pytest.approx(100 * 2 * least_full / 1200e-6)
    # the kernel's own time, not the scope's: the projections inside the scope are not in the denominator
    assert wpa.scoped_kernel_time(op_scopes.of_cell(CELL), trace.devices[0], "window_attention") == (pytest.approx(500e-6), 5)
    for r in ("window_attn_roofline", "full_attn_roofline"):
        assert 0 < reader(r).value(trace, counters, CELL) < 100
    assert reader("held_experts_hit_share").value(trace, counters, CELL) == pytest.approx(100 * (80 + 86) / (2 * 6 * 16))


def test_the_readers_find_nothing_where_their_scope_or_model_is_absent(monkeypatch):
    counters = {"model": MODEL, "rows_log": [{"mixed": False, "rows": ROWS}]}
    for name in NEW_READERS:
        assert reader(name).value(None, counters, CELL) is None  # no trace
    # a program without the scopes (the parent's): the kernel is traced, the scope is not named
    unscoped = {k: v.replace("window_attention/", "").replace("attention/", "") for k, v in STACKS.items()}
    trace, _ = synthetic(monkeypatch, stacks=unscoped)
    for name in NEW_READERS:
        assert reader(name).value(trace, counters, CELL) is None, name
    # another model's shape (no window layers, every layer routed): nothing, even with the scopes there
    trace, _ = synthetic(monkeypatch, spans=[program_spans.Span("serve.settle", 0.0, 1e-4, "t", {"moe_experts_hit": 80})])
    other = {"model": {"num_heads": 64, "num_kv_heads": 8, "head_dim": 128, "num_layers": 4, "num_experts": 40}, "rows_log": counters["rows_log"]}
    for name in NEW_READERS:
        assert reader(name).value(trace, other, CELL) is None, name


# --- the cell, rehearsed -----------------------------------------------------------


def _run(*argv):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, *argv], cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)


def test_the_cell_rehearses_correct_with_a_trace_and_a_large_seed():
    done = _run("benchmark/run.py", "--workload", CELL_NAME, "--seed", "3000000019", "--seconds", "2", "--trace", "1", "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed" and last["correct"] is True and last["failed"] == 0
    assert "serve.compiles_in_window" in last["metric_names"]


def test_the_logits_tool_rehearses():
    done = _run("benchmark/tools/mimo_logits_check.py", "--rehearse", "--seed", "5")
    assert done.returncode == 0, done.stderr[-2000:]
    report = json.loads(done.stdout.strip().splitlines()[-1])
    assert report["within_limits"] is True
    assert set(report["controls_refused"]) >= {"no_sink", "window_127", "window_129", "window_theta_1e7", "no_value_scale", "15_of_16_experts", "experts_fp8"}
