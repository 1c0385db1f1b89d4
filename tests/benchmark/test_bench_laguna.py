"""What PR 45 added to the benchmark for Laguna-S-2.1: the configuration file
against the catalog row's published keys, its cuts and floors and its memory
arithmetic, the reference's independence, the two per-kind rooflines by hand
for two rows at 48 and 72 query heads, the two new readers and the accepted
``full_attn_roofline`` (the full layers' 48 heads are the model's
``num_heads``, so the accepted reader reckons them as they are) on a synthetic
trace (device events with the name stacks the program's scopes give them) and
where scope or model is absent, and the traffic file against the engine's
``max_seq_len``. The cell's rehearsal is ``test_bench_rehearsal.py``'s, which
finds every cell by itself. Entries are found by search
(``spec_lookup.readers_of``): neither a count nor a position is pinned."""

import ast
import json
import os

import pytest

from benchmark import files, op_scopes, program_spans
from benchmark import trace_reduce as tr
from benchmark.kernels import windowed_paged_attention as wpa
from tests.benchmark.spec_lookup import readers_of

HERE = os.path.dirname(__file__)
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
NAME, CELL_NAME = "laguna-s-2.1-l9-ep16", "laguna_s21_long_decode"
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
CELL = {"name": "a_serving_cell", "config": {"engine": {"kind": "serve"}}, "peak": PEAK}
NEW_READERS = ["window_gqa_roofline", "head_gate_time_share"]
SHARED_READERS = [
    "device_idle_share", "decode_step_device_ms", "mixed_step_device_ms", "compiles_in_window", "step_host_share", "mixed_step_token_fill",
    "step_admit_ms", "step_pack_ms", "step_dispatch_ms", "step_settle_ms", "rows_per_step", "kv_pages_in_use_share", "expert_ffn_time_share",
    "expert_ffn_roofline", "moe_route_time_share", "max_expert_load", "held_assignments_share", "held_experts_hit_share",
    "window_attn_time_share", "full_attn_time_share", "full_attn_roofline", "exec_gap_ms", "host_turnaround_ms", "enqueue_call_ms", "run_ahead_share",
]
# config.json of poolside/Laguna-S-2.1 as the model-configs catalog holds it
FULL_ROPE = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128, "original_max_position_embeddings": 8192, "beta_slow": 1,
             "beta_fast": 32, "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5}
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072, "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128, "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
    "shared_expert_intermediate_size": 1024, "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 512,
    "rope_parameters": {"full_attention": FULL_ROPE, "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}},
    "layer_types": ["full_attention" if i % 4 == 0 else "sliding_attention" for i in range(48)],
    "moe_apply_router_weight_on_input": False, "mlp_layer_types": ["dense"] + ["sparse"] * 47, "gating_types": ["per_head"] * 48,
    "moe_routed_scaling_factor": 2.5, "num_attention_heads_per_layer": [48 if i % 4 == 0 else 72 for i in range(48)],
    "moe_router_logit_softcapping": 0,
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
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["reduced"] == body["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert body["source"] == entry["source"] == "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"
    differs = {k for k, v in PUBLISHED.items() if k not in body or body[k] != v}
    assert differs == set(body["reduced"])
    assert (body["num_hidden_layers"], body["num_experts"], body["vocab_size"]) == (9, 16, 12544)
    # the published counts stand beside the cuts, and the floors of a model_config PR hold
    assert (body["published"]["num_hidden_layers"], body["published"]["num_experts"], body["published"]["vocab_size"]) == (48, 256, 100352)
    assert body["num_experts"] >= 8 and body["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    kwargs = body["model"]["kwargs"]
    # the leading dense layer counted once, then two whole periods of the published pattern: layers 1-8, 3:1
    assert kwargs["layer_types"] == ["softmax"] + (["window"] * 3 + ["softmax"]) * 2 and kwargs["leading_dense_layers"] == len(PUBLISHED["mlp_only_layers"])
    assert [{"softmax": "full_attention", "window": "sliding_attention"}[t] for t in kwargs["layer_types"]] == PUBLISHED["layer_types"][:9]
    assert len(kwargs["layer_types"]) - 1 >= 4
    assert (kwargs["moe_router_experts"], kwargs["num_experts"], kwargs["moe_expert_share"], kwargs["moe_top_k"]) == (256, 16, [0, 16], 10)
    # every width, both head counts, the window and both rotary terms as published
    widths = {"hidden_size": 3072, "intermediate_size": 12288, "expert_intermediate_size": 1024, "num_heads": 48, "window_num_heads": 72,
              "num_kv_heads": 8, "head_dim": 128, "window": 512, "rope_dim": 64, "window_rope_dim": 128}
    assert {k: kwargs[k] for k in widths} == widths
    assert {kwargs["num_heads"], kwargs["window_num_heads"]} == set(PUBLISHED["num_attention_heads_per_layer"])
    full, sliding = PUBLISHED["rope_parameters"]["full_attention"], PUBLISHED["rope_parameters"]["sliding_attention"]
    assert (kwargs["rope_theta"], kwargs["rope_yarn_factor"], kwargs["rope_yarn_original_positions"], kwargs["rope_yarn_beta_fast"],
            kwargs["rope_yarn_beta_slow"], kwargs["rope_yarn_attention_factor"]) == tuple(
        full[k] for k in ("rope_theta", "factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "attention_factor"))
    assert kwargs["rope_dim"] == full["partial_rotary_factor"] * 128 and kwargs["window_rope_dim"] == sliding["partial_rotary_factor"] * 128
    assert kwargs["window_rope_theta"] == sliding["rope_theta"]
    for published, ours in body["model"]["published_keys"].items():
        if published not in body["reduced"]:
            assert kwargs[ours] == PUBLISHED[published], published
    assert (kwargs["position"], kwargs["moe_scoring"], kwargs["moe_select_bias"], kwargs["moe_shared_experts"], kwargs["moe_routed_scaling"],
            kwargs["attn_head_gate"]) == ("rope", "softmax", False, 1, 2.5, True)
    # each point the issue lists as assumed, with what a checkpoint would settle
    assert {"gate", "router_scoring", "shared_expert", "qk_norm", "rotary", "yarn", "window_edge"} <= set(body["assumed"])
    assert all("settles" in body["assumed"][k] for k in ("gate", "router_scoring", "shared_expert", "qk_norm", "rotary", "yarn"))
    assert "sixteen v5e chips share each layer" in body["deployment"]
    check = body["engine"]["check"]
    assert check["max_context"] == 2048 and check["sample"] == 4 and "float8" in check["why"]
    assert body["model"]["seeded"]["rescaled"] == [] and "gate" in body["model"]["seeded"]["why"]


def test_the_memory_arithmetic_is_the_programs():
    """The deployment text's numbers, recomputed from the program's own
    ``init`` shapes and the pool's layout."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference.kv_pool import key_lanes, window_ring_pages

    body = load("benchmark", "configs", NAME + ".json")
    model, shape = files.build_model(body)
    cfg = model.config
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), None))
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    periods = shapes["periods"]
    assert count(shapes["leading"][0]["mixer"]) == count(periods["softmax"]) // 2 == 44_190_720  # a full mixer, 48 heads
    assert count(periods["window"]) // 6 == 63_138_816  # a window mixer, 72 heads
    assert periods["softmax"]["wg_head"].shape[-2:] == (3072, 48) and periods["window"]["wg_head"].shape[-2:] == (3072, 72)
    assert count(periods["moe"]["experts"]) // (8 * 16) == count(periods["moe"]["shared"]) // 8 == 9_437_184
    assert count(shapes) == 1_991_500_800 and "1,991.5M = 3.98 GB" in body["deployment"]
    paged = body["engine"]["init_inference"]["paged_kv"]
    pages = paged["max_slots"] * (paged["max_seq_len"] // paged["page_size"]) + 1
    ring = window_ring_pages(cfg.window, paged["page_size"], paged["prefill_chunk"])
    assert (pages, ring, key_lanes(cfg.head_dim)) == (4097, 10, 128)
    token = cfg.num_kv_heads * (cfg.head_dim + cfg.v_head_dim) * 2
    full = pages * paged["page_size"] * cfg.layers_of("softmax") * token
    rings = (1 + paged["max_slots"] * ring) * paged["page_size"] * cfg.layers_of("window") * token
    assert token == 4096 and round(full / 1e9, 2) == 3.22 and round(rings / 1e9, 2) == 1.01
    assert "3.22 GB" in body["deployment"] and "1.01 GB" in body["deployment"] and "10 pages a slot" in body["deployment"]
    # over the driver's floor for a new cell: a quarter of the chip's 16 GB, resident alone
    assert (2 * count(shapes) + full + rings) / 16e9 > 0.5
    assert (shape["num_full_layers"], shape["num_window_layers"], shape["num_moe_layers"], shape["window"]) == (3, 6, 8, 512)
    assert (shape["full_heads"], shape["window_heads"], shape["full_kv_heads"], shape["window_kv_heads"]) == (48, 72, 8, 8)
    assert (shape["num_experts"], shape["router_experts"], shape["experts_per_token"], shape["qk_head_dim"], shape["v_head_dim"]) == (16, 256, 10, 128, 128)


def test_the_traffic_fills_the_engines_max_seq_len_and_the_cell_is_in_its_lists():
    spec = load("BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == CELL_NAME)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "long_decode", 1) and len(cell["why"]) <= 200
    mix = load("benchmark", "traffic", "long_decode.json")
    body = load("benchmark", "configs", NAME + ".json")
    paged = body["engine"]["init_inference"]["paged_kv"]
    assert mix["kind"] == "closed_loop" and mix["clients"] == "max_slots" and paged["max_slots"] == 64
    assert paged["max_seq_len"] == mix["prompt_len"]["max"] + mix["output_len"]["max"] == 4096
    # every prompt is shorter than half the check's context: each sampled stream holds >= 1,024 served tokens, past the window
    assert 2 * mix["prompt_len"]["max"] <= body["engine"]["check"]["max_context"] and body["model"]["kwargs"]["window"] < 1024
    rehearse = files.load_cell(spec, CELL_NAME, rehearse=True)
    r_paged, r_mix = rehearse["config_file"]["engine"]["init_inference"]["paged_kv"], rehearse["traffic_file"]
    assert r_paged["max_seq_len"] == r_mix["prompt_len"]["max"] + r_mix["output_len"]["max"] == 96
    assert CELL_NAME in next(m for m in spec["end_to_end"] if m["name"] == "serve_tokens_per_s")["workloads"]
    family = readers_of(spec, CELL_NAME)
    assert set(family) == set(NEW_READERS) | set(SHARED_READERS) and all(m["moves"] == "serve_tokens_per_s" for m in family.values())
    for name in NEW_READERS:
        assert family[name]["name"] == "serve." + name and family[name]["workloads"] == [CELL_NAME], name
        assert (family[name]["unit"], family[name]["source"]) == ("%", "device_trace")
    assert (family["window_gqa_roofline"]["layer"], family["full_attn_roofline"]["layer"], family["head_gate_time_share"]["layer"]) == ("kernels", "kernels", "model")
    # what reckons the model's ``num_heads`` for the window layers, one head layout for every layer, or every layer as routed, is not asked of this cell
    assert not set(family) & {"window_attn_roofline", "ragged_attn_time_share", "ragged_attn_roofline", "ragged_kernel_call_us", "experts_hit_share"}


def test_the_reference_imports_nothing_of_the_program_and_refuses_another_block():
    path = os.path.join(ROOT, "benchmark", "reference", "laguna_decoder.py")
    with open(path) as f:
        source = f.read()
    tree = ast.parse(source)
    imported = {n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)} | {a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names}
    assert not any(name.startswith(("deepspeed_tpu", "benchmark")) for name in imported), imported
    for stated in ("ASSUMED", "LEFT OUT", "i - 512 < j <= i", "1.4852", "rotate-half", "one scalar a head", 'default_matmul_precision("highest")'):
        assert stated in source, stated
    ref = files.load_module("reference", "laguna_decoder")
    body = load("benchmark", "configs", NAME + ".json")
    arch = ref.arch_of(body["model"])
    assert dict(arch["heads"]) == {"softmax": 48, "window": 72} and arch["window"] == 512 and arch["attention_factor"] == 1.4852030263919618
    with pytest.raises((ValueError, KeyError)):
        ref.arch_of(load("benchmark", "configs", "mimo-v2.5-l7-ep16.json")["model"])
    with pytest.raises(ValueError, match="does not describe"):
        ref.arch_of({"kwargs": {**body["model"]["kwargs"], "attn_head_gate": False}})
    with pytest.raises(ValueError, match="does not describe"):
        ref.arch_of({"kwargs": {**body["model"]["kwargs"], "moe_scoring": "sigmoid"}})


# --- the two rooflines by hand ------------------------------------------------------

MODEL = {"num_heads": 48, "full_heads": 48, "window_heads": 72, "qk_head_dim": 128, "v_head_dim": 128, "window": 512, "num_window_layers": 6,
         "num_full_layers": 3, "window_kv_heads": 8, "full_kv_heads": 8, "num_moe_layers": 8, "num_experts": 16, "num_layers": 9}
TWO_ROWS = [(1, 2000), (1, 300)]


def test_the_two_rooflines_by_hand_for_two_rows_at_48_and_72_heads():
    """A decode row at 2,000 keys and one at 300, bf16. A window layer reads
    512 + 300 keys of 8 KV heads x 256 numbers and 72 query heads' q and o; a
    full layer reads 2,300 keys and 48 heads' q and o. Both are bound by
    memory (9 and 6 operations a byte of keys against the chip's 240)."""
    window_bytes = ((512 + 300) * 8 * 256 + 2 * 72 * 256) * 2
    full_bytes = (2300 * 8 * 256 + 2 * 48 * 256) * 2
    assert wpa.ops_and_bytes(TWO_ROWS, 72, 8, 128, 128, 512) == (2 * 256 * (512 + 300) * 72, window_bytes)
    assert wpa.ops_and_bytes(TWO_ROWS, 48, 8, 128, 128, None) == (2 * 256 * 2300 * 48, full_bytes)
    assert wpa.min_seconds(TWO_ROWS, 72, 8, 128, 128, PEAK, 512) == (pytest.approx(window_bytes / 819e9), "memory")
    assert wpa.min_seconds(TWO_ROWS, 48, 8, 128, 128, PEAK, None) == (pytest.approx(full_bytes / 819e9), "memory")
    # a 128-token chunk behind 512 keys is bound by compute in a window layer
    assert wpa.min_seconds([(128, 640)], 72, 8, 128, 128, PEAK, 512)[1] == "compute"


# --- the readers on a synthetic trace --------------------------------------------

WINDOW_KERNEL = 'custom-call(s32[64,64] %a, s32[64] %b, s32[64] %c, bf16[64,8,11,128] %x), custom_call_target="tpu_custom_call"'
FULL_KERNEL = 'custom-call(s32[64,64] %a, s32[64] %b, s32[64] %c, bf16[64,8,8,128] %x), custom_call_target="tpu_custom_call"'
STACKS = {
    WINDOW_KERNEL: "jit(paged_ragged_r64_w1)/jit(main)/while/body/window_attention/ragged_paged_attention/pallas_call:",
    FULL_KERNEL: "jit(paged_ragged_r64_w1)/jit(main)/while/body/attention/ragged_paged_attention/pallas_call:",
    "fusion.window_proj": "jit(paged_ragged_r64_w1)/jit(main)/while/body/window_attention/dot_general:",
    "fusion.window_gate": "jit(paged_ragged_r64_w1)/jit(main)/while/body/window_attention/head_gate/dot_general:",
    "fusion.full_gate": "jit(paged_ragged_r64_w1)/jit(main)/attention/head_gate/logistic:",
    "fusion.experts": "jit(paged_ragged_r64_w1)/jit(main)/while/body/mlp/moe_experts/dot_general:",
}


def synthetic(monkeypatch, stacks=STACKS):
    """One traced step: six window-layer kernel calls of 100 us, three
    full-layer calls of 300 us, projections, gates and an expert matmul."""
    t, events = 0.0, []
    for name, n, us in ((WINDOW_KERNEL, 6, 100), (FULL_KERNEL, 3, 300), ("fusion.window_proj", 6, 60), ("fusion.window_gate", 6, 5),
                        ("fusion.full_gate", 3, 4), ("fusion.experts", 8, 180)):
        for _ in range(n):
            events.append(tr.Event(name, t, t + us * 1e-6))
            t += us * 1e-6
    dev = tr.DeviceTrace(0, events, events, [], [], [(0.0, t)])
    trace = tr.ReducedTrace(0.0, t, [dev], [])
    names = op_scopes.OpNames({"/device:TPU:0": {name: [{op_scopes.NAME_STACK: stack}] for name, stack in stacks.items()}})
    monkeypatch.setattr(op_scopes, "of_cell", lambda cell: names)
    monkeypatch.setattr(program_spans, "of_cell", lambda trace, cell: [])
    return trace, t


def test_the_new_readers_and_the_accepted_full_roofline_on_a_synthetic_trace(monkeypatch):
    trace, busy = synthetic(monkeypatch)
    counters = {"model": MODEL, "rows_log": [{"mixed": False, "rows": TWO_ROWS}]}
    least_window = wpa.min_seconds(TWO_ROWS, 72, 8, 128, 128, PEAK, 512)[0]
    least_full = wpa.min_seconds(TWO_ROWS, 48, 8, 128, 128, PEAK, None)[0]
    # each kind's own heads, its own layers, over the kernel's own time in its own scope (not the projections')
    assert reader("window_gqa_roofline").value(trace, counters, CELL) == pytest.approx(100 * 6 * least_window / 600e-6)
    # the full layers' 48 heads are the shape's ``num_heads``: the accepted reader's number is theirs
    assert reader("full_attn_roofline").value(trace, counters, CELL) == pytest.approx(100 * 3 * least_full / 900e-6)
    # the accepted window reader would reckon 48 heads for the window layers too: not this cell's
    assert reader("window_attn_roofline").value(trace, counters, CELL) < reader("window_gqa_roofline").value(trace, counters, CELL)
    assert reader("head_gate_time_share").value(trace, counters, CELL) == pytest.approx(100 * (6 * 5 + 3 * 4) * 1e-6 / busy)
    for name in ("window_gqa_roofline", "full_attn_roofline"):
        assert 0 < reader(name).value(trace, counters, CELL) < 100


def test_the_readers_find_nothing_where_their_scope_or_model_is_absent(monkeypatch):
    counters = {"model": MODEL, "rows_log": [{"mixed": False, "rows": TWO_ROWS}]}
    for name in NEW_READERS:
        assert reader(name).value(None, counters, CELL) is None  # no trace
    # a program without the scopes (the parent's): the kernel is traced, the scopes are not named
    unscoped = {k: v.replace("window_attention/", "").replace("attention/", "").replace("head_gate/", "") for k, v in STACKS.items()}
    trace, _ = synthetic(monkeypatch, stacks=unscoped)
    for name in NEW_READERS:
        assert reader(name).value(trace, counters, CELL) is None, name
    # another model's shape (one head count for both kinds: MiMo's): no roofline, even with the scopes there
    trace, _ = synthetic(monkeypatch)
    other = {"model": {k: v for k, v in MODEL.items() if k not in ("full_heads", "window_heads")}, "rows_log": counters["rows_log"]}
    assert reader("window_gqa_roofline").value(trace, other, CELL) is None
    # no rows log (a run without a traced slice): nothing
    assert reader("window_gqa_roofline").value(trace, {"model": MODEL}, CELL) is None


# --- the tool that says who lost a long step's time ------------------------------


@pytest.mark.parametrize("child,thread,collections,who", [
    ([(10.002, 0.110)], [(10.002, 0.110)], [], "machine"),  # a process that ran nothing of ours stood still too
    ([], [(10.004, 0.105)], [(10.004, 0.104, 2)], "interpreter (a full collection)"),
    ([], [(10.004, 0.105)], [(10.004, 0.002, 0)], "interpreter"),
    ([], [], [(10.050, 0.001, 0)], "wait"),  # everyone else ran: the main thread waited, for the device if the phase is the fetch
    ([(9.0, 0.110), (10.120, 0.110)], [], [], "wait"),  # pauses beside the call are not the call's
], ids=["machine", "full_collection", "interpreter", "wait", "pause_elsewhere"])
def test_step_stalls_blames_a_long_call_on_who_saw_the_pause(child, thread, collections, who):
    tool = files.load_module("tools", "step_stalls")
    assert tool.blame(10.0, 10.125, child, thread, collections) == who
    assert tool.overlapping([(9.9, 0.2)], 10.0, 10.125) == pytest.approx(0.1)
