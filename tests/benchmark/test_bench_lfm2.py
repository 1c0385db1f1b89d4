"""What PR 63 added to the benchmark for LFM2-24B-A2B: the configuration file
against the catalog row's published keys (every one verbatim but
``num_experts``, the one key of ``reduced``; all 40 layers), the cut's
arithmetic against the program's own shapes, the adapter's shape (conv keys
AND expert keys) and its three rescalings by leaf name, the reference's
independence and what it refuses, the new kernel file's counts at this model's
shapes by hand, the three new readers on recorded traces (which have no conv
scope: nothing to read, no raise) and on a stand-in trace, the cell in its
readers' lists by name, and the rehearsals of the cell (traced, a large seed)
and of the logits tool. Entries are found by search: neither a count of cells
nor a position in a list is pinned."""

import ast
import dataclasses
import json
import os
import subprocess
import sys

import pytest

from benchmark import files, op_scopes
from benchmark import trace_reduce as tr
from benchmark.kernels import grouped_expert_matmul as gmm
from benchmark.kernels import short_conv_mixer as conv
from tests.benchmark.spec_lookup import readers_of

HERE = os.path.dirname(__file__)
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
NAME, CELL_NAME = "lfm2-24b-a2b-ep8", "lfm2_24b_decode_heavy"
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
CELL = {"name": "a_serving_cell", "config": {"engine": {"kind": "serve"}}, "peak": PEAK}
GRANITE = os.path.join(HERE, "data", "granite_tpu.xplane.pb")  # a model with an ``attention`` scope and no conv layer, recorded on the chip (PR 52)
NEW_READERS = ["conv_mixer_time_share", "conv_attn_time_share", "conv_mixer_roofline"]
EXPERT_READERS = ["expert_ffn_time_share", "expert_ffn_roofline", "moe_route_time_share", "max_expert_load", "held_assignments_share", "held_experts_hit_share"]
SHARED_READERS = [
    "device_idle_share", "decode_step_device_ms", "mixed_step_device_ms", "step_host_share", "kv_pages_in_use_share", "compiles_in_window",
    "step_admit_ms", "step_pack_ms", "step_dispatch_ms", "step_settle_ms", "rows_per_step", "mixed_step_token_fill",
    "exec_gap_ms", "host_turnaround_ms", "enqueue_call_ms", "run_ahead_share",
]
LAYER_TYPES = ["full_attention" if i % 4 == 2 else "conv" for i in range(40)]
# config.json of LiquidAI/LFM2-24B-A2B as the model-configs catalog holds it
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776, "layer_types": LAYER_TYPES,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536,
}
REDUCED = {"num_experts": 8}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def reader(name):
    return files.load_module("layer_metrics", name)


# --- the configuration ---------------------------------------------------------


def test_configuration_holds_the_published_keys_all_forty_layers_and_one_cut():
    body = load("benchmark", "configs", NAME + ".json")
    entry = next(c for c in load("BENCHMARK.json")["configs"] if c["name"] == NAME)
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and len(entry["why"]) <= 200
    assert entry["reduced"] == body["reduced"] == ["num_experts"]
    assert body["source"] == entry["source"] == "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    assert {k: body[k] for k in PUBLISHED} == {**PUBLISHED, **REDUCED}  # every other key verbatim, layer_types whole
    assert body["published"]["num_experts"] == 64 and "64" in body["published"]["router_width"]
    assert LAYER_TYPES.count("conv") == 30 and [i for i, t in enumerate(LAYER_TYPES) if t != "conv"] == list(range(2, 40, 4)) and LAYER_TYPES[-1] == "conv"
    kwargs = body["model"]["kwargs"]
    assert kwargs["layer_types"] == [{"conv": "conv", "full_attention": "softmax"}[t] for t in LAYER_TYPES]
    widths = {"hidden_size": 2048, "intermediate_size": 11776, "expert_intermediate_size": 1536, "num_heads": 32, "num_kv_heads": 8, "head_dim": 64,
              "conv_kernel": 3, "moe_top_k": 4, "moe_router_experts": 64}
    assert {k: kwargs[k] for k in widths} == widths  # no width cut: the router's 64 outputs and its 4 a token as published
    assert (kwargs["num_experts"], kwargs["moe_expert_share"], kwargs["vocab_size"], kwargs["num_layers"], kwargs["leading_dense_layers"]) == (8, [0, 8], 65536, 40, 2)
    assert (kwargs["activation"], kwargs["position"], kwargs["rope_theta"], kwargs["tie_embeddings"], kwargs["qk_norm"]) == ("swiglu", "rope", 1e6, True, "head")
    assert (kwargs["moe_scoring"], kwargs["moe_select_bias"], kwargs["moe_routed_scaling"], kwargs["moe_norm_topk_prob"], kwargs["moe_shared_experts"], kwargs["use_bias"]) == ("sigmoid", True, 1.0, True, 0, False)
    for published, ours in body["model"]["published_keys"].items():
        assert kwargs[ours] == body[published], published
    assert kwargs["rope_theta"] == body["rope_parameters"]["rope_theta"]  # a nested group and the renamed kinds: not in the table, held here
    assert {"head_dim", "qk_norm", "rope", "tied_head", "conv", "conv_state", "router", "experts", "serving_max_seq_len", "seeded", "left_out"} <= set(body["assumed"])
    assert "1e-6" in body["assumed"]["router"] and "bare sum" in body["assumed"]["router"] and "AFTER B *" in body["assumed"]["conv_state"]
    assert "WHOLE" in body["assumed"]["conv"] and "null" in body["assumed"]["head_dim"] and "BEFORE the rotation" in body["assumed"]["qk_norm"]
    assert "eight v5e chips" in body["deployment"] and "NO exchange" in body["deployment"]
    paged = body["engine"]["init_inference"]["paged_kv"]
    assert (paged["page_size"], paged["max_slots"], paged["prefill_chunk"], paged["max_seq_len"], paged["num_pages"]) == (64, 64, 128, 1536, 0)
    check = body["engine"]["check"]
    assert check["sample"] == 4 and check["max_context"] == 1536 and 0 < check["mean_logit_gap"] < check["logit_margin"]
    for stated in ("float8", "tail not shifted", "swapped", "head norm left out", "trailing layers left out"):
        assert stated in check["why"], stated
    seeded = body["model"]["seeded"]
    assert set(seeded) == {"out_std", "qk_std", "qk_norm_scale", "why"} and "selection bias" in seeded["why"] and "TIED" in seeded["why"]
    small = body["rehearse"]["model"]["kwargs"]  # the rehearsal holds two leading layers, two whole periods and the partial one
    assert small["layer_types"] == ["conv", "conv"] + ["softmax", "conv", "conv", "conv"] * 2 + ["softmax", "conv"] and small["num_layers"] == 12


def test_the_cuts_arithmetic_is_the_programs():
    """The deployment text's numbers, recomputed from the program's own
    ``init`` shapes and the pool's layout."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference import hybrid_decode
    from deepspeed_tpu.inference.kv_pool import heads_per_group

    body = load("benchmark", "configs", NAME + ".json")
    model, shape = files.build_model(body)
    cfg = model.config
    assert cfg.period == ("softmax", "conv", "conv", "conv") and cfg.num_periods == 9 and cfg.remainder == ("softmax", "conv")  # nine scanned, two behind
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), None))
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    periods = shapes["periods"]
    a_conv, an_attention, a_routed = count(periods["conv"]) // 27, count(periods["softmax"]) // 9, count(periods["moe"]) // 36
    assert (a_conv, an_attention, a_routed) == (16_785_408, 10_487_936, 75_630_656)
    assert [count(t["mixer"]) for t in shapes["leading"]] == [a_conv] * 2 and [count(t["ffn"]) for t in shapes["leading"]] == [72_353_792] * 2
    assert [count(t["mixer"]) for t in shapes["trailing"]] == [an_attention, a_conv] and [count(t["moe"]) for t in shapes["trailing"]] == [a_routed] * 2
    an_expert = count(periods["moe"]["experts"]) // (36 * 8)
    assert an_expert == 3 * 2048 * 1536 and count(shapes["embed"]) == 65536 * 2048 and "lm_head" not in shapes
    assert count(shapes) == 30 * a_conv + 10 * an_attention + 2 * 72_353_792 + 38 * a_routed + 65536 * 2048 + 2048 == 3_761_333_888  # 7.52 GB in bf16
    # the published model whole, from the same per-layer counts: the name's 24B
    assert round((count(shapes) + 38 * 56 * an_expert) / 1e9, 2) == 23.84
    paged = body["engine"]["init_inference"]["paged_kv"]
    pages = paged["max_slots"] * (paged["max_seq_len"] // paged["page_size"]) + 1
    state_shape, tail_shape = hybrid_decode.state_shapes(cfg, paged["max_slots"])
    assert state_shape is None and tail_shape == (30, 65, 2, 16, 128) and pages == 1537  # 2,048 channels: 16 lane tiles, one sublane tile, whole
    assert heads_per_group(cfg.head_dim, cfg.v_head_dim, cfg.num_kv_heads) == 2  # heads of 64: two a lane tile
    tails = int(np.prod(tail_shape)) * 2
    kv = pages * paged["page_size"] * 10 * 2 * cfg.num_kv_heads * cfg.head_dim * 2
    assert (round(tails / 1e6, 1), round(kv / 1e9, 2), 10 * 2 * 8 * 64 * 2) == (16.0, 2.01, 20 * 1024)
    resident = 2 * count(shapes) + tails + kv
    assert round(resident / 1e9, 2) == 9.55 and resident / 16e9 > 0.5  # twice the floor of a quarter of the chip
    for stated in ("3,761,333,888", "7.52 GB", "2.01 GB", "16.0 MB", "9.55 GB", "20 KiB a token"):
        assert stated in body["deployment"], stated
    want = {"num_layers": 40, "num_conv_layers": 30, "num_attention_layers": 10, "num_moe_layers": 38, "num_linear_layers": 0, "num_ssm_layers": 0,
            "conv_channels": 2048, "conv_taps": 3, "conv_tail_bytes_per_row": 8192, "num_heads": 32, "num_kv_heads": 8, "head_dim": 64, "vocab_size": 65536,
            "num_experts": 8, "router_experts": 64, "experts_per_token": 4, "expert_intermediate_size": 1536, "expert_matrices": 3, "hidden_size": 2048}
    assert {k: shape[k] for k in want} == want


def test_the_traffic_fills_the_engines_max_seq_len_and_the_cell_is_in_its_lists():
    spec = load("BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == CELL_NAME)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "decode_heavy", 1) and len(cell["why"]) <= 200
    assert [w["name"] for w in spec["workloads"] if w["config"] == NAME] == [CELL_NAME]  # one cell, no second
    assert not os.path.exists(os.path.join(ROOT, "benchmark", "cells", CELL_NAME + ".json"))  # the mix unedited
    mix = load("benchmark", "traffic", "decode_heavy.json")
    paged = load("benchmark", "configs", NAME + ".json")["engine"]["init_inference"]["paged_kv"]
    assert mix["kind"] == "closed_loop" and mix["clients"] == "max_slots" and paged["max_slots"] == 64
    assert paged["max_seq_len"] == mix["prompt_len"]["max"] + mix["output_len"]["max"] == 1536
    rehearse = files.load_cell(spec, CELL_NAME, rehearse=True)
    r_paged, r_mix = rehearse["config_file"]["engine"]["init_inference"]["paged_kv"], rehearse["traffic_file"]
    assert r_paged["max_seq_len"] >= r_mix["prompt_len"]["max"] + r_mix["output_len"]["max"]
    assert CELL_NAME in next(m for m in spec["end_to_end"] if m["name"] == "serve_tokens_per_s")["workloads"]
    family = readers_of(spec, CELL_NAME)
    assert set(NEW_READERS + EXPERT_READERS + SHARED_READERS) == set(family)
    for r, m in family.items():
        assert m["moves"] == "serve_tokens_per_s"
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", r + ".py"))
    for r, layer, better in (("conv_mixer_time_share", "model", "lower"), ("conv_attn_time_share", "model", "lower"), ("conv_mixer_roofline", "kernels", "higher")):
        new = family[r]
        assert (new["name"], new["unit"], new["source"], new["layer"], new["better"], new["workloads"]) == ("serve." + r, "%", "device_trace", layer, better, [CELL_NAME])
    for r in EXPERT_READERS:  # with the models that hold a share of a sigmoid router's experts
        assert "nemotron3_nano_long_decode" in family[r]["workloads"]
    # what reckons num_layers calls of the ragged kernel, a linear, window, latent or state-space layer, or a shared expert is not asked of this cell
    assert not set(family) & {"ragged_attn_time_share", "ragged_attn_roofline", "ragged_kernel_call_us", "kda_state_roofline", "linear_attn_time_share",
                              "latent_attn_time_share", "window_attn_time_share", "state_cache_share", "ssm_time_share", "ssd_state_roofline",
                              "ssm_attn_time_share", "shared_expert_time_share", "softmax_attn_time_share"}


def test_the_adapter_builds_the_programs_model_and_rescales_three_kinds_of_leaf_wherever_they_lie():
    import jax
    import numpy as np

    body = load("benchmark", "configs", NAME + ".json")
    small = files.overlay(body, body["rehearse"])
    model, shape = files.build_model(small)
    assert type(model).__mro__[1].__name__ == "HybridMoETransformerLM"
    assert (shape["num_layers"], shape["num_conv_layers"], shape["num_attention_layers"], shape["num_moe_layers"]) == (12, 9, 3, 10)
    assert (shape["num_experts"], shape["router_experts"], shape["expert_matrices"], shape["conv_channels"], shape["conv_taps"]) == (2, 16, 3, 128, 3)
    init = lambda m: jax.jit(lambda key: m.init(key, None))(jax.random.PRNGKey(3))  # one program a model, not a kernel a leaf
    seeded, plain = init(model), init(type(model).__mro__[1](model.config))
    s = small["model"]["seeded"]
    out = s["out_std"] / (0.02 / (2 * 12) ** 0.5)
    want = {"wo": out, "w_out": out, "wq": s["qk_std"] / 0.02, "wk": s["qk_std"] / 0.02, "q_norm_scale": s["qk_norm_scale"], "k_norm_scale": s["qk_norm_scale"]}
    differing = set()
    for (path, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(seeded)[0], jax.tree_util.tree_flatten_with_path(plain)[0]):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            differing.add(jax.tree_util.keystr(path))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b) * want[path[-1].key], rtol=1e-6)
    # every leaf of those names and no other: in the period's stacks, the leading and the trailing layers' own leaves
    named = {jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_flatten_with_path(plain)[0] if path[-1].key in want}
    assert differing == named and {"['trailing'][0]['mixer']['q_norm_scale']", "['trailing'][1]['moe']['experts']['w_out']", "['leading'][0]['ffn']['w_out']",
                                   "['periods']['conv']['wo']", "['periods']['softmax']['wk']"} <= differing


def test_the_reference_imports_nothing_of_the_program_and_refuses_another_block():
    path = os.path.join(ROOT, "benchmark", "reference", "lfm2_moe_decoder.py")
    with open(path) as f:
        source = f.read()
    tree = ast.parse(source)
    imported = {n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)} | {a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names}
    assert not any(name.startswith(("deepspeed_tpu", "benchmark")) for name in imported), imported
    for stated in ("ASSUMED", "DEPARTURES", "THE SHARE", "NO bias, NO activation", "AFTER ``B *``", "BEFORE the rotation", "+ 1e-6", "CHOICE alone",
                   'default_matmul_precision("highest")', "LAST hidden state", "three shifted arrays"):
        assert stated in source, stated
    assert "silu(v" not in source and "ragged_dot" not in source and "argsort" not in source and "tail" not in source.split('"""', 2)[2]  # no cache, no sorted rows
    ref = files.load_module("reference", "lfm2_moe_decoder")
    body = load("benchmark", "configs", NAME + ".json")
    arch = ref.arch_of(body["model"])
    assert (arch["num_heads"], arch["num_kv_heads"], arch["head_dim"], arch["theta"], arch["taps"]) == (32, 8, 64, 1e6, 3)
    assert (arch["held"], arch["first_held"], arch["experts_per_token"], arch["routed_scaling"], arch["leading"]) == (8, 0, 4, 1.0, 2)
    assert ref.arch_of({"kwargs": {**body["model"]["kwargs"], "moe_expert_share": [5, 8]}})["first_held"] == 40  # another chip's share
    for other in ("granite-4.0-h-micro", "solar-open2-250b-l4-ep8", "nemotron-3-nano-30b-a3b-l16-ep2", "mistral-7b-v0.3-l16", "olmoe-1b-7b-0125-l12"):
        with pytest.raises((ValueError, KeyError)):
            ref.arch_of(load("benchmark", "configs", other + ".json")["model"])
    for wrong in ({"position": "none"}, {"qk_norm": None}, {"tie_embeddings": False}, {"moe_scoring": "softmax"}, {"moe_select_bias": False}, {"rope_dim": 32},
                  {"moe_shared_experts": 1}, {"activation": "relu2"}, {"moe_router_experts": 32}, {"layer_types": ["ssm"] * 40}, {"attn_softmax_scale": 0.1}):
        with pytest.raises(ValueError, match="does not describe"):
            ref.arch_of({"kwargs": {**body["model"]["kwargs"], **wrong}})


# --- operations and bytes at this model's shapes ---------------------------------


def test_the_mixers_counts_for_a_narrow_and_a_mixed_step_worked_by_hand():
    """One conv layer of one step at the published shapes: W_in 2048 x 6144,
    W_out 2048 x 2048, three taps and the norm's scale, read ONCE (33.57 MB)
    whatever the rows; a live row's tail of 2 x 2,048 products in and out; a
    token's hidden state in and its output out. The thirty layers of a 64-row
    narrow step move the issue's 1.01 GB and are bound by memory; a chunk of
    128 tokens beside them adds its tokens' bytes and no second read of the
    weights. And the expert layers' grouped matmuls: an expert hit is 3 x
    2,048 x 1,536 x 2 bytes, ~63 of 8 x 8... of the 304 held a step."""
    H = 2048
    weights = (4 * H * H + 4 * H) * 2
    assert weights == 33_570_816
    tail, token = 2 * H * 2, 2 * H * 2
    assert conv.ops_and_bytes([(1, 900)], H) == (2 * H * 4 * H, weights + 2 * tail + token)
    narrow = [(1, 700)] * 64
    ops, moved = conv.ops_and_bytes(narrow, H)
    assert moved == weights + 64 * (2 * tail + token) and 1.00e9 < 30 * moved < 1.08e9  # the issue's 1.01 GB: the weights are 97% of it
    seconds, bound = conv.min_seconds(narrow, H, PEAK)
    assert bound == "memory" and seconds == pytest.approx(moved / 819e9) and 30 * seconds == pytest.approx(1.27e-3, rel=2e-2)
    mixed = narrow[:63] + [(128, 384), (0, 0)]  # a dead row needs nothing
    assert conv.ops_and_bytes(mixed, H)[1] == weights + 64 * 2 * tail + (63 + 128) * token
    assert conv.ops_and_bytes([(0, 0)] * 64, H)[0] == 0 and conv.ops_and_bytes(narrow, H, itemsize=4)[1] == 2 * moved
    assert conv.min_seconds([(128, 128)] * 64, H, PEAK)[1] == "compute"  # a house full of chunks: 8,192 tokens a layer
    an_expert = 3 * 2048 * 1536 * 2
    hit = round(38 * 8 * (1 - (60 / 64) ** 64))  # a held expert is missed with probability (60/64)^64 = 1.6%
    assert 38 * 8 - 8 <= hit <= 38 * 8 and an_expert == 18_874_368
    assignments = 38 * 64 * 4 // 8  # an eighth of a step's assignments are to held experts
    _, moved = gmm.ops_and_bytes(assignments, hit, 2048, 1536, matrices=3)
    assert 5.5e9 < moved < 5.8e9 and gmm.min_seconds(assignments, hit, 2048, 1536, PEAK, 3)[1] == "memory"  # the issue's 5.65 GB


# --- the new readers ------------------------------------------------------------------


def reduced(path, monkeypatch):
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: path)
    trace = tr.reduce_xplane(path, ("train_step", "server_step"), ("server_step",))
    return dataclasses.replace(trace, lo=float("-inf"), hi=float("inf"))  # no bench_slice: the whole trace


MODEL = {"num_conv_layers": 30, "conv_channels": 2048, "conv_taps": 3, "conv_tail_bytes_per_row": 8192}


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_finds_nothing_in_another_models_trace_and_nothing_without_one(monkeypatch, name):
    """What the parent, or any model without a conv layer, gives a new reader: None, and no raise."""
    trace = reduced(GRANITE, monkeypatch)
    rows = [{"mixed": False, "rows": [(1, 700)] * 64}]
    assert reader(name).value(trace, {"model": {"num_ssm_layers": 36}, "rows_log": rows}, CELL) is None  # granite's own shape: no conv key
    assert reader(name).value(None, {"model": MODEL, "rows_log": rows}, CELL) is None
    if name != "conv_attn_time_share":  # this trace has an ``attention`` scope and no ``conv_mixer`` one
        assert reader(name).value(trace, {"model": MODEL, "rows_log": rows}, CELL) is None


def test_the_new_readers_on_a_trace_whose_scope_stands_in_for_the_mixers(monkeypatch):
    """granite's recorded trace with its ``ssm_mixer`` scope read as ``conv_mixer``: the time share is the scope's
    device time over busy time, the attention share the ``attention`` scope's, and the roofline the thirty layers'
    least time for the logged rows over the scope's time."""
    trace = reduced(GRANITE, monkeypatch)
    names, dev = op_scopes.load(GRANITE), trace.devices[0]
    in_scope = op_scopes.in_scope
    monkeypatch.setattr(op_scopes, "in_scope", lambda stack, scope: in_scope(stack, "ssm_mixer" if scope == "conv_mixer" else scope))
    spent = op_scopes.scope_self_time(names, dev, "conv_mixer")
    assert spent == pytest.approx(op_scopes.scope_self_time(names, dev, "ssm_mixer")) and spent > 0
    rows = [{"mixed": False, "rows": [(1, 700)] * 64}, {"mixed": True, "rows": [(1, 700)] * 63 + [(128, 256)]}]
    counters = {"model": MODEL, "rows_log": rows}
    assert reader("conv_mixer_time_share").value(trace, counters, CELL) == pytest.approx(100.0 * spent / dev.busy_s())
    assert reader("conv_attn_time_share").value(trace, counters, CELL) == pytest.approx(100.0 * op_scopes.scope_self_time(names, dev, "attention") / dev.busy_s())
    least = sum(conv.min_seconds(step["rows"], 2048, PEAK)[0] for step in rows)
    assert reader("conv_mixer_roofline").value(trace, counters, CELL) == pytest.approx(100.0 * 30 * least / spent)
    assert reader("conv_mixer_roofline").value(trace, {"model": MODEL}, CELL) is None  # no rows logged: nothing to reckon from


# --- the rehearsals ----------------------------------------------------------------


def _run(*argv):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, *argv], cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)


def test_the_cell_rehearses_correct_with_a_trace_and_a_large_seed():
    done = _run("benchmark/run.py", "--workload", CELL_NAME, "--seed", "3000000019", "--seconds", "2", "--trace", "1", "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed" and last["correct"] is True and last["failed"] == 0
    assert "serve.compiles_in_window" in last["metric_names"]


def test_the_logits_tool_rehearses_with_the_harnesss_own_verdict_on_every_stream():
    done = _run("benchmark/tools/lfm2_logits_check.py", "--rehearse", "--seed", "5", "--only", "tail_not_shifted,trailing_layers_left_out")
    assert done.returncode == 0, done.stderr[-2000:]
    report = json.loads(done.stdout.strip().splitlines()[-1])
    assert report["within_limits"] is True and report["layers"] == 12 and report["periods"] == 2 and report["remainder"] == ["softmax", "conv"]
    wanted = {"tail_not_shifted", "trailing_layers_left_out"}
    # each run judged by the harness's own comparison (``ServeSession.check_streams`` on the run's greedy stream)
    assert set(report["cell_check"]) == wanted | {"ours"} and report["cell_check"]["ours"]["correct"] is True
    assert report["cell_check"]["ours"]["reference_tokens"] == report["sequences"] * report["decode"]
    assert set(report["controls_refused_by_the_cells_limits"]) == wanted
    # at the toy widths, float32 weights rounded once to bfloat16: the program is the reference to a rounding, each control is not
    assert report["mean_abs_diff"] < min(report[name][1] for name in wanted) / 3
