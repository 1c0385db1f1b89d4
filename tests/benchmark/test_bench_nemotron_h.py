"""What PR 59 added to the benchmark for NVIDIA-Nemotron-3-Nano-30B-A3B: the
configuration file against the catalog row's published keys (every one
verbatim but the four of ``reduced``, no width among them), the cut's
arithmetic against the program's own shapes, the adapter's shape (state-space
keys AND expert keys, ``expert_matrices`` 2) and its one rescaled leaf, the
reference's independence and what it refuses, both kernels' counts at this
model's shapes by hand, the new reader on recorded traces, the cell in its
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
from benchmark.kernels import ssd_recurrence as ssd
from tests.benchmark.spec_lookup import readers_of

HERE = os.path.dirname(__file__)
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
NAME, CELL_NAME = "nemotron-3-nano-30b-a3b-l16-ep2", "nemotron3_nano_long_decode"
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
CELL = {"name": "a_serving_cell", "config": {"engine": {"kind": "serve"}}, "peak": PEAK}
SOLAR = os.path.join(HERE, "data", "solar_tpu.xplane.pb")  # a model with a shared expert, recorded on the chip (PR 31)
GRANITE = os.path.join(HERE, "data", "granite_tpu.xplane.pb")  # a model with no expert at all (PR 52)
DENSE = os.path.join(HERE, "data", "named_tpu.xplane.pb")
NEW_READERS = ["shared_expert_time_share"]
# granite's three state-space readers read this cell's traces too (the builder's traced runs: PERF.md section 5), but the cell is
# NOT on their lists: ``test_bench_granite.py`` pins each list to granite's cell alone, and this PR may edit no accepted benchmark file
STATE_SPACE_READERS = ["ssm_time_share", "ssd_state_roofline", "ssm_attn_time_share"]
EXPERT_READERS = ["expert_ffn_time_share", "expert_ffn_roofline", "moe_route_time_share", "max_expert_load", "held_assignments_share", "held_experts_hit_share"]
SHARED_READERS = [
    "device_idle_share", "decode_step_device_ms", "mixed_step_device_ms", "step_host_share", "kv_pages_in_use_share", "compiles_in_window",
    "step_admit_ms", "step_pack_ms", "step_dispatch_ms", "step_settle_ms", "rows_per_step", "mixed_step_token_fill",
    "exec_gap_ms", "host_turnaround_ms", "enqueue_call_ms", "run_ahead_share",
]
PATTERN, PUBLISHED_PATTERN = "MEMEM*EMEMEM*EME", "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# config.json of nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 as the model-configs catalog holds it
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": PUBLISHED_PATTERN, "intermediate_size": 1856, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64, "mamba_proj_bias": False, "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h", "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
    "n_group": 1, "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6, "num_hidden_layers": 52, "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None, "ssm_state_size": 128, "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1, "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072,
}
REDUCED = {"num_hidden_layers": 16, "hybrid_override_pattern": PATTERN, "n_routed_experts": 64, "vocab_size": 65536}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def reader(name):
    return files.load_module("layer_metrics", name)


# --- the configuration ---------------------------------------------------------


def test_configuration_holds_the_published_keys_with_four_cuts_and_no_width_among_them():
    body = load("benchmark", "configs", NAME + ".json")
    entry = next(c for c in load("BENCHMARK.json")["configs"] if c["name"] == NAME)
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and len(entry["why"]) <= 200
    assert entry["reduced"] == body["reduced"] == list(REDUCED)
    assert body["source"] == entry["source"] == "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json"
    assert {k: body[k] for k in PUBLISHED} == {**PUBLISHED, **REDUCED}  # every other key verbatim
    assert {k: body["published"][k] for k in REDUCED} == {k: PUBLISHED[k] for k in REDUCED}  # the published values beside the cuts
    assert PUBLISHED_PATTERN.startswith(PATTERN) and (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) == (7, 7, 2)
    assert (PUBLISHED_PATTERN.count("M"), PUBLISHED_PATTERN.count("E"), PUBLISHED_PATTERN.count("*")) == (23, 23, 6) and len(PUBLISHED_PATTERN) == 52
    kwargs = body["model"]["kwargs"]
    assert kwargs["layer_types"] == [{"M": "ssm", "E": "ffn", "*": "softmax"}[c] for c in PATTERN]
    widths = {"hidden_size": 2688, "intermediate_size": 1856, "expert_intermediate_size": 1856, "num_heads": 32, "num_kv_heads": 2, "head_dim": 128,
              "ssm_num_heads": 64, "ssm_head_dim": 64, "ssm_state": 128, "ssm_groups": 8, "ssm_conv_kernel": 4, "moe_top_k": 6, "moe_router_experts": 128}
    assert {k: kwargs[k] for k in widths} == widths  # no width cut: the router's 128 outputs and its 6 a token as published
    assert kwargs["ssm_num_heads"] * kwargs["ssm_head_dim"] == 4096 != PUBLISHED["expand"] * PUBLISHED["hidden_size"]  # the head keys decide
    assert kwargs["moe_shared_experts"] * kwargs["expert_intermediate_size"] == PUBLISHED["moe_shared_expert_intermediate_size"]
    assert (kwargs["num_experts"], kwargs["moe_expert_share"], kwargs["vocab_size"], kwargs["num_layers"]) == (64, [0, 2], 65536, 16)
    assert (kwargs["activation"], kwargs["position"], kwargs["tie_embeddings"], kwargs["moe_scoring"], kwargs["moe_select_bias"]) == ("relu2", "none", False, "sigmoid", True)
    assert (kwargs["moe_routed_scaling"], kwargs["moe_norm_topk_prob"], kwargs["leading_dense_layers"], kwargs["use_bias"]) == (2.5, True, 0, False)
    for published, ours in body["model"]["published_keys"].items():
        assert kwargs[ours] == body[published], published
    assert {"position", "dt", "state", "d_inner", "groups", "shared_expert", "experts", "chunk", "seeded", "left_out", "serving_max_seq_len"} <= set(body["assumed"])
    assert "arXiv:2504.03624" in body["assumed"]["position"] and "NO clamp" in body["assumed"]["dt"] and "float32" in body["assumed"]["state"]
    assert "5,376" in body["assumed"]["d_inner"] and "3,712" in body["assumed"]["shared_expert"] and "NOT zero" in body["assumed"]["seeded"]
    assert "eight v5e chips, four pipeline stages of two" in body["deployment"]
    paged = body["engine"]["init_inference"]["paged_kv"]
    assert (paged["page_size"], paged["max_slots"], paged["prefill_chunk"], paged["max_seq_len"], paged["num_pages"]) == (64, 64, 128, 4096, 0)
    check = body["engine"]["check"]
    assert check["sample"] == 4 and 0 < check["mean_logit_gap"] < check["logit_margin"] and "float8" in check["why"] and "bfloat16 state" in check["why"]
    seeded = body["model"]["seeded"]
    assert seeded["wq_std"] > 0.02 and set(seeded) == {"wq_std", "why"} and "selection bias" in seeded["why"]
    small = body["rehearse"]["model"]["kwargs"]  # the rehearsal holds the same sixteen letters at toy widths
    assert small["layer_types"] == kwargs["layer_types"] and small["num_layers"] == 16 and small["ssm_groups"] > 1


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
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), None))
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    periods = shapes["periods"]
    ssm, attn, moe = count(periods["ssm"]) // 7, count(periods["softmax"]) // 2, count(periods["moe"]) // 7
    assert (ssm, attn, moe) == (38_744_896, 23_399_040, 658_885_376)  # the issue's 38.74M, 23.40M, 658.9M
    an_expert = count(periods["moe"]["experts"]) // (7 * 64)
    assert (an_expert, count(periods["moe"]["shared"]) // 7, count(periods["moe"]["gate"]) // 7) == (2 * 2688 * 1856, 2 * 2688 * 3712, 2688 * 128 + 128)
    assert count(shapes["embed"]) == count(shapes["lm_head"]) == 65536 * 2688
    assert count(shapes) == 7 * ssm + 2 * attn + 7 * moe + 2 * 65536 * 2688 + 2688 == 5_282_534_208  # 10.57 GB in bf16
    # the published model whole, from the same per-block counts: the row's 31.6B
    assert round((23 * ssm + 6 * attn + 23 * (moe + 64 * an_expert) + 2 * 131072 * 2688) / 1e9, 2) == 31.58
    paged = body["engine"]["init_inference"]["paged_kv"]
    pages = paged["max_slots"] * (paged["max_seq_len"] // paged["page_size"]) + 1
    state_shape, conv_shape = hybrid_decode.state_shapes(cfg, paged["max_slots"])
    assert state_shape == (7, 65, 64, 64, 128) and conv_shape == (7, 65, 3, 48, 128) and pages == 4097  # 6,144 channels: 48 lane tiles, whole
    assert heads_per_group(cfg.head_dim, cfg.v_head_dim, cfg.num_kv_heads) == 1  # heads of 128: whole lane tiles
    state, tails = int(np.prod(state_shape)) * 4, 7 * 65 * 3 * cfg.ssm_conv_channels * 2
    kv = pages * paged["page_size"] * 2 * 2 * cfg.num_kv_heads * cfg.head_dim * 2
    assert (round(state / 1e9, 2), round(tails / 1e9, 2), round(kv / 1e9, 2)) == (0.95, 0.02, 0.54)
    resident = 2 * count(shapes) + state + tails + kv
    assert round(resident / 1e9, 2) == 12.07 and resident / 16e9 > 0.75  # three times the floor of a quarter of the chip
    for stated in ("10.57 GB", "0.95 GB", "0.02 GB", "0.54 GB", "14.7 MB a row", "12.07 GB"):
        assert stated in body["deployment"], stated
    want = {"num_layers": 16, "num_ssm_layers": 7, "num_attention_layers": 2, "num_moe_layers": 7, "num_linear_layers": 0, "ssm_heads": 64, "ssm_head_dim": 64,
            "ssm_state": 128, "ssm_conv_channels": 6144, "ssm_conv_kernel": 4, "num_heads": 32, "num_kv_heads": 2, "head_dim": 128, "vocab_size": 65536,
            "num_experts": 64, "router_experts": 128, "experts_per_token": 6, "expert_intermediate_size": 1856, "expert_matrices": 2, "hidden_size": 2688}
    assert {k: shape[k] for k in want} == want


def test_the_traffic_fills_the_engines_max_seq_len_and_the_cell_is_in_its_lists():
    spec = load("BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == CELL_NAME)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "long_decode", 1) and len(cell["why"]) <= 200
    assert [w["name"] for w in spec["workloads"] if w["config"] == NAME] == [CELL_NAME]  # one cell, no second
    assert not os.path.exists(os.path.join(ROOT, "benchmark", "cells", CELL_NAME + ".json"))  # the mix unedited
    mix = load("benchmark", "traffic", "long_decode.json")
    paged = load("benchmark", "configs", NAME + ".json")["engine"]["init_inference"]["paged_kv"]
    assert mix["kind"] == "closed_loop" and mix["clients"] == "max_slots" and paged["max_slots"] == 64
    assert paged["max_seq_len"] == mix["prompt_len"]["max"] + mix["output_len"]["max"] == 4096
    rehearse = files.load_cell(spec, CELL_NAME, rehearse=True)
    r_paged, r_mix = rehearse["config_file"]["engine"]["init_inference"]["paged_kv"], rehearse["traffic_file"]
    assert r_paged["max_seq_len"] >= r_mix["prompt_len"]["max"] + r_mix["output_len"]["max"]
    assert CELL_NAME in next(m for m in spec["end_to_end"] if m["name"] == "serve_tokens_per_s")["workloads"]
    family = readers_of(spec, CELL_NAME)
    assert set(NEW_READERS + EXPERT_READERS + SHARED_READERS) == set(family)
    for r, m in family.items():
        assert m["moves"] == "serve_tokens_per_s"
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", r + ".py"))
    new = family["shared_expert_time_share"]
    assert (new["name"], new["unit"], new["source"], new["layer"], new["better"], new["workloads"]) == ("serve.shared_expert_time_share", "%", "device_trace", "model", "lower", [CELL_NAME])
    for r in STATE_SPACE_READERS:  # the one-group state-space model's, whose lists an accepted test holds to that cell (PERF.md section 7)
        assert r not in family and next(m for m in spec["per_layer"] if m["name"] == "serve." + r)["workloads"] == ["granite4h_micro_decode_heavy"]
    for r in EXPERT_READERS:  # and with the models that hold a share of a sigmoid router's experts
        assert "kimi_linear_long_decode" in family[r]["workloads"]
    # what reckons num_layers calls of the ragged kernel, a linear, window or latent layer is not asked of this cell
    assert not set(family) & {"ragged_attn_time_share", "ragged_attn_roofline", "ragged_kernel_call_us", "kda_state_roofline", "linear_attn_time_share",
                              "latent_attn_time_share", "window_attn_time_share", "state_cache_share"}


def test_the_adapter_builds_the_programs_model_and_rescales_the_attention_blocks_query_alone():
    import jax
    import numpy as np

    body = load("benchmark", "configs", NAME + ".json")
    small = files.overlay(body, body["rehearse"])
    model, shape = files.build_model(small)
    assert type(model).__mro__[1].__name__ == "HybridMoETransformerLM"
    assert (shape["num_layers"], shape["num_ssm_layers"], shape["num_attention_layers"], shape["num_moe_layers"]) == (16, 7, 2, 7)
    assert (shape["num_experts"], shape["router_experts"], shape["expert_matrices"], shape["ssm_conv_channels"]) == (4, 8, 2, 256 + 2 * 2 * 128)
    init = lambda m: jax.jit(lambda key: m.init(key, None))(jax.random.PRNGKey(3))  # one program a model, not a kernel a leaf
    seeded, plain = init(model), init(type(model).__mro__[1](model.config))
    s = small["model"]["seeded"]
    want = {"['periods']['softmax']['wq']": s["wq_std"] / 0.02}
    differing = {}
    for (path, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(seeded)[0], jax.tree_util.tree_flatten_with_path(plain)[0]):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            differing[jax.tree_util.keystr(path)] = None
            np.testing.assert_allclose(np.asarray(a), np.asarray(b) * want[jax.tree_util.keystr(path)], rtol=1e-6)
    assert sorted(differing) == sorted(want), differing
    assert set(seeded["periods"]["moe"]["experts"]) == {"w_in_t", "w_out"}  # two matrices an expert, both [held, width, hidden]


def test_the_reference_imports_nothing_of_the_program_and_refuses_another_block():
    path = os.path.join(ROOT, "benchmark", "reference", "nemotron_h_decoder.py")
    with open(path) as f:
        source = f.read()
    tree = ast.parse(source)
    imported = {n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)} | {a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names}
    assert not any(name.startswith(("deepspeed_tpu", "benchmark")) for name in imported), imported
    for stated in ("ASSUMED", "LEFT OUT", "TOKEN BY TOKEN", "THE SHARE", "the gate BEFORE the norm", "APART", "head n reads group n // 8", "NO positional term",
                   "arXiv:2504.03624", "no clamp", 'default_matmul_precision("highest")', "chunk_size", "NO gate matrix", "ALL tokens"):
        assert stated in source, stated
    assert "ssd_chunked" not in source and "cumsum" not in source and "ragged_dot" not in source and "argsort" not in source  # no chunk form, no sorted rows
    ref = files.load_module("reference", "nemotron_h_decoder")
    body = load("benchmark", "configs", NAME + ".json")
    arch = ref.arch_of(body["model"])
    assert (arch["ssm_heads"], arch["ssm_head_dim"], arch["ssm_state"], arch["ssm_groups"]) == (64, 64, 128, 8)
    assert (arch["num_heads"], arch["num_kv_heads"], arch["head_dim"], arch["experts_per_token"], arch["routed_scaling"]) == (32, 2, 128, 6, 2.5)
    assert (arch["held"], arch["first_held"]) == (64, 0)
    assert ref.arch_of({"kwargs": {**body["model"]["kwargs"], "moe_expert_share": [1, 2]}})["first_held"] == 64  # the other chip's share
    for other in ("granite-4.0-h-micro", "solar-open2-250b-l4-ep8", "kimi-linear-48b-a3b-l13-ep8", "mistral-7b-v0.3-l16", "olmoe-1b-7b-0125-l12"):
        with pytest.raises((ValueError, KeyError)):
            ref.arch_of(load("benchmark", "configs", other + ".json")["model"])
    mixers_only = [t for t in body["model"]["kwargs"]["layer_types"] if t != "ffn"]
    for wrong in ({"position": "rope"}, {"activation": "swiglu"}, {"activation": "relu"}, {"tie_embeddings": True}, {"moe_scoring": "softmax"}, {"moe_select_bias": False},
                  {"moe_shared_experts": 0}, {"leading_dense_layers": 1}, {"attn_output_gate": True}, {"ssm_groups": 3}, {"residual_multiplier": 0.22},
                  {"layer_types": mixers_only, "num_layers": len(mixers_only)}, {"moe_router_experts": 64}):
        with pytest.raises(ValueError, match="does not describe"):
            ref.arch_of({"kwargs": {**body["model"]["kwargs"], **wrong}})


# --- operations and bytes at this model's shapes ---------------------------------


def test_both_kernels_counts_for_a_narrow_step_worked_by_hand():
    """One Mamba-2 block of one step at the published shapes: 64 heads x 64 x
    128 float32 of state in and out (2,097,152 bytes each way), the tail's
    three inputs of 6,144 channels in and out, the token's 6,144 channels and
    64 ``dt`` in and its 4,096 outputs out; bound by memory. The seven blocks
    of a 64-row narrow step move the issue's 1.9 GB of state. And the expert
    blocks' grouped matmuls with TWO matrices an expert: an expert hit is
    2 x 2,688 x 1,856 x 2 bytes, ~61 of 64 hit a block."""
    H, P, N, C = 64, 64, 128, 6144
    state, tail, token = H * P * N * 4, 3 * C * 2, C * 2 + H * 4 + H * P * 2
    assert ssd.ops_and_bytes([(1, 900)], H, P, N, C) == (5 * H * P * N, 2 * state + 2 * tail + token)
    seconds, bound = ssd.min_seconds([(1, 900)] * 64, H, P, N, C, PEAK)
    assert bound == "memory" and seconds == pytest.approx(64 * (2 * state + 2 * tail + token) / 819e9)
    assert 7 * 64 * 2 * state == 1_879_048_192 and 7 * seconds == pytest.approx(2.35e-3, rel=1e-2)  # 1.9 GB: 2.3 ms of a narrow step
    an_expert = 2 * 2688 * 1856 * 2
    assert an_expert == 19_955_712
    hit = round(7 * 64 * (1 - (122 / 128) ** 64))  # ~61 of 64 a block
    assert 7 * 60 <= hit <= 7 * 62
    ops, moved = gmm.ops_and_bytes(7 * 64 * 3, hit, 2688, 1856, matrices=2)
    assert moved == hit * an_expert + 7 * 64 * 3 * 2 * 2688 * 2 and ops == 7 * 64 * 3 * 2 * 2 * 2688 * 1856
    seconds, bound = gmm.min_seconds(7 * 64 * 3, hit, 2688, 1856, PEAK, 2)
    assert bound == "memory" and 8.3e9 < moved < 8.7e9 and seconds == pytest.approx(moved / 819e9)  # the issue's 8.5 GB: ~10.4 ms
    assert gmm.ops_and_bytes(10, 4, 2688, 1856, matrices=3)[1] > gmm.ops_and_bytes(10, 4, 2688, 1856, matrices=2)[1]  # three would overstate the floor


# --- the new reader on recorded traces --------------------------------------------


def reduced(path, monkeypatch):
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: path)
    trace = tr.reduce_xplane(path, ("train_step", "server_step"), ("server_step",))
    return dataclasses.replace(trace, lo=float("-inf"), hi=float("inf"))  # no bench_slice: the whole trace


def test_the_shared_expert_reader_on_a_trace_with_a_shared_expert(monkeypatch):
    trace = reduced(SOLAR, monkeypatch)
    names, dev = op_scopes.load(SOLAR), trace.devices[0]
    value = reader("shared_expert_time_share").value(trace, {"model": {}}, CELL)
    assert value == pytest.approx(100.0 * op_scopes.scope_self_time(names, dev, "moe_shared") / dev.busy_s()) and 0 < value < 100
    experts = reader("expert_ffn_time_share").value(trace, {"model": {}}, CELL)
    routing = reader("moe_route_time_share").value(trace, {"model": {}}, CELL)
    assert value + experts + routing < 100  # three scopes of one ``mlp`` scope, none inside another


@pytest.mark.parametrize("path", [GRANITE, DENSE], ids=["granite_trace", "dense_trace"])
def test_the_shared_expert_reader_finds_nothing_without_the_scope_and_without_a_trace(monkeypatch, path):
    trace = reduced(path, monkeypatch)
    assert reader("shared_expert_time_share").value(trace, {"model": {}}, CELL) is None  # no raise: what the parent gives a new reader
    assert reader("shared_expert_time_share").value(None, {"model": {}}, CELL) is None


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


def test_the_logits_tool_rehearses_and_every_control_is_refused():
    done = _run("benchmark/tools/nemotron_logits_check.py", "--rehearse", "--seed", "5")
    assert done.returncode == 0, done.stderr[-2000:]
    report = json.loads(done.stdout.strip().splitlines()[-1])
    assert report["within_limits"] is True and report["layers"] == 6 and report["isolated"] is True
    wanted = {"group_0_for_every_head", "one_norm_over_all_features", "gate_behind_the_norm", "relu_for_relu2", "swiglu_shaped_expert", "no_routed_scaling",
              "no_selection_bias", "shared_expert_dropped", "rotary_applied", "state_not_carried", "conv_tail_not_carried", "state_bfloat16", "weights_fp8"}
    assert set(report["controls_refused"]) == wanted
    # each run judged by the harness's own comparison too (``ServeSession.check_streams`` on the run's greedy stream)
    assert set(report["cell_check"]) == wanted | {"ours"} and report["cell_check"]["ours"]["correct"] is True
    assert report["cell_check"]["ours"]["reference_tokens"] == report["sequences"] * report["decode"]
    # float32 throughout at the toy widths: a bfloat16 state is the one control whose difference is itself a rounding
    assert all(refused for name, refused in report["controls_refused"].items() if name != "state_bfloat16"), report["controls_refused"]
