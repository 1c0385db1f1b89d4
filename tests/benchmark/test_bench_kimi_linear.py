"""What PR 49 added to the benchmark for Kimi-Linear-48B-A3B: the configuration
file against the catalog row's published keys, its cuts and floors and its
memory arithmetic, the adapter's shape (the linear readers' keys AND the latent
readers'), the reference's independence, the two accepted kernels' operations
and bytes at this model's shapes by hand (32 heads; a query with no low rank
changes neither count), the new reader ``state_cache_share`` on synthetic spans
and where the attributes are absent, the traffic file against the engine's
``max_seq_len``, and the cell's and the logits tool's rehearsals. Entries are
found by search: neither a count of cells nor a position in a list is pinned."""

import ast
import json
import os
import subprocess
import sys

import pytest

from benchmark import files, program_spans
from benchmark.kernels import kda_recurrence as kda
from benchmark.kernels import latent_paged_attention as lpa
from tests.benchmark.spec_lookup import readers_of

HERE = os.path.dirname(__file__)
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
NAME, CELL_NAME = "kimi-linear-48b-a3b-l13-ep8", "kimi_linear_long_decode"
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
CELL = {"name": "a_serving_cell", "config": {"engine": {"kind": "serve"}}, "peak": PEAK}
NEW_READERS = ["state_cache_share"]
# what ISSUE 49 lists: the readers every serving cell has, the expert readers of a model with leading dense layers,
# the linear layers' two (Solar-Open2's), the latent layers' three (GLM-4.7-Flash's), PR 36's four
SHARED_READERS = [
    "device_idle_share", "decode_step_device_ms", "mixed_step_device_ms", "step_host_share", "kv_pages_in_use_share", "compiles_in_window",
    "step_admit_ms", "step_pack_ms", "step_dispatch_ms", "step_settle_ms", "rows_per_step", "mixed_step_token_fill",
    "expert_ffn_time_share", "expert_ffn_roofline", "moe_route_time_share", "max_expert_load", "held_assignments_share", "held_experts_hit_share",
    "linear_attn_time_share", "kda_state_roofline", "latent_attn_time_share", "latent_attn_roofline", "latent_proj_time_share",
    "exec_gap_ms", "host_turnaround_ms", "enqueue_call_ms", "run_ahead_share",
]
# config.json of moonshotai/Kimi-Linear-48B-A3B-Instruct as the model-configs catalog holds it
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26], "num_heads": 32, "short_conv_kernel_size": 4,
    },
    "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear", "moe_intermediate_size": 1024, "moe_layer_freq": 1,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid", "num_attention_heads": 32, "num_expert_group": 1, "num_experts": 256,
    "num_experts_per_token": 8, "num_hidden_layers": 27, "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840,
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
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and len(entry["why"]) <= 200
    assert entry["reduced"] == body["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert body["source"] == entry["source"] == "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json"
    differs = {k for k, v in PUBLISHED.items() if k not in body or body[k] != v}
    assert differs == set(body["reduced"])  # the nested linear_attn_config is copied whole
    assert (body["num_hidden_layers"], body["num_experts"], body["vocab_size"]) == (13, 32, 20480)
    # the published counts stand beside the cuts, and the floors of a model_config PR hold
    assert (body["published"]["num_hidden_layers"], body["published"]["num_experts"], body["published"]["vocab_size"]) == (27, 256, 163840)
    assert body["num_experts"] >= 8 and body["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    kwargs = body["model"]["kwargs"]
    # the leading dense layer, a KDA one, counted once; then three whole periods as published layers 2-13 read
    kinds = {i: "latent" if i in PUBLISHED["linear_attn_config"]["full_attn_layers"] else "linear" for i in range(1, 14)}
    assert set(PUBLISHED["linear_attn_config"]["kda_layers"]) >= {i for i, k in kinds.items() if k == "linear"}
    assert kwargs["layer_types"] == [kinds[i] for i in range(1, 14)] and kwargs["leading_dense_layers"] == PUBLISHED["first_k_dense_replace"] == 1
    assert kwargs["layer_types"][0] == "linear" and kwargs["layer_types"][1:] == ["linear", "linear", "latent", "linear"] * 3
    assert (kwargs["moe_router_experts"], kwargs["num_experts"], kwargs["moe_expert_share"], kwargs["moe_top_k"]) == (256, 32, [0, 8], 8)
    widths = {"hidden_size": 2304, "intermediate_size": 9216, "expert_intermediate_size": 1024, "num_heads": 32, "head_dim": 192,
              "v_head_dim": 128, "q_lora_rank": 0, "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "linear_num_heads": 32, "linear_head_dim": 128, "linear_conv_kernel": 4, "linear_gate_rank": 128}
    assert {k: kwargs[k] for k in widths} == widths
    lin = PUBLISHED["linear_attn_config"]
    assert (kwargs["linear_num_heads"], kwargs["linear_head_dim"], kwargs["linear_conv_kernel"]) == (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"])
    for published, ours in body["model"]["published_keys"].items():
        if published not in body["reduced"]:
            assert kwargs[ours] == PUBLISHED[published], published
    assert (kwargs["position"], kwargs["linear_allow_neg_eigval"], kwargs["moe_scoring"], kwargs["moe_select_bias"], kwargs["moe_shared_experts"],
            kwargs["moe_routed_scaling"]) == ("none", False, "sigmoid", True, 1, 2.446)
    assert {"nope", "q_proj", "softmax_scale", "kv_norm", "kv_b_proj", "head_dim", "linear_low_ranks", "linear_state", "linear_norms", "linear_decay",
            "linear_beta", "short_conv", "router", "seeded", "left_out", "serving_max_seq_len", "latent_pages"} <= set(body["assumed"])
    assert "unrotated" in body["assumed"]["nope"] and "72" in body["assumed"]["head_dim"] and "ONCE" in body["assumed"]["linear_beta"]
    assert "sixteen v5e chips as two pipeline stages of eight" in body["deployment"]
    check = body["engine"]["check"]
    assert check["max_context"] == 2048 and check["sample"] == 4 and 0 < check["mean_logit_gap"] < check["logit_margin"]
    assert "float8" in check["why"] and "margin" in check["why"]
    assert body["model"]["seeded"]["wq_std"] > 0.02 and "score" in body["model"]["seeded"]["why"]


def test_the_memory_arithmetic_is_the_programs():
    """The deployment text's numbers, recomputed from the program's own
    ``init`` shapes and the pool's layout."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference import hybrid_decode
    from deepspeed_tpu.inference.kv_pool import key_lanes

    body = load("benchmark", "configs", NAME + ".json")
    model, shape = files.build_model(body)
    cfg = model.config
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), None))
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    assert count(shapes["leading"][0]["mixer"]) == count(shapes["periods"]["linear"]) // 9 == 39_516_576  # 39.52M a KDA mixer
    assert count(shapes["periods"]["latent"]) // 3 == 29_117_184 and "wq_a" not in shapes["periods"]["latent"]  # 29.12M, one wq
    assert count(shapes["periods"]["moe"]["experts"]) // (12 * 32) == count(shapes["periods"]["moe"]["shared"]) // 12 == 7_077_888
    assert count(shapes["leading"][0]["ffn"]) == 63_703_296
    assert count(shapes) == 3_450_547_008  # 6.90 GB in bf16
    paged = body["engine"]["init_inference"]["paged_kv"]
    pages = paged["max_slots"] * (paged["max_seq_len"] // paged["page_size"]) + 1
    assert pages == 4097 and cfg.latent_width == 576 and key_lanes(cfg.latent_width) == 640
    latent = pages * paged["page_size"] * cfg.layers_of("latent") * key_lanes(cfg.latent_width) * 2
    state_shape, conv_shape = hybrid_decode.state_shapes(cfg, paged["max_slots"])
    state, conv = int(np.prod(state_shape)) * 4, int(np.prod(conv_shape)) * 2
    assert state_shape == (10, 65, 32, 128, 128)
    assert (round(latent / 1e9, 2), round(state / 1e9, 2), round(conv / 1e9, 2)) == (1.01, 1.36, 0.05)
    for stated in ("6.90 GB", "1.36 GB", "1.01 GB", "9.32 GB"):
        assert stated in body["deployment"], stated
    resident = 2 * count(shapes) + latent + state + conv
    assert round(resident / 1e9, 2) == 9.32 and resident / 16e9 > 0.5  # over the driver's floor of a quarter of the chip
    # a row: 21.0 MB of state whatever its length, 15.7 MB of latent pages at 4,096 tokens
    assert round(state / 65 / 1e6, 1) == 21.0 and round(3 * 1280 * 4096 / 1e6, 1) == 15.7
    want = {"num_layers": 13, "num_moe_layers": 12, "num_linear_layers": 10, "linear_heads": 32, "linear_head_dim": 128, "linear_conv_kernel": 4,
            "num_latent_layers": 3, "kv_lora_rank": 512, "qk_rope_head_dim": 64, "num_heads": 32, "num_experts": 32, "router_experts": 256,
            "experts_per_token": 8, "expert_intermediate_size": 1024, "vocab_size": 20480}
    assert {k: shape[k] for k in want} == want


def test_the_traffic_fills_the_engines_max_seq_len_and_the_cell_is_in_its_lists():
    spec = load("BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == CELL_NAME)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "long_decode", 1) and len(cell["why"]) <= 200
    mix = load("benchmark", "traffic", "long_decode.json")
    paged = load("benchmark", "configs", NAME + ".json")["engine"]["init_inference"]["paged_kv"]
    assert mix["kind"] == "closed_loop" and mix["clients"] == "max_slots" and paged["max_slots"] == 64 and paged["num_pages"] == 0
    assert paged["max_seq_len"] == mix["prompt_len"]["max"] + mix["output_len"]["max"] == 4096
    assert mix["prompt_len"]["max"] < load("benchmark", "configs", NAME + ".json")["engine"]["check"]["max_context"]
    rehearse = files.load_cell(spec, CELL_NAME, rehearse=True)
    r_paged, r_mix = rehearse["config_file"]["engine"]["init_inference"]["paged_kv"], rehearse["traffic_file"]
    assert r_paged["max_seq_len"] == r_mix["prompt_len"]["max"] + r_mix["output_len"]["max"] == 96
    r_kwargs = rehearse["config_file"]["model"]["kwargs"]
    assert r_kwargs["layer_types"][0] == "linear" and r_kwargs["q_lora_rank"] == 0 and r_kwargs["position"] == "none"
    assert CELL_NAME in next(m for m in spec["end_to_end"] if m["name"] == "serve_tokens_per_s")["workloads"]
    family = readers_of(spec, CELL_NAME)
    assert set(NEW_READERS + SHARED_READERS) == set(family)
    for r, m in family.items():
        assert m["moves"] == "serve_tokens_per_s"
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", r + ".py"))
    new = family["state_cache_share"]
    assert (new["name"], new["unit"], new["source"], new["workloads"]) == ("serve.state_cache_share", "%", "program_span", [CELL_NAME])
    assert new["layer"] == family["kv_pages_in_use_share"]["layer"]
    # what reckons num_layers calls of one head layout, every layer as routed, or a softmax or window layer, is not asked of this cell
    assert not set(family) & {"ragged_attn_time_share", "ragged_attn_roofline", "ragged_kernel_call_us", "experts_hit_share", "softmax_attn_time_share",
                              "window_attn_time_share", "full_attn_time_share"}


def test_the_adapter_builds_the_programs_model_and_scales_the_latent_layers_query_alone():
    import jax
    import numpy as np

    body = load("benchmark", "configs", NAME + ".json")
    small = files.overlay(body, body["rehearse"])
    model, shape = files.build_model(small)
    assert type(model).__mro__[1].__name__ == "HybridMoETransformerLM"
    assert (shape["num_layers"], shape["num_moe_layers"], shape["num_linear_layers"], shape["num_latent_layers"], shape["num_experts"], shape["router_experts"]) == (9, 8, 7, 2, 4, 16)
    seeded = model.init(jax.random.PRNGKey(3), None)
    plain = type(model).__mro__[1](model.config).init(jax.random.PRNGKey(3), None)
    scale = small["model"]["seeded"]["wq_std"] / 0.02
    differing = []
    for (path, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(seeded)[0], jax.tree_util.tree_flatten_with_path(plain)[0]):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            differing.append(jax.tree_util.keystr(path))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b) * scale, rtol=1e-6)
    # the latent stack's one query matrix; the linear layers' ``wq`` (the leading layer's among them) keep init's
    assert differing == ["['periods']['latent']['wq']"], differing


def test_the_reference_imports_nothing_of_the_program_and_refuses_another_block():
    path = os.path.join(ROOT, "benchmark", "reference", "kimi_linear_decoder.py")
    with open(path) as f:
        source = f.read()
    tree = ast.parse(source)
    imported = {n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)} | {a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names}
    assert not any(name.startswith(("deepspeed_tpu", "benchmark")) for name in imported), imported
    for stated in ("DEPARTURES", "LEFT OUT", "UNROTATED", "NO low rank", "192^-0.5", "the 512 alone", "2.446", "a KEY CHANNEL", "ONCE",
                   'default_matmul_precision("highest")', "PUBLISHED (expanded)", "token by token", "head_dim`` 72"):
        assert stated in source, stated
    ref = files.load_module("reference", "kimi_linear_decoder")
    body = load("benchmark", "configs", NAME + ".json")
    arch = ref.arch_of(body["model"])
    assert (arch["kv_lora_rank"], arch["nope"], arch["shared"], arch["held"], arch["first_held"], arch["routed_scaling"], arch["leading"]) == (512, 128, 64, 32, 0, 2.446, 1)
    with pytest.raises((ValueError, KeyError)):
        ref.arch_of(load("benchmark", "configs", "glm-4.7-flash-l16-ep8.json")["model"])  # rotary, a low-rank query
    with pytest.raises((ValueError, KeyError)):
        ref.arch_of(load("benchmark", "configs", "solar-open2-250b-l4-ep8.json")["model"])  # b doubled, softmax layers
    for other in ({"linear_allow_neg_eigval": True}, {"position": "rope"}, {"q_lora_rank": 768}, {"moe_shared_experts": 0}):
        with pytest.raises(ValueError, match="does not describe"):
            ref.arch_of({"kwargs": {**body["model"]["kwargs"], **other}})


# --- operations and bytes at this model's shapes ---------------------------------


def test_both_kernels_counts_at_32_heads_by_hand():
    """The accepted count files at Kimi-Linear's shapes. A decode row of the
    KDA kernel: 32 heads x 128 x 128 float32 of state in and out (1.05 MB
    each way), bound by memory; ten layers x 64 rows = 1.34 GB a narrow step
    each way. A decode row of the latent kernel at 2,000 entries: 32 heads x 2
    x (576 + 512) operations an entry over 576 numbers read once; the query
    arrives absorbed at 32 x 576 whether or not a low rank made it, so the
    count has no term for ``q_lora_rank``."""
    H, D = 32, 128
    state, tail, token = H * D * D * 4, 3 * 3 * H * D * 2, 3 * H * D * 2 + H * D * 4 + H * 4 + H * D * 2
    assert kda.ops_and_bytes([(1, 1800)], H, D) == (7 * H * D * D, 2 * state + 2 * tail + token)
    assert kda.ops_and_bytes([(128, 128)], H, D) == (128 * 7 * H * D * D, 2 * state + 2 * tail + 128 * token)
    seconds, bound = kda.min_seconds([(1, 1800)] * 64, H, D, PEAK)
    assert bound == "memory" and 10 * 64 * 2 * state == 2_684_354_560  # the issue's 2.7 GB of state a narrow step
    assert seconds == pytest.approx(64 * (2 * state + 2 * tail + token) / 819e9)
    ops, moved = lpa.ops_and_bytes([(1, 2000)], 32, 512, 64)
    assert ops == 2000 * 32 * 2 * (576 + 512) and moved == (2000 * 576 + 576 + 32 * 576 + 32 * 512) * 2
    assert lpa.min_seconds([(1, 2000)], 32, 512, 64, PEAK)[1] == "memory" and 58 < ops / moved < 60  # 59 operations a byte against the chip's 240
    assert lpa.min_seconds([(128, 640)], 32, 512, 64, PEAK)[1] == "compute"


# --- the new reader on synthetic spans ---------------------------------------------


def _spans(monkeypatch, attrs):
    spans = [program_spans.Span("serve.step", float(i), i + 0.5, "main", a) for i, a in enumerate(attrs)]
    monkeypatch.setattr(program_spans, "of_cell", lambda trace, cell: spans)


def test_state_cache_share_is_the_mean_over_the_steps_that_hold_something(monkeypatch):
    value = reader("state_cache_share").value
    a_slot, a_token = 21_708_800, 3 * 1280
    steps = [
        {"pages_in_use": 0, "pages_total": 4096, "state_bytes_in_use": 0, "latent_bytes_in_use": 0},  # an empty server: no share
        {"pages_in_use": 64 * 8, "pages_total": 4096, "state_bytes_in_use": 64 * a_slot, "latent_bytes_in_use": 64 * 8 * 64 * a_token},
        {"pages_in_use": 64 * 32, "pages_total": 4096, "state_bytes_in_use": 64 * a_slot, "latent_bytes_in_use": 64 * 32 * 64 * a_token},
    ]
    _spans(monkeypatch, steps)
    short, long = a_slot / (a_slot + 512 * a_token), a_slot / (a_slot + 2048 * a_token)
    assert value(object(), {}, CELL) == pytest.approx(100 * (short + long) / 2)
    assert 91 < 100 * short < 92 and 73 < 100 * long < 74  # the share falls as contexts grow
    assert value(None, {}, CELL) is None  # no trace
    # the parent, and a model with neither cache: the span carries no such attribute; an empty server alone: nothing to average
    _spans(monkeypatch, [{"pages_in_use": 3, "pages_total": 4096}])
    assert value(object(), {}, CELL) is None
    _spans(monkeypatch, steps[:1])
    assert value(object(), {}, CELL) is None
    _spans(monkeypatch, [])
    assert value(object(), {}, CELL) is None


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


def test_the_logits_tool_rehearses_and_every_control_is_refused():
    done = _run("benchmark/tools/kimi_logits_check.py", "--rehearse", "--seed", "5")
    assert done.returncode == 0, done.stderr[-2000:]
    report = json.loads(done.stdout.strip().splitlines()[-1])
    assert report["within_limits"] is True and 0 < report["held_assignments"] < report["routed_assignments"] and report["layers"] == 5
    wanted = {"rotary_on_q_r_and_k_r", "shared_features_dropped", "b_doubled", "decay_a_head", "no_factor_2.446", "no_shared_expert",
              "leading_state_not_carried", "leading_conv_tail_not_carried", "7_of_8_experts", "weights_fp8"}
    assert set(report["controls_refused"]) == wanted and all(report["controls_refused"].values()), report["controls_refused"]
