"""What PR 41 added to the benchmark for GLM-4.7-Flash: the configuration file
against the catalog row's published keys, its cuts and floors and its memory
arithmetic, the reference's independence, the latent kernel's operations and
bytes by hand, the three new readers on a synthetic trace (device
events with the name stacks the program's scope and kernel name give them) and
where scope or model is absent, the traffic file against the engine's
``max_seq_len``, and the cell's and the logits tool's rehearsals. Entries are
found by search: neither a count of cells nor a position in a list is pinned."""

import ast
import json
import os
import subprocess
import sys

import pytest

from benchmark import files, op_scopes, program_spans
from benchmark import trace_reduce as tr
from benchmark.kernels import latent_paged_attention as lpa
from tests.benchmark.spec_lookup import readers_of

HERE = os.path.dirname(__file__)
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
NAME, CELL_NAME = "glm-4.7-flash-l16-ep8", "glm47_flash_long_decode"
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
CELL = {"name": "a_serving_cell", "config": {"engine": {"kind": "serve"}}, "peak": PEAK}
NEW_READERS = ["latent_attn_time_share", "latent_attn_roofline", "latent_proj_time_share"]
# the readers MiMo's cell lists and PR 36's four, as far as they apply: all 22 that ISSUE 41 listed (ten of them were out
# from PR 41 to PR 44, while every family had entries of its own and ``per_layer`` stood at its cap of 128)
SHARED_READERS = [
    "device_idle_share", "decode_step_device_ms", "mixed_step_device_ms", "step_host_share", "kv_pages_in_use_share", "compiles_in_window",
    "expert_ffn_time_share", "expert_ffn_roofline", "moe_route_time_share", "held_assignments_share", "held_experts_hit_share", "exec_gap_ms",
    "step_admit_ms", "step_pack_ms", "step_dispatch_ms", "step_settle_ms", "rows_per_step", "mixed_step_token_fill", "max_expert_load",
    "host_turnaround_ms", "enqueue_call_ms", "run_ahead_share",
]
# config.json of zai-org/GLM-4.7-Flash as the model-configs catalog holds it
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536, "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4, "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
    "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880,
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
    assert entry["reduced"] == body["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert body["source"] == entry["source"] == "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"
    differs = {k for k, v in PUBLISHED.items() if k not in body or body[k] != v}
    assert differs == set(body["reduced"])
    assert (body["num_hidden_layers"], body["n_routed_experts"], body["vocab_size"]) == (16, 8, 19360)
    # the published counts stand beside the cuts, and the floors of a model_config PR hold
    assert (body["published"]["num_hidden_layers"], body["published"]["n_routed_experts"], body["published"]["vocab_size"]) == (47, 64, 154880)
    assert body["n_routed_experts"] >= 8 and body["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    kwargs = body["model"]["kwargs"]
    # the leading dense layer counted once, then 15 routed layers of the one kind: a period is one layer, 15 >= 4
    assert kwargs["layer_types"] == ["latent"] * 16 and kwargs["leading_dense_layers"] == PUBLISHED["first_k_dense_replace"] == 1
    assert (kwargs["moe_router_experts"], kwargs["num_experts"], kwargs["moe_expert_share"], kwargs["moe_top_k"]) == (64, 8, [0, 8], 4)
    widths = {"hidden_size": 2048, "intermediate_size": 10240, "expert_intermediate_size": 1536, "num_heads": 20, "head_dim": 256,
              "v_head_dim": 256, "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64}
    assert {k: kwargs[k] for k in widths} == widths
    for published, ours in body["model"]["published_keys"].items():
        if published not in body["reduced"]:
            assert kwargs[ours] == PUBLISHED[published], published
    assert (kwargs["position"], kwargs["moe_scoring"], kwargs["moe_select_bias"], kwargs["moe_shared_experts"], kwargs["moe_routed_scaling"]) == ("rope", "sigmoid", True, 1, 1.8)
    assert {"head_dim", "rotary", "softmax_scale", "kv_norm", "kv_b_proj", "router", "left_out", "latent_pages"} <= set(body["assumed"])
    assert "rotate-half" in body["assumed"]["rotary"] and "multi-token-prediction" in body["assumed"]["left_out"]
    assert "24 v5e chips as three pipeline stages of eight" in body["deployment"]
    check = body["engine"]["check"]
    assert check["max_context"] == 2048 and check["sample"] == 4
    assert body["model"]["seeded"]["wq_b_std"] > 0.02 and "score" in body["model"]["seeded"]["why"]


def test_the_memory_arithmetic_is_the_programs():
    """The deployment text's numbers, recomputed from the program's own
    ``init`` shapes and the pool's layout."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference.kv_pool import key_lanes

    body = load("benchmark", "configs", NAME + ".json")
    model, shape = files.build_model(body)
    cfg = model.config
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), None))
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    assert count(shapes["leading"][0]["mixer"]) == count(shapes["periods"]["latent"]) // 15 == 21_761_280  # 21.76M a mixer
    assert count(shapes["periods"]["moe"]["experts"]) // (15 * 8) == count(shapes["periods"]["moe"]["shared"]) // 15 == 9_437_184
    assert count(shapes) == 1_766_415_296  # 3.53 GB in bf16
    paged = body["engine"]["init_inference"]["paged_kv"]
    pages = paged["max_slots"] * (paged["max_seq_len"] // paged["page_size"]) + 1
    assert pages == 4097 and cfg.latent_width == 576 and key_lanes(cfg.latent_width) == 640
    pool = pages * paged["page_size"] * cfg.layers_of("latent") * key_lanes(cfg.latent_width) * 2
    assert round(pool / 1e9, 2) == 5.37 and "5.37 GB" in body["deployment"] and "3.53 GB" in body["deployment"]
    # over the driver's floor for a new cell: a quarter of the chip's 16 GB, resident alone
    assert (2 * count(shapes) + pool) / 16e9 > 0.5
    expanded = paged["max_slots"] * paged["max_seq_len"] * 16 * cfg.num_heads * (cfg.head_dim + cfg.v_head_dim) * 2
    assert round(expanded / 1e9, 1) == 85.9
    assert (shape["num_latent_layers"], shape["kv_lora_rank"], shape["qk_rope_head_dim"], shape["num_moe_layers"]) == (16, 512, 64, 15)


def test_the_traffic_fills_the_engines_max_seq_len_and_the_cell_is_in_its_lists():
    spec = load("BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == CELL_NAME)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "long_decode", 1) and len(cell["why"]) <= 200
    mix = load("benchmark", "traffic", "long_decode.json")
    paged = load("benchmark", "configs", NAME + ".json")["engine"]["init_inference"]["paged_kv"]
    assert mix["kind"] == "closed_loop" and mix["clients"] == "max_slots" and paged["max_slots"] == 64
    assert (mix["prompt_len"]["min"], mix["prompt_len"]["max"], mix["output_len"]["min"], mix["output_len"]["max"]) == (256, 1024, 1536, 3072)
    assert paged["max_seq_len"] == mix["prompt_len"]["max"] + mix["output_len"]["max"] == 4096
    assert mix["prompt_len"]["max"] < load("benchmark", "configs", NAME + ".json")["engine"]["check"]["max_context"]
    rehearse = files.load_cell(spec, CELL_NAME, rehearse=True)
    r_paged, r_mix = rehearse["config_file"]["engine"]["init_inference"]["paged_kv"], rehearse["traffic_file"]
    assert r_paged["max_seq_len"] == r_mix["prompt_len"]["max"] + r_mix["output_len"]["max"] == 96
    assert CELL_NAME in next(m for m in spec["end_to_end"] if m["name"] == "serve_tokens_per_s")["workloads"]
    family = readers_of(spec, CELL_NAME)
    assert set(NEW_READERS + SHARED_READERS) == set(family)
    for r, m in family.items():
        assert m["moves"] == "serve_tokens_per_s"
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", r + ".py"))
    assert family["latent_attn_roofline"]["unit"] == "%" and family["latent_attn_roofline"]["layer"] == "kernels"
    # what reckons num_layers calls of one head layout, or every layer as routed, is not asked of this cell
    assert not set(family) & {"ragged_attn_time_share", "ragged_attn_roofline", "ragged_kernel_call_us", "experts_hit_share"}


def test_the_adapter_builds_the_programs_model_and_scales_one_leaf():
    import jax
    import numpy as np

    body = load("benchmark", "configs", NAME + ".json")
    small = files.overlay(body, body["rehearse"])
    model, shape = files.build_model(small)
    assert type(model).__mro__[1].__name__ == "HybridMoETransformerLM"
    assert (shape["num_layers"], shape["num_moe_layers"], shape["num_latent_layers"], shape["num_experts"], shape["router_experts"]) == (4, 3, 4, 4, 16)
    seeded = model.init(jax.random.PRNGKey(3), None)
    plain = type(model).__mro__[1](model.config).init(jax.random.PRNGKey(3), None)
    scale = body["model"]["seeded"]["wq_b_std"] / 0.02
    differing = []
    for (path, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(seeded)[0], jax.tree_util.tree_flatten_with_path(plain)[0]):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            differing.append(jax.tree_util.keystr(path))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b) * scale, rtol=1e-6)
    assert differing and all(path.endswith("['wq_b']") for path in differing), differing
    assert len(differing) == 2  # the leading layer's and the periods' stack


def test_the_reference_imports_nothing_of_the_program_and_refuses_another_block():
    path = os.path.join(ROOT, "benchmark", "reference", "glm4_moe_lite_decoder.py")
    with open(path) as f:
        source = f.read()
    tree = ast.parse(source)
    imported = {n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)} | {a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names}
    assert not any(name.startswith(("deepspeed_tpu", "benchmark")) for name in imported), imported
    for stated in ("DEPARTURES", "LEFT OUT", "multi-token-prediction", "rotate-half", "256^-0.5", "the 512 alone", "1.8", 'default_matmul_precision("highest")', "PUBLISHED (expanded)"):
        assert stated in source, stated
    ref = files.load_module("reference", "glm4_moe_lite_decoder")
    body = load("benchmark", "configs", NAME + ".json")
    arch = ref.arch_of(body["model"])
    assert (arch["kv_lora_rank"], arch["nope"], arch["rope"], arch["held"], arch["first_held"], arch["routed_scaling"]) == (512, 192, 64, 8, 0, 1.8)
    with pytest.raises((ValueError, KeyError)):
        ref.arch_of(load("benchmark", "configs", "mimo-v2.5-l7-ep16.json")["model"])
    with pytest.raises(ValueError, match="does not describe"):
        ref.arch_of({"kwargs": {**body["model"]["kwargs"], "moe_shared_experts": 0}})


# --- operations and bytes --------------------------------------------------------


def test_latent_attention_ops_and_bytes_by_hand():
    assert lpa.pairs(1, 2000) == 2000 and lpa.pairs(128, 128) == 128 * 129 // 2 and lpa.pairs(128, 640) == 128 * 640 - 128 * 127 // 2
    # a decode row of 2,000 live entries: 20 heads x 2 x (576 + 512) operations an entry; its 2,000 entries of
    # 576 numbers read ONCE (no value array), its one new entry written, q in at 20 x 576 and o out at 20 x 512
    ops, moved = lpa.ops_and_bytes([(1, 2000)], 20, 512, 64)
    assert ops == 2000 * 20 * 2 * (576 + 512) == 87_040_000
    assert moved == (2000 * 576 + 1 * 576 + 20 * 576 + 20 * 512) * 2 == 2_348_672
    # beside a prefill chunk of 128 that ends at 640, and a dead row
    rows = [(1, 2000), (128, 640), (0, 0)]
    ops2, moved2 = lpa.ops_and_bytes(rows, 20, 512, 64)
    assert ops2 - ops == (128 * 640 - 128 * 127 // 2) * 20 * 2 * 1088
    assert moved2 - moved == ((640 + 128) * 576 + 128 * 20 * 1088) * 2
    # the decode row is bound by memory (37 operations a byte against the chip's 240), the chunk by compute
    seconds, bound = lpa.min_seconds([(1, 2000)], 20, 512, 64, PEAK)
    assert bound == "memory" and seconds == pytest.approx(moved / 819e9) and 36 < ops / moved < 38
    assert lpa.min_seconds([(128, 640)], 20, 512, 64, PEAK)[1] == "compute"
    assert lpa.min_seconds(rows, 20, 512, 64, PEAK)[0] == pytest.approx(max(ops2 / 197e12, moved2 / 819e9))


# --- the readers on a synthetic trace --------------------------------------------

KERNEL = 'custom-call(s32[64,64] %a, s32[64] %b, s32[64] %c, bf16[64,21,640] %x, bf16[65552,64,640] %p), custom_call_target="tpu_custom_call"'
STACKS = {
    KERNEL: "jit(paged_ragged_r64_w1)/jit(main)/while/body/latent_attention/latent_paged_attention/pallas_call:",
    "fusion.absorb": "jit(paged_ragged_r64_w1)/jit(main)/while/body/latent_attention/dot_general:",
    "fusion.out_proj": "jit(paged_ragged_r64_w1)/jit(main)/latent_attention/dot_general:",
    "fusion.experts": "jit(paged_ragged_r64_w1)/jit(main)/while/body/mlp/moe_experts/dot_general:",
}
MODEL = {"num_heads": 20, "kv_lora_rank": 512, "qk_rope_head_dim": 64, "num_latent_layers": 16, "num_moe_layers": 15, "num_experts": 8, "num_layers": 16}
ROWS = [(1, 2000)] * 60 + [(1, 700)] * 4


def synthetic(monkeypatch, stacks=STACKS):
    """One traced step: sixteen latent kernel calls of 300 us, the mixer's
    other ops, an expert matmul a routed layer; 10.06 ms busy."""
    t, events = 0.0, []
    for name, n, us in ((KERNEL, 16, 300), ("fusion.absorb", 16, 50), ("fusion.out_proj", 16, 60), ("fusion.experts", 15, 200)):
        for _ in range(n):
            events.append(tr.Event(name, t, t + us * 1e-6))
            t += us * 1e-6
    dev = tr.DeviceTrace(0, events, events, [], [], [(0.0, t)])
    trace = tr.ReducedTrace(0.0, t, [dev], [])
    names = op_scopes.OpNames({"/device:TPU:0": {name: [{op_scopes.NAME_STACK: stack}] for name, stack in stacks.items()}})
    monkeypatch.setattr(op_scopes, "of_cell", lambda cell: names)
    monkeypatch.setattr(program_spans, "of_cell", lambda trace, cell: [])
    return trace, t


def test_the_three_readers_on_a_synthetic_trace(monkeypatch):
    trace, busy = synthetic(monkeypatch)
    counters = {"model": MODEL, "rows_log": [{"mixed": False, "rows": ROWS}]}
    assert reader("latent_attn_time_share").value(trace, counters, CELL) == pytest.approx(100 * 16 * (300 + 50 + 60) * 1e-6 / busy)
    assert reader("latent_proj_time_share").value(trace, counters, CELL) == pytest.approx(100 * 16 * (50 + 60) * 1e-6 / busy)
    least = lpa.min_seconds(ROWS, 20, 512, 64, PEAK)[0]
    roofline = reader("latent_attn_roofline").value(trace, counters, CELL)
    # the kernel's own time, not the scope's: the projections inside the scope are not in the denominator
    assert roofline == pytest.approx(100 * 16 * least / (16 * 300e-6)) and 0 < roofline < 100
    assert lpa.scope_and_kernel_time(trace, CELL) == (pytest.approx(16 * 410e-6), pytest.approx(16 * 300e-6))


def test_the_readers_find_nothing_where_their_scope_or_model_is_absent(monkeypatch):
    counters = {"model": MODEL, "rows_log": [{"mixed": False, "rows": ROWS}]}
    for name in NEW_READERS:
        assert reader(name).value(None, counters, CELL) is None  # no trace
    # a program without the scope (the parent has neither scope nor kernel)
    trace, _ = synthetic(monkeypatch, stacks={k: v.replace("latent_attention/", "") for k, v in STACKS.items()})
    for name in NEW_READERS:
        assert reader(name).value(trace, counters, CELL) is None, name
    # another model's shape (no latent layers): nothing, even with the scope there
    trace, _ = synthetic(monkeypatch)
    other = {"model": {"num_heads": 64, "num_kv_heads": 8, "head_dim": 128, "num_layers": 4, "num_experts": 40}, "rows_log": counters["rows_log"]}
    for name in NEW_READERS:
        assert reader(name).value(trace, other, CELL) is None, name
    # no rows log (a run that was not traced through the driver's slice): no roofline, the shares stand
    assert reader("latent_attn_roofline").value(trace, {"model": MODEL}, CELL) is None


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
    done = _run("benchmark/tools/glm_logits_check.py", "--rehearse", "--seed", "5")
    assert done.returncode == 0, done.stderr[-2000:]
    report = json.loads(done.stdout.strip().splitlines()[-1])
    assert report["within_limits"] is True and 0 < report["held_assignments"] < report["routed_assignments"]
    wanted = {"no_rotary_on_q", "no_rotary_on_k", "norm_over_576", "scale_of_192", "no_factor_1.8", "no_shared_expert", "7_of_8_experts", "weights_fp8"}
    assert set(report["controls_refused"]) == wanted and all(report["controls_refused"].values()), report["controls_refused"]
