"""What PR 31 added to the benchmark for Solar-Open2-250B: the configuration
file against the catalog row's published keys, the reference's independence,
the recurrence's operations and bytes on hand-worked cases, the four new
readers (``solar.`` entries until PR 44, ``serve.`` since) on a small trace recorded on a v5e chip from the program itself
(``benchmark/tools/record_solar_trace.py``: a one-period toy of the model's
shape, a paged server's steps) and on a dense and an MoE model's traces,
where they have to find nothing, and the cell's rehearsal."""

import ast
import dataclasses
import json
import os
import subprocess
import sys

import pytest

from benchmark import files, op_scopes, program_spans
from benchmark import trace_reduce as tr
from benchmark.kernels import kda_recurrence as kda
from tests.benchmark.spec_lookup import readers_of

HERE = os.path.dirname(__file__)
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
SOLAR = os.path.join(HERE, "data", "solar_tpu.xplane.pb")
MOE = os.path.join(HERE, "data", "moe_tpu.xplane.pb")
DENSE = os.path.join(HERE, "data", "named_tpu.xplane.pb")
CELL = {"name": "a_serving_cell", "config": {"engine": {"kind": "serve"}}, "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
NAME = "solar-open2-250b-l4-ep8"
READERS = ["linear_attn_time_share", "softmax_attn_time_share", "kda_state_roofline", "held_assignments_share"]
# the recorded model (record_solar_trace.py's MODEL) and what the tool printed of its steps
RECORDED = {"num_layers": 4, "num_experts": 4, "hidden_size": 256, "expert_intermediate_size": 128, "expert_matrices": 3,
            "num_linear_layers": 3, "num_attention_layers": 1, "linear_heads": 8, "linear_head_dim": 128, "linear_conv_kernel": 4}
with open(os.path.join(HERE, "data", "solar_rows_log.json")) as f:
    ROWS_LOG = json.load(f)
# config.json of upstage/Solar-Open2-250B as the model-configs catalog holds it
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64, "head_dim": 128, "num_key_value_heads": 8,
    "vocab_size": 196608, "intermediate_size": 10240, "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "tie_word_embeddings": False, "max_position_embeddings": 1048576, "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44], "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320, "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 8,
}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def reader(name):
    return files.load_module("layer_metrics", name)


def reduced(path, monkeypatch):
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: path)
    trace = tr.reduce_xplane(path, ("train_step", "server_step"), ("server_step",))
    return dataclasses.replace(trace, lo=float("-inf"), hi=float("inf"))  # no bench_slice: the whole trace


# --- the configuration ---------------------------------------------------------


def test_configuration_holds_the_published_keys_with_three_cuts_and_no_width_among_them():
    body = load("benchmark", "configs", NAME + ".json")
    entry = next(c for c in load("BENCHMARK.json")["configs"] if c["name"] == NAME)
    assert entry["reduced"] == body["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert body["source"] == entry["source"] == "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json"
    differs = {k for k, v in PUBLISHED.items() if k not in body or body[k] != v}
    assert differs == set(body["reduced"])
    assert (body["num_hidden_layers"], body["n_routed_experts"], body["vocab_size"]) == (4, 40, 24576)
    # the published counts stand beside the cuts, and the floors of a model_config PR hold
    assert (body["published"]["num_hidden_layers"], body["published"]["n_routed_experts"], body["published"]["vocab_size"]) == (48, 320, 196608)
    assert body["n_routed_experts"] >= 8 and body["vocab_size"] * 8 >= PUBLISHED["vocab_size"] and body["num_hidden_layers"] >= 4
    kwargs = body["model"]["kwargs"]
    assert kwargs["layer_types"] == ["softmax", "linear", "linear", "linear"]  # one whole period of gqa_layers, 3:1
    assert (kwargs["moe_router_experts"], kwargs["num_experts"], kwargs["moe_expert_share"], kwargs["moe_top_k"]) == (320, 40, [0, 8], 8)
    assert (kwargs["position"], kwargs["moe_scoring"], kwargs["moe_select_bias"], kwargs["attn_output_gate"]) == ("none", "sigmoid", True, True)
    lin = PUBLISHED["linear_attn_config"]
    assert (kwargs["linear_num_heads"], kwargs["linear_head_dim"], kwargs["linear_conv_kernel"]) == (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"])
    for key in ("softmax_gate", "linear_low_ranks", "linear_state", "linear_decay", "router", "intermediate_size", "serving_max_seq_len"):
        assert key in body["assumed"], key
    assert "eight v5e chips share each layer" in body["deployment"] and "19.3 MB a row" in body["deployment"]
    assert body["model"]["adapter"] == "hybrid_moe_transformer" and body["model"]["reference"] == "solar_open2_decoder"
    paged = body["engine"]["init_inference"]["paged_kv"]
    assert (paged["page_size"], paged["max_slots"], paged["prefill_chunk"], paged["max_seq_len"]) == (64, 64, 128, 1536)
    assert set(paged) == {"page_size", "max_slots", "prefill_chunk", "num_pages", "max_seq_len"}  # no new knob
    mix = load("benchmark", "traffic", "decode_heavy.json")
    assert paged["max_seq_len"] == mix["prompt_len"]["max"] + mix["output_len"]["max"] and mix["clients"] == "max_slots"
    check = body["engine"]["check"]
    assert check["sample"] == 4 and check["max_context"] == 512 and len(check["why"]) > 100


def test_the_cell_and_its_metric_family():
    spec = load("BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == "solar_open2_decode_heavy")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "decode_heavy", 1)
    tokens = next(m for m in spec["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert "solar_open2_decode_heavy" in tokens["workloads"] and 0.01 <= tokens["bound"] <= 0.1  # its value is the benchmark's to refit (PERF.md section 2)
    family = readers_of(spec, "solar_open2_decode_heavy")
    assert len(family) >= 21 and all(m["moves"] == "serve_tokens_per_s" for m in family.values())
    assert set(family) >= set(READERS) and not set(family) & {"ragged_attn_time_share", "ragged_attn_roofline", "ragged_kernel_call_us"}
    # no position and no count of the benchmark as a whole is pinned here: the next PR's cell comes after this one
    assert any(c["name"] == NAME for c in spec["configs"])


def test_the_adapter_builds_the_programs_model_and_says_its_state_shape():
    model, shape = files.build_model(load("benchmark", "configs", NAME + ".json"))
    assert type(model).__name__ == "HybridMoETransformerLM"
    assert (shape["num_layers"], shape["num_attention_layers"], shape["num_linear_layers"]) == (4, 1, 3)
    assert (shape["num_experts"], shape["router_experts"], shape["experts_per_token"], shape["expert_intermediate_size"]) == (40, 320, 8, 1280)
    assert (shape["linear_heads"], shape["linear_head_dim"], shape["vocab_size"]) == (64, 128, 24576)
    assert model.config.held_experts == (0, 40) and model.config.period == ("softmax", "linear", "linear", "linear")


def test_the_reference_imports_nothing_of_the_program_and_refuses_another_block():
    path = os.path.join(ROOT, "benchmark", "reference", "solar_open2_decoder.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    imported = {n.module if isinstance(n, ast.ImportFrom) else a.name for n in ast.walk(tree) if isinstance(n, (ast.Import, ast.ImportFrom)) for a in n.names}
    assert not any(m and m.split(".")[0] in ("deepspeed_tpu", "benchmark") for m in imported), imported
    ref = files.load_module("reference", "solar_open2_decoder")
    kwargs = load("benchmark", "configs", NAME + ".json")["model"]["kwargs"]
    arch = ref.arch_of({"kwargs": kwargs})
    assert (arch["held"], arch["first_held"], arch["experts_per_token"]) == (40, 0, 8)
    for wrong in (dict(moe_scoring="softmax"), dict(position="rope"), dict(attn_output_gate=False), dict(moe_shared_experts=0)):
        with pytest.raises(ValueError, match="does not describe"):
            ref.arch_of({"kwargs": dict(kwargs, **wrong)})
    doc = ast.get_docstring(tree)
    for said in ("ASSUMED", "THE SHARE", "low ranks", "selection-only bias", "plain ``lax.scan``"):
        assert said in doc, said


# --- the recurrence's operations and bytes ---------------------------------------

HEADS, D = 64, 128
STATE = HEADS * D * D * 4  # one row's state of one layer, float32
TAIL = 3 * 3 * HEADS * D * 2  # its convolution tail, bfloat16
TOKEN = 3 * HEADS * D * 2 + HEADS * D * 4 + HEADS * 4 + HEADS * D * 2  # q~ k~ v~, log decay, b, o


def test_kda_recurrence_ops_and_bytes_by_hand():
    # one decode row: the state in and out, the tail in and out, one token
    assert kda.ops_and_bytes([(1, 300)], HEADS, D) == (7 * HEADS * D * D, 2 * STATE + 2 * TAIL + TOKEN)
    # a chunk of 128 tokens still moves the state once: that is what the chunkwise form is for
    assert kda.ops_and_bytes([(128, 128)], HEADS, D) == (128 * 7 * HEADS * D * D, 2 * STATE + 2 * TAIL + 128 * TOKEN)
    # a dead row needs nothing; rows add up
    assert kda.ops_and_bytes([(0, 0)], HEADS, D) == (0, 0)
    both = kda.ops_and_bytes([(1, 300), (128, 128), (0, 0)], HEADS, D)
    assert both == tuple(a + b for a, b in zip(kda.ops_and_bytes([(1, 300)], HEADS, D), kda.ops_and_bytes([(128, 128)], HEADS, D)))
    # the cell's narrow step: 64 rows x 8.4 MB of state a layer at 819 GB/s = 0.67 ms, bound by memory
    least, bound = kda.min_seconds([(1, 500)] * 64, HEADS, D, CELL["peak"])
    assert bound == "memory" and least == pytest.approx(64 * (2 * STATE + 2 * TAIL + TOKEN) / 819e9) and 0.65e-3 < least < 0.70e-3
    assert 3 * 64 * 2 * STATE == pytest.approx(1.61e9, rel=0.01)  # ISSUE 31's "1.6 GB a step" of three layers


# --- the readers on recorded traces -----------------------------------------------

def test_the_solar_trace_holds_the_scopes_the_kernel_and_the_counts(monkeypatch):
    trace = reduced(SOLAR, monkeypatch)
    names = op_scopes.load(SOLAR)
    dev = trace.devices[0]
    kernels = op_scopes.kernel_events(names, dev, ["kda_decode", "ragged_paged_attention", "moe_grouped_matmul"])
    steps = program_spans.attr_values(trace, CELL, "serve.settle", "moe_assignments", "moe_routed_assignments")
    assert len(steps) == len(ROWS_LOG) >= 3
    # a step: one ragged kernel (the softmax layer), three kda_decode (the linear layers), three grouped matmuls a layer
    assert len(kernels["kda_decode"]) == 3 * len(kernels["ragged_paged_attention"]) > 0
    assert len(kernels["moe_grouped_matmul"]) == 12 * len(kernels["ragged_paged_attention"])
    for scope in ("linear_attention", "kda_recurrence", "attention", "moe_shared", "moe_experts"):
        assert op_scopes.scope_self_time(names, dev, scope) > 0, scope
    # every live token routes 3 experts in each of 4 layers; about half of the router's 8 are held
    live = [sum(q for q, _ in step["rows"]) for step in ROWS_LOG]
    assert [routed for _, routed in steps] == [n * 3 * 4 for n in live]
    assert all(0 < held < routed for held, routed in steps)
    # the accepted signature reader finds the ragged kernel alone: one call a step, kda_decode is not mistaken for it
    from benchmark.kernels import ragged_paged_attention as rpa
    assert len(dev.checked_kernel_events(rpa.EVENTS, rpa.calls_per_step(1))["ragged"]) == len(kernels["ragged_paged_attention"])


def test_the_four_readers_on_the_solar_trace(monkeypatch):
    trace = reduced(SOLAR, monkeypatch)
    counters = {"model": RECORDED, "rows_log": ROWS_LOG}
    values = {name: reader(name).value(trace, counters, CELL) for name in READERS}
    assert all(v is not None for v in values.values()), values
    assert 0 < values["softmax_attn_time_share"] < values["linear_attn_time_share"] < 100  # three layers against one
    assert values["linear_attn_time_share"] + values["softmax_attn_time_share"] < 100
    assert 0 < values["kda_state_roofline"] <= 100
    steps = program_spans.attr_values(trace, CELL, "serve.settle", "moe_assignments", "moe_routed_assignments")
    assert values["held_assignments_share"] == pytest.approx(100.0 * sum(h / r for h, r in steps) / len(steps))
    assert 25 < values["held_assignments_share"] < 75  # 4 of 8 held


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("path, model", [(DENSE, {"num_layers": 2, "remat": True}),
                                         (MOE, {"num_layers": 2, "num_experts": 8, "hidden_size": 256, "expert_intermediate_size": 128, "expert_matrices": 3})],
                         ids=["dense_trace", "moe_trace"])
def test_a_reader_finds_nothing_in_another_models_trace_and_without_a_trace(monkeypatch, name, path, model):
    trace = reduced(path, monkeypatch)
    assert reader(name).value(trace, {"model": model, "rows_log": [{"mixed": False, "rows": [(1, 10)]}]}, CELL) is None
    assert reader(name).value(None, {"model": RECORDED, "rows_log": []}, CELL) is None
    # and a model with linear layers whose trace has no such scope or attribute (the parent): None, no raise
    assert reader(name).value(trace, {"model": RECORDED, "rows_log": [{"mixed": False, "rows": [(1, 10)]}]}, CELL) is None or name == "softmax_attn_time_share"


# --- the cell ---------------------------------------------------------------------------


def test_the_cell_rehearses_correct_with_a_trace_and_a_large_seed():
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", "solar_open2_decode_heavy", "--seed", str(2**31 + 4321),
         "--seconds", "3", "--trace", "1", "--rehearse"], cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, (done.stdout[-2000:], done.stderr[-1500:])
    info, last = (json.loads(line) for line in done.stdout.strip().splitlines()[-2:])
    assert last["rehearsal"] == "passed" and last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert info["info"]["reference_sample"] == 4 and info["info"]["reference_tokens"] > 0
    assert "metrics" not in last and last["device"]["platform"] == "cpu"


def test_the_logits_tool_rehearses():
    tool = os.path.join(ROOT, "benchmark", "tools", "solar_logits_check.py")
    done = subprocess.run([sys.executable, tool, "--rehearse", "--only", "no_shared_expert,state_bf16"], cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, (done.stdout[-2000:], done.stderr[-1500:])
    report = json.loads(done.stdout.strip().splitlines()[-1])
    assert 0 < report["held_assignments"] < report["routed_assignments"] and report["mean_abs_diff"] < report["no_shared_expert"][1]
