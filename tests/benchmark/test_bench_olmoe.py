"""What PR 25 added to the benchmark for OLMoE: the configuration file against
the catalog's published keys, the reference's independence, the grouped expert
matmul's operations and bytes on hand-worked cases, the five new readers
(``moe.`` entries until PR 44, ``serve.`` since) on a small trace recorded on a v5e chip from the program itself
(``benchmark/tools/record_moe_trace.py``: a two-layer, eight-expert paged
server's five steps) and on a dense model's trace, where they have to find
nothing, and the cell's rehearsal."""

import ast
import dataclasses
import json
import os
import subprocess
import sys

import pytest

from benchmark import files, op_scopes, program_spans
from benchmark import trace_reduce as tr
from benchmark.kernels import grouped_expert_matmul as gem
from tests.benchmark.spec_lookup import readers_of

HERE = os.path.dirname(__file__)
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
MOE = os.path.join(HERE, "data", "moe_tpu.xplane.pb")
DENSE = os.path.join(HERE, "data", "named_tpu.xplane.pb")
CELL = {"name": "a_serving_cell", "config": {"engine": {"kind": "serve"}}, "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
# the recorded model (record_moe_trace.py's MODEL)
COUNTERS = {"model": {"num_layers": 2, "num_experts": 8, "hidden_size": 256, "expert_intermediate_size": 128, "expert_matrices": 3}}
READERS = ["expert_ffn_time_share", "moe_route_time_share", "experts_hit_share", "expert_ffn_roofline", "max_expert_load"]
# config.json of allenai/OLMoE-1B-7B-0125-Instruct as the model-configs catalog holds it
PUBLISHED = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe", "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16, "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304,
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


def test_configuration_holds_the_published_keys_with_depth_as_the_one_cut():
    body = load("benchmark", "configs", "olmoe-1b-7b-0125-l12.json")
    entry = next(c for c in load("BENCHMARK.json")["configs"] if c["name"] == "olmoe-1b-7b-0125-l12")
    assert entry["reduced"] == body["reduced"] == ["num_hidden_layers"] and body["source"] == entry["source"]
    assert entry["source"] == "https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/blob/main/config.json"
    differs = {k for k, v in PUBLISHED.items() if k not in body or body[k] != v}
    assert differs == {"num_hidden_layers"} and body["num_hidden_layers"] == 12
    kwargs = body["model"]["kwargs"]
    assert (kwargs["qk_norm"], kwargs["moe_drop_tokens"], kwargs["activation"], kwargs["norm"], kwargs["position"]) == (
        "projection", False, "swiglu", "rmsnorm", "rope")
    assert kwargs["head_dim"] * kwargs["num_heads"] == kwargs["hidden_size"] and "qk_norm" in body["assumed"]
    assert body["model"]["adapter"] == "moe_transformer" and body["model"]["reference"] == "olmoe_decoder"
    paged = body["engine"]["init_inference"]["paged_kv"]
    assert (paged["page_size"], paged["max_slots"], paged["prefill_chunk"], paged["max_seq_len"]) == (64, 16, 128, 1536)
    mix = load("benchmark", "traffic", "decode_heavy.json")
    assert paged["max_seq_len"] == mix["prompt_len"]["max"] + mix["output_len"]["max"]
    check = body["engine"]["check"]
    assert check["sample"] == 4 and check["max_context"] == 512 and len(check["why"]) > 100
    # the rehearsal block: the small size of the CPU tests
    small = body["rehearse"]["model"]["kwargs"]
    assert (small["num_experts"], small["moe_top_k"], small["hidden_size"], small["num_layers"]) == (8, 3, 64, 2)


def test_the_cell_and_its_metric_family():
    spec = load("BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == "olmoe_decode_heavy")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("olmoe-1b-7b-0125-l12", "decode_heavy", 1)
    assert len(spec["workloads"]) >= 5 and sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    tokens = next(m for m in spec["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert {"mistral7b_decode_heavy", "olmoe_decode_heavy"} <= set(tokens["workloads"]) and 0.01 <= tokens["bound"] <= 0.1  # its value is the benchmark's to refit (PERF.md section 2)
    family = readers_of(spec, "olmoe_decode_heavy")
    assert len(family) >= 20 and all(m["moves"] == "serve_tokens_per_s" for m in family.values())
    assert set(family) >= set(READERS)
    # neither a position nor a count of the benchmark as a whole is pinned: later cells and entries come after these
    assert any(c["name"] == cell["config"] for c in spec["configs"])


def test_the_adapter_builds_the_programs_model_and_says_its_expert_shape():
    model, shape = files.build_model(load("benchmark", "configs", "olmoe-1b-7b-0125-l12.json"))
    assert type(model).__name__ == "MoETransformerLM"
    assert (shape["num_layers"], shape["num_heads"], shape["num_kv_heads"], shape["head_dim"]) == (12, 16, 16, 128)
    assert (shape["num_experts"], shape["experts_per_token"], shape["expert_intermediate_size"], shape["expert_matrices"]) == (64, 8, 1024, 3)
    assert model.config.moe_drop_tokens is False and model.config.moe_norm_topk_prob is False and model.config.qk_norm == "projection"


def test_the_adapter_gives_the_seeded_router_its_trained_like_scale_and_nothing_else():
    import jax
    import numpy as np

    body = load("benchmark", "configs", "olmoe-1b-7b-0125-l12.json")
    small = files.overlay(body, body["rehearse"])
    assert body["model"]["seeded"]["router_std"] == 0.045 and small["model"]["seeded"] == body["model"]["seeded"]
    seeded, _ = files.build_model(small)
    training, _ = files.build_model({**small, "model": {k: v for k, v in small["model"].items() if k != "seeded"}})
    key, batch = jax.random.PRNGKey(3), np.zeros((1, 8), np.int32)
    got, plain = seeded.init(key, batch), training.init(key, batch)
    wg = np.asarray(got["layers"]["moe"]["gate"]["wg"])
    assert wg.std() == pytest.approx(0.045, rel=0.1)  # 2 x 64 x 8 draws
    np.testing.assert_allclose(wg, np.asarray(plain["layers"]["moe"]["gate"]["wg"]) * (0.045 / 0.02), rtol=1e-6)  # the same directions
    got["layers"]["moe"]["gate"]["wg"] = plain["layers"]["moe"]["gate"]["wg"]
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(plain)))


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "benchmark", "reference", "olmoe_decoder.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported == {"__future__", "functools", "typing", "jax"}
    ref = files.load_module("reference", "olmoe_decoder")
    with pytest.raises(ValueError):  # a block it does not describe: renormalised capacity routing
        ref.arch_of({"kwargs": dict(load("benchmark", "configs", "olmoe-1b-7b-0125-l12.json")["model"]["kwargs"], moe_drop_tokens=True)})


# --- the kernel's operations and bytes ----------------------------------------

H, I = 2048, 1024
MATRIX = H * I  # one of an expert's three


def test_grouped_expert_matmul_ops_and_bytes_by_hand():
    # one assignment, so one expert hit: its three matrices once, one row in and one out
    assert gem.ops_and_bytes(1, 1, H, I) == (6 * MATRIX, (3 * MATRIX + 2 * H) * 2)
    # a narrow step's layer: 128 assignments over all 64 experts
    assert gem.ops_and_bytes(128, 64, H, I) == (128 * 6 * MATRIX, (64 * 3 * MATRIX + 128 * 2 * H) * 2)
    # nothing routed: nothing to do and nothing to read
    assert gem.ops_and_bytes(0, 0, H, I) == (0, 0)
    # a plain (two-matrix) expert in float32
    assert gem.ops_and_bytes(10, 3, 64, 32, matrices=2, itemsize=4) == (10 * 2 * 2 * 64 * 32, (3 * 2 * 64 * 32 + 10 * 2 * 64) * 4)
    peak = CELL["peak"]
    # 128 rows over 55 experts: bound by memory, 55 x 12.6 MB at 819 GB/s = 0.85 ms
    least, bound = gem.min_seconds(128, 55, H, I, peak)
    assert bound == "memory" and least == pytest.approx((55 * 3 * MATRIX + 128 * 2 * H) * 2 / 819e9) and 0.8e-3 < least < 0.9e-3
    # a full mixed step's 16,384 rows: 1.05 ms of compute under 1.15 ms of memory; four times the rows are bound by compute
    assert gem.min_seconds(16384, 64, H, I, peak)[1] == "memory"
    least, bound = gem.min_seconds(65536, 64, H, I, peak)
    assert bound == "compute" and least == pytest.approx(65536 * 6 * MATRIX / 197e12)
    # the sum over layers is never more than the layers' own least times added up
    apart = gem.min_seconds(16384, 64, H, I, peak)[0] + gem.min_seconds(128, 64, H, I, peak)[0]
    assert gem.min_seconds(16384 + 128, 128, H, I, peak)[0] <= apart


# --- the readers on recorded traces -----------------------------------------------


def test_the_moe_trace_holds_the_scopes_the_kernel_and_the_counts(monkeypatch):
    trace = reduced(MOE, monkeypatch)
    names = op_scopes.load(MOE)
    dev = trace.devices[0]
    kernels = op_scopes.kernel_events(names, dev, ["moe_grouped_matmul", "ragged_paged_attention"])
    steps = program_spans.attr_values(trace, CELL, "serve.settle", "moe_assignments", "moe_experts_hit", "moe_max_expert_load")
    assert len(steps) >= 3
    # three grouped matmuls and one attention call a layer a step, two layers
    assert len(kernels["moe_grouped_matmul"]) == 3 * len(kernels["ragged_paged_attention"]) > 0
    assert op_scopes.scope_self_time(names, dev, "moe_experts") > 0 and op_scopes.scope_self_time(names, dev, "moe_route") > 0
    # the prompts were served once before the trace, so the long one's first two pages (128 tokens) attach from
    # the prefix cache: 22 + 8 prompt tokens and 2 x 2 decode tokens went through the model, 3 experts each, in 2 layers
    assert sum(a for a, _, _ in steps) == (22 + 8 + 2 * 2) * 3 * 2
    assert all(0 < hit <= 2 * 8 and 0 < load <= a for a, hit, load in steps)
    # the accepted signature reader still finds the ragged kernel alone: one call a layer
    from benchmark.kernels import ragged_paged_attention as rpa
    assert len(dev.checked_kernel_events(rpa.EVENTS, rpa.calls_per_step(2))["ragged"]) == len(kernels["ragged_paged_attention"])


def test_the_five_readers_on_the_moe_trace(monkeypatch):
    trace = reduced(MOE, monkeypatch)
    values = {name: reader(name).value(trace, COUNTERS, CELL) for name in READERS}
    assert all(v is not None for v in values.values()), values
    assert 0 < values["expert_ffn_time_share"] < 100 and 0 < values["moe_route_time_share"] < 100
    assert values["expert_ffn_time_share"] + values["moe_route_time_share"] < 100
    steps = program_spans.attr_values(trace, CELL, "serve.settle", "moe_experts_hit", "moe_max_expert_load")
    assert values["experts_hit_share"] == pytest.approx(100.0 * sum(h for h, _ in steps) / (len(steps) * 2 * 8))
    assert values["max_expert_load"] == pytest.approx(sum(m for _, m in steps) / len(steps)) and values["max_expert_load"] >= 1
    assert 0 < values["expert_ffn_roofline"] <= 100


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_in_a_dense_models_trace_and_without_a_trace(monkeypatch, name):
    trace = reduced(DENSE, monkeypatch)
    dense = {"model": {"num_layers": 2, "remat": True}}
    assert reader(name).value(trace, dense, CELL) is None
    assert reader(name).value(None, COUNTERS, CELL) is None


# --- the cell ---------------------------------------------------------------------------


def test_the_cell_rehearses_correct_with_a_trace_and_a_large_seed():
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", "olmoe_decode_heavy", "--seed", str(2**31 + 12345),
         "--seconds", "3", "--trace", "1", "--rehearse"], cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, (done.stdout[-2000:], done.stderr[-1500:])
    info, last = (json.loads(line) for line in done.stdout.strip().splitlines()[-2:])
    assert last["rehearsal"] == "passed" and last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert info["info"]["reference_sample"] == 4 and info["info"]["reference_tokens"] > 0
    assert "metrics" not in last and last["device"]["platform"] == "cpu"
