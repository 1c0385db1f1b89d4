"""What PR 56 added to the benchmark for Ouro-2.6B: the configuration file
against the catalog row's published keys (every one verbatim, nothing
reduced), its memory arithmetic against the program's own shapes, the
adapter's shape (``num_layers`` the layers a STEP runs), the reference's
independence and its wrong blocks, the weight stream's count by hand, the
three new readers on a trace recorded on the chip
(``benchmark/tools/record_ouro_trace.py``) and on traces that hold nothing of
theirs, the traffic file against the engine's ``max_seq_len``, and the logits
tool's rehearsal (the cell's own rehearsal is a case of
``test_bench_rehearsal.py``, which takes every cell of ``BENCHMARK.json``).
Entries are found by search: neither a count of cells nor a position in a list
is pinned."""

import ast
import dataclasses
import json
import os
import subprocess
import sys

import pytest

from benchmark import files, op_scopes
from benchmark import trace_reduce as tr
from benchmark.kernels import dense_weight_stream as stream
from benchmark.kernels import ragged_paged_attention as ragged
from tests.benchmark.spec_lookup import readers_of

HERE = os.path.dirname(__file__)
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
NAME, CELL_NAME = "ouro-2.6b", "ouro26b_decode_short"
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
CELL = {"name": "a_serving_cell", "config": {"engine": {"kind": "serve"}}, "peak": PEAK}
OURO = os.path.join(HERE, "data", "ouro_tpu.xplane.pb")
DENSE = os.path.join(HERE, "data", "named_tpu.xplane.pb")
NEW_READERS = ["loop_pass_device_ms", "weight_stream_time_share", "weight_stream_roofline"]
# the recorded model (record_ouro_trace.py's MODEL) as the adapter describes it
RECORDED = {"num_layers": 8, "weight_layers": 2, "num_loops": 4, "hidden_size": 256, "intermediate_size": 512, "swiglu": True,
            "tie_embeddings": False, "num_heads": 2, "num_kv_heads": 2, "head_dim": 128, "vocab_size": 512}
# config.json of ByteDance/Ouro-2.6B as the model-configs catalog holds it
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16, "num_hidden_layers": 48,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1, "use_sliding_window": False, "vocab_size": 49152,
}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def reader(name):
    return files.load_module("layer_metrics", name)


# --- the configuration ---------------------------------------------------------


def test_configuration_holds_every_published_key_and_cuts_nothing():
    body = load("benchmark", "configs", NAME + ".json")
    entry = next(c for c in load("BENCHMARK.json")["configs"] if c["name"] == NAME)
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and len(entry["why"]) <= 200
    assert entry["reduced"] == body["reduced"] == []  # no depth cut, no pass cut, no vocabulary slice
    assert body["source"] == entry["source"] == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    assert {k: body[k] for k in PUBLISHED} == PUBLISHED  # every key verbatim, the layer list whole
    kwargs = body["model"]["kwargs"]
    widths = {"hidden_size": 2048, "intermediate_size": 5632, "num_heads": 16, "num_kv_heads": 16, "head_dim": 128, "vocab_size": 49152,
              "num_layers": 48, "num_loops": 4}
    assert {k: kwargs[k] for k in widths} == widths
    assert (kwargs["post_sublayer_norm"], kwargs["exit_gate"], kwargs["early_exit_threshold"], kwargs["position"], kwargs["norm"]) == (True, True, 1.0, "rope", "rmsnorm")
    for published, ours in body["model"]["published_keys"].items():
        assert kwargs[ours] == PUBLISHED[published], published
    assert {"total_ut_steps", "early_exit_threshold", "num_hidden_layers", "head_dim", "rope_theta"} <= set(body["model"]["published_keys"])
    assert {"norms", "pass_norm", "gate", "cache", "rope", "precision", "seeded", "left_out", "serving_max_seq_len"} <= set(body["assumed"])
    for key in ("norms", "pass_norm", "gate", "cache"):
        assert "modeling_ouro.py" in body["assumed"][key], key  # each with its pointer into the published code
    assert "input_layernorm_2" in body["assumed"]["norms"] and "current_ut x num_hidden_layers + layer_idx" in body["assumed"]["cache"]
    assert "one v5e chip holds the model whole" in body["deployment"]
    paged = body["engine"]["init_inference"]["paged_kv"]
    assert (paged["page_size"], paged["max_slots"], paged["prefill_chunk"], paged["max_seq_len"], paged["num_pages"]) == (64, 8, 128, 576, 0)
    check = body["engine"]["check"]
    assert check["max_context"] == 576 and check["sample"] == 4 and 0 < check["mean_logit_gap"] < check["logit_margin"]
    for stated in ("float8", "three passes", "pass 0's", "norm between passes", "post-sublayer"):
        assert stated in check["why"], stated
    small = body["rehearse"]["model"]["kwargs"]
    assert (small["num_layers"], small["num_loops"], small["hidden_size"], small["num_heads"], small["head_dim"], small["vocab_size"]) == (3, 4, 64, 4, 16, 512)


def test_the_memory_arithmetic_is_the_programs():
    """The deployment text's numbers, recomputed from the program's own
    ``init`` shapes and the pool's layout."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.kv_pool import init_paged_cache

    body = load("benchmark", "configs", NAME + ".json")
    model, shape = files.build_model(body)
    cfg = model.config
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), None))
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    assert count(shapes["layers"]) // 48 == 51_388_416  # the issue's layer: 4 x 2048^2 + 3 x 2048 x 5632 + 4 norms
    assert (count(shapes["embed"]), count(shapes["lm_head"]), count(shapes["exit_gate"])) == (100_663_296, 100_663_296, 2049)
    assert count(shapes) == 2_667_974_657  # 5.34 GB in bfloat16
    paged = body["engine"]["init_inference"]["paged_kv"]
    pages = paged["max_slots"] * (paged["max_seq_len"] // paged["page_size"]) + 1
    cache = jax.eval_shape(lambda: init_paged_cache(cfg, pages, paged["page_size"], dtype=jnp.bfloat16))
    assert cache.k_pages.shape == cache.v_pages.shape == (192, 73, 16, 64, 128)
    per_token = 192 * 16 * 2 * 128 * 2
    kv = 2 * int(np.prod(cache.k_pages.shape)) * 2
    assert per_token == 1_572_864 and kv == pages * 64 * per_token and round(kv / 1e9, 2) == 7.35
    for stated in ("5.34 GB", "7.35 GB", "1,572,864 B", "[192, 73, 16, 64, 128]", "12.7 GB"):
        assert stated in body["deployment"], stated
    assert round((2 * count(shapes) + kv) / 1e9, 1) == 12.7 and (2 * count(shapes) + kv) / 2**34 > 0.7  # far over the floor of a quarter of the chip
    want = {"num_layers": 192, "weight_layers": 48, "num_loops": 4, "num_heads": 16, "num_kv_heads": 16, "head_dim": 128, "vocab_size": 49152,
            "hidden_size": 2048, "intermediate_size": 5632, "swiglu": True, "tie_embeddings": False, "max_seq_len": 65536}
    assert {k: shape[k] for k in want} == want
    assert not {"num_experts", "num_ssm_layers", "num_linear_layers", "num_latent_layers"} & set(shape)


def test_the_traffic_fills_the_engines_max_seq_len_and_the_cell_is_in_its_lists():
    spec = load("BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == CELL_NAME)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "decode_short", 1) and len(cell["why"]) <= 200
    assert [w["name"] for w in spec["workloads"] if w["config"] == NAME] == [CELL_NAME]  # one cell, no second
    mix = load("benchmark", "traffic", "decode_short.json")
    paged = load("benchmark", "configs", NAME + ".json")["engine"]["init_inference"]["paged_kv"]
    assert mix["kind"] == "closed_loop" and mix["clients"] == "max_slots" and paged["max_slots"] == 8 and mix["trace_seconds"] == 8.0
    assert (mix["prompt_len"], mix["output_len"]) == ({"dist": "uniform", "min": 64, "max": 192}, {"dist": "uniform", "min": 192, "max": 384})
    assert paged["max_seq_len"] == mix["prompt_len"]["max"] + mix["output_len"]["max"] == 576 == 9 * paged["page_size"]
    rehearse = files.load_cell(spec, CELL_NAME, rehearse=True)
    r_paged, r_mix = rehearse["config_file"]["engine"]["init_inference"]["paged_kv"], rehearse["traffic_file"]
    assert r_paged["max_seq_len"] >= r_mix["prompt_len"]["max"] + r_mix["output_len"]["max"]
    assert CELL_NAME in next(m for m in spec["end_to_end"] if m["name"] == "serve_tokens_per_s")["workloads"]
    family = readers_of(spec, CELL_NAME)
    # every reader Mistral's decode-heavy cell reports and the three of the loop, but the five that read a step's own record:
    # ``test_bench_step_record.py::test_the_nine_entries`` holds their lists to four cells by ``==`` (PERF.md section 7)
    step_record = {"narrow_exec_ms", "mixed_exec_ms", "mixed_step_share", "kv_tokens_per_step", "rows_record_mismatch"}
    assert set(family) == (set(readers_of(spec, "mistral7b_decode_heavy")) - step_record) | set(NEW_READERS)
    assert {"ragged_attn_time_share", "ragged_attn_roofline", "ragged_kernel_call_us", "device_idle_share", "decode_step_device_ms",
            "mixed_step_device_ms", "exec_gap_ms", "kv_pages_in_use_share", "rows_per_step", "run_ahead_share"} <= set(family)
    for r, m in family.items():
        assert m["moves"] == "serve_tokens_per_s"
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", r + ".py"))
    for name, unit, better in (("loop_pass_device_ms", "ms", "lower"), ("weight_stream_time_share", "%", "lower"), ("weight_stream_roofline", "%", "higher")):
        new = family[name]
        assert (new["name"], new["unit"], new["source"], new["layer"], new["better"], new["workloads"]) == ("serve." + name, unit, "device_trace", "model", better, [CELL_NAME])


def test_the_adapter_builds_the_programs_model_and_counts_the_layers_a_step_runs():
    body = load("benchmark", "configs", NAME + ".json")
    small = files.overlay(body, body["rehearse"])
    model, shape = files.build_model(small)
    assert type(model).__name__ == "TransformerLM" and (model.config.num_layers, model.config.num_loops) == (3, 4)
    assert (shape["num_layers"], shape["weight_layers"], shape["num_loops"]) == (12, 3, 4)
    assert ragged.calls_per_step(shape["num_layers"]) == {"ragged": 12}  # one kernel call a layer a pass
    # a model that runs its stack once: the two numbers coincide, as in every other adapter
    once = files.overlay(small, {"model": {"kwargs": {"num_loops": 1, "exit_gate": False}}})
    assert files.build_model(once)[1]["num_layers"] == 3


def test_the_reference_imports_nothing_of_the_program_and_refuses_another_block():
    path = os.path.join(ROOT, "benchmark", "reference", "ouro_decoder.py")
    with open(path) as f:
        source = f.read()
    tree = ast.parse(source)
    imported = {n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)} | {a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names}
    assert not any(name.startswith(("deepspeed_tpu", "benchmark")) for name in imported), imported
    for stated in ("ASSUMED", "LEFT OUT", "the SAME weights in every pass", "THIS pass's k and v alone", "after the last layer of EVERY pass",
                   'default_matmul_precision("highest")', "the last pass the remainder"):
        assert stated in source, stated
    assert "lax.scan" not in source and "fori_loop" not in source  # two Python loops
    ref = files.load_module("reference", "ouro_decoder")
    assert ref.WRONG == ("three_passes", "shared_cache", "no_pass_norm", "no_post_norm", "weights_fp8")
    body = load("benchmark", "configs", NAME + ".json")
    assert ref.arch_of(body["model"]) == {"num_layers": 48, "num_loops": 4, "num_heads": 16, "head_dim": 128, "norm_eps": 1e-06, "rope_theta": 1e6}
    for other in ("granite-4.0-h-micro", "mistral-7b-v0.3-l16", "gpt2-125m"):
        with pytest.raises((ValueError, KeyError)):
            ref.arch_of(load("benchmark", "configs", other + ".json")["model"])
    for wrong in ({"position": "learned"}, {"num_loops": 1}, {"post_sublayer_norm": False}, {"tie_embeddings": True}, {"num_kv_heads": 4},
                  {"early_exit_threshold": 0.5}, {"exit_gate": False}):
        with pytest.raises(ValueError, match="does not describe"):
            ref.arch_of({"kwargs": {**body["model"]["kwargs"], **wrong}})
    with pytest.raises(ValueError, match="unknown wrong block"):
        ref.logits(body["model"], None, [[0]], wrong="something_else")


# --- bytes at this model's shapes -------------------------------------------------


def test_the_weight_streams_count_for_a_step_worked_by_hand():
    """Ouro-2.6B: a layer's matrices are 4 x 2048 x 2048 + 3 x 2048 x 5632 = 51,380,224 weights = 102.8 MB; 48 of them
    4.93 GB, read once a PASS: four times a step, and the head's 201 MB once: the issue's 19.93 GB, 24.3 ms at the bus.
    Whatever the step's rows or tiles: the count takes none."""
    m = {"hidden_size": 2048, "intermediate_size": 5632, "num_heads": 16, "num_kv_heads": 16, "head_dim": 128, "vocab_size": 49152,
         "swiglu": True, "num_layers": 192, "weight_layers": 48, "num_loops": 4}
    assert stream.layer_weight_bytes(m) == 2 * (4 * 2048 * 2048 + 3 * 2048 * 5632) == 102_760_448
    assert stream.step_weight_bytes(m) == 4 * 48 * 102_760_448 + 2 * 2048 * 49152 == 19_931_332_608
    assert stream.min_seconds(m, 10, PEAK) == pytest.approx(10 * 19_931_332_608 / 819e9) and stream.min_seconds(m, 1, PEAK) == pytest.approx(24.34e-3, rel=1e-3)
    # a model that runs its stack once and whose adapter names no pass: Mistral's 16 layers, read once
    mistral = {"hidden_size": 4096, "intermediate_size": 14336, "num_heads": 32, "num_kv_heads": 8, "head_dim": 128, "vocab_size": 32768,
               "swiglu": True, "num_layers": 16}
    assert stream.step_weight_bytes(mistral) == 16 * 2 * (4096 * (32 + 16) * 128 + 32 * 128 * 4096 + 3 * 4096 * 14336) + 2 * 4096 * 32768
    gelu = dict(mistral, swiglu=False)
    assert stream.layer_weight_bytes(mistral) - stream.layer_weight_bytes(gelu) == 2 * 4096 * 14336


# --- the readers on recorded traces -----------------------------------------------


def reduced(path, monkeypatch):
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: path)
    trace = tr.reduce_xplane(path, ("train_step", "server_step"), ("server_step",))
    return dataclasses.replace(trace, lo=float("-inf"), hi=float("inf"))  # no bench_slice: the whole trace


def _rows_log():
    return load("tests", "benchmark", "data", "ouro_rows_log.json")


def test_the_ouro_trace_holds_the_scopes_and_a_kernel_call_a_layer_a_pass(monkeypatch):
    trace = reduced(OURO, monkeypatch)
    names = op_scopes.load(OURO)
    dev = trace.devices[0]
    rows_log = _rows_log()
    kernels = op_scopes.kernel_events(names, dev, ["ragged_paged_attention"])["ragged_paged_attention"]
    # 2 layers x 4 passes: 8 calls an executed step (the server runs a step ahead: the last logged call only settles)
    assert len(kernels) % 8 == 0 and len(kernels) // 8 in (len(rows_log) - 1, len(rows_log))
    assert any(s["mixed"] for s in rows_log) and not all(s["mixed"] for s in rows_log)
    for scope in ("loop_pass", "pass_norm", "attention", "mlp", "head_sample"):
        assert op_scopes.scope_self_time(names, dev, scope) > 0, scope
    in_pass = op_scopes.scope_self_time(names, dev, "loop_pass")
    assert sum(ev.duration for ev in kernels) < in_pass  # the kernel's calls lie inside a pass
    assert op_scopes.scope_self_time(names, dev, "pass_norm") < 0.2 * in_pass
    stacks = {names.stack(dev.ordinal, ev.name) for ev in dev.leaves}
    assert any(op_scopes.in_scope(s, "loop_pass") and op_scopes.in_scope(s, "attention") for s in stacks)  # the existing scopes INSIDE a pass
    assert not any(op_scopes.in_scope(s, "loop_pass") and op_scopes.in_scope(s, "pass_norm") for s in stacks)


def test_the_three_readers_on_the_ouro_trace(monkeypatch):
    trace = reduced(OURO, monkeypatch)
    counters = {"model": RECORDED, "rows_log": _rows_log()}
    values = {name: reader(name).value(trace, counters, CELL) for name in NEW_READERS}
    assert all(v is not None for v in values.values()), values
    names, dev = op_scopes.load(OURO), trace.devices[0]
    steps = len(counters["rows_log"])
    assert values["loop_pass_device_ms"] == pytest.approx(1e3 * op_scopes.scope_self_time(names, dev, "loop_pass") / (steps * 4))
    spent = stream.matmul_time(trace, CELL)
    assert values["weight_stream_time_share"] == pytest.approx(100.0 * spent / dev.busy_s()) and 0 < values["weight_stream_time_share"] < 100
    assert values["weight_stream_roofline"] == pytest.approx(100.0 * stream.min_seconds(RECORDED, steps, PEAK) / spent) and 0 < values["weight_stream_roofline"] <= 100
    # the matmuls lie inside the three scopes and outside the kernel
    assert spent < sum(op_scopes.scope_self_time(names, dev, s) for s in stream.SCOPES)
    # the shared readers that take m["num_layers"] count 8 calls a step and pass their check
    assert reader("ragged_attn_time_share").value(trace, counters, CELL) > 0
    assert 0 < reader("ragged_attn_roofline").value(trace, counters, CELL) <= 100
    with pytest.raises(ValueError, match="holds kernel calls"):
        reader("ragged_attn_time_share").value(trace, {**counters, "model": {**RECORDED, "num_layers": 2}}, CELL)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_finds_nothing_in_another_models_trace_and_without_a_trace(monkeypatch, name):
    trace = reduced(DENSE, monkeypatch)
    rows = [{"mixed": False, "rows": [(1, 10)]}]
    assert reader(name).value(None, {"model": RECORDED, "rows_log": rows}, CELL) is None
    assert reader(name).value(trace, {"model": {"num_layers": 2, "remat": True}, "rows_log": rows}, CELL) is None  # another adapter's shape
    # a looped model whose trace has no loop_pass scope (the parent): None, no raise; the matmuls' three scopes are in every serving trace
    assert reader(name).value(trace, {"model": RECORDED, "rows_log": rows}, CELL) is None or name.startswith("weight_stream")
    assert reader(name).value(trace, {"model": RECORDED}, CELL) is None or name == "weight_stream_time_share"  # an untraced run logs no rows


# --- the logits tool, rehearsed ---------------------------------------------------


def test_the_logits_tool_rehearses_and_every_wrong_block_shows():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run([sys.executable, "benchmark/tools/ouro_logits_check.py", "--rehearse", "--seed", "5"], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    report = json.loads(done.stdout.strip().splitlines()[-1])
    assert report["within_limits"] is True and (report["layers"], report["passes"]) == (3, 4)
    assert report["worst_abs_diff"] < 1e-5 and report["served_argmax_regret_worst_mean"] == [0.0, 0.0]  # float32 at the toy widths
    assert set(report["wrong_blocks_refused_by_the_cells_limits"]) == {"three_passes", "shared_cache", "no_pass_norm", "no_post_norm", "weights_fp8"}
    for name in report["wrong_blocks_refused_by_the_cells_limits"]:
        assert report[name][1] > 1000 * report["mean_abs_diff"], name  # each a different function, not a rounding
