"""What PR 52 added to the benchmark for granite-4.0-h-micro: the configuration
file against the catalog row's published keys (every one verbatim, nothing
reduced), its memory arithmetic against the program's own shapes, the
adapter's shape and its three rescaled leaves, the reference's independence,
the new kernel count by hand, the three new readers on a trace recorded on the
chip (``benchmark/tools/record_granite_trace.py``) and on traces that hold
nothing of theirs, the traffic file against the engine's ``max_seq_len``, and
the logits tool's rehearsal (the cell's own rehearsal is a case of
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
from benchmark.kernels import ssd_recurrence as ssd
from tests.benchmark.spec_lookup import readers_of

HERE = os.path.dirname(__file__)
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
NAME, CELL_NAME = "granite-4.0-h-micro", "granite4h_micro_decode_heavy"
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
CELL = {"name": "a_serving_cell", "config": {"engine": {"kind": "serve"}}, "peak": PEAK}
GRANITE = os.path.join(HERE, "data", "granite_tpu.xplane.pb")
SOLAR = os.path.join(HERE, "data", "solar_tpu.xplane.pb")
DENSE = os.path.join(HERE, "data", "named_tpu.xplane.pb")
NEW_READERS = ["ssm_time_share", "ssd_state_roofline", "ssm_attn_time_share"]
# what ISSUE 52 lists: the sixteen readers every serving cell has (PR 36's four among them)
SHARED_READERS = [
    "device_idle_share", "decode_step_device_ms", "mixed_step_device_ms", "step_host_share", "kv_pages_in_use_share", "compiles_in_window",
    "step_admit_ms", "step_pack_ms", "step_dispatch_ms", "step_settle_ms", "rows_per_step", "mixed_step_token_fill",
    "exec_gap_ms", "host_turnaround_ms", "enqueue_call_ms", "run_ahead_share",
]
# the recorded model (record_granite_trace.py's MODEL) as the adapter would describe it
RECORDED = {"num_layers": 3, "hidden_size": 256, "num_attention_layers": 1, "num_linear_layers": 0, "num_ssm_layers": 2, "ssm_heads": 4,
            "ssm_head_dim": 64, "ssm_state": 128, "ssm_conv_channels": 512, "ssm_conv_kernel": 4}
# config.json of ibm-granite/granite-4.0-h-micro as the model-configs catalog holds it
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": ["mamba"] * 5 + ["attention"] + (["mamba"] * 9 + ["attention"]) * 3 + ["mamba"] * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm", "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 0, "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352,
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
    assert entry["reduced"] == body["reduced"] == []  # no depth cut, no vocabulary slice, no expert share
    assert body["source"] == entry["source"] == "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"
    assert len(PUBLISHED["layer_types"]) == 40 and [i for i, t in enumerate(PUBLISHED["layer_types"]) if t == "attention"] == [5, 15, 25, 35]
    assert {k: body[k] for k in PUBLISHED} == PUBLISHED  # every key verbatim, the layer list whole
    kwargs = body["model"]["kwargs"]
    assert kwargs["layer_types"] == ["softmax" if t == "attention" else "ssm" for t in PUBLISHED["layer_types"]]
    widths = {"hidden_size": 2048, "intermediate_size": 8192, "num_heads": 32, "num_kv_heads": 8, "head_dim": 64, "ssm_num_heads": 64,
              "ssm_head_dim": 64, "ssm_state": 128, "ssm_groups": 1, "ssm_conv_kernel": 4, "vocab_size": 100352, "num_layers": 40}
    assert {k: kwargs[k] for k in widths} == widths
    assert kwargs["ssm_num_heads"] * kwargs["ssm_head_dim"] == PUBLISHED["mamba_expand"] * PUBLISHED["hidden_size"]
    assert (kwargs["embedding_multiplier"], kwargs["residual_multiplier"], kwargs["logits_scaling"], kwargs["attn_softmax_scale"]) == (12.0, 0.22, 8.0, 0.015625)
    assert (kwargs["position"], kwargs["tie_embeddings"], kwargs["num_experts"], kwargs["leading_dense_layers"], kwargs["use_bias"]) == ("none", True, 0, 0, False)
    for published, ours in body["model"]["published_keys"].items():
        assert kwargs[ours] == PUBLISHED[published], published
    assert {"state", "dt", "chunk", "conv", "norm", "attention", "multipliers", "seeded", "left_out", "serving_max_seq_len", "in_proj"} <= set(body["assumed"])
    assert "float32" in body["assumed"]["state"] and "NO clamp" in body["assumed"]["dt"] and "256" in body["assumed"]["chunk"] and "128" in body["assumed"]["chunk"]
    assert "one v5e chip holds the model whole" in body["deployment"]
    paged = body["engine"]["init_inference"]["paged_kv"]
    assert (paged["page_size"], paged["max_slots"], paged["prefill_chunk"], paged["max_seq_len"], paged["num_pages"]) == (64, 64, 128, 1536, 0)
    check = body["engine"]["check"]
    assert check["max_context"] == 1536 and check["sample"] == 4 and 0 < check["mean_logit_gap"] < check["logit_margin"]
    assert "float8" in check["why"] and "bfloat16 state" in check["why"]
    seeded = body["model"]["seeded"]
    assert seeded["wq_std"] > 0.02 and seeded["out_std"] == 0.02 and seeded["embed_std"] < 0.02 and "tied" in seeded["why"]
    # the rehearsal holds one whole period of ten at toy widths
    small = body["rehearse"]["model"]["kwargs"]
    assert small["layer_types"] == kwargs["layer_types"][:10] and small["num_layers"] == 10


def test_the_memory_arithmetic_is_the_programs():
    """The deployment text's numbers, recomputed from the program's own
    ``init`` shapes and the pool's layout."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference import hybrid_decode

    body = load("benchmark", "configs", NAME + ".json")
    model, shape = files.build_model(body)
    cfg = model.config
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), None))
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    ssm, attn, ffn = count(shapes["periods"]["ssm"]) // 36, count(shapes["periods"]["softmax"]) // 4, count(shapes["periods"]["ffn"]) // 40
    assert (ssm + ffn, attn + ffn, count(shapes["embed"])) == (76_182_976, 60_821_504, 205_520_896)  # the issue's 76.18M, 60.82M, 205.5M
    assert count(shapes) == 36 * (ssm + ffn) + 4 * (attn + ffn) + count(shapes["embed"]) + 2048 == 3_191_396_096  # 6.38 GB in bf16
    paged = body["engine"]["init_inference"]["paged_kv"]
    pages = paged["max_slots"] * (paged["max_seq_len"] // paged["page_size"]) + 1
    state_shape, conv_shape = hybrid_decode.state_shapes(cfg, paged["max_slots"])
    assert state_shape == (36, 65, 64, 64, 128) and conv_shape == (36, 65, 3, 48, 128) and pages == 1537
    state, tails = int(np.prod(state_shape)) * 4, 36 * 65 * 3 * cfg.ssm_conv_channels * 2
    kv = pages * paged["page_size"] * 4 * 2 * cfg.num_kv_heads * cfg.head_dim * 2
    assert (round(state / 1e9, 2), round(tails / 1e9, 2), round(kv / 1e9, 2)) == (4.91, 0.06, 0.81)
    for stated in ("6.38 GB", "4.91 GB", "0.06 GB", "0.81 GB", "75.5 MB a row"):
        assert stated in body["deployment"], stated
    assert round(state / 65 / 1e6, 1) == 75.5 and (2 * count(shapes) + state + tails + kv) / 16e9 > 0.75  # far over the floor of a quarter of the chip
    want = {"num_layers": 40, "num_ssm_layers": 36, "num_attention_layers": 4, "num_linear_layers": 0, "ssm_heads": 64, "ssm_head_dim": 64,
            "ssm_state": 128, "ssm_conv_channels": 4352, "ssm_conv_kernel": 4, "num_heads": 32, "num_kv_heads": 8, "head_dim": 64, "vocab_size": 100352}
    assert {k: shape[k] for k in want} == want
    assert not {"num_experts", "router_experts", "experts_per_token", "num_moe_layers"} & set(shape)  # no expert key: the expert readers find no model


def test_the_traffic_fills_the_engines_max_seq_len_and_the_cell_is_in_its_lists():
    spec = load("BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == CELL_NAME)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "decode_heavy", 1) and len(cell["why"]) <= 200
    assert [w["name"] for w in spec["workloads"] if w["config"] == NAME] == [CELL_NAME]  # one cell, no second
    mix = load("benchmark", "traffic", "decode_heavy.json")
    paged = load("benchmark", "configs", NAME + ".json")["engine"]["init_inference"]["paged_kv"]
    assert mix["kind"] == "closed_loop" and mix["clients"] == "max_slots" and paged["max_slots"] == 64
    assert paged["max_seq_len"] == mix["prompt_len"]["max"] + mix["output_len"]["max"] == 1536
    rehearse = files.load_cell(spec, CELL_NAME, rehearse=True)
    r_paged, r_mix = rehearse["config_file"]["engine"]["init_inference"]["paged_kv"], rehearse["traffic_file"]
    assert r_paged["max_seq_len"] >= r_mix["prompt_len"]["max"] + r_mix["output_len"]["max"]
    assert CELL_NAME in next(m for m in spec["end_to_end"] if m["name"] == "serve_tokens_per_s")["workloads"]
    family = readers_of(spec, CELL_NAME)
    assert set(NEW_READERS + SHARED_READERS) == set(family)
    for r, m in family.items():
        assert m["moves"] == "serve_tokens_per_s"
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", r + ".py"))
    for name, layer, better in (("ssm_time_share", "model", "lower"), ("ssd_state_roofline", "kernels", "higher"), ("ssm_attn_time_share", "model", "lower")):
        new = family[name]
        assert (new["name"], new["unit"], new["source"], new["layer"], new["better"], new["workloads"]) == ("serve." + name, "%", "device_trace", layer, better, [CELL_NAME])
    # what reckons num_layers calls of the ragged kernel, an expert layer, a linear, window or latent layer is not asked of this cell
    assert not set(family) & {"ragged_attn_time_share", "ragged_attn_roofline", "ragged_kernel_call_us", "expert_ffn_time_share", "kda_state_roofline",
                              "linear_attn_time_share", "softmax_attn_time_share", "full_attn_time_share", "state_cache_share"}


def test_the_adapter_builds_the_programs_model_and_rescales_three_kinds_of_leaf():
    import jax
    import numpy as np

    body = load("benchmark", "configs", NAME + ".json")
    small = files.overlay(body, body["rehearse"])
    model, shape = files.build_model(small)
    assert type(model).__mro__[1].__name__ == "HybridMoETransformerLM"
    assert (shape["num_layers"], shape["num_ssm_layers"], shape["num_attention_layers"], shape["ssm_conv_channels"]) == (10, 9, 1, 384)
    init = lambda m: jax.jit(lambda key: m.init(key, None))(jax.random.PRNGKey(3))  # one program a model, not a kernel a leaf
    seeded, plain = init(model), init(type(model).__mro__[1](model.config))
    s = small["model"]["seeded"]
    out = s["out_std"] / (0.02 / (2 * 10) ** 0.5)
    want = {"['embed']['tokens']": s["embed_std"] / 0.02, "['periods']['ffn']['w_out']": out, "['periods']['softmax']['wo']": out,
            "['periods']['softmax']['wq']": s["wq_std"] / 0.02, "['periods']['ssm']['wo']": out}
    differing = {}
    for (path, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(seeded)[0], jax.tree_util.tree_flatten_with_path(plain)[0]):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            differing[jax.tree_util.keystr(path)] = None
            np.testing.assert_allclose(np.asarray(a), np.asarray(b) * want[jax.tree_util.keystr(path)], rtol=1e-6)
    assert sorted(differing) == sorted(want), differing


def test_the_reference_imports_nothing_of_the_program_and_refuses_another_block():
    path = os.path.join(ROOT, "benchmark", "reference", "granite_hybrid_decoder.py")
    with open(path) as f:
        source = f.read()
    tree = ast.parse(source)
    imported = {n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)} | {a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names}
    assert not any(name.startswith(("deepspeed_tpu", "benchmark")) for name in imported), imported
    for stated in ("ASSUMED", "LEFT OUT", "TOKEN BY TOKEN", "NOT 64^-0.5", "the gate BEFORE the norm", "BOTH branches", "no clamp",
                   'default_matmul_precision("highest")', "mamba_chunk_size", "tied"):
        assert stated in source, stated
    assert "ssd_chunked" not in source and "cumsum" not in source  # never the chunk form
    ref = files.load_module("reference", "granite_hybrid_decoder")
    body = load("benchmark", "configs", NAME + ".json")
    arch = ref.arch_of(body["model"])
    assert (arch["softmax_scale"], arch["embedding_multiplier"], arch["residual_multiplier"], arch["logits_scaling"]) == (0.015625, 12.0, 0.22, 8.0)
    assert (arch["ssm_heads"], arch["ssm_head_dim"], arch["ssm_state"], arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]) == (64, 64, 128, 32, 8, 64)
    for other in ("solar-open2-250b-l4-ep8", "kimi-linear-48b-a3b-l13-ep8", "mistral-7b-v0.3-l16"):
        with pytest.raises((ValueError, KeyError)):
            ref.arch_of(load("benchmark", "configs", other + ".json")["model"])
    for wrong in ({"position": "rope"}, {"ssm_groups": 2}, {"tie_embeddings": False}, {"num_experts": 8}, {"leading_dense_layers": 1}, {"attn_output_gate": True}):
        with pytest.raises(ValueError, match="does not describe"):
            ref.arch_of({"kwargs": {**body["model"]["kwargs"], **wrong}})


# --- operations and bytes at this model's shapes ---------------------------------


def test_the_recurrences_count_for_a_step_worked_by_hand():
    """One Mamba-2 layer of one step at the published shapes. A decode row: 64
    heads x 64 x 128 float32 of state in and out (2,097,152 bytes each way),
    the tail's three inputs of 4,352 channels in and out, the token's 4,352
    channels and 64 ``dt`` in and its 4,096 outputs out; bound by memory. A
    narrow step of 64 rows over 36 layers moves the issue's 9.66 GB of state.
    A dead row needs nothing."""
    H, P, N, C = 64, 64, 128, 4352
    state, tail, token = H * P * N * 4, 3 * C * 2, C * 2 + H * 4 + H * P * 2
    assert state == 2_097_152
    assert ssd.ops_and_bytes([(1, 900)], H, P, N, C) == (5 * H * P * N, 2 * state + 2 * tail + token)
    assert ssd.ops_and_bytes([(128, 128)], H, P, N, C) == (128 * 5 * H * P * N, 2 * state + 2 * tail + 128 * token)
    assert ssd.ops_and_bytes([(0, 0), (1, 5), (0, 7)], H, P, N, C) == ssd.ops_and_bytes([(1, 5)], H, P, N, C)  # never "all slots"
    seconds, bound = ssd.min_seconds([(1, 900)] * 64, H, P, N, C, PEAK)
    assert bound == "memory" and 36 * 64 * 2 * state == 9_663_676_416
    assert seconds == pytest.approx(64 * (2 * state + 2 * tail + token) / 819e9)
    assert 36 * seconds == pytest.approx(11.93e-3, rel=1e-2)  # the state-space layers' floor of a narrow step: 11.9 ms
    # a chunk of 128 tokens is still bound by memory: 5.4 GFLOP against 5.6 MB
    assert ssd.min_seconds([(128, 128)], H, P, N, C, PEAK)[1] == "memory"


# --- the readers on recorded traces -----------------------------------------------


def reduced(path, monkeypatch):
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: path)
    trace = tr.reduce_xplane(path, ("train_step", "server_step"), ("server_step",))
    return dataclasses.replace(trace, lo=float("-inf"), hi=float("inf"))  # no bench_slice: the whole trace


def _rows_log():
    return load("tests", "benchmark", "data", "granite_rows_log.json")


def test_the_granite_trace_holds_the_scopes_and_the_kernel(monkeypatch):
    trace = reduced(GRANITE, monkeypatch)
    names = op_scopes.load(GRANITE)
    dev = trace.devices[0]
    rows_log = _rows_log()
    kernels = op_scopes.kernel_events(names, dev, ["ssd_decode", "ragged_paged_attention"])
    # an executed step: two ssd_decode (the state-space layers); the attention layer's ragged kernel once, twice in a
    # mixed step. (The server runs a step ahead: the last of the logged calls only settles, so one step fewer ran.)
    narrow, mixed = sum(not s["mixed"] for s in rows_log), sum(s["mixed"] for s in rows_log)
    executed = len(kernels["ssd_decode"]) // 2
    assert len(kernels["ssd_decode"]) == 2 * executed > 0 and executed in (len(rows_log) - 1, len(rows_log)) and mixed > 0 and narrow > 0
    assert executed + mixed - 1 <= len(kernels["ragged_paged_attention"]) <= narrow + 2 * mixed
    for scope in ("ssm_mixer", "ssd_recurrence", "attention", "mlp", "head_sample"):
        assert op_scopes.scope_self_time(names, dev, scope) > 0, scope
    assert op_scopes.scope_self_time(names, dev, "ssd_recurrence") < op_scopes.scope_self_time(names, dev, "ssm_mixer")  # one scope inside the other


def test_the_three_readers_on_the_granite_trace(monkeypatch):
    trace = reduced(GRANITE, monkeypatch)
    counters = {"model": RECORDED, "rows_log": _rows_log()}
    values = {name: reader(name).value(trace, counters, CELL) for name in NEW_READERS}
    assert all(v is not None for v in values.values()), values
    assert 0 < values["ssm_attn_time_share"] < 100 and 0 < values["ssm_time_share"] < 100
    assert values["ssm_time_share"] + values["ssm_attn_time_share"] < 100
    assert 0 < values["ssd_state_roofline"] <= 100
    # the roofline reader is the count file's least time over the scope's device time
    names, dev = op_scopes.load(GRANITE), trace.devices[0]
    least = sum(ssd.min_seconds(step["rows"], 4, 64, 128, 512, PEAK)[0] for step in counters["rows_log"])
    assert values["ssd_state_roofline"] == pytest.approx(100.0 * 2 * least / op_scopes.scope_self_time(names, dev, "ssd_recurrence"))


@pytest.mark.parametrize("name", NEW_READERS)
@pytest.mark.parametrize("path, model", [(DENSE, {"num_layers": 2, "remat": True}),
                                         (SOLAR, {"num_layers": 4, "num_linear_layers": 3, "num_attention_layers": 1, "linear_heads": 8, "linear_head_dim": 128})],
                         ids=["dense_trace", "solar_trace"])
def test_a_reader_finds_nothing_in_another_models_trace_and_without_a_trace(monkeypatch, name, path, model):
    trace = reduced(path, monkeypatch)
    rows = [{"mixed": False, "rows": [(1, 10)]}]
    assert reader(name).value(trace, {"model": model, "rows_log": rows}, CELL) is None
    assert reader(name).value(None, {"model": RECORDED, "rows_log": []}, CELL) is None
    # a model with state-space layers whose trace has no such scope (the parent): None, no raise; the ``attention`` scope
    # is in every serving trace, so that reader alone reads it
    assert reader(name).value(trace, {"model": RECORDED, "rows_log": rows}, CELL) is None or name == "ssm_attn_time_share"


# --- the logits tool, rehearsed ---------------------------------------------------


def test_the_logits_tool_rehearses_and_every_control_is_refused():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run([sys.executable, "benchmark/tools/granite_logits_check.py", "--rehearse", "--seed", "5"], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=1500)
    assert done.returncode == 0, done.stderr[-2000:]
    report = json.loads(done.stdout.strip().splitlines()[-1])
    assert report["within_limits"] is True and report["layers"] == 10 and report["isolated"] is True
    wanted = {"softmax_scale_rsqrt_64", "conv_bias_dropped", "gate_behind_the_norm", "D_dropped", "no_logits_scaling", "no_residual_multiplier",
              "state_not_carried", "conv_tail_not_carried", "state_bfloat16", "weights_fp8"}
    assert set(report["controls_refused"]) == wanted
    # float32 throughout at the toy widths: a bfloat16 state is the one control whose difference is itself a rounding
    assert all(refused for name, refused in report["controls_refused"].items() if name != "state_bfloat16"), report["controls_refused"]
