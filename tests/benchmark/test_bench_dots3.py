"""What PR 66 added to the benchmark for dots3-note-prev: the configuration
file against the catalog row's published keys (every one verbatim but the
three of ``reduced``), the cut's arithmetic against the program's own shapes
and pools, the share test of the guide's section 4 (the sixteen shares' routed
parts and the shared expert once are the uncut layer), the adapter's shape,
the reference's independence and what it refuses, the two new kernel files'
counts at this model's shapes by hand, the eight new readers on a recorded
trace of another model (nothing to read, no raise) and on a stand-in trace,
the cell in its readers' lists by name, and ONE rehearsal of the logits tool.
The cell's own rehearsal is ``test_bench_rehearsal.py``'s case of it. Entries
are found by search: neither a count of cells nor a position in a list is
pinned."""

import ast
import dataclasses
import json
import os
import subprocess
import sys

import pytest

from benchmark import files, op_scopes, program_spans
from benchmark import trace_reduce as tr
from benchmark.kernels import ring_latent_attention as ring
from benchmark.kernels import sparse_latent_attention as sparse
from tests.benchmark.spec_lookup import readers_of

HERE = os.path.dirname(__file__)
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
NAME, CELL_NAME = "dots3-note-prev-l9-ep16", "dots3_note_long_context_decode"
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
CELL = {"name": "a_serving_cell", "config": {"engine": {"kind": "serve"}}, "peak": PEAK}
GRANITE = os.path.join(HERE, "data", "granite_tpu.xplane.pb")  # a model with ``ssm_mixer`` and ``attention`` scopes and no sparse layer, recorded on the chip (PR 52)
NEW_READERS = ["sparse_index_time_share", "sparse_index_roofline", "sparse_select_time_share", "sparse_attn_time_share", "sparse_attn_roofline",
               "window_latent_time_share", "window_latent_roofline", "selected_keys_share"]
LAYER_TYPES = ["full_attention" if i == 0 or i % 4 == 1 else "sliding_attention" for i in range(46)]
# config.json of dots-studio/dots3-note-prev as the model-configs catalog holds it
PUBLISHED = {
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False, "attention_gate_type": "headwise", "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 5120, "index_head_dim": 128, "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824, "kv_lora_rank": 512,
    "layer_types": LAYER_TYPES, "max_position_embeddings": 524288, "model_type": "dots3_note", "moe_intermediate_size": 1536, "moe_layer_freq": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 46, "num_key_value_heads": 128, "q_lora_rank": 1024, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 80000000, "routed_scaling_factor": 1, "scoring_func": "sigmoid", "sliding_window_size": 513,
    "swa_attention_gate_type": "headwise", "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64, "swa_num_key_value_heads": 64,
    "swa_q_lora_rank": 1024, "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64, "swa_rope_theta": 50000, "swa_v_head_dim": 128,
    "tie_word_embeddings": False, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 152064,
}
REDUCED = {"num_hidden_layers": 9, "n_routed_experts": 16, "vocab_size": 19008}
MODEL = {"num_sparse_layers": 3, "index_heads": 64, "index_head_dim": 128, "index_topk": 2048, "num_heads": 128, "kv_lora_rank": 512, "qk_rope_head_dim": 64,
         "num_window_latent_layers": 6, "window": 513, "window_heads": 64, "window_kv_lora_rank": 1024, "window_qk_rope_head_dim": 64}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def reader(name):
    return files.load_module("layer_metrics", name)


# --- the configuration ---------------------------------------------------------


def test_configuration_holds_the_published_keys_and_three_cuts():
    body = load("benchmark", "configs", NAME + ".json")
    spec = load("BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == NAME)
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and len(entry["why"]) <= 200
    assert entry["reduced"] == body["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert body["source"] == entry["source"] == "https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json"
    assert {k: body[k] for k in PUBLISHED} == {**PUBLISHED, **REDUCED}  # every other key verbatim, layer_types whole
    assert {k: body["published"][k] for k in REDUCED} == {k: PUBLISHED[k] for k in REDUCED}
    assert LAYER_TYPES.count("full_attention") == 13 and LAYER_TYPES[:6] == ["full_attention"] * 2 + ["sliding_attention"] * 3 + ["full_attention"]
    kwargs = body["model"]["kwargs"]
    assert kwargs["layer_types"] == [{"full_attention": "sparse_latent", "sliding_attention": "window_latent"}[t] for t in LAYER_TYPES[:9]]
    for published, ours in body["model"]["published_keys"].items():  # no width cut: each is the source's under the program's name
        assert kwargs[ours] == body[published], published
    assert (kwargs["head_dim"], kwargs["moe_router_experts"], kwargs["moe_expert_share"], kwargs["attn_head_gate"]) == (192, 256, [0, 16], True)
    assert (kwargs["moe_scoring"], kwargs["moe_select_bias"], kwargs["position"], kwargs["activation"]) == ("sigmoid", True, "rope", "swiglu")
    assert {"head_dim", "apply_mla_qkv_lora_rescale", "indexer", "rotary", "softmax_scale", "kv_norm", "kv_b_proj", "sliding_window_size", "attention_gate_type",
            "router", "seeded", "left_out", "serving_max_seq_len", "latent_pages"} <= set(body["assumed"])
    assert "EXACT" in body["assumed"]["indexer"] and "vision and audio" in body["assumed"]["left_out"] and "multi-token-prediction" in body["assumed"]["left_out"]
    assert "five pipeline stages of sixteen" in body["deployment"] and "4,603.4M = 9.21 GB" in body["deployment"]
    paged = body["engine"]["init_inference"]["paged_kv"]
    assert (paged["page_size"], paged["max_slots"], paged["prefill_chunk"], paged["max_seq_len"], paged["num_pages"]) == (64, 32, 512, 16384, 0)
    check = body["engine"]["check"]
    assert 0 < check["mean_logit_gap"] < check["logit_margin"] and body["model"]["seeded"]["rescaled"] == []
    small = files.overlay(body, body["rehearse"])  # the rehearsal's contexts pass its index_topk and its window
    assert small["model"]["kwargs"]["index_topk"] < load("benchmark", "traffic", "long_context_decode.json")["rehearse"]["prompt_len"]["min"]


def test_the_traffic_passes_index_topk_fills_max_seq_len_and_the_cell_is_in_its_lists():
    spec = load("BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == CELL_NAME)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "long_context_decode", 1) and len(cell["why"]) <= 200
    mix = load("benchmark", "traffic", "long_context_decode.json")
    body = load("benchmark", "configs", NAME + ".json")
    paged, check = body["engine"]["init_inference"]["paged_kv"], body["engine"]["check"]
    assert mix["kind"] == "closed_loop" and mix["clients"] == "max_slots" and paged["max_slots"] == 32
    assert (mix["prompt_len"]["min"], mix["prompt_len"]["max"], mix["output_len"]["min"], mix["output_len"]["max"]) == (4096, 8192, 6144, 8192)
    assert paged["max_seq_len"] == mix["prompt_len"]["max"] + mix["output_len"]["max"] == 16384
    # every served token sits past index_topk and the window; the check's context holds every prompt and served tokens behind it
    assert mix["prompt_len"]["min"] >= 2 * PUBLISHED["index_topk"] and mix["prompt_len"]["max"] < check["max_context"] and check["max_context"] % 512 == 0
    rehearse = files.load_cell(spec, CELL_NAME, rehearse=True)
    r_paged, r_mix = rehearse["config_file"]["engine"]["init_inference"]["paged_kv"], rehearse["traffic_file"]
    assert r_paged["max_seq_len"] == r_mix["prompt_len"]["max"] + r_mix["output_len"]["max"] == 128
    assert r_mix["prompt_len"]["max"] < rehearse["config_file"]["engine"]["check"]["max_context"]
    assert CELL_NAME in next(m for m in spec["end_to_end"] if m["name"] == "serve_tokens_per_s")["workloads"]
    family = readers_of(spec, CELL_NAME)
    assert set(NEW_READERS) <= set(family) and all(m["moves"] == "serve_tokens_per_s" for m in family.values())
    for name in NEW_READERS:
        assert family[name]["name"] == "serve." + name and family[name]["workloads"] == [CELL_NAME] and family[name]["unit"] == "%", name
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    assert {family[n]["layer"] for n in NEW_READERS if n.endswith("_roofline")} == {"kernels"} and family["selected_keys_share"]["source"] == "program_span"
    assert {"device_idle_share", "decode_step_device_ms", "expert_ffn_roofline", "held_assignments_share", "exec_gap_ms"} <= set(family)
    # what reckons the latent kernel's calls, one head layout for every layer, or every layer as routed, is not asked of this cell
    assert not set(family) & {"latent_attn_roofline", "latent_attn_time_share", "ragged_attn_roofline", "ragged_kernel_call_us", "experts_hit_share"}


def test_the_cuts_arithmetic_is_the_programs_and_the_adapter_says_both_kinds():
    """The deployment text's numbers, recomputed from the program's own ``init`` shapes and the pools' layouts."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference import hybrid_decode
    from deepspeed_tpu.inference.kv_pool import window_ring_pages

    body = load("benchmark", "configs", NAME + ".json")
    model, shape = files.build_model(body)
    cfg = model.config
    assert type(model).__name__ == "HybridMoETransformerLM" and {k: shape[k] for k in MODEL} == MODEL and "num_latent_layers" not in shape
    assert (shape["num_layers"], shape["num_moe_layers"], shape["num_experts"], shape["router_experts"], shape["experts_per_token"]) == (9, 8, 16, 256, 8)
    assert cfg.period == ("sparse_latent", "window_latent", "window_latent", "window_latent") and cfg.num_periods == 2 and cfg.remainder == ()
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), None))
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    periods, lead = shapes["periods"], shapes["leading"][0]
    full, window = count(lead["mixer"]), count(periods["window_latent"]) // 6
    assert (full, window, count(periods["sparse_latent"]) // 2, count(lead["ffn"])) == (144_055_040, 90_840_064, 144_055_040, 212_341_760)
    assert (count(periods["moe"]["experts"]) // 8, count(periods["moe"]["shared"]) // 8) == (16 * 23_592_960, 23_592_960)
    assert round(count(shapes) / 1e6, 1) == 4603.4 and count(shapes["embed"]) == count(shapes["lm_head"]) == 19008 * 5120
    pages = 32 * (16384 // 64) + 1
    latent, index = hybrid_decode.paged_latent_shapes(cfg, pages, 64)
    assert (latent, index) == ((3, 8193, 64, 640), (3, 8193, 64, 128))  # 4,608 B a token: the issue's 2.42 GB
    ring_pages = window_ring_pages(513, 64, 512)
    assert ring_pages == 16 and hybrid_decode.window_latent_shape(cfg, 32, 64, ring_pages) == (6, 1 + 32 * 16, 64, 1152)
    cache = (np.prod(latent) + np.prod(index) + np.prod((6, 513, 64, 1152))) * 2
    assert 2.85e9 < cache < 2.9e9 and 12.0e9 < cache + 2 * count(shapes) < 12.2e9  # resident ~12.1 GB of 16


def test_the_sixteen_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer():
    """The guide's section 4 at toy widths: one routed layer's FFN through
    ``hybrid_moe.moe_ffn`` with all 16 experts held, against the sum of the
    four shares' outputs (4 held each) with the shared expert's output counted
    once; and the reference's share arithmetic agrees for share 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models import hybrid_moe as hm

    whole_cfg = hm.dots3_note_config("tiny", dtype="float32", num_experts=16, moe_expert_share=(0, 1))
    whole = jax.jit(lambda key: hm.HybridMoETransformerLM(whole_cfg).init(key, None))(jax.random.PRNGKey(1))
    p = jax.tree_util.tree_map(lambda a: a[0, 0], whole["periods"]["moe"])
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 64), jnp.float32)
    from deepspeed_tpu.moe.experts import apply_dense_ffn

    @jax.jit
    def parts(p, h):
        want, _ = hm.moe_ffn(whole_cfg, p, h)
        shared = apply_dense_ffn(p["shared"], h.reshape(-1, 64), "swiglu").reshape(h.shape)
        total, held_counts = shared, []
        for i in range(4):
            share_cfg = hm.dots3_note_config("tiny", dtype="float32", moe_expert_share=(i, 4))
            held = {**p, "experts": jax.tree_util.tree_map(lambda a: a[4 * i : 4 * i + 4], p["experts"])}
            out, counts = hm.moe_ffn(share_cfg, held, h)
            total = total + (out - shared)  # a share's output holds the shared expert: counted once
            held_counts.append(counts.sum())
        return want, shared, total, jnp.stack(held_counts)

    want, shared, total, held_counts = parts(p, h)
    assert int(held_counts.min()) > 0 and int(held_counts.sum()) == 2 * 24 * 3  # every assignment is some share's
    err, routed = float(jnp.abs(total - want).max()), float(jnp.abs(want - shared).max())
    assert err < 1e-8 and routed > 1e-4, (err, routed)  # the routed part is ~1e-3 at init's 0.02: the sum is exact to its rounding


def test_the_reference_imports_nothing_of_the_program_and_refuses_another_block():
    path = os.path.join(ROOT, "benchmark", "reference", "dots3_note_decoder.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    imported = {n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)} | {a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names}
    assert not any(m.startswith(("deepspeed_tpu", "benchmark")) for m in imported), imported
    ref = files.load_module("reference", "dots3_note_decoder")
    body = load("benchmark", "configs", NAME + ".json")
    arch = ref.arch_of(body["model"])
    assert arch["full"] == (128, 1024, 512, 128, 64, 128, 8e7) and arch["window_dims"] == (64, 1024, 1024, 192, 64, 128, 5e4)
    assert (arch["window"], arch["index_topk"], arch["held"], arch["first_held"], arch["experts_per_token"]) == (513, 2048, 16, 0, 8)
    for wrong in ({"latent_lora_rescale": False}, {"attn_head_gate": False}, {"moe_scoring": "softmax"}, {"layer_types": ["latent"] * 9}, {"position": "none"}):
        with pytest.raises(ValueError, match="does not describe"):
            ref.arch_of({"kwargs": {**body["model"]["kwargs"], **wrong}})


# --- the counts --------------------------------------------------------------------


def test_the_counts_for_a_decode_row_and_a_chunk_worked_by_hand():
    """One sparse layer and one window layer of one step at the published
    shapes. A decode row at 8,192 keys: the index reads 8,192 x 128 numbers
    (2.0 MB, the issue's) and spends 64 x 258 operations a key; the attention
    reads 2,048 entries of 576 (2.36 MB) where all 8,192 would be 9.4 MB, and
    128 heads x 2 x 1,088 operations an entry put it ON the ridge (242 FLOP/B
    against 240). A window row reads 513 entries of 1,088."""
    row = [(1, 8192)]
    ops, moved = sparse.index_ops_and_bytes(row, 64, 128)
    assert ops == (2 * 128 + 2) * 64 * 8192 and moved == (8192 * 128 + 64 * 128) * 2 == 2_113_536
    ops, moved = sparse.attend_ops_and_bytes(row, 128, 512, 64, 2048)
    assert ops == 2 * 1088 * 2048 * 128 and moved == (2048 * 576 + 128 * 1088) * 2 == 2_637_824
    assert 215 < ops / moved < 245 and abs(ops / 197e12 - moved / 819e9) / (moved / 819e9) < 0.12  # on the ridge: both floors within 12%
    assert sparse.attend_ops_and_bytes([(1, 1500)], 128, 512, 64, 2048)[0] == 2 * 1088 * 1500 * 128  # fewer keys than the selection keeps: all
    chunk = [(512, 6144)]  # queries at positions 5,632..6,143: every one past index_topk
    assert sparse.pairs(512, 6144) == 512 * 6144 - 512 * 511 // 2 and sparse.pairs(512, 6144, 2048) == 512 * 2048
    assert sparse.attend_ops_and_bytes(chunk, 128, 512, 64, 2048)[1] == ((2048 + 511) * 576 + 512 * 128 * 1088) * 2
    assert sparse.min_seconds(*sparse.attend_ops_and_bytes(chunk, 128, 512, 64, 2048), PEAK) == pytest.approx(2 * 1088 * 512 * 2048 * 128 / 197e12)  # compute-bound
    ops, moved = ring.ops_and_bytes([(1, 9000)], 64, 1024, 64, 513)
    assert ops == 2 * 2112 * 513 * 64 and moved == ((513 + 1) * 1088 + 64 * 2112) * 2
    assert ring.ops_and_bytes([(1, 100)], 64, 1024, 64, 513)[0] == 2 * 2112 * 100 * 64 and ring.ops_and_bytes([(0, 0)], 64, 1024, 64, 513) == (0, 0)
    assert ring.pairs(512, 6144, 513) == 512 * 513 and ring.min_seconds([(512, 6144)], 64, 1024, 64, 513, PEAK) == pytest.approx(2 * 2112 * 512 * 513 * 64 / 197e12)
    narrow = [(1, 8192)] * 32  # the issue's narrow step: chosen latents + indexer keys ~0.45 GB, rings ~0.23 GB of entries (0.27 with q and o)
    per_step = 3 * (sparse.index_ops_and_bytes(narrow, 64, 128)[1] + sparse.attend_ops_and_bytes(narrow, 128, 512, 64, 2048)[1])
    assert 0.44e9 < per_step < 0.47e9 and 0.22e9 < 6 * ring.ops_and_bytes(narrow, 64, 1024, 64, 513)[1] < 0.28e9


# --- the new readers ------------------------------------------------------------------


def reduced(path, monkeypatch):
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: path)
    trace = tr.reduce_xplane(path, ("train_step", "server_step"), ("server_step",))
    return dataclasses.replace(trace, lo=float("-inf"), hi=float("inf"))  # no bench_slice: the whole trace


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_finds_nothing_in_another_models_trace_and_nothing_without_one(monkeypatch, name):
    """What the parent, or any model without these layers, gives a new reader: None, and no raise."""
    trace = reduced(GRANITE, monkeypatch)
    rows = [{"mixed": False, "rows": [(1, 5000)] * 32}]
    assert reader(name).value(trace, {"model": {"num_ssm_layers": 36}, "rows_log": rows}, CELL) is None  # granite's own shape
    assert reader(name).value(None, {"model": MODEL, "rows_log": rows}, CELL) is None
    assert reader(name).value(trace, {"model": MODEL, "rows_log": rows}, CELL) is None  # the scopes and the two counts are not in this trace


def test_the_new_readers_on_a_trace_whose_scopes_stand_in(monkeypatch):
    """granite's recorded trace with its ``ssm_mixer`` scope read as each of the new scopes: a time share is the
    scope's device time over busy time, a roofline the layers' least time for the logged rows over the scope's time;
    and the selected share from ``serve.pack`` spans that carry the two counts."""
    trace = reduced(GRANITE, monkeypatch)
    names, dev = op_scopes.load(GRANITE), trace.devices[0]
    in_scope = op_scopes.in_scope
    ours = {"sparse_index_scores", "sparse_select", "sparse_attend", "ring_latent_attend", "window_latent_attention"}
    monkeypatch.setattr(op_scopes, "in_scope", lambda stack, scope: in_scope(stack, "ssm_mixer" if scope in ours else scope))
    spent = op_scopes.scope_self_time(names, dev, "ssm_mixer")
    assert spent > 0
    rows = [{"mixed": False, "rows": [(1, 5000)] * 31 + [(0, 0)]}, {"mixed": True, "rows": [(1, 5000)] * 31 + [(512, 4096)]}]
    counters = {"model": MODEL, "rows_log": rows}
    share = pytest.approx(100.0 * spent / dev.busy_s())
    for name in ("sparse_index_time_share", "sparse_select_time_share", "sparse_attn_time_share", "window_latent_time_share"):
        assert reader(name).value(trace, counters, CELL) == share, name
    least = sum(sparse.min_seconds(*sparse.index_ops_and_bytes(s["rows"], 64, 128), PEAK) for s in rows)
    assert reader("sparse_index_roofline").value(trace, counters, CELL) == pytest.approx(100.0 * 3 * least / spent)
    least = sum(sparse.min_seconds(*sparse.attend_ops_and_bytes(s["rows"], 128, 512, 64, 2048), PEAK) for s in rows)
    assert reader("sparse_attn_roofline").value(trace, counters, CELL) == pytest.approx(100.0 * 3 * least / spent)
    least = sum(ring.min_seconds(s["rows"], 64, 1024, 64, 513, PEAK) for s in rows)
    assert reader("window_latent_roofline").value(trace, counters, CELL) == pytest.approx(100.0 * 6 * least / spent)
    assert reader("sparse_attn_roofline").value(trace, {"model": MODEL}, CELL) is None  # no rows logged: nothing to reckon from
    monkeypatch.setattr(program_spans, "attr_values", lambda trace, cell, name, *attrs: [(2048 * 32, 6000 * 32), (2048 * 32, 6100 * 32)] if name == "serve.pack" else [])
    assert reader("selected_keys_share").value(trace, counters, CELL) == pytest.approx(100.0 * 2 * 2048 / 12100)


# --- the rehearsal ----------------------------------------------------------------


def test_the_logits_tool_rehearses_and_its_limit_refuses_its_controls():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run([sys.executable, "benchmark/tools/dots3_logits_check.py", "--rehearse", "--seed", "5", "--only", "recent_2048"],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
    assert done.returncode == 0, done.stderr[-2000:]
    report = json.loads(done.stdout.strip().splitlines()[-1])
    assert report["within_limits"] is True and (report["layers"], report["index_topk"], report["window"]) == (3, 16, 9)
    assert report["prompt"] + report["decode"] > 3 * report["index_topk"] and 0 < report["held_assignments"] < report["routed_assignments"]
    assert report["controls_refused"] == {"recent_2048": True}  # ONE control here: the other eight are the chip's (CHANGES.md has each one's reading)
    # float32 throughout at the toy widths: the program is the reference to the order of its sums, each control is another function
    assert report["mean_abs_diff"] < 1e-6 < 2e-4 < min(report[name][1] for name in report["controls_refused"])
