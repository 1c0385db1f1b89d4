"""BENCHMARK.json against the contract's limits, and against the files the
harness finds by name: a later PR that adds an entry without its file, or a
name outside the allowed characters, fails here before any chip time."""

import json
import os
import re

import pytest

from benchmark.files import load_cell, reader_of

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state_size|proj|head_dim|head_size|_dim$|_rank$|expand|experts_per_tok)")


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


SPEC = load("BENCHMARK.json")
CELLS = [w["name"] for w in SPEC["workloads"]]


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    assert 1 <= len(SPEC["paths"]) <= 16
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    # a full check with the full 24 cells fits the driver's budget
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_units_and_keys_are_within_the_contract(section):
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves", "workloads"},
    }[section]
    names = [e["name"] for e in SPEC[section]]
    assert len(names) == len(set(names))
    for e in SPEC[section]:
        assert set(e) <= allowed and set(e) >= allowed - {"workloads"}, e
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e and section in ("configs", "workloads", "per_layer") and key != "source":
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] and "\t" not in e[key], (e["name"], key, len(e[key]))
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
    metric_names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def test_configs_name_their_source_and_cut_no_width():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert 1 <= len(c["source"]) <= 200  # a public URL or a paper
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        body = load(c["file"])
        assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
        assert "assumed" in body and "deployment" in body
        # the file names its model adapter and its plain reference, found like everything else
        model = body["model"]
        assert set(model) >= {"adapter", "reference", "kwargs", "published_keys"}
        assert os.path.exists(os.path.join(ROOT, "benchmark", "models", model["adapter"] + ".py")), model["adapter"]
        assert os.path.exists(os.path.join(ROOT, "benchmark", "reference", model["reference"] + ".py")), model["reference"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key


def test_config_files_agree_with_the_published_keys():
    # each file maps the source's keys to the model's own; the two must say the same
    for c in SPEC["configs"]:
        body = load(c["file"])
        table = body["model"]["published_keys"]
        assert table, c["name"]
        for published, ours in table.items():
            assert body[published] == body["model"]["kwargs"][ours], (c["name"], published)
        for key in c["reduced"]:
            assert key in body, (c["name"], key)
    xl = load("benchmark/configs/gpt2-xl.json")
    assert (xl["n_layer"], xl["n_embd"], xl["n_head"]) == (48, 1600, 25)
    m = load("benchmark/configs/mistral-7b-v0.3-l16.json")
    assert (m["hidden_size"], m["intermediate_size"], m["num_attention_heads"], m["num_key_value_heads"], m["vocab_size"]) == (4096, 14336, 32, 8, 32768)
    assert m["num_hidden_layers"] == 16 and m["tie_word_embeddings"] is False  # 32 in the source: the one cut


def test_cells_and_their_files():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert 2 <= len(pairs) <= 24 and len(pairs) == len(set(pairs))
    configs = {c["name"] for c in SPEC["configs"]}
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(pairs) // 4)
    for w in SPEC["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        traffic = load("benchmark", "traffic", w["traffic"] + ".json")
        assert os.path.exists(os.path.join(ROOT, "benchmark", "drivers", traffic["kind"] + ".py"))
        if traffic["kind"] == "open_loop":  # its rate is a number fixed in the cell, not searched for
            assert isinstance(load_cell(SPEC, w["name"], rehearse=False)["traffic_file"]["rate_rps"], (int, float))


def test_every_metric_has_a_reader_and_every_cell_its_metrics():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1 and "workloads" not in e2e["setup_s"]
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
        assert os.path.exists(os.path.join(ROOT, "benchmark", "end_to_end", m["name"] + ".py")), m["name"]
    for m in SPEC["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", reader_of(m["name"]) + ".py")), m["name"]
        assert m["moves"] in e2e
        # reported only where the metric it moves is
        assert set(cells_of(m)) <= set(cells_of(e2e[m["moves"]])), m["name"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in CELLS:
        assert sum(1 for m in SPEC["end_to_end"] if cell in cells_of(m)) >= 2, cell
        assert any(cell in cells_of(m) for m in SPEC["per_layer"]), cell


def test_one_entry_a_reader_and_moved_metric_and_no_more_than_the_contract_holds():
    # a prefix says which end-to-end metric the entry moves (``train.``, ``chat.``, ``serve.``; ``step.`` for the four
    # readers of ``step_seq.py``): under it a reader has ONE entry, and the cells that report it are its ``workloads``
    pairs = [(reader_of(m["name"]), m["moves"]) for m in SPEC["per_layer"]]
    assert len(pairs) == len(set(pairs)), sorted(p for p in set(pairs) if pairs.count(p) > 1)
    assert 1 <= len(SPEC["per_layer"]) <= 128  # the contract's cap: what refused PR 41's first draft (138)
    for m in SPEC["per_layer"]:
        assert cells_of(m) == sorted(set(cells_of(m)), key=CELLS.index), m["name"]  # each cell once, in the cells' order


def test_a_metric_family_shares_its_reader_and_layers_are_those_of_perf_md():
    assert reader_of("chat.device_idle_share") == reader_of("train.device_idle_share") == reader_of("device_idle_share") == "device_idle_share"
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in SPEC["per_layer"]:
        assert f"| {m['layer']} |" in perf, f"PERF.md section 3 has no row for layer {m['layer']!r}"


def test_peaks_table_is_keyed_by_device_kind_with_a_source():
    peaks = load("benchmark", "peaks.json")
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9 and "Google Cloud" in v5e["source"]
