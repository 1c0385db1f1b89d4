"""How the harness finds what belongs to one cell: everything is a file,
found by the name ``BENCHMARK.json`` or a data file gives. A later PR adds a
configuration, a model family, a reference, a traffic mix, a driver, a
kernel or a metric by adding files and entries, and edits none.

    BENCHMARK.json                       the cells, the metrics, their bounds
    benchmark/configs/<config>.json      the model as it is run, and its engine;
                                         names its model adapter and its reference
    benchmark/models/<adapter>.py        build(model) -> the program's model + its shape
    benchmark/reference/<reference>.py   logits / loss(model, params, tokens), plain float32
    benchmark/traffic/<traffic>.json     kind + parameters of the mix
    benchmark/cells/<cell>.json          optional: numbers of this cell alone
                                         that override the mix (``rate_rps``)
    benchmark/drivers/<kind>.py          one driver per traffic kind
    benchmark/end_to_end/<metric>.py     value(window, cell)
    benchmark/layer_metrics/<reader>.py  value(trace, counters, cell)
    benchmark/kernels/<kernel>.py        operations and bytes from shapes
    benchmark/peaks.json                 the chip's peaks, by device_kind

A per-layer metric names the one end-to-end metric it should move, so a
reader has one entry for each end-to-end metric its cells report, named
``<prefix>.<reader>``: ``train.`` moves ``train_tokens_per_s_per_chip``,
``chat.`` moves ``itl_p50_ms``, ``serve.`` moves ``serve_tokens_per_s``, and
``step.`` is ``serve.`` for the four readers of ``step_seq.py`` (PR 36's
names, kept). Under a prefix a reader has ONE entry, whatever the model, and
the cells that report it are the entry's ``workloads`` (since PR 44; until
then every model's cell had entries of its own, ``moe.`` / ``solar.`` /
``mimo.`` / ``glm.``, and 128 entries held 67 such pairs). A later PR's new
reader gets one entry under the prefix of the metric it moves, and its cell
joins the ``workloads`` of the readers it shares. The file is the reader's,
the harness drops the prefix (``reader_of``) and picks a cell's entries by
their lists (``metrics_of``). A name without a dot is its own reader.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` by path: a name may hold characters a
    module name may not."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"{kind} {name!r} has no file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reader_of(metric: str) -> str:
    """The reader file of a per-layer metric: ``<prefix>.<reader>`` or ``<reader>``."""
    return metric.split(".", 1)[-1]


def overlay(base: Dict, over: Dict) -> Dict:
    """``over`` on top of ``base``, nested dicts merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = overlay(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def metrics_of(spec: Dict, section: str, cell: str) -> List[Dict]:
    return [m for m in spec[section] if "workloads" not in m or cell in m["workloads"]]


def load_cell(spec: Dict, name: str, rehearse: bool) -> Dict:
    """The cell's entry with its configuration and traffic files read,
    the cell's own numbers laid over the mix and, in a rehearsal, each
    file's ``rehearse`` block laid over the file."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    cell = dict(cells[name])
    by_name = {c["name"]: c for c in spec["configs"]}
    config = load_json(ROOT, by_name[cell["config"]]["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    own = os.path.join(HERE, "cells", name + ".json")
    if os.path.exists(own):
        traffic = overlay(traffic, load_json(own).get("traffic", {}))
    if rehearse:
        config = overlay(config, config.get("rehearse", {}))
        traffic = overlay(traffic, traffic.get("rehearse", {}))
    cell.update(config_file=config, traffic_file=traffic)
    return cell


def build_model(config: Dict):
    """The program's model for a configuration file, through the adapter the
    file names, and the model's shape (what drivers and readers need of it)."""
    return load_module("models", config["model"]["adapter"]).build(config["model"])


def reference_of(config: Dict):
    """The plain reference the configuration file names."""
    return load_module("reference", config["model"]["reference"])
