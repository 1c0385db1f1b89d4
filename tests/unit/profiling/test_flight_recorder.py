"""Flight-recorder tests (ISSUE 10).

The crash postmortem: a chaos fault injection (the PR-8 kill harness) must
leave a parseable dump whose last timeline entry names the armed injection
point — in-process via the ChaosKilled simulation, and (``-m slow``) in a
real subprocess that dies via ``os._exit(137)``, proving the dump happens
BEFORE the no-atexit death."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.scheduler import PagedServer
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.config import TransformerConfig
from deepspeed_tpu.profiling.tracer import (
    FlightRecorder,
    MetricsRegistry,
    Tracer,
)
from deepspeed_tpu.utils import chaos

CFG = dict(
    vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
    num_kv_heads=2, max_seq_len=64, norm="rmsnorm", position="rope",
    activation="swiglu", use_bias=False, tie_embeddings=False,
    flash_attention=False, dtype="float32",
)


@pytest.fixture(scope="module")
def model_and_params():
    cfg = TransformerConfig(**CFG)
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), toks)
    return cfg, model, params


def test_manual_dump_shape(tmp_path):
    tr = Tracer()
    m = MetricsRegistry()
    m.counter("tok").inc(3)
    with tr.span("phase"):
        pass
    fr = FlightRecorder(tr, m, path=str(tmp_path / "fr.json"), last_spans=128)
    path = fr.dump(reason="manual")
    obj = json.load(open(path))
    assert obj["reason"] == "manual" and obj["pid"] == os.getpid()
    assert obj["spans"][-1]["name"] == "phase"
    assert obj["metrics"]["counters"]["tok"] == 3.0
    assert obj["open_spans"] == []


def test_dump_respects_last_spans_cap(tmp_path):
    tr = Tracer(max_spans=4096)
    for i in range(500):
        with tr.span(f"s{i}"):
            pass
    fr = FlightRecorder(tr, path=str(tmp_path / "fr.json"), last_spans=16)
    obj = json.load(open(fr.dump()))
    assert len(obj["spans"]) == 16
    assert obj["spans"][-1]["name"] == "s499"  # the NEWEST window


def test_chaos_kill_leaves_postmortem_with_armed_point(tmp_path):
    """The in-process simulation: an armed ChaosKilled fires the kill hook
    before the raise — the dump exists, names the point, and the timeline's
    last entry is the chaos event."""
    tr = Tracer()
    fr = FlightRecorder(tr, path=str(tmp_path / "fr.json")).install(on_exit=False)
    try:
        chaos.install(chaos.ChaosSchedule([chaos.ChaosRule("journal.append")]))
        with pytest.raises(chaos.ChaosKilled):
            with tr.span("serve.step"):
                chaos.point("journal.append")
    finally:
        chaos.uninstall()
        fr.uninstall()
    obj = json.load(open(str(tmp_path / "fr.json")))
    assert obj["reason"] == "chaos" and obj["point"] == "journal.append"
    assert obj["spans"][-1]["name"] == "chaos.journal.append"
    assert obj["chaos_fired"] == ["journal.append#1:raise"]
    # the in-flight span at death is visible — "what was it doing"
    assert [s["name"] for s in obj["open_spans"]] == ["serve.step"]


def test_uninstalled_recorder_stops_dumping(tmp_path):
    tr = Tracer()
    fr = FlightRecorder(tr, path=str(tmp_path / "fr.json")).install(on_exit=False)
    fr.uninstall()
    try:
        chaos.install(chaos.ChaosSchedule([chaos.ChaosRule("journal.append")]))
        with pytest.raises(chaos.ChaosKilled):
            chaos.point("journal.append")
    finally:
        chaos.uninstall()
    assert not os.path.exists(str(tmp_path / "fr.json"))


def test_serving_chaos_kill_dumps_mid_step(tmp_path, model_and_params):
    """The real serving loop: a chaos kill at serve.mid_step (inside the
    scheduler's step span, before the journal flush) leaves a dump whose
    last entry names the point and whose open spans show the step in
    flight."""
    cfg, _, params = model_and_params
    tr = Tracer()
    server = PagedServer(
        cfg, params, page_size=8, max_slots=4, prefill_chunk=8,
        attn_impl="xla", dtype=jnp.float32, tracer=tr,
    )
    fr = FlightRecorder(tr, path=str(tmp_path / "fr.json")).install(on_exit=False)
    server.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=6)
    try:
        chaos.install(
            chaos.ChaosSchedule([chaos.ChaosRule("serve.mid_step", hit=3)])
        )
        with pytest.raises(chaos.ChaosKilled):
            server.run()
    finally:
        chaos.uninstall()
        fr.uninstall()
    obj = json.load(open(str(tmp_path / "fr.json")))
    assert obj["point"] == "serve.mid_step"
    assert obj["spans"][-1]["name"] == "chaos.serve.mid_step"
    assert "serve.step" in [s["name"] for s in obj["open_spans"]]
    # the two completed scheduler rounds are on the timeline
    names = [s["name"] for s in obj["spans"]]
    assert names.count("serve.step") == 2
    # and each step's own record came through the dump's JSON as it went in: a prompt of 8 is one chunk, then decode rows
    packs = [s["attrs"] for s in obj["spans"] if s["name"] == "serve.pack"]
    assert [(a["seq"], a["mixed"], a["kv_tokens"], a["row_lens"]) for a in packs][:3] == [(0, 1, 8, "8:8"), (1, 0, 9, "1:9"), (2, 0, 10, "1:10")]


# ---------------------------------------------------------------------------
# the real death: a subprocess os._exit(137) kill still leaves the dump
# ---------------------------------------------------------------------------
_CHILD = r"""
import sys, numpy as np, jax, jax.numpy as jnp
from deepspeed_tpu.inference.scheduler import PagedServer
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.config import TransformerConfig
from deepspeed_tpu.profiling.tracer import FlightRecorder, MetricsRegistry, Tracer
from deepspeed_tpu.utils import chaos

dump_path = sys.argv[1]
cfg = TransformerConfig(
    vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
    num_kv_heads=2, max_seq_len=64, norm="rmsnorm", position="rope",
    activation="swiglu", use_bias=False, tie_embeddings=False,
    flash_attention=False, dtype="float32",
)
model = TransformerLM(cfg)
toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
params = model.init(jax.random.PRNGKey(0), toks)
tracer = Tracer()
metrics = MetricsRegistry()
FlightRecorder(tracer, metrics, path=dump_path).install(on_exit=False)
server = PagedServer(cfg, params, page_size=8, max_slots=4, prefill_chunk=8,
                     attn_impl="xla", dtype=jnp.float32, tracer=tracer,
                     metrics=metrics)
server.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=8)
chaos.install(chaos.ChaosSchedule(
    [chaos.ChaosRule("serve.mid_step", hit=4, action="exit")]
))
server.run()
print("UNREACHABLE")  # the kill must fire before the serve completes
sys.exit(3)
"""


@pytest.mark.slow
def test_subprocess_exit_kill_leaves_parseable_postmortem(tmp_path):
    """A REAL abrupt death (os._exit(137): no atexit, no flushing, nothing
    downstream) — the kill hook runs before the exit, so the postmortem
    file exists, parses, and its last span matches the armed injection
    point."""
    dump = str(tmp_path / "postmortem.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, dump],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 137, proc.stderr[-2000:]
    assert "UNREACHABLE" not in proc.stdout
    obj = json.load(open(dump))
    assert obj["reason"] == "chaos" and obj["point"] == "serve.mid_step"
    assert obj["spans"][-1]["name"] == "chaos.serve.mid_step"
    assert obj["spans"][-1]["attrs"] == {"action": "exit"}
    assert "serve.step" in [s["name"] for s in obj["open_spans"]]
    assert obj["chaos_fired"] == ["serve.mid_step#4:exit"]
    # three completed rounds before the fourth died mid-step
    assert [s["name"] for s in obj["spans"]].count("serve.step") == 3
