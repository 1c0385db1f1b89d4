"""End-to-end convergence sanity checks (nightly).

Counterpart of the reference's ``tests/model/`` suite
(``tests/model/run_sanity_check.py``: BingBertSquad / Megatron GPT-2 trained
to a loss target): the tiny llama family is trained ~100 steps on a fixed
synthetic corpus under {ZeRO-3, pipeline, MoE}, asserting (a) the final loss
beats a recorded threshold and (b) dp1 and the sharded mesh land on the same
curve.

Each scenario runs in its OWN subprocess with a device count sized to its
mesh (the harness box can be a single core; an 8-virtual-device mesh there
spends its time in XLA's in-process collective rendezvous, not math — and a
dp2 ZeRO-3 run exercises the same sharded-master/gather paths). The corpus
is a deterministic next-token rule (an affine map over the vocab), which a
2-layer decoder learns quickly.

Run with: ``pytest -m nightly tests/model/``
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

# nightly AND slow: the ini's `addopts = -m "not nightly and not slow"` is
# OVERRIDDEN by any explicit -m on the command line, and the tier-1 command
# runs `-m 'not slow'` — which used to pull these ~100-step subprocess
# convergence legs into tier-1 and stall it past its timeout (the standing
# PR-9/-10/-11 note in CHANGES.md). Double-marking keeps them out of every
# non-nightly selection; run them with `pytest -m nightly tests/model/`.
pytestmark = [pytest.mark.nightly, pytest.mark.slow]

_HERE = os.path.abspath(__file__)
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(_HERE)))

VOCAB = 257  # prime: exercises non-divisible partition dims too
SEQ = 64
STEPS = int(os.environ.get("DS_CONV_STEPS", "100"))


def _run_scenario(name: str, n_devices: int, timeout_s: int = 1500) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, _HERE, name],
        env=env,
        cwd=_REPO,
        capture_output=True,
        text=True,
        timeout=timeout_s,
    )
    assert proc.returncode == 0, f"{name} failed:\n{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
    last = [l for l in proc.stdout.splitlines() if l.startswith("{")][-1]
    return json.loads(last)


class TestDenseConvergence:
    def test_zero3_dp2(self):
        rec = _run_scenario("zero3_dp2", 2)
        assert rec["final"] < 1.0, rec
        assert rec["final"] < rec["first"] / 4, rec

    def test_sharded_matches_single_device(self):
        """Same model/data/seeds at dp1 and dp2/zero3 (fp32): the sharding
        must not change the math beyond accumulation-order noise."""
        a = _run_scenario("zero3_dp2", 2)
        b = _run_scenario("dense_dp1", 1)
        assert b["final"] < 1.0, b
        assert abs(a["final"] - b["final"]) < 0.3, (a, b)


class TestPipelineConvergence:
    def test_pipe2(self):
        rec = _run_scenario("pipe2", 2)
        assert rec["final"] < 1.2, rec
        assert rec["final"] < rec["first"] / 4, rec


class TestMoEConvergence:
    def test_moe_ep2(self):
        # the MoE step (gate + capacity einsums + all_to_all) is the
        # slowest scenario on a small host; give it more wall clock
        rec = _run_scenario("moe_ep2", 2, timeout_s=3000)
        assert rec["final"] < 1.5, rec
        assert rec["final"] < rec["first"] / 3, rec


# ---------------------------------------------------------------------------
# child scenarios (run as `python test_convergence.py <name>` with the env
# set by _run_scenario; no pytest/conftest in this path)


def _corpus(rng, batch):
    import numpy as np

    start = rng.randint(0, VOCAB, (batch, 1))
    seqs = [start]
    for _ in range(SEQ):
        seqs.append((7 * seqs[-1] + 3) % VOCAB)
    toks = np.concatenate(seqs, axis=1).astype(np.int32)
    return {"input_ids": toks[:, :-1], "labels": toks[:, 1:]}


def _train_engine(engine, batch_size, seed=0):
    import jax
    import numpy as np

    rng = np.random.RandomState(seed)
    first = None
    for step in range(STEPS):
        batch = _corpus(rng, batch_size)
        loss = engine(batch)
        engine.backward(loss)
        engine.step()
        if step == 0:
            first = float(jax.device_get(loss))
    return {"first": first, "final": float(jax.device_get(loss))}


def _scenario_zero3_dp2():
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM, llama_config

    cfg = llama_config("tiny", num_layers=2, max_seq_len=SEQ, vocab_size=VOCAB)
    engine, *_ = ds.initialize(
        model=TransformerLM(cfg),
        config={
            "train_micro_batch_size_per_gpu": 8,
            "optimizer": {"type": "adam", "params": {"lr": 3e-3}},
            "zero_optimization": {"stage": 3},
            "gradient_clipping": 1.0,
            "mesh": {"data": 2},
            "steps_per_print": 10_000,
        },
        dist_init_required=False,
    )
    return _train_engine(engine, engine.train_batch_size())


def _scenario_dense_dp1():
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM, llama_config

    cfg = llama_config("tiny", num_layers=2, max_seq_len=SEQ, vocab_size=VOCAB)
    engine, *_ = ds.initialize(
        model=TransformerLM(cfg),
        config={
            "train_micro_batch_size_per_gpu": 16,
            "optimizer": {"type": "adam", "params": {"lr": 3e-3}},
            "zero_optimization": {"stage": 0},
            "gradient_clipping": 1.0,
            "steps_per_print": 10_000,
        },
        dist_init_required=False,
    )
    return _train_engine(engine, engine.train_batch_size())


def _scenario_pipe2():
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM, llama_config
    from deepspeed_tpu.models.transformer import cross_entropy_loss
    from deepspeed_tpu.pipe import LayerSpec, PipelineModule
    import numpy as np

    cfg = llama_config("tiny", num_layers=2, max_seq_len=SEQ, vocab_size=VOCAB)

    class _Embed:
        def init(self, rng, x):  # noqa: ARG002
            return {"tokens": jax.random.normal(rng, (cfg.vocab_size, cfg.hidden_size)) * 0.02}

        def apply(self, p, toks, train=True):  # noqa: ARG002
            return p["tokens"][toks]

    class _Block:
        def init(self, rng, x):  # noqa: ARG002
            m = TransformerLM(cfg)
            full = m.init(rng, None)
            return jax.tree_util.tree_map(lambda a: a[0], full["layers"])

        def apply(self, p, x, train=True):
            import jax.numpy as jnp

            m = TransformerLM(cfg)
            T = x.shape[1]
            positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], x.shape[:2])
            out, _ = m._layer(x, p, positions, None, train)
            return out

    class _Head:
        def init(self, rng, x):  # noqa: ARG002
            return {"w": jax.random.normal(rng, (cfg.hidden_size, cfg.vocab_size)) * 0.02}

        def apply(self, p, x, train=True):  # noqa: ARG002
            return x @ p["w"].astype(x.dtype)

    pm = PipelineModule(
        [LayerSpec(_Embed), LayerSpec(_Block), LayerSpec(_Block), LayerSpec(_Head)],
        loss_fn=cross_entropy_loss,
    )
    engine, *_ = ds.initialize(
        model=pm,
        config={
            "train_micro_batch_size_per_gpu": 8,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "adam", "params": {"lr": 3e-3}},
            "zero_optimization": {"stage": 1},
            "mesh": {"pipe": 2},
            "steps_per_print": 10_000,
        },
        dist_init_required=False,
    )
    rng = np.random.RandomState(0)
    first = None
    for step in range(STEPS):
        b = _corpus(rng, engine.train_batch_size())
        loss = engine.train_batch(batch=(b["input_ids"], b["labels"]))
        if step == 0:
            first = float(loss)
    return {"first": first, "final": float(loss)}


def _scenario_moe_ep2():
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import MoETransformerLM, moe_llama_config

    cfg = moe_llama_config(
        "tiny", num_layers=2, max_seq_len=SEQ, vocab_size=VOCAB, num_experts=2
    )
    engine, *_ = ds.initialize(
        model=MoETransformerLM(cfg),
        config={
            "train_micro_batch_size_per_gpu": 8,
            "optimizer": {"type": "adam", "params": {"lr": 3e-3}},
            "zero_optimization": {"stage": 2},
            "gradient_clipping": 1.0,
            "mesh": {"expert": 2},
            "steps_per_print": 10_000,
        },
        dist_init_required=False,
    )
    return _train_engine(engine, engine.train_batch_size())


_SCENARIOS = {
    "zero3_dp2": _scenario_zero3_dp2,
    "dense_dp1": _scenario_dense_dp1,
    "pipe2": _scenario_pipe2,
    "moe_ep2": _scenario_moe_ep2,
}


if __name__ == "__main__":
    import jax

    jax.config.update("jax_platforms", "cpu")
    rec = _SCENARIOS[sys.argv[1]]()
    print(json.dumps(rec))
