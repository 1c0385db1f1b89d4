"""Test model fixtures (reference: ``tests/unit/simple_model.py``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class SimpleModel:
    """MLP regression model (reference SimpleModel :18)."""

    def __init__(self, hidden_dim: int = 16, nlayers: int = 2):
        self.hidden_dim = hidden_dim
        self.nlayers = nlayers

    def init(self, rng, batch):
        params = {}
        for i in range(self.nlayers):
            rng, sub = jax.random.split(rng)
            params[f"w{i}"] = jax.random.normal(sub, (self.hidden_dim, self.hidden_dim)) * 0.1
        return params

    def apply(self, params, batch, rngs=None, train=True):
        x, y = batch
        h = x
        for i in range(self.nlayers):
            h = h @ params[f"w{i}"]
            if i < self.nlayers - 1:
                h = jnp.tanh(h)
        return jnp.mean((h - y) ** 2)


def random_dataloader(model_dim: int = 16, total_samples: int = 64, batch_size: int = 8, seed: int = 0):
    rs = np.random.RandomState(seed)
    x = rs.randn(total_samples, model_dim).astype(np.float32)
    y = rs.randn(total_samples, model_dim).astype(np.float32)
    for i in range(0, total_samples, batch_size):
        yield (x[i : i + batch_size], y[i : i + batch_size])


def learnable_dataloader(model_dim: int = 16, total_samples: int = 64, batch_size: int = 8, seed: int = 0):
    """Deterministic regression stream with a GUARANTEED loss gradient:
    every step yields the same (x, y) batch, with y a fixed contraction of
    x — a target the MLP can move toward from its small-init state. A
    working optimizer therefore decreases the loss on every early step;
    "did the run learn" becomes a property of the optimizer, not of which
    random targets the step happened to draw (random_dataloader's fresh
    noise per step made 5-step loss-decrease asserts flake under jax-rng
    changes: the "did not learn in 5 steps" class in fast_tests.sh)."""
    rs = np.random.RandomState(seed)
    x = rs.randn(batch_size, model_dim).astype(np.float32)
    y = (0.5 * x).astype(np.float32)
    for _ in range(0, total_samples, batch_size):
        yield (x, y)


def rel_loss_decrease(losses) -> float:
    """Relative loss decrease over a run — the de-flaked learning criterion
    (scale-free, so it holds across dtypes and quantized variants)."""
    first = float(losses[0])
    return (first - float(losses[-1])) / max(abs(first), 1e-12)


def sequence_dataloader(vocab: int = 128, seq: int = 32, total: int = 32, batch: int = 8, seed: int = 0):
    rs = np.random.RandomState(seed)
    toks = rs.randint(0, vocab, (total, seq + 1)).astype(np.int32)
    for i in range(0, total, batch):
        chunk = toks[i : i + batch]
        yield {"input_ids": chunk[:, :-1], "labels": chunk[:, 1:]}


# --- comm-free training-loop utilities -------------------------------------
# Shared by the fused-grad-accum parity and compile-telemetry tests: drive
# full optimizer steps on the virtual CPU mesh with no collectives beyond
# the engine's own GSPMD-emitted ones, deterministically enough that two
# engines built from the same config can be compared leaf-for-leaf.


def step_batch(model_dim: int = 16, batch_size: int = 8, seed: int = 0):
    """One deterministic FULL-step (x, y) batch for SimpleModel parity runs
    (slice or pass to ``train_batch(batch=...)``)."""
    rs = np.random.RandomState(seed)
    x = rs.randn(batch_size, model_dim).astype(np.float32)
    y = rs.randn(batch_size, model_dim).astype(np.float32)
    return (x, y)


def train_steps_micro(engine, batch, steps: int):
    """Drive ``steps`` optimizer steps through the per-microbatch
    forward/backward/step protocol, slicing ``batch`` into gas microbatches
    each step. Returns per-step mean losses as host floats."""
    gas = engine.gradient_accumulation_steps()
    micro = engine._split_step_batch(batch, gas)
    losses = []
    for _ in range(steps):
        vals = []
        for b in micro:
            loss = engine.forward(b)
            engine.backward(loss)
            engine.step()
            vals.append(float(jax.device_get(loss)))
        losses.append(sum(vals) / len(vals))
    return losses


def train_steps_batch(engine, batch, steps: int):
    """Drive ``steps`` optimizer steps through ``train_batch`` (the fused
    single-dispatch path when ``compile.fuse_grad_accum`` is on). Returns
    per-step mean losses as host floats."""
    return [float(engine.train_batch(batch=batch)) for _ in range(steps)]


def master_snapshot(engine):
    """Host copy of the fp32 master tree for cross-engine parity asserts."""
    return jax.tree_util.tree_map(
        lambda x: np.asarray(jax.device_get(x)), engine.get_master_params()
    )


def assert_same_master(a, b):
    """Two ``master_snapshot`` trees hold the same leaves, bit for bit."""
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
