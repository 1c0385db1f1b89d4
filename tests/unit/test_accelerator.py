"""Accelerator abstraction tests (reference: tests/accelerator/)."""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.accelerator import get_accelerator, real_accelerator


def test_singleton_and_name():
    acc = get_accelerator()
    assert acc is get_accelerator()
    assert acc._name in ("tpu", "cpu")


def test_backend_error_is_not_reported_as_cpu(monkeypatch):
    """A backend that fails to initialise must fail the caller: answering
    "cpu" would silently run every kernel interpreted."""

    def broken_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.delenv("DS_ACCELERATOR", raising=False)
    monkeypatch.setattr(jax, "default_backend", broken_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        real_accelerator._detect_platform()


def test_device_api():
    acc = get_accelerator()
    assert acc.device_count() >= 1
    assert acc.is_available()
    acc.set_device(0)
    assert acc.current_device() == 0
    assert str(acc.current_device_name()).endswith(":0")


def test_streams_and_events():
    acc = get_accelerator()
    s = acc.Stream()
    with acc.stream(s):
        pass
    e1, e2 = acc.Event(enable_timing=True), acc.Event(enable_timing=True)
    e1.record()
    e2.record()
    assert e1.elapsed_time(e2) >= 0
    acc.synchronize()


def test_dtype_support():
    acc = get_accelerator()
    assert acc.is_bf16_supported()
    assert jnp.bfloat16 in acc.supported_dtypes()


def test_comm_backend_name():
    assert get_accelerator().communication_backend_name() == "xla"


def test_op_builder_dispatch():
    acc = get_accelerator()
    builder = acc.create_op_builder("fused_adam")
    assert builder is not None and builder.is_compatible()
    mod = builder.load()
    assert hasattr(mod, "FusedAdam")


def test_rng_api():
    acc = get_accelerator()
    acc.manual_seed(7)
    assert acc.initial_seed() == 7
    k = acc.get_rng_state()
    acc.set_rng_state(k)
