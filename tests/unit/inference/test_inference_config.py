"""Inference config surface (reference: tests/unit/inference/
test_inference_config.py): alias handling, legacy mp_size remap, dtype
parsing, and that init_inference accepts both kwargs and a config dict."""

import numpy as np
import pytest
from pydantic import ValidationError

import deepspeed_tpu as ds
import deepspeed_tpu.parallel.mesh as mesh_mod
from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig, DtypeEnum


class TestConfigModel:
    def test_defaults(self):
        cfg = DeepSpeedInferenceConfig()
        assert cfg.dtype == DtypeEnum.bf16
        assert cfg.tensor_parallel.tp_size == 1
        assert cfg.max_out_tokens == 1024
        assert not cfg.replace_with_kernel_inject

    def test_aliases(self):
        cfg = DeepSpeedInferenceConfig(
            kernel_inject=True, tp={"tp_size": 4}, max_tokens=2048
        )
        assert cfg.replace_with_kernel_inject
        assert cfg.tensor_parallel.tp_size == 4
        assert cfg.max_out_tokens == 2048

    def test_legacy_mp_size_maps_to_tp(self):
        cfg = DeepSpeedInferenceConfig(mp_size=2)
        assert cfg.tensor_parallel.tp_size == 2

    def test_explicit_tp_wins_over_mp_size(self):
        cfg = DeepSpeedInferenceConfig(mp_size=2, tensor_parallel={"tp_size": 8})
        assert cfg.tensor_parallel.tp_size == 8

    def test_dtype_strings(self):
        for name in ("fp32", "fp16", "bf16", "int8"):
            assert DeepSpeedInferenceConfig(dtype=name).dtype == DtypeEnum(name)
        with pytest.raises(ValidationError):
            DeepSpeedInferenceConfig(dtype="fp64")

    def test_unknown_key_rejected(self):
        with pytest.raises(ValidationError):
            DeepSpeedInferenceConfig(definitely_not_a_key=1)

    @pytest.mark.parametrize("through", ["config_model", "init_inference"])
    @pytest.mark.parametrize(
        "section, key, value",
        [("paged_kv", "ragged", True), ("paged_kv", "slot_buckets", [1, 2, 4]), ("spec_decode", "spec_lens", [2, 4]),
         ("paged_kv", "multi_step", {"enable": True, "horizon": 8}), ("paged_kv", "multi_step", {"enable": False})],
        ids=["ragged", "slot_buckets", "spec_lens", "multi_step_on", "multi_step_off"],
    )
    def test_removed_serving_knobs_refused_by_name(self, section, key, value, through):
        """The bucketed serving path's three options went with it (PR 28) and
        the multi-step window's with it (PR 61): a config that still sets one
        is refused by the key's name, not read past, whatever the value, and
        before ``init_inference`` builds anything."""
        with pytest.raises(ValidationError, match=rf"{section}\.{key}\b"):
            if through == "config_model":
                DeepSpeedInferenceConfig(**{section: {key: value}})
            else:
                ds.init_inference(object(), config={section: {key: value}})


    def test_the_server_takes_no_multi_step_argument(self):
        """The constructor's ``multi_step=`` went with the window: a caller
        that still passes it is told so by name, before anything is built."""
        from deepspeed_tpu.inference.scheduler import PagedServer

        with pytest.raises(TypeError, match="multi_step"):
            PagedServer(None, None, multi_step={"enable": True, "horizon": 4})


class TestInitInference:
    def _model(self):
        from deepspeed_tpu.models import TransformerLM, llama_config

        return TransformerLM(llama_config("tiny", num_layers=2, remat=False))

    def test_config_dict(self, eight_devices):
        mesh_mod.reset_topology()
        model = self._model()
        engine = ds.init_inference(model, config={"dtype": "bf16", "max_tokens": 128})
        toks = np.random.RandomState(0).randint(0, model.config.vocab_size, (2, 16)).astype(np.int32)
        engine.init_params(toks)
        out = engine(toks)
        assert out.shape == (2, 16, model.config.vocab_size)

    def test_kwargs_equiv(self, eight_devices):
        mesh_mod.reset_topology()
        model = self._model()
        engine = ds.init_inference(model, dtype="bf16", max_tokens=128)
        assert engine._config.max_out_tokens == 128
