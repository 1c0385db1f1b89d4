"""Inference engine.

Counterpart of the reference's ``InferenceEngine``
(``deepspeed/inference/engine.py:37``). Round-1 scope: jitted forward over a
(possibly model-sharded) param tree with dtype conversion, checkpoint loading
through the Orbax engine, and greedy ``generate``. The CUDA-graph
capture/replay pair (engine.py:489,508) maps onto jit's compile cache — the
first call compiles, subsequent calls replay. Kernel-injection policies and
paged KV-cache attention land with the module_inject/auto-TP subsystem.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import functools

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig, DtypeEnum
from deepspeed_tpu.models.config import has_latent_layers, has_state_layers
from deepspeed_tpu.models.transformer import TransformerLM
from deepspeed_tpu.parallel.mesh import get_topology
from deepspeed_tpu.profiling.compile_telemetry import CompileTelemetry
from deepspeed_tpu.profiling.tracer import MetricsRegistry, ObservabilityHub, Tracer
from deepspeed_tpu.runtime.module import wrap_module
from deepspeed_tpu.utils.logging import log_dist

_DTYPES = {
    DtypeEnum.fp32: jnp.float32,
    DtypeEnum.fp16: jnp.float16,
    DtypeEnum.bf16: jnp.bfloat16,
    DtypeEnum.int8: jnp.int8,
}


def _is_hf_model(model) -> bool:
    cfg = getattr(model, "config", None)
    return cfg is not None and hasattr(cfg, "model_type") and hasattr(model, "state_dict")


class InferenceEngine:
    def __init__(self, model, config: Optional[DeepSpeedInferenceConfig] = None):
        self._config = config or DeepSpeedInferenceConfig()
        self.topology = get_topology()
        # the engine owns TP-group creation (reference
        # _create_model_parallel_group, inference/engine.py:217): when the
        # config asks for tp_size and the live topology has no model axis,
        # rebuild the mesh as model=tp_size x data=rest
        tp_req = int(self._config.tensor_parallel.tp_size or 1)
        if tp_req > 1 and self.topology.get_model_parallel_world_size() == 1:
            from deepspeed_tpu.parallel.mesh import build_serving_mesh, set_topology

            self.topology = build_serving_mesh(tp_req)
            set_topology(self.topology)
        self.mesh = self.topology.mesh
        self.dtype = _DTYPES[self._config.dtype]
        self._params = None
        self._jit_forward = None
        self._cached_tp_rules = None
        self._rng = jax.random.PRNGKey(0)
        # TransformerConfig of the KV-cached decode / paged serving path: an
        # injected HF model's converted config, or a native TransformerLM's own
        self._ds_config = None
        # ZeRO-Inference (reference engine.py:1499-1520: stage-3 offload
        # without an optimizer): params live in host DRAM / on NVMe and
        # stream through HBM per layer — capacity over latency
        self._param_stream = None
        self._zero_config = self._parse_zero_inference()
        # model profiling (reference engine.py:167 profile_model_time,
        # :518 model_times): per-forward wall latency, drained at read
        self.model_profile_enabled = False
        self._model_times = []
        # compile telemetry over every jitted program this engine runs
        # (forward, the KV-cached decode loops, the paged serving programs)
        # — same contract as the training engine's compile_stats()
        self._telemetry = CompileTelemetry()
        # unified tracing/metrics plane: serving step phases + per-request
        # lifecycle spans land here (the PagedServer gets this tracer);
        # observability() merges it with compile/analysis/serve stats
        tcfg = self._config.tracing
        self.tracer = Tracer(max_spans=tcfg.max_spans, enabled=tcfg.enabled)
        # every span() is also an event of the profiler's own trace, on the
        # device ops' clock (tracer.py itself may not import jax)
        self.tracer.sink = jax.profiler.TraceAnnotation
        self.metrics = MetricsRegistry()
        self._obs_hub = ObservabilityHub(self.tracer, self.metrics)
        self._obs_hub.add_source("compile", self.compile_stats)
        self._obs_hub.add_source("analysis", self.analysis_report)
        self._obs_hub.add_source("serve", self.serve_stats)
        # enforce=False: an over-budget ledger surfaces IN the snapshot
        # rather than failing the observability read
        self._obs_hub.add_source(
            "memory", lambda: self.memory_report(enforce=False)
        )
        if tcfg.flight_recorder:
            self._obs_hub.install_flight_recorder(
                dump_dir=tcfg.flight_recorder_dir,
                last_spans=tcfg.flight_recorder_spans,
            )
        self._paged_server = None  # lazy; rebuilt when weights change
        # analysis.verify: static passes on each program at first compile
        if self._config.analysis.verify != "off":
            self._telemetry.on_compile = self._verify_program_static

        injected = False
        if self._config.replace_with_kernel_inject and _is_hf_model(model):
            # reference _apply_injection_policy (inference/engine.py:371):
            # convert the HF model to the fused TPU decoder + weights
            from deepspeed_tpu.module_inject.replace_module import replace_transformer_layer

            ds_model, params = replace_transformer_layer(
                model=model, dtype=jnp.dtype(self.dtype).name
            )
            self._ds_config = ds_model.config
            self.module = ds_model
            if params is not None:
                self.set_params(params)
            injected = True
        else:
            self.module = wrap_module(model)
            if isinstance(model, TransformerLM):
                self._ds_config = model.config
        # checkpoint handed to init_inference (reference engine.py:406):
        # a path string — engine-format dir, or an mp-checkpoint manifest
        ckpt = self._config.checkpoint
        if isinstance(ckpt, str) and ckpt.endswith(".json") and not self._is_mp_manifest(ckpt):
            self._load_sd_checkpoint(ckpt)
        elif isinstance(ckpt, str):
            self._load_checkpoint(ckpt)
        elif isinstance(ckpt, dict):
            # the reference's SD-loader descriptor form (engine.py:406 →
            # SDLoaderFactory.get_sd_loader_json): a dict/json naming the
            # legacy sharded file list
            self._load_sd_checkpoint(ckpt)
        elif ckpt is not None:
            raise NotImplementedError(
                "init_inference checkpoint= takes a path string (engine "
                "checkpoint dir or mp-checkpoint manifest) or an SD-loader "
                "descriptor dict/json ({'type': 'Megatron', 'checkpoints': "
                "[...], 'version': ...})"
            )
        log_dist(
            f"InferenceEngine: dtype={self._config.dtype} "
            f"tp_size={self._config.tensor_parallel.tp_size} kernel_inject={injected}",
            ranks=[0],
        )

    def _parse_zero_inference(self):
        """DeepSpeedZeroConfig when the config asks for ZeRO-Inference
        (stage 3 + offload_param), else None."""
        zdict = self._config.zero or {}
        if not zdict:
            return None
        from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig

        zcfg = DeepSpeedZeroConfig(**zdict)
        off = zcfg.offload_param
        if int(zcfg.stage) >= 3 and off is not None and str(off.device) not in (
            "none",
            "OffloadDeviceEnum.none",
        ):
            return zcfg
        return None

    def _init_param_stream(self, params) -> None:
        """ZeRO-Inference: install params into the layer-stream store
        (host DRAM or NVMe) instead of HBM."""
        from deepspeed_tpu.runtime.zero.param_offload import ParamStreamEngine

        self._param_stream = ParamStreamEngine(
            self.module,
            params,
            self.topology,
            self._zero_config,
            {},  # no optimizer: inference never steps (moments stay unallocated)
            self.dtype,
        )
        self._params = None

    # --- weights --------------------------------------------------------
    def set_params(self, params: Any) -> None:
        """Install a param pytree (cast to the inference dtype). Sharded
        over the 'model' axis (AutoTP) when tp_size > 1 and over the
        'expert' axis for MoE modules when ep_size > 1 — the reference's MP
        + expert inference groups (``deepspeed/inference/engine.py:217,230``),
        expressed as GSPMD placements instead of process groups."""
        if self._zero_config is not None:
            if self._config.save_mp_checkpoint_path:
                log_dist(
                    "save_mp_checkpoint_path is ignored under ZeRO-Inference "
                    "offload (weights live in the layer stream, not HBM)",
                    ranks=[0],
                )
            self._init_param_stream(params)
            return
        cast = jax.tree_util.tree_map(
            lambda p: jnp.asarray(p).astype(self.dtype)
            if jnp.issubdtype(jnp.asarray(p).dtype, jnp.floating)
            else jnp.asarray(p),
            params,
        )
        if self._config.quant.enabled:
            # weight quantization (reference MoQ inference): int8 roundtrip
            # per group — numerics match int8-weight kernels; the wire/HBM
            # win comes from qwZ-style boundaries when sharded
            from deepspeed_tpu.ops.quantizer import fake_quantize

            gs = int(self._config.quant.group_size or 64)
            bits = int(self._config.quant.num_bits or 8)

            def quant_leaf(p):
                if jnp.ndim(p) < 2 or not jnp.issubdtype(p.dtype, jnp.floating):
                    return p
                # group count must divide the element count exactly
                groups = p.size // gs if gs and p.size % gs == 0 else 1
                return fake_quantize(p, num_groups=groups, num_bits=bits)

            cast = jax.tree_util.tree_map(quant_leaf, cast)
        tp = self.topology.get_model_parallel_world_size() > 1
        ep = self.topology.axis_size("expert") > 1
        self._cached_tp_rules = None
        if tp or ep:
            from jax.sharding import NamedSharding, PartitionSpec

            tp_rules = self._tp_rules(cast)
            self._cached_tp_rules = tp_rules  # save_mp_checkpoint reuses this
            shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s),
                tp_rules,
                is_leaf=lambda x: isinstance(x, PartitionSpec),
            )
            cast = jax.device_put(cast, shardings)
        self._params = cast
        self._jit_forward = None
        self._paged_server = None
        if self._config.save_mp_checkpoint_path:
            # reference inference/engine.py:406: persist the sharded layout
            # the moment the weights are resident, so later engines load
            # pre-split files
            self.save_mp_checkpoint(self._config.save_mp_checkpoint_path)

    def _tp_rules(self, params):
        """PartitionSpec tree for the weights: model-family rules when the
        module provides them (carry 'model' and 'expert' axes), else the
        AutoTP walk (reference module_inject/auto_tp.py:170)."""
        tp_rules = None
        if hasattr(self.module, "tp_partition_rules"):
            tp_rules = self.module.tp_partition_rules(params)
        if tp_rules is None:
            from deepspeed_tpu.module_inject.auto_tp import AutoTP

            tp_rules = AutoTP().partition_specs(params)
        return tp_rules

    def save_mp_checkpoint(self, save_path: str, tag: str = "ds-inference") -> str:
        """Write a pre-sharded TP inference checkpoint + manifest (reference
        ``save_mp_checkpoint_path``, inference/engine.py:406). Returns the
        manifest path; load it back via ``init_inference(model,
        checkpoint=<manifest>)`` or ``load_checkpoint``."""
        if self._param_stream is not None:
            raise NotImplementedError(
                "save_mp_checkpoint is unsupported under ZeRO-Inference "
                "offload: the weights live in the layer stream, not HBM"
            )
        if self._params is None:
            raise RuntimeError("save_mp_checkpoint before weights are set")
        from deepspeed_tpu.inference.mp_checkpoint import save_mp_checkpoint

        rules = self._cached_tp_rules
        if rules is None:
            rules = self._tp_rules(self._params)
        tp_size = max(1, self.topology.get_model_parallel_world_size())
        return save_mp_checkpoint(
            self._params,
            rules,
            save_path,
            tag=tag,
            tp_size=tp_size,
        )

    def init_params(self, batch, rng=None) -> None:
        if rng is not None:
            self._rng = rng
        params = self.module.init(self._rng, batch)
        self.set_params(params)

    @staticmethod
    def _is_mp_manifest(path: str) -> bool:
        from deepspeed_tpu.inference.mp_checkpoint import is_mp_checkpoint

        try:
            return is_mp_checkpoint(path)
        except Exception:
            return False

    def _load_sd_checkpoint(self, descriptor) -> None:
        """Legacy sharded (SplitCheckpoint) load: merge the file list to the
        FULL state dict (reference per-rank loads are GSPMD placements here)
        and convert through the container policy for the descriptor's
        model_type (default megatron)."""
        from deepspeed_tpu.module_inject.containers import policy_for
        from deepspeed_tpu.runtime.state_dict_factory import SDLoaderFactory

        # precondition first: merging can be GBs of torch.load — don't pay
        # for it just to discover the module can't accept the weights
        mcfg = getattr(self.module, "config", None)
        if mcfg is None:
            raise ValueError(
                "SD-loader checkpoints need an injected module with a model "
                "config (build the model via init_inference kernel injection "
                "or replace_transformer_layer first)"
            )
        if isinstance(descriptor, str):
            import json as _json

            with open(descriptor) as f:
                descriptor = _json.load(f)
        loader = SDLoaderFactory.get_sd_loader_json(descriptor)
        if isinstance(loader, dict):
            raise NotImplementedError(
                f"pre-sharded '{loader.get('type')}' descriptors load via the "
                "mp-checkpoint manifest path"
            )
        _, sd, _ = loader.load(mp_world_size=1, mp_rank=0)
        merged = loader.get_module(sd)
        model_type = descriptor.get("model_type", "megatron")
        policy = policy_for(model_type)
        self.set_params(policy.convert_weights(merged, mcfg))

    def _load_checkpoint(self, load_dir: str) -> None:
        from deepspeed_tpu.inference.mp_checkpoint import is_mp_checkpoint, load_mp_checkpoint

        if is_mp_checkpoint(load_dir):
            # pre-sharded layout (manifest json or its directory)
            params, _ = load_mp_checkpoint(load_dir)
            self.set_params(params)
            return
        from deepspeed_tpu.runtime.checkpoint_engine.orbax_checkpoint_engine import OrbaxCheckpointEngine

        state = OrbaxCheckpointEngine().load(load_dir)
        params = state.get("module", state)
        self.set_params(params)

    load_checkpoint = _load_checkpoint

    def profile_model_time(self, use_cuda_events: bool = True) -> None:  # noqa: ARG002
        """Record per-forward latency (reference engine.py:167; cuda events
        map onto a device-sync'd wall clock here)."""
        self.model_profile_enabled = True

    def model_times(self):
        """Collected per-forward latencies, cleared on read (reference
        engine.py:518)."""
        assert self.model_profile_enabled, "model profiling is not enabled"
        times = self._model_times
        self._model_times = []
        return times

    # --- forward --------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        if self.model_profile_enabled:
            # timed through the tracer's clock (DS-R009: no raw
            # perf_counter in the hot loop) and recorded on the timeline
            t0 = self.tracer.clock()
            out = self._forward_impl(*inputs, **kwargs)
            # close the async dispatch window: wait on one output element
            leaf = jax.tree_util.tree_leaves(out)[0]
            if hasattr(leaf, "ravel"):
                jax.device_get(jnp.ravel(leaf)[:1])
            t1 = self.tracer.clock()
            self.tracer.add_span("infer.forward", t0, t1)
            self._model_times.append(t1 - t0)
            return out
        return self._forward_impl(*inputs, **kwargs)

    def _forward_impl(self, *inputs, **kwargs):
        if self._zero_config is not None:
            batch = inputs[0] if len(inputs) == 1 else (inputs if inputs else kwargs)
            if self._param_stream is None:
                self.init_params(batch)
            from deepspeed_tpu.models.transformer import _split_batch

            tokens, labels = _split_batch(batch)
            return self._param_stream.eval_forward(jnp.asarray(tokens), labels)
        if self._params is None:
            batch = inputs[0] if inputs else kwargs
            self.init_params(batch)
        if self._jit_forward is None:
            module = self.module

            def fwd(params, batch, rng):
                return module.apply(params, batch, rngs={"dropout": rng}, train=False)

            self._jit_forward = self._telemetry.instrument("forward", fwd)
        batch = inputs[0] if len(inputs) == 1 else (inputs if inputs else kwargs)
        self._rng, sub = jax.random.split(self._rng)
        return self._jit_forward(self._params, batch, sub)

    __call__ = forward

    # --- generation -----------------------------------------------------
    def generate(self, *args, **kwargs):
        """Latency-recording wrapper over ``_generate_impl`` (whose
        signature this function adopts via functools.wraps below)."""
        if not self.model_profile_enabled:
            return self._generate_impl(*args, **kwargs)
        t0 = self.tracer.clock()
        out = self._generate_impl(*args, **kwargs)
        np.asarray(out[..., -1:])  # drain: wait for the last emitted token
        # one entry per generate call (the reference records per-token
        # kernel times; the whole decode is one program here)
        t1 = self.tracer.clock()
        self.tracer.add_span("infer.generate", t0, t1)
        self._model_times.append(t1 - t0)
        return out

    def _generate_impl(
        self,
        input_ids,
        max_new_tokens: int = 32,
        eos_token_id: Optional[int] = None,
        pad_token_id: int = 0,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        num_beams: int = 1,
        length_penalty: float = 1.0,
    ):
        """Token generation (greedy by default; temperature/top-k/top-p
        sampling and beam search like the reference's HF-generate dispatch,
        ``deepspeed/inference/engine.py:578``). Kernel-injected models take
        the KV-cached single-program decode loop (beam search reorders the
        cache on device); arbitrary modules get one full-forward compiled
        program per (batch, max_len) bucket."""
        from deepspeed_tpu.inference.generation import greedy_generate

        if num_beams > 1:
            if self._ds_config is None or self._params is None:
                raise NotImplementedError(
                    "num_beams > 1 requires the kernel-injected (KV-cached) "
                    "path: build the engine with replace_with_kernel_inject "
                    "or a converted model family"
                )
            if temperature or top_k or top_p < 1.0:
                raise ValueError(
                    "beam search is deterministic; temperature/top_k/top_p "
                    "cannot be combined with num_beams > 1"
                )
            from deepspeed_tpu.inference.decode import beam_generate

            return beam_generate(
                self._ds_config,
                self._params,
                input_ids,
                max_new_tokens,
                num_beams=num_beams,
                eos_token_id=eos_token_id,
                pad_token_id=pad_token_id,
                length_penalty=length_penalty,
                telemetry=self._telemetry,
            )
        if self._zero_config is not None:
            if self._param_stream is None:
                self.init_params(jnp.asarray(input_ids))
            return self._zero_generate(
                input_ids, max_new_tokens, eos_token_id, pad_token_id,
                temperature=temperature, top_k=top_k, top_p=top_p,
            )
        if self._ds_config is not None and self._params is not None:
            # kernel-injected path: KV-cached prefill + on-device decode loop
            from deepspeed_tpu.inference.decode import generate as kv_generate

            self._rng, sub = jax.random.split(self._rng)
            return kv_generate(
                self._ds_config,
                self._params,
                input_ids,
                max_new_tokens,
                eos_token_id=eos_token_id,
                temperature=temperature,
                rng=sub,
                top_k=top_k,
                top_p=top_p,
                pad_token_id=pad_token_id,
                telemetry=self._telemetry,
            )
        if self._params is None:
            self.init_params(jnp.asarray(input_ids))
        module = self.module

        def apply_fn(params, tokens, rng):
            return module.apply(params, tokens, rngs={"dropout": rng}, train=False)

        if not hasattr(self, "_gen_cache"):
            self._gen_cache = {}
        self._rng, sub = jax.random.split(self._rng)
        return greedy_generate(
            apply_fn,
            self._params,
            input_ids,
            max_new_tokens,
            sub,
            eos_token_id=eos_token_id,
            pad_token_id=pad_token_id,
            jit_cache=self._gen_cache,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            telemetry=self._telemetry,
        )

    # the public generate adopts _generate_impl's signature/doc — one
    # source of truth for the sampling controls
    generate = functools.wraps(_generate_impl)(generate)

    # --- paged serving --------------------------------------------------
    def compile_stats(self):
        """Per-program compile telemetry snapshot — the inference-side
        counterpart of the training engine's ``compile_stats()``: for each
        jitted program (``forward``, ``kv_prefill`` / ``kv_decode_loop`` /
        ``kv_beam_loop``, ``full_fwd_gen_step``, and the serving programs
        ``paged_ragged_r<rows>_w<width>``) the trace, compile, and dispatch
        counters. The serving contract: ≤ 2 compiled ``paged_*`` programs
        for a whole mixed serve and exactly one ``paged_ragged_*`` dispatch
        per scheduler step."""
        return self._telemetry.stats()

    def program_text(self, name: str) -> str:
        """Lowered (StableHLO) text of one dispatched ``compile_stats()``
        program: which kernels and collectives it really contains."""
        return self._telemetry.lowered_text(name)

    def analysis_report(self, programs=None, passes=None):
        """Static-analysis report over every dispatched inference program
        (or the named subset) — same contract as the training engine's
        ``analysis_report()``: donation-aliasing, dtype-promotion,
        host-transfer, and collective-schedule pass results per program,
        retrace-cause diffs, and aggregate totals (``donation_verified``,
        static collective bytes). The serving invariants become checkable
        properties: every ``paged_ragged_*`` program must alias its donated
        page buffers and contain no host callback."""
        from deepspeed_tpu.analysis import engine_analysis_report

        return engine_analysis_report(
            self._telemetry,
            self._config.analysis,
            programs=programs,
            passes=passes,
            extra_config=self._analysis_extra_config(),
        )

    def _analysis_extra_config(self):
        """Engine-declared analysis-pass inputs: with tensor-parallel
        serving armed, the TP context's declared comm schedule and sharding
        rules let the memory pass flag pjit-inserted resharding collectives
        and large weights left replicated against the layout contract."""
        srv = getattr(self._paged_server, "server", self._paged_server)
        tp = getattr(srv, "tp", None)
        if tp is not None and tp.degree > 1:
            return {
                "declared_collectives": tp.declared_collectives(),
                "sharding_rules": tp.sharding_rules(),
            }
        return None

    def _verify_program_static(self, name: str) -> None:
        from deepspeed_tpu.analysis import verify_program
        from deepspeed_tpu.utils.logging import logger

        verify_program(
            self._telemetry,
            self._config.analysis,
            name,
            logger=logger,
            extra_config=self._analysis_extra_config(),
        )

    def memory_report(self, include_programs: bool = False, enforce: bool = True):
        """Static per-chip HBM residency ledger for the inference engine:
        the dense-path param tree, the (possibly resharded / int8) serving
        weights, and the paged KV pool — per-chip bytes under each leaf's
        sharding, with the pool's host-side page tables accounted as host
        RAM (the tp serving contract: KV bytes/chip == total/tp, tables
        never on device). ``include_programs=True`` folds in per-program
        transient estimates from the analysis memory pass (one re-trace
        each). ``enforce=True`` applies ``analysis.hbm_budget_bytes`` —
        over budget raises ``HbmBudgetError`` with per-buffer attribution
        (or warns, per ``analysis.hbm_budget``)."""
        from deepspeed_tpu.analysis import MemoryLedger
        from deepspeed_tpu.utils.logging import logger

        acfg = self._config.analysis
        ledger = MemoryLedger(
            hbm_budget_bytes=getattr(acfg, "hbm_budget_bytes", None),
            mode=getattr(acfg, "hbm_budget", "raise"),
        )
        if self._params is not None:
            ledger.add_tree("params", self._params, kind="params")
        srv = getattr(self._paged_server, "server", self._paged_server)
        if srv is not None:
            sp = getattr(srv, "params", None)
            if sp is not None and sp is not self._params:
                ledger.add_tree("serving_params", sp, kind="params")
            pool = getattr(srv, "pool", None)
            if pool is not None:
                rep = pool.memory_report()
                ledger.add_persistent(
                    "kv_pages",
                    per_chip_bytes=rep["kv_bytes_per_chip"],
                    global_bytes=rep["kv_total_bytes"],
                    kind="kv_pool",
                    detail=rep,
                )
                if "state_total_bytes" in rep:
                    # a hybrid model's second cache: recurrent states and
                    # convolution tails, one entry a slot (kv_pool.StateStore)
                    ledger.add_persistent(
                        "recurrent_state",
                        per_chip_bytes=rep["state_total_bytes"],
                        global_bytes=rep["state_total_bytes"],
                        kind="kv_pool",
                        detail={k: v for k, v in rep.items() if k.startswith("state_")},
                    )
                if "window_total_bytes" in rep:
                    # its third: the sliding-window layers' page rings, the same
                    # size a slot whatever the row's length
                    ledger.add_persistent(
                        "window_kv",
                        per_chip_bytes=rep["window_total_bytes"],
                        global_bytes=rep["window_total_bytes"],
                        kind="kv_pool",
                        detail={k: v for k, v in rep.items() if k.startswith("window_")},
                    )
                if "latent_total_bytes" in rep:
                    # the latent-attention layers' pages: one entry a token a layer under
                    # the pool's page ids, no value array (kv_pool.StateStore.latent)
                    ledger.add_persistent(
                        "latent_kv",
                        per_chip_bytes=rep["latent_total_bytes"],
                        global_bytes=rep["latent_total_bytes"],
                        kind="kv_pool",
                        detail={k: v for k, v in rep.items() if k.startswith("latent_")},
                    )
                ledger.add_persistent(
                    "kv_page_tables",
                    per_chip_bytes=rep["host_table_bytes"],
                    location="host",
                    kind="kv_pool",
                )
        if include_programs:
            try:
                rep = self.analysis_report(passes=["memory"])
                for pname, entry in rep.get("programs", {}).items():
                    est = (
                        entry.get("passes", {})
                        .get("memory", {})
                        .get("summary", {})
                        .get("estimate")
                    )
                    if est:
                        ledger.add_program(pname, est)
            except Exception as e:  # analysis failure ≠ ledger failure
                logger.warning(f"memory ledger: program estimates failed: {e}")
        if enforce:
            return ledger.enforce(logger=logger)
        return ledger.report()

    def _build_paged_server(self):
        from deepspeed_tpu.inference.scheduler import PagedServer

        if self._ds_config is None or self._params is None:
            raise NotImplementedError(
                "serve() requires the kernel-injected (KV-cached) path: build "
                "the engine with replace_with_kernel_inject or a converted "
                "model family"
            )
        pcfg = self._config.paged_kv
        if not pcfg.enabled:
            raise ValueError("paged serving is disabled (inference config paged_kv.enabled)")
        # crash-recovery journal (inference.journal): replay BEFORE the new
        # writer opens its segment, then hand the replayed state to the
        # fresh server — a restart resumes every journaled stream
        # byte-identically from its last emitted token
        journal = None
        recovered_states = None
        next_uid = 0
        jcfg = self._config.journal
        if jcfg.enabled:
            from deepspeed_tpu.inference.journal import RequestJournal

            if not jcfg.dir:
                raise ValueError("inference.journal.enabled requires journal.dir")
            recovered_states, next_uid = RequestJournal.replay(jcfg.dir)
            journal = RequestJournal(
                jcfg.dir, segment_bytes=jcfg.segment_bytes, fsync=jcfg.fsync
            )
        # multi-chip tensor-parallel serving (ISSUE 13): the ragged
        # programs run under shard_map on a model-axis mesh — weights
        # column/row-parallel per the AutoTP map, kv pages sharded on the
        # kv-head axis, host-side scheduling untouched. The serving mesh
        # is ONE tp group over the first tp_degree devices; replication
        # across groups is the fleet layer's job (inference/fleet.py).
        scfg = pcfg.sharded
        tp_degree = int(scfg.tp_degree or self._config.tensor_parallel.tp_size or 1)
        tp_ctx = None
        params = self._params
        if tp_degree > 1:
            from deepspeed_tpu.inference.tp import TPServing, serving_mesh

            tp_ctx = TPServing(
                mesh=serving_mesh(tp_degree),
                quantized_allreduce=scfg.quantized_allreduce,
                comm_chunks=scfg.comm_chunks,
            )
        if scfg.weight_quant_bits == 8:
            # quantize BEFORE sharding: per-output-channel scales stay
            # global, so row-parallel partial sums dequantize consistently
            from deepspeed_tpu.compression.int8 import quantize_params_int8

            params = quantize_params_int8(params)
        prefix_cache = pcfg.prefix_cache
        unshareable = has_state_layers(self._ds_config) or has_latent_layers(self._ds_config)
        if unshareable and "prefix_cache" not in pcfg.model_fields_set:
            # the default is on; a model with recurrent-state or sliding-window
            # layers cannot attach a cached prefix (no state snapshot at that
            # position, no ring of the pages before it), and the pool's
            # copy-on-write does not copy a latent layer's pages yet, so the
            # default is off for them. Asked for by name, it is refused
            log_dist("paged_kv.prefix_cache defaults to off for a model with recurrent-state, sliding-window or latent-attention layers", ranks=[0])
            prefix_cache = False
        server = PagedServer(
            self._ds_config,
            params,
            page_size=pcfg.page_size,
            num_pages=pcfg.num_pages,
            max_slots=pcfg.max_slots,
            max_seq_len=pcfg.max_seq_len,
            prefill_chunk=pcfg.prefill_chunk,
            attn_impl=pcfg.attn_impl,
            dtype=self.dtype,
            telemetry=self._telemetry,
            spec_decode=self._config.spec_decode,
            prefix_cache=prefix_cache,
            journal=journal,
            tracer=self.tracer,
            metrics=self.metrics,
            tp=tp_ctx,
        )
        self._record_route_plan(server)
        self._record_sparse_attend_form(server)
        if recovered_states:
            server.recover(recovered_states, next_uid)
        tcfg = self._config.traffic
        if tcfg.enabled:
            # multi-tenant SLA layer (inference/traffic.py): weighted-deficit
            # + priority scheduling, queue-cap admission control, per-tenant
            # serve_stats() breakdowns — same serve()/submit()/step surface
            from deepspeed_tpu.inference.traffic import MultiTenantServer

            server = MultiTenantServer(
                server, tenants=[t.model_dump() for t in tcfg.tenants]
            )
        return server

    def _record_route_plan(self, server) -> None:
        """Which form a routed layer's assignment plan takes in the server's
        programs, and with it the layer's two ways between token order and
        expert order (``combine``: ``live_rows`` | ``gather``,
        ``moe/live_rows.py``): ``moe/route_plan.py::plan_path``, the question
        ``routed_ffn`` itself asks, at the tokens one call routes in the
        narrow and in the mixed program, said once a shape where the server
        is built (the ops have no tracer): nothing in a step. As the training
        engine says ``flash.operand_layout``."""
        from deepspeed_tpu.inference.decode import routed_rows
        from deepspeed_tpu.moe.route_plan import plan_path

        cfg = self._ds_config
        width = getattr(cfg, "moe_router_experts", None) or getattr(cfg, "num_experts", 0)
        for tokens in sorted({routed_rows(cfg, server.pool.max_slots, w) for w in (server._ragged_w_decode, server._ragged_w_mixed)} - {0}):
            self.tracer.event("moe.route_plan", **plan_path(tokens, width, cfg.moe_top_k))

    def _record_sparse_attend_form(self, server) -> None:
        """Which form a sparse latent layer's decode rows attend their chosen
        keys in (``walk``: the kernel over a row's live pages under the
        selection's mask; ``gather``: XLA's gather of the chosen entries):
        ``sparse_latent_attention.decode_form``, the question the layer itself
        asks of its page table's width, said once a program (``window``: the
        narrow one's and the mixed one's, whose one-token row group is the
        same call) where the server is built: nothing in a step. As
        ``_record_route_plan`` says ``moe.route_plan``."""
        cfg = self._ds_config
        if "sparse_latent" not in (getattr(cfg, "layer_types", None) or ()):
            return
        from deepspeed_tpu.ops.transformer.sparse_latent_attention import decode_form

        form = decode_form(server.pool.max_pages_per_slot * server.pool.page_size, cfg.index_topk)
        for window in sorted({server._ragged_w_decode, server._ragged_w_mixed}):
            self.tracer.event("sparse_attend.form", window=window, **form)

    def serve(self, prompts, max_new_tokens=32, eos_token_id=None):
        """Continuous-batching greedy generation over the paged KV pool:
        requests are admitted/evicted every step, prompts prefill in chunks
        riding the SAME dispatch as in-flight decoders, and each step is
        ONE dispatch of the unified ragged program
        (``inference/scheduler.py``). With
        ``inference.spec_decode.enable`` host-side n-gram drafts verify
        inside the same per-step dispatch (per-request spec-K), token-exact
        under greedy. Accepts a list of 1-D
        prompts (ragged — no padding to a common length) and a scalar or
        per-request ``max_new_tokens``; returns one 1-D output array per
        request in submission order. The server (and its page pool)
        persists across calls, sized by the ``paged_kv`` config section."""
        if self._paged_server is None:
            self._paged_server = self._build_paged_server()
        return self._paged_server.serve(
            prompts, max_new_tokens=max_new_tokens, eos_token_id=eos_token_id
        )

    def serve_stats(self):
        """Observability of the live paged server: scheduler counters
        (admitted, preempted, finished, prefill_chunks, decode_steps,
        spec_rounds, ``dispatches_per_token``), speculation quality
        (``spec_accept_rate``,
        ``spec_mean_accepted_per_round``, the ``spec_accept_hist`` draft-hit
        histogram), pool occupancy/utilization, prefix-cache counters
        (``prefix`` — hit rate, CoW copies, cached pages), latency SLOs
        (``ttft_ms`` / ``tpot_ms`` p50/p99), and per-tenant breakdowns
        (``tenants`` — plus budget/goodput shares and SLA attainment when
        ``inference.traffic`` is enabled)."""
        if self._paged_server is None:
            return {}
        return self._paged_server.serve_stats()

    def observability(self, analysis: bool = True):
        """The merged observability report (ISSUE 10), inference side: the
        serving ``timeline`` (per-step admit/pack/dispatch/emit/journal
        phases + per-request lifecycle spans) and ``metrics`` next to
        ``compile`` (``compile_stats()``), ``analysis``
        (``analysis_report()``; ``analysis=False`` skips its re-compile
        cost), and ``serve`` (``serve_stats()``). Chrome-trace export and
        the flight recorder hang off ``engine.observability_hub``."""
        return self._obs_hub.report(exclude=() if analysis else ("analysis",))

    @property
    def observability_hub(self):
        return self._obs_hub

    def _zero_generate(self, input_ids, max_new_tokens, eos_token_id, pad_token_id,
                       temperature=0.0, top_k=0, top_p=1.0):
        """Decode with layer-streamed params (ZeRO-Inference); greedy or
        temperature/top-k/top-p sampled like the in-HBM paths.

        Every step re-runs the full fixed-shape forward (one compile) and
        streams all layers through HBM — the reference's capacity-first
        trade (15T params on one GPU at batch-latency cost,
        docs/_posts/2022-09-10-zero-inference.md)."""
        from deepspeed_tpu.inference.sampling import sample_logits

        tokens = np.asarray(input_ids)
        if tokens.ndim == 1:
            tokens = tokens[None, :]
        B, P = tokens.shape
        L = P + max_new_tokens
        padded = np.full((B, L), pad_token_id, dtype=tokens.dtype)
        padded[:, :P] = tokens
        finished = np.zeros(B, dtype=bool)
        cursor = P
        for cur in range(P, L):
            logits = np.asarray(
                self._param_stream.eval_forward(jnp.asarray(padded), None)
            )
            self._rng, sub = jax.random.split(self._rng)
            nxt = np.asarray(
                sample_logits(jnp.asarray(logits[:, cur - 1]), sub,
                              temperature=temperature, top_k=top_k, top_p=top_p)
            ).astype(padded.dtype)
            if eos_token_id is not None:
                # finished rows keep emitting EOS — same padding contract as
                # the in-HBM decode paths
                nxt = np.where(finished, eos_token_id, nxt)
            padded[:, cur] = nxt
            cursor = cur + 1
            if eos_token_id is not None:
                finished |= nxt == eos_token_id
                if finished.all():
                    break
        return padded[:, :cursor]
