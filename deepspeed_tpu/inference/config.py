"""Inference config (reference: ``deepspeed/inference/config.py``, 304 LoC)."""

from __future__ import annotations

from enum import Enum
from typing import Any, Dict, List, Optional

from pydantic import Field, model_validator

from deepspeed_tpu.runtime.config import AnalysisConfig, TracingConfig
from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigModel


class DtypeEnum(str, Enum):
    fp32 = "fp32"
    fp16 = "fp16"
    bf16 = "bf16"
    int8 = "int8"


class DeepSpeedTPConfig(DeepSpeedConfigModel):
    enabled: bool = True
    tp_size: int = 1
    mpu: Optional[Any] = None
    tp_group: Optional[Any] = None


class DeepSpeedMoEConfig(DeepSpeedConfigModel):
    enabled: bool = True
    ep_size: int = 1
    moe_experts: list = Field(default_factory=lambda: [1])
    type: str = "standard"


class QuantizationConfig(DeepSpeedConfigModel):
    enabled: bool = False
    num_bits: int = 8
    group_size: int = 64


class BaseQuantConfig(DeepSpeedConfigModel):
    enabled: bool = False
    num_bits: int = 8
    group_size: int = 64


class WeightQuantConfig(BaseQuantConfig):
    pass


class ActivationQuantConfig(BaseQuantConfig):
    pass


class QKVQuantConfig(DeepSpeedConfigModel):
    enabled: bool = False


class CheckpointConfig(DeepSpeedConfigModel):
    checkpoint_dir: Optional[str] = None
    save_mp_checkpoint_path: Optional[str] = None
    base_dir: Optional[str] = None


class ShardedServingConfig(DeepSpeedConfigModel):
    """Multi-chip tensor-parallel serving knobs (``inference/tp.py``).

    With an effective tp degree > 1 (``tp_degree``, or — when 0 — the
    engine-level ``tensor_parallel.tp_size``), the ragged serving programs
    run under ``shard_map`` on a ``model``-axis mesh: weights shard
    column-parallel (q/k/v/gate/up) and row-parallel (o/down) per the
    AutoTP map, the paged KV pools shard over the **kv-head axis** (page
    tables stay host-side and replicated — prefix cache, CoW, journal,
    and the fleet router are untouched), and greedy streams stay
    **byte-identical** to single-chip serving for fp32/bf16 weights.

    ``quantized_allreduce`` swaps the row-parallel projections' fp psum
    for the EQuARX-style int8 exchange (all-to-all + local fp32 reduce +
    all-gather): 4x fewer bytes on the decode critical path at a bounded
    quantization error — the serving contract under this knob is
    allclose, not byte-identical. ``comm_chunks`` splits each projection
    so every all-reduce overlaps the next chunk's matmul (the ``overlap``
    analysis pass verifies the schedule). ``weight_quant_bits = 8`` stores
    the matmul weights int8 with per-output-channel scales
    (``compression/int8.py``), dequantized in the matmul epilogue —
    elementwise weight error ≤ max|w_channel|/254."""

    tp_degree: int = 0  # 0 = follow tensor_parallel.tp_size; 1 = single-chip
    quantized_allreduce: bool = False
    comm_chunks: int = 2  # row-parallel output split for comm/compute overlap
    weight_quant_bits: int = 0  # 0 = off; 8 = int8 per-channel weights

    @model_validator(mode="after")
    def _check(self):
        if self.tp_degree < 0:
            raise ValueError(f"sharded.tp_degree must be >= 0, got {self.tp_degree}")
        if self.comm_chunks < 1:
            raise ValueError(f"sharded.comm_chunks must be >= 1, got {self.comm_chunks}")
        if self.weight_quant_bits not in (0, 8):
            raise ValueError(
                f"sharded.weight_quant_bits supports 0 (off) or 8 (int8), "
                f"got {self.weight_quant_bits}"
            )
        return self


class PagedKVConfig(DeepSpeedConfigModel):
    """Paged-KV serving knobs (``engine.serve()``: block-pool cache +
    continuous batching, ``inference/kv_pool.py`` / ``inference/scheduler.py``).

    Cache HBM is ``num_pages × page_size × bytes_per_token`` where
    ``bytes_per_token = 2 · L · NKV · D · dtype_bytes`` — sized to LIVE
    tokens instead of the dense workspace's ``batch × max_len``. With
    ``num_pages = 0`` the pool is sized worst-case
    (``max_slots × ceil(max_seq_len / page_size) + 1``, preemption-free);
    set it lower to oversubscribe and trade HBM for recompute preemptions.

    Every step is ONE dispatch of the unified ragged program
    (``decode.py:build_ragged_step``): mixed prefill-chunk, decode, and
    verify rows ride together, driven by per-row ``(kv_len, q_len)``
    metadata arrays, so shifting traffic never retraces and total compiled
    serving programs is ≤ 2 (the narrow decode/verify width plus the mixed
    width covering prefill chunks) — chunked prefill shares the dispatch
    with decoders instead of stealing whole steps, and spec-K varies freely
    per request.

    ``prefix_cache`` turns on page-level prefix sharing: full KV pages are
    indexed by a content chain hash, requests attach the longest cached
    prefix of their context by reference (refcounted, copy-on-write on
    divergence), and N requests sharing a system prompt pay its prefill
    and HBM once. Greedy streams stay byte-identical to sharing-off
    serving; sharing adds zero programs and zero dispatches.
    """

    enabled: bool = True
    page_size: int = 16
    num_pages: int = 0  # 0 = worst-case auto-size (no preemption possible)
    max_slots: int = 8  # concurrent sequences (rows of the decode batch)
    max_seq_len: int = 0  # 0 = the model config's max_seq_len
    prefill_chunk: int = 32  # prompt tokens per interleaved prefill dispatch
    attn_impl: str = "auto"  # auto | pallas | xla (decode attention backend)
    prefix_cache: bool = True  # page-level prefix sharing (hash-of-block + CoW)
    # multi-chip tensor-parallel serving: sharded weights +
    # kv-head-sharded pages + quantized comms knobs
    sharded: ShardedServingConfig = Field(default_factory=ShardedServingConfig)


class TenantConfig(DeepSpeedConfigModel):
    """One tenant's serving contract (``inference/traffic.py:TenantSpec``):
    token-budget ``weight`` (fair share of served tokens), strict
    ``priority`` class (admitted first, preempted last), TTFT/TPOT SLA
    targets (reported as attainment, not enforced), and admission-control
    caps (``max_queued`` submissions rejected beyond the queue depth;
    ``max_live_slots`` bounds concurrent slots)."""

    name: str
    weight: float = 1.0
    priority: int = 0
    ttft_target_ms: Optional[float] = None
    tpot_target_ms: Optional[float] = None
    max_queued: Optional[int] = None
    max_live_slots: Optional[int] = None


class TrafficConfig(DeepSpeedConfigModel):
    """Multi-tenant SLA serving knobs. With ``enabled`` the engine wraps
    its ``PagedServer`` in a ``MultiTenantServer``: weighted-deficit +
    priority scheduling over the per-tenant contracts in ``tenants``,
    per-tenant breakdowns in ``serve_stats()``, and queue-cap admission
    control at ``submit``. Unknown tenants fall back to a weight-1
    priority-0 default."""

    enabled: bool = False
    tenants: List[TenantConfig] = Field(default_factory=list)


class JournalConfig(DeepSpeedConfigModel):
    """Serving crash-recovery journal (``inference/journal.py``).

    With ``enabled`` (and a ``dir``) every admitted request and emitted
    token is appended to an on-disk journal — durable once per scheduler
    step — and building the server on a directory that already holds
    records REPLAYS it first: finished results are restored, live requests
    re-queue with their journaled tokens pre-seeded, and every stream
    resumes byte-identically from its last emitted token (re-prefill rides
    the prefix cache, so shared prompts pay nearly nothing). Segments seal
    atomically at ``segment_bytes``; ``fsync=False`` trades durability of
    the last step for write latency (replay still never reads a torn
    record — CRCs gate every line)."""

    enabled: bool = False
    dir: Optional[str] = None
    segment_bytes: int = 1 << 20
    fsync: bool = True


class SpecDecodeConfig(DeepSpeedConfigModel):
    """Speculative-decoding knobs for paged serving (``engine.serve()``).

    Each speculative round drafts up to ``max_draft`` tokens per request
    host-side (``inference/spec_decode.py``: model-free n-gram /
    prompt-lookup of order ``ngram_order``) and verifies them in ONE
    device dispatch — the step's own, whose decode/verify width is
    ``max_draft + 1``; greedy outputs stay byte-identical to
    speculation-off serving.
    """

    enable: bool = False
    max_draft: int = 4  # drafted tokens per request per round (the K cap)
    ngram_order: int = 3  # longest suffix n-gram the drafter looks up


class DeepSpeedInferenceConfig(DeepSpeedConfigModel):
    replace_with_kernel_inject: bool = Field(False, alias="kernel_inject")
    dtype: DtypeEnum = DtypeEnum.bf16
    tensor_parallel: DeepSpeedTPConfig = Field(default_factory=DeepSpeedTPConfig, alias="tp")
    enable_cuda_graph: bool = False  # parity flag; maps to jit compile cache
    use_triton: bool = False
    triton_autotune: bool = False
    zero: Dict[str, Any] = Field(default_factory=dict)
    triangular_masking: bool = Field(True, alias="tm")
    moe: DeepSpeedMoEConfig = Field(default_factory=DeepSpeedMoEConfig)
    quant: QuantizationConfig = Field(default_factory=QuantizationConfig)
    paged_kv: PagedKVConfig = Field(default_factory=PagedKVConfig)
    spec_decode: SpecDecodeConfig = Field(default_factory=SpecDecodeConfig)
    traffic: TrafficConfig = Field(default_factory=TrafficConfig)
    journal: JournalConfig = Field(default_factory=JournalConfig)
    analysis: AnalysisConfig = Field(default_factory=AnalysisConfig)
    # unified tracing/metrics plane (profiling/tracer.py): serving step
    # phases (admit/pack/dispatch/emit/journal-sync) + per-request
    # lifecycle spans, merged by engine.observability(); same knobs as the
    # training side incl. the crash flight recorder
    tracing: TracingConfig = Field(default_factory=TracingConfig)
    checkpoint: Optional[Any] = None
    base_dir: str = ""
    set_empty_params: bool = False
    save_mp_checkpoint_path: Optional[str] = None
    checkpoint_config: CheckpointConfig = Field(default_factory=CheckpointConfig, alias="ckpt_config")
    return_tuple: bool = True
    training_mp_size: int = 1
    replace_method: str = "auto"
    injection_policy: Optional[Dict] = Field(None, alias="injection_dict")
    injection_policy_tuple: Optional[tuple] = None
    config: Optional[Dict] = None
    max_out_tokens: int = Field(1024, alias="max_tokens")
    min_out_tokens: int = Field(1, alias="min_tokens")
    transposed_mode: bool = False
    ep_size: int = 1
    ep_group: Optional[Any] = Field(None, alias="expert_group")
    ep_mp_group: Optional[Any] = Field(None, alias="expert_mp_group")
    moe_experts: list = Field(default_factory=lambda: [1])
    moe_type: str = "standard"

    @model_validator(mode="before")
    @classmethod
    def _legacy_mp_size(cls, values):
        """Reference's deprecated ``mp_size`` maps onto tensor_parallel.tp_size."""
        if isinstance(values, dict) and "mp_size" in values:
            mp = values.pop("mp_size")
            values.setdefault("tensor_parallel", {"tp_size": mp})
        return values

