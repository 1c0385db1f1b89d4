"""A decoder whose layers are of more than one kind: softmax attention over
every earlier key in some, over a sliding window in others, gated delta-rule
linear attention (``ops/transformer/linear_attention.py``) in others, latent
attention (one low-rank latent a token in place of keys and values a head) in
others, a Mamba-2 state-space mixer (``ops/transformer/state_space.py``) in
others, a gated short convolution (no attention, no recurrence: all a row
carries is the convolution's tail) in others; a routed FFN that may have a shared expert and may hold only this chip's share
of the experts its router chooses from, behind ``leading_dense_layers`` layers
whose FFN is dense: a leading layer of any kind; or, with ``num_experts`` 0, a
dense FFN in EVERY layer, stacked by period like the mixers. A layer is a mixer
AND an FFN, unless the list names FFN blocks (``ffn``): a block is then ONE
sublayer, ``x + f(RMSNorm(x))`` with ``f`` a mixer alone or the FFN alone
(Nemotron-H), and nothing stands behind a mixer. The experts are SwiGLU ones of
three matrices or, with a pointwise ``activation`` (``relu2``), of two.

``HybridMoEConfig.layer_types`` says what each layer is (``softmax`` /
``window`` / ``linear`` / ``latent`` / ``ssm`` / ``conv``, and ``ffn`` for a block that is the FFN
alone); after the leading dense layers the list repeats
with a period (one softmax layer and three linear ones, say, or five window
layers and a softmax one; a list that repeats nothing is one period of all its layers),
whole or, at least twice whole and then once more IN PART (``[a c c c] x 9 + [a
c]``: nine periods and a ``remainder`` of two). Parameters are stacked by KIND inside a period and
by period in front; a leading layer and a layer of the remainder have their own::

    params["leading"][i]          {"mixer": its kind's leaves, "ffn": a dense FFN's}
    params["periods"]["softmax"]  leaves [periods, softmax layers a period, ...]
    params["periods"]["window"]   leaves [periods, window layers a period, ...]
    params["periods"]["linear"]   leaves [periods, linear layers a period, ...]
    params["periods"]["latent"]   leaves [periods, latent layers a period, ...]
    params["periods"]["ssm"]      leaves [periods, state-space layers a period, ...]
    params["periods"]["conv"]     leaves [periods, conv layers a period, ...]
    params["periods"]["moe"]      leaves [periods, layers a period, ...]; [periods, FFN blocks a period, ...] where the list names them
    params["periods"]["ffn"]      in place of "moe" where ``num_experts`` is 0: a dense FFN a layer (or an FFN block)
    params["trailing"][i]         {"mixer": its kind's leaves, "moe": ONE routed FFN's (or "ffn": a dense one's)}; no remainder: no such key

so the leading layers, then one ``lax.scan`` over the whole periods, then the
trailing layers run the model, the scan's body holding the period's layers in order. The functions below are
the layer's mathematics, shared by ``HybridMoETransformerLM.apply`` (a whole
sequence, no cache: what the parity tests use; training this family is not
supported) and by the paged serving step (``inference/hybrid_decode.py``).

The softmax and the window layer (``attn_project``, ``attn_heads``): ``q k v = h Wq, h Wk, h Wv``
as the kind's query heads (``num_heads``, ``window_num_heads``: ``heads_of``)
and the kind's KV heads (``num_kv_heads``, ``window_num_kv_heads``) of
``head_dim``, values of ``v_head_dim``; ``qk_norm="head"`` norms q and k over each head's ``head_dim`` features
(RMSNorm, one learned scale ``[head_dim]`` for q and one for k, BEFORE any
rotation: a page holds normed, rotated keys); ``position="rope"`` rotates the
leading ``rope_dim`` / ``window_rope_dim`` features of q and k (rotate-half, at
the token's absolute position, theta ``rope_theta`` / ``window_rope_theta``;
in a softmax layer with ``rope_yarn_factor`` the YaRN frequencies, cos and sin
times the attention factor: ``rope_frequencies``), ``"none"`` has no
positional term at all; ``v`` times ``attn_value_scale``; causal softmax over
grouped heads, in a window layer over the newest ``window`` keys only (itself
included) and, with ``window_sinks``, with one learned scalar a head as one
more column of the softmax that is then dropped, so that a row's weights sum
to less than one; ``o = (attn * gate) Wo`` (``output_gate``), the gate
``sigmoid(h Wg)`` a feature in a softmax layer (``attn_output_gate``) or one
scalar a head in both kinds (``attn_head_gate``). The linear layer:
``q~ k~ v~ = h Wq, h Wk, h Wv``, each through a depthwise causal convolution
of ``linear_conv_kernel`` taps and SiLU; per head ``q = l2norm(q~) / sqrt(Dk)``,
``k = l2norm(k~)``; decay ``a = exp(-exp(A_log) softplus(Wf_up (Wf_down h) +
dt_bias))`` a key channel; ``b = 2 sigmoid(h w_b)`` a head (``1 x`` without
``linear_allow_neg_eigval``); the delta rule; output
``(RMSNorm_head(o) * sigmoid(Wg_up (Wg_down h))) Wo``. The latent layer
(``latent_project``, ``latent_absorb``, ``latent_output``), with or without a
low-rank query, with rotary or none: ``c_q = RMSNorm(h Wq_a)`` of
``q_lora_rank``, a query head ``[q_nope ; q_rope] = c_q Wq_b`` of
``qk_nope_head_dim + qk_rope_head_dim`` (= ``head_dim``), or, with
``q_lora_rank`` 0, ``h Wq`` with no low rank and no query norm; ``[c ; r] = h
Wkv_a``, ``c_kv = RMSNorm(c)`` of ``kv_lora_rank`` (the norm over those alone),
``k_rope = RoPE(r)`` of ``qk_rope_head_dim``, ONE for all heads; a head's key is
``[c_kv Wk_b,h ; k_rope]``, its value ``c_kv Wv_b,h`` of ``v_head_dim``
(``Wk_b`` and ``Wv_b`` are the published ``kv_b_proj``'s two parts, stored
apart); rotate-half on the rope parts at the token's absolute position, or,
with ``position="none"``, ``q_rope`` and ``k_rope = r`` as projected (the
shared features are kept, nothing is rotated); scale ``head_dim^-0.5``.
``apply`` computes that (the expanded form); the server keeps
``[c_kv ; k_rope]`` a token and computes the same numbers absorbed: ``q~ =
q_nope Wk_b,h^T`` against ``c_kv``, ``o = (P c_kv) Wv_b,h``. The state-space
layer (``ssm_inputs``, ``ssm_conv``, ``ssm_split``, ``ssm_output``; Mamba-2 with
``ssm_groups`` groups of ``B`` and ``C``, ``G``): ``[z ; xBC ; dt] = h W_in`` (``d_inner = ssm_num_heads x
ssm_head_dim``, ``d_inner + 2 G ssm_state``, ``ssm_num_heads``; the published
``in_proj``'s three parts are three leaves, ``w_z``, ``w_xbc``, ``w_dt``); ``xBC`` through
one depthwise causal convolution of ``ssm_conv_kernel`` taps WITH a bias and
SiLU, then split into ``x`` a head, ``B`` and ``C`` of ``ssm_state`` a group (one group: shared by
all heads; more: head n reads group ``n // (heads / G)``); ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` a head; the
state ``S`` ``[heads, head_dim, ssm_state]`` float32, ``S_t = exp(dt_t A) S_{t-1}
+ dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``; output ``RMSNorm(y * silu(z))
W_out``, the gate BEFORE the norm, the norm over each of the ``G`` groups of
``d_inner / G`` features apart (one group: over all of them). The conv layer
(``conv_inputs``, ``gated_conv``, ``shifted_tail``, ``conv_output``; the gated
short convolution of the LFM2 family): ``[B ; C ; x~] = h W_in`` (three parts of
``H``; the published ``in_proj`` is ONE leaf, ``w_in`` ``[H, 3 H]``: its parts are
whole lane tiles), ``u = B * x~`` a channel, ``v_t = sum_j w_j u_{t - (K - 1) +
j}``: one depthwise causal convolution of ``conv_kernel`` taps over the ``H``
channels of ``u`` with NO bias and NO activation, zeros before the sequence;
output ``(C * v) W_out``. No state: what a row carries is ``u`` of its last ``K -
1`` tokens. The
scalar multipliers (each 1.0 puts nothing into a program): the embedding times
``embedding_multiplier``, BOTH branches of every layer times
``residual_multiplier`` before they are added, the logits divided by
``logits_scaling``; a softmax layer's scale is ``attn_softmax_scale``. The FFN: ``moe_scoring``
over ``moe_router_experts`` outputs, the ``moe_top_k`` largest of score +
selection bias, gates normalised over the chosen (``moe_norm_topk_prob``) and
scaled by ``moe_routed_scaling``; of those, the experts this chip holds
(``moe_expert_share = (index, of)``: experts ``index * num_experts ..``), plus
the shared expert, once. An expert is ``(silu(h Wg) * (h Wu)) Wd`` or, with a
pointwise activation, ``act(h W_in) W_out`` (``relu2``: the rectified input
squared; ``moe/experts.py`` owns the activations); a routed two-matrix
expert's ``W_in`` is kept by its output rows (``w_in_t`` ``[.., E, I, H]``,
``init`` says why).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.compression.int8 import qmatmul
from deepspeed_tpu.models.moe_transformer import MoETransformerConfig, MoETransformerLM
from deepspeed_tpu.models.transformer import _norm

LAYER_KINDS = ("softmax", "linear", "window", "latent", "ssm", "conv", "sparse_latent", "window_latent")  # the mixers
FFN_BLOCK = "ffn"  # in ``layer_types``: a block that is the FFN alone; a list that names one has nothing behind its mixers
# the named scope around a kind's mixer, which the benchmark's readers find device time by
SCOPES = {"softmax": "attention", "linear": "linear_attention", "window": "window_attention", "latent": "latent_attention",
          "ssm": "ssm_mixer", "conv": "conv_mixer", "sparse_latent": "sparse_latent_attention",
          "window_latent": "window_latent_attention"}
# the kinds that keep one low-rank entry a token: under the page table (``latent``; ``sparse_latent``, with an indexer's key
# beside it) or in a ring a slot (``window_latent``)
LATENT_KINDS = ("latent", "sparse_latent", "window_latent")
WINDOW_KINDS = ("window", "window_latent")  # the kinds whose query sees the newest ``window`` keys, kept in a ring a slot
# the kinds whose layers keep something a SLOT whatever the row's length: a recurrent state and a convolution tail, or
# (``conv``) a convolution tail alone
STATE_KINDS = ("linear", "ssm", "conv")


class LatentDims(NamedTuple):
    """What a latent layer of one kind is made of (``HybridMoEConfig.latent_dims``)."""

    heads: int
    q_rank: int  # 0: the query has no low rank
    kv_rank: int  # an entry's value part, ``c_kv``
    nope: int
    rope: int  # the part all heads share, beside ``c_kv`` in an entry
    v: int
    theta: float
    q_rescale: float  # the normed low ranks times these; 1.0: nothing
    kv_rescale: float

    @property
    def width(self) -> int:
        """What the layer keeps of a token: ``[c_kv ; k_rope]``."""
        return self.kv_rank + self.rope

    @property
    def scale(self) -> float:
        return float(self.nope + self.rope) ** -0.5


@dataclasses.dataclass
class HybridMoEConfig(MoETransformerConfig):
    # what each layer is (a mixer of ``LAYER_KINDS``, or ``ffn``: a block that is the FFN alone, which makes every
    # mixer a block alone too); None: every layer ``softmax``
    layer_types: Optional[Sequence[str]] = None
    leading_dense_layers: int = 0  # layers in front whose FFN is dense (``intermediate_size``), not routed
    attn_output_gate: bool = False  # softmax layers: attn * sigmoid(h Wg) before Wo, a gate a feature
    attn_head_gate: bool = False  # softmax, window and every latent kind's layers: one sigmoid scalar a head on its output, before Wo
    v_head_dim: int = 0  # a value head's width; 0: head_dim
    attn_value_scale: float = 1.0  # v times this, before P v
    window: int = 0  # window and window_latent layers: query i sees keys j with i - window < j <= i
    window_num_heads: int = 0  # a window layer's query heads; 0: num_heads
    window_num_kv_heads: int = 0  # 0: num_kv_heads
    window_rope_theta: float = 0.0  # 0: rope_theta
    window_rope_dim: int = 0  # a window layer's rotated width; 0: rope_dim
    # softmax layers: YaRN frequency scaling by its published numbers (factor 0: none)
    rope_yarn_factor: float = 0.0
    rope_yarn_original_positions: int = 0
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_yarn_attention_factor: float = 0.0  # cos and sin times this; 0: 0.1 ln(factor) + 1
    window_sinks: bool = False  # window layers: a learned scalar a head, one more column of the softmax
    linear_num_heads: int = 0  # 0: num_heads
    linear_head_dim: int = 0  # 0: head_dim (keys and values alike)
    linear_conv_kernel: int = 4
    linear_gate_rank: int = 0  # the decay's and the output gate's low rank; 0: linear_head_dim
    linear_allow_neg_eigval: bool = True
    # latent layers: the two low ranks (q_lora_rank 0: the query has none, one ``wq`` and no query norm) and a
    # query/key head's two parts (head_dim is their sum; ``position="none"`` leaves the second unrotated)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    # every latent kind: the normed low ranks times (hidden_size / rank)^0.5 (``apply_mla_qkv_lora_rescale``); off: times
    # 1.0, which puts nothing into a program
    latent_lora_rescale: bool = False
    # window_latent layers: a latent layer's widths of their own (heads: ``window_num_heads``; theta: ``window_rope_theta``)
    window_q_lora_rank: int = 0
    window_kv_lora_rank: int = 0
    window_qk_nope_head_dim: int = 0
    window_qk_rope_head_dim: int = 0
    window_v_head_dim: int = 0  # 0: v_head_dim
    # sparse_latent layers: the indexer's heads and their width, and the keys a query attends (the highest-scoring
    # ``index_topk`` causal ones; all of them while there are no more)
    index_num_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # the router
    moe_scoring: str = "softmax"  # softmax | sigmoid
    moe_select_bias: bool = False  # a learned bias added to the scores for the choice alone
    moe_router_experts: Optional[int] = None  # the router's width; None: num_experts (all held)
    moe_expert_share: Tuple[int, int] = (0, 1)  # (index, of): which share of the router's experts is held
    moe_shared_experts: int = 0  # shared experts, run as one FFN of that many expert widths
    moe_routed_scaling: float = 1.0
    # state-space (Mamba-2) layers: heads of ``ssm_head_dim`` over a state of ``ssm_state`` a feature, B and C in
    # ``ssm_groups`` groups (1: shared by all heads; G: head n reads group n // (heads / G), and the gated norm
    # runs over each group's features apart), one convolution over [x ; B ; C]
    ssm_num_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv_kernel: int = 4
    conv_kernel: int = 3  # conv layers: the taps of the gated short convolution over ``hidden_size`` channels
    # the scalar multipliers: 1.0 is no multiply anywhere (decided in Python when a program is built)
    embedding_multiplier: float = 1.0  # the embedding times this
    residual_multiplier: float = 1.0  # each branch of a layer times this, before it is added
    logits_scaling: float = 1.0  # the logits DIVIDED by this

    def __post_init__(self):
        dense_width_named = self.intermediate_size is not None
        super().__post_init__()
        self.layer_types = tuple(self.layer_types or ("softmax",) * self.num_layers)
        if len(self.layer_types) != self.num_layers or set(self.layer_types) - set(LAYER_KINDS) - {FFN_BLOCK}:
            raise ValueError(f"layer_types must name {self.num_layers} layers of {LAYER_KINDS + (FFN_BLOCK,)}, got {self.layer_types}")
        if self.single_sublayer:
            if set(self.layer_types) == {FFN_BLOCK}:
                raise ValueError("a model of FFN blocks only mixes no token with another: layer_types must name a mixer")
            if self.leading_dense_layers:
                raise ValueError("a list that names FFN blocks says where every FFN is: it has no leading_dense_layers (a mixer AND a dense FFN)")
            if not self.num_experts and not dense_width_named:
                raise ValueError("an FFN block of a model with num_experts=0 is a dense FFN and needs its width: intermediate_size")
        self.v_head_dim = self.v_head_dim or self.head_dim
        self.window_num_heads = self.window_num_heads or self.num_heads
        self.window_num_kv_heads = self.window_num_kv_heads or self.num_kv_heads
        self.window_rope_theta = self.window_rope_theta or self.rope_theta
        self.window_rope_dim = self.window_rope_dim or self.rope_dim
        if self.window_num_heads % self.window_num_kv_heads:
            raise ValueError(f"a window layer's {self.window_num_heads} query heads are no multiple of its {self.window_num_kv_heads} KV heads")
        if self.rope_yarn_factor and (self.position != "rope" or self.rope_yarn_factor <= 1 or self.rope_yarn_original_positions < 1):
            raise ValueError("rope_yarn_factor needs position='rope', a factor above 1 and rope_yarn_original_positions")
        if self.attn_output_gate and self.attn_head_gate:
            raise ValueError("attn_output_gate (a gate a feature) and attn_head_gate (a gate a head) are two forms of one gate: name one")
        if set(WINDOW_KINDS) & set(self.layer_types) and self.window < 1:
            raise ValueError("a window layer needs window >= 1")
        if not 0 <= self.leading_dense_layers < self.num_layers:
            raise ValueError(f"leading_dense_layers={self.leading_dense_layers} of {self.num_layers} layers")
        if "latent" in self.layer_types:
            sizes = (self.kv_lora_rank, self.qk_nope_head_dim, self.qk_rope_head_dim)
            if min(sizes) < 1 or self.q_lora_rank < 0 or self.qk_nope_head_dim + self.qk_rope_head_dim != self.head_dim:
                raise ValueError(
                    "a latent layer needs kv_lora_rank, qk_nope_head_dim and qk_rope_head_dim, head_dim = the last two's sum "
                    f"and q_lora_rank >= 0 (0: no low rank): got {sizes}, q_lora_rank={self.q_lora_rank}, head_dim={self.head_dim}"
                )
        self.window_v_head_dim = self.window_v_head_dim or self.v_head_dim
        if "sparse_latent" in self.layer_types:
            sizes = (self.kv_lora_rank, self.qk_nope_head_dim, self.qk_rope_head_dim, self.q_lora_rank, self.index_num_heads, self.index_topk)
            if min(sizes) < 1 or self.index_head_dim < self.qk_rope_head_dim or self.qk_nope_head_dim + self.qk_rope_head_dim != self.head_dim:
                raise ValueError(
                    "a sparse_latent layer needs a latent layer's widths with a low-rank query (the indexer's queries come from it), "
                    f"index_num_heads, index_topk and index_head_dim >= qk_rope_head_dim (its rotated part): got {sizes}, "
                    f"index_head_dim={self.index_head_dim}, head_dim={self.head_dim}"
                )
            if self.position != "rope" or "latent" in self.layer_types:
                raise NotImplementedError(
                    "a sparse_latent layer rotates its indexer (position='rope'), and a model's paged latent layers are of ONE kind "
                    "(kv_pool.StateStore.latent holds the latent layers' pages or the sparse_latent layers', not both)"
                )
        if "window_latent" in self.layer_types:
            sizes = (self.window_kv_lora_rank, self.window_qk_nope_head_dim, self.window_qk_rope_head_dim)
            if min(sizes) < 1 or self.window_q_lora_rank < 0:
                raise ValueError(f"a window_latent layer needs window_kv_lora_rank, window_qk_nope_head_dim and window_qk_rope_head_dim: got {sizes}")
            if "window" in self.layer_types:
                raise NotImplementedError(
                    "a model with window layers AND window_latent layers: the per-slot store holds ONE kind of ring "
                    "(kv_pool.StateStore: keys and values a head, or latents); no published model asks for both"
                )
        self.linear_num_heads = self.linear_num_heads or self.num_heads
        self.linear_head_dim = self.linear_head_dim or self.head_dim
        self.linear_gate_rank = self.linear_gate_rank or self.linear_head_dim
        if "ssm" in self.layer_types:
            if min(self.ssm_num_heads, self.ssm_head_dim, self.ssm_state) < 1 or self.ssm_conv_kernel < 2:
                raise ValueError("a state-space layer needs ssm_num_heads, ssm_head_dim, ssm_state and ssm_conv_kernel >= 2")
            if self.ssm_groups < 1 or self.ssm_num_heads % self.ssm_groups:
                raise ValueError(
                    f"ssm_groups={self.ssm_groups}: a state-space layer's {self.ssm_num_heads} heads read B and C in whole "
                    "groups (head n reads group n // (ssm_num_heads / ssm_groups))"
                )
            if self.ssm_conv_channels % 128:
                raise ValueError(
                    f"a state-space layer's convolved channels d_inner + 2 ssm_groups ssm_state = {self.ssm_conv_channels} must be "
                    "whole lane tiles of 128: the per-slot store keeps a row's tail a lane tile a row"
                )
            if "linear" in self.layer_types:
                raise NotImplementedError(
                    "a model with delta-rule linear layers AND state-space layers: the per-slot store holds ONE kind of "
                    "recurrent state (kv_pool.StateStore: one state array, one tail array, their shapes the kind's); "
                    "no published model asks for both"
                )
        if "conv" in self.layer_types:
            if self.conv_kernel < 2 or self.hidden_size % 128:
                raise ValueError(
                    f"a conv layer needs conv_kernel >= 2 and its convolved channels, hidden_size = {self.hidden_size}, in whole "
                    "lane tiles of 128: the per-slot store keeps a row's tail a lane tile a row"
                )
            if self.state_kind != "conv":
                raise NotImplementedError(
                    f"a model with conv layers AND {self.state_kind} layers: the per-slot store holds ONE kind's "
                    "convolution tails (kv_pool.StateStore: one tail array, its shape the kind's); no published model asks for both"
                )
        if self.qk_norm == "projection":
            raise NotImplementedError(
                "qk_norm='projection' (one norm over the whole q and k projections) is the uniform family's "
                "(models/transformer.py); a softmax or window layer here norms q and k a head: qk_norm='head'"
            )
        self.moe_expert_share = tuple(self.moe_expert_share)
        index, of = self.moe_expert_share
        if self.moe_router_experts is None:
            self.moe_router_experts = self.num_experts * of
        if self.num_experts == 0 and (self.leading_dense_layers or self.moe_shared_experts):
            raise ValueError("num_experts=0 makes EVERY layer's FFN a dense one of intermediate_size, stacked by period: "
                             "it has no leading dense layers apart and no shared expert")
        if self.num_experts * of != self.moe_router_experts or not 0 <= index < of:
            raise ValueError(
                f"share {index} of {of} of a router over {self.moe_router_experts} experts holds "
                f"{self.moe_router_experts // of}, not num_experts={self.num_experts}"
            )
        from deepspeed_tpu.moe.experts import POINTWISE_ACTIVATIONS

        if self.moe_layer_freq != 1 or self.moe_drop_tokens or self.activation not in ("swiglu",) + POINTWISE_ACTIVATIONS:
            raise ValueError("a hybrid model routes every FFN behind its leading dense ones droplessly, through SwiGLU experts of "
                             f"three matrices or experts of two with one of {POINTWISE_ACTIVATIONS} between them: "
                             "moe_layer_freq=1, moe_drop_tokens=False, activation='swiglu' or one of those")
        if self.position not in ("none", "rope") or self.use_bias or self.norm != "rmsnorm":
            raise ValueError("a hybrid model is pre-norm RMSNorm without biases, with rotary positions or none (position='rope'|'none')")

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest prefix that the layers behind the leading dense ones
        repeat: whole, or, where a layer is a mixer AND an FFN, at least
        twice whole and then once more in part (``remainder``). A list that
        names FFN blocks repeats its period whole or is one period of all its
        blocks, as is a list whose second period would be the partial one."""
        body = self.layer_types[self.leading_dense_layers :]
        for n in range(1, len(body) + 1):
            whole = len(body) % n == 0 or (len(body) // n >= 2 and not self.single_sublayer)
            if whole and all(body[i] == body[i % n] for i in range(len(body))):
                return tuple(body[:n])
        raise AssertionError

    @property
    def num_periods(self) -> int:
        """The WHOLE periods: what the parameters' stacks hold and the layer scan runs."""
        return (self.num_layers - self.leading_dense_layers) // len(self.period)

    @property
    def remainder(self) -> Tuple[str, ...]:
        """The layers behind the last whole period, a proper prefix of the
        period (``[a c c c] x 9 + [a c]``: two), each a mixer and a routed FFN
        with leaves of its own (``params["trailing"]``), run once behind the
        scan as the leading layers are in front of it. Empty: no such leaves,
        nothing in any program."""
        return tuple(self.layer_types[self.leading_dense_layers + self.num_periods * len(self.period) :])

    @property
    def single_sublayer(self) -> bool:
        """Whether a block is ONE sublayer, a mixer or the FFN alone: ``layer_types`` names FFN blocks."""
        return FFN_BLOCK in self.layer_types

    @property
    def ffns_per_period(self) -> int:
        """The FFNs of a period, which its FFN stacks hold: one a layer, or the period's FFN blocks."""
        return self.period.count(FFN_BLOCK) if self.single_sublayer else len(self.period)

    @property
    def num_moe_layers(self) -> int:
        """Layers with a routed FFN: those behind the leading dense ones, or the FFN blocks of a list that names
        them; none where ``num_experts`` is 0."""
        if not self.num_experts:
            return 0
        return self.layers_of(FFN_BLOCK) if self.single_sublayer else self.num_layers - self.leading_dense_layers

    @property
    def ssm_inner(self) -> int:
        """A state-space layer's inner width: its heads side by side."""
        return self.ssm_num_heads * self.ssm_head_dim

    @property
    def ssm_conv_channels(self) -> int:
        """What a state-space layer convolves of a token: ``[x ; B ; C]``."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def state_kind(self) -> Optional[str]:
        """The kind whose layers keep a recurrent state and a convolution tail a slot, or (``conv``) a tail alone
        (``STATE_KINDS``: a model names one at most), or None."""
        return next((kind for kind in STATE_KINDS if kind in self.layer_types), None)

    def layers_of(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    def leading_of(self, kind: str) -> int:
        """Leading dense layers of ``kind``: they have the first entries of the kind's cache."""
        return sum(t == kind for t in self.layer_types[: self.leading_dense_layers])

    def trailing_of(self, kind: str) -> int:
        """The first entry of the kind's cache that a trailing layer (``remainder``) has: behind the leading layers' and the whole periods'."""
        return self.leading_of(kind) + self.num_periods * self.period.count(kind)

    def heads_of(self, kind: str) -> int:
        """Query heads of a softmax, window or latent layer of any kind."""
        return self.window_num_heads if kind in WINDOW_KINDS else self.num_heads

    def latent_dims(self, kind: str = "latent") -> "LatentDims":
        """A latent kind's widths: the model's (``latent``, ``sparse_latent``) or the window layers' own."""
        rescale = lambda rank: (self.hidden_size / rank) ** 0.5 if self.latent_lora_rescale and rank else 1.0
        if kind == "window_latent":
            q, kv = self.window_q_lora_rank, self.window_kv_lora_rank
            return LatentDims(self.window_num_heads, q, kv, self.window_qk_nope_head_dim, self.window_qk_rope_head_dim,
                              self.window_v_head_dim, float(self.window_rope_theta), rescale(q), rescale(kv))
        q, kv = self.q_lora_rank, self.kv_lora_rank
        return LatentDims(self.num_heads, q, kv, self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
                          float(self.rope_theta), rescale(q), rescale(kv))

    def kv_heads_of(self, kind: str) -> int:
        return self.window_num_kv_heads if kind == "window" else self.num_kv_heads

    def rope_dim_of(self, kind: str) -> int:
        """The leading features of a q or k head that a softmax or window layer rotates."""
        return (self.window_rope_dim if kind == "window" else self.rope_dim) or self.head_dim

    def rope_frequencies(self, kind: str):
        """``(inverse frequencies [rotated width / 2] float32, what cos and sin
        are multiplied by)`` of a layer whose frequencies are scaled: a softmax
        layer under ``rope_yarn_factor``. None where they are the plain
        ``theta^(-n / half)``. YaRN by its published numbers (arXiv:2309.00071
        as the family's modelling code computes it): pair ``n`` of the rotated
        width ``d`` keeps its frequency below the pair that turns ``beta_fast``
        times in the original positions, has it divided by the factor above the
        pair that turns ``beta_slow`` times, and a linear ramp between (the
        ramp's ends floor and ceil of ``d ln(L / (2 pi beta)) / (2 ln theta)``).
        Made with NumPy from the config alone: constants of whatever program
        uses them, outside any layer loop."""
        if kind != "softmax" or not self.rope_yarn_factor:
            return None
        d, theta, L = self.rope_dim_of(kind), float(self.rope_theta), self.rope_yarn_original_positions
        turns_at = lambda beta: d * np.log(L / (2 * np.pi * beta)) / (2 * np.log(theta))
        low = max(int(np.floor(turns_at(self.rope_yarn_beta_fast))), 0)
        high = min(int(np.ceil(turns_at(self.rope_yarn_beta_slow))), d - 1)
        plain = theta ** (-np.arange(d // 2, dtype=np.float64) / (d // 2))
        kept = 1.0 - np.clip((np.arange(d // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
        factor = self.rope_yarn_attention_factor or 0.1 * np.log(self.rope_yarn_factor) + 1.0
        return (plain / self.rope_yarn_factor * (1.0 - kept) + plain * kept).astype(np.float32), float(factor)

    @property
    def latent_width(self) -> int:
        """What a latent (or sparse_latent) layer keeps of a token: ``[c_kv ; k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def paged_latent_kind(self) -> Optional[str]:
        """The kind whose entries lie under the page table (``latent`` or ``sparse_latent``: a model names one at most), or None."""
        return next((kind for kind in ("latent", "sparse_latent") if kind in self.layer_types), None)

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(the first held expert's index at the router, how many are held)."""
        return self.moe_expert_share[0] * self.num_experts, self.num_experts


# --- the layers' mathematics -------------------------------------------------------


def attn_project(p, h):
    """A softmax or window layer's projections of the normed ``h`` [..., H],
    heads side by side: ``h Wq, h Wk, h Wv``."""
    return qmatmul(h, p["wq"]), qmatmul(h, p["wk"]), qmatmul(h, p["wv"])


def attn_heads(cfg: HybridMoEConfig, kind: str, q, k, v, positions, p=None):
    """``attn_project``'s three as heads, ``q`` [B, T, NH, D], ``k`` [B, T,
    NKV, D], ``v`` [B, T, NKV, Dv], at ``positions`` [B, T]: under
    ``qk_norm="head"`` q and k normed over each head's ``D`` features (the
    layer's ``p["q_norm_scale"]`` and ``p["k_norm_scale"]`` [D]) BEFORE the
    rotation; the kind's leading features of q and k rotated with the kind's
    frequencies, v scaled."""
    from deepspeed_tpu.models.transformer import _rope

    NH, NKV, D, Dv = cfg.heads_of(kind), cfg.kv_heads_of(kind), cfg.head_dim, cfg.v_head_dim
    q, k, v = (a.reshape(a.shape[:-1] + shape) for a, shape in zip((q, k, v), ((NH, D), (NKV, D), (NKV, Dv))))
    if cfg.qk_norm == "head":
        q = _norm(q, p["q_norm_scale"], None, "rmsnorm", cfg.norm_eps)
        k = _norm(k, p["k_norm_scale"], None, "rmsnorm", cfg.norm_eps)
    if cfg.position == "rope":
        scaled = cfg.rope_frequencies(kind)
        if scaled is not None:
            q, k = (_rope_scaled(a, positions, *scaled) for a in (q, k))
        else:
            theta = cfg.window_rope_theta if kind == "window" else cfg.rope_theta
            q, k = (_rope(a, positions, theta, cfg.rope_dim_of(kind)) for a in (q, k))
    if cfg.attn_value_scale != 1.0:
        v = v * jnp.asarray(cfg.attn_value_scale, v.dtype)
    return q, k, v


def _rope_scaled(x, positions, inv_freq, factor):
    """``x`` [B, T, N, D] at ``positions`` [B, T]: the leading ``2 len(inv_freq)``
    features rotated (rotate-half) at the given frequencies, cos and sin times
    ``factor``; the tail passes through."""
    half = inv_freq.shape[0]
    angles = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq)  # [B, T, half]
    cos, sin = (jnp.cos(angles) * factor)[:, :, None, :], (jnp.sin(angles) * factor)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half : 2 * half]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., 2 * half :].astype(jnp.float32)], axis=-1).astype(x.dtype)


def output_gate(p, h, attn):
    """``attn`` [..., NH * Dv] times the layer's output gate, from the same
    normed ``h`` as its queries: ``wg`` [H, NH * Dv] a gate a feature, ``wg_head``
    [H, NH] one scalar a head; a layer with neither gives ``attn`` back."""
    if "wg" in p:
        return attn * jax.nn.sigmoid(qmatmul(h, p["wg"])).astype(attn.dtype)
    if "wg_head" in p:
        with jax.named_scope("head_gate"):
            gate = jax.nn.sigmoid(qmatmul(h, p["wg_head"]).astype(jnp.float32)).astype(attn.dtype)  # [..., NH]
            heads = attn.reshape(attn.shape[:-1] + (gate.shape[-1], -1))
            return (heads * gate[..., None]).reshape(attn.shape)
    return attn


def linear_inputs(cfg: HybridMoEConfig, p, h):
    """What a linear layer computes of one token before its recurrence, from
    the normed ``h`` [..., H]: the pre-convolution ``q~ k~ v~`` side by side
    [..., 3 NH D] in h's type, the log decay [..., NH D] and ``b`` [..., NH] in
    float32."""
    NH, D = cfg.linear_num_heads, cfg.linear_head_dim
    qkv = jnp.concatenate([qmatmul(h, p["wq"]), qmatmul(h, p["wk"]), qmatmul(h, p["wv"])], axis=-1)
    f = qmatmul(qmatmul(h, p["wf_down"]), p["wf_up"]).astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)
    rate = jnp.repeat(jnp.exp(p["A_log"].astype(jnp.float32)), D)  # a head's rate, on each of its channels
    log_a = -rate * jax.nn.softplus(f)
    beta = jax.nn.sigmoid(qmatmul(h, p["wb"]).astype(jnp.float32))
    return qkv, log_a, (2.0 * beta if cfg.linear_allow_neg_eigval else beta)


def short_conv(p, tails, qkv):
    """The three depthwise causal convolutions and SiLU, as one over the
    channels side by side: ``qkv`` [B, T, 3C] after the ``K - 1`` inputs that
    came before it (``tails`` [B, K - 1, 3C]). Float32 [B, T, 3C]."""
    w = jnp.concatenate([p["conv_q"], p["conv_k"], p["conv_v"]], axis=-1).astype(jnp.float32)  # [K, 3C]
    T = qkv.shape[1]
    ext = jnp.concatenate([tails, qkv], axis=1).astype(jnp.float32)
    return jax.nn.silu(sum(w[j] * ext[:, j : j + T] for j in range(w.shape[0])))


def linear_qkv(cfg: HybridMoEConfig, y):
    """The convolved ``y`` [..., 3 NH D] float32, split and normed: ``q k v``
    [..., NH, D]."""
    NH, D = cfg.linear_num_heads, cfg.linear_head_dim
    q, k, v = (a.reshape(a.shape[:-1] + (NH, D)) for a in jnp.split(y, 3, axis=-1))
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    return unit(q) * (D ** -0.5), unit(k), v


def linear_output(cfg: HybridMoEConfig, p, h, o):
    """``o`` [..., NH, D] of the recurrence: the head-wise RMSNorm, the
    low-rank output gate from ``h``, the output projection. [..., H]."""
    gate = jax.nn.sigmoid(qmatmul(qmatmul(h, p["wg_down"]), p["wg_up"]).astype(jnp.float32))
    o = _norm(o.astype(jnp.float32), p["o_norm_scale"], None, "rmsnorm", cfg.norm_eps)
    return qmatmul((o.reshape(gate.shape) * gate).astype(h.dtype), p["wo"])


def ssm_inputs(cfg: HybridMoEConfig, p, h):
    """What a state-space layer computes of one token before its convolution,
    from the normed ``h`` [..., H]: the gate ``z`` [..., d_inner] and the
    pre-convolution ``[x ; B ; C]`` [..., d_inner + 2 G N] in h's type, and
    ``dt = softplus(dt + dt_bias)`` [..., NH] in float32 (no clamp: the config
    names none)."""
    dt = jax.nn.softplus(qmatmul(h, p["w_dt"]).astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    return qmatmul(h, p["w_z"]), qmatmul(h, p["w_xbc"]), dt


def ssm_conv(p, tails, xbc):
    """The depthwise causal convolution WITH its bias, and SiLU: ``xbc`` [B, T,
    C] after the ``K - 1`` inputs that came before it (``tails`` [B, K - 1,
    C]). Float32 [B, T, C]: the decode kernel's own arithmetic
    (``state_space.decode_conv``) over the window's ``K`` shifted views."""
    from deepspeed_tpu.ops.transformer.state_space import decode_conv

    T = xbc.shape[1]
    ext = jnp.concatenate([tails, xbc], axis=1)
    return decode_conv(p["conv_w"], p["conv_b"], [ext[:, j : j + T] for j in range(p["conv_w"].shape[0])])


def ssm_split(cfg: HybridMoEConfig, y):
    """The convolved ``y`` [..., d_inner + 2 G N], split: ``x`` [..., NH, P],
    ``B`` and ``C`` [..., N] (one group: shared by all heads) or, with
    ``ssm_groups`` above one, [..., G, N] (head n reads group ``n // (NH / G)``)."""
    inner, N, G = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_groups
    x = y[..., :inner].reshape(y.shape[:-1] + (cfg.ssm_num_heads, cfg.ssm_head_dim))
    if G == 1:
        return x, y[..., inner : inner + N], y[..., inner + N :]
    return x, y[..., inner : inner + G * N].reshape(y.shape[:-1] + (G, N)), y[..., inner + G * N :].reshape(y.shape[:-1] + (G, N))


def ssm_output(cfg: HybridMoEConfig, p, z, y):
    """``y`` [..., d_inner] of the recurrence and the gate ``z``: the gate
    BEFORE the norm, the RMSNorm over all ``d_inner`` features (one group) or
    over each of the ``ssm_groups`` groups of ``d_inner / G`` apart (one learned
    scale [d_inner] either way), the output projection. [..., H]."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    if cfg.ssm_groups > 1:
        by_group = (cfg.ssm_groups, cfg.ssm_inner // cfg.ssm_groups)
        normed = _norm(gated.reshape(gated.shape[:-1] + by_group), p["o_norm_scale"].reshape(by_group), None, "rmsnorm", cfg.norm_eps)
        return qmatmul(normed.reshape(gated.shape).astype(z.dtype), p["wo"])
    return qmatmul(_norm(gated, p["o_norm_scale"], None, "rmsnorm", cfg.norm_eps).astype(z.dtype), p["wo"])


def conv_inputs(p, h):
    """What a conv layer computes of one token before its convolution, from
    the normed ``h`` [..., H]: ``[B ; C ; x~] = h W_in``; returns the gated
    product ``u = B * x~`` (what the layer convolves and what its tail keeps)
    and the output gate ``C``, both [..., H] in h's type."""
    b, c, x = jnp.split(qmatmul(h, p["w_in"]), 3, axis=-1)
    return b * x, c


def gated_conv(p, tails, u):
    """The depthwise causal convolution of a conv layer, with NO bias and NO
    activation: ``u`` [B, T, C] after the ``K - 1`` products that came before
    it (``tails`` [B, K - 1, C]), ``v_t = sum_j w_j u_{t - (K - 1) + j}``.
    Float32 [B, T, C]."""
    w = p["conv_w"].astype(jnp.float32)  # [K, C]
    T = u.shape[1]
    ext = jnp.concatenate([tails, u], axis=1).astype(jnp.float32)
    return sum(w[j] * ext[:, j : j + T] for j in range(w.shape[0]))


def shifted_tail(tail, u):
    """A conv layer's tail ``[..., K - 1, C]`` after one more token's product ``u`` ``[..., C]``: the oldest entry gone, ``u`` the newest."""
    return jnp.concatenate([tail[..., 1:, :], u[..., None, :]], axis=-2)


def conv_output(p, c, v):
    """``(C * v) W_out`` (the leaf ``wo``, as every mixer's output projection): ``c`` [..., H] the gate, ``v`` [..., H]
    float32 the convolved product. [..., H]."""
    return qmatmul((c.astype(jnp.float32) * v).astype(c.dtype), p["wo"])


def scaled(x, by: float):
    """``x`` times a config's scalar multiplier; at 1.0 ``x`` itself, no multiply in any program."""
    return x if by == 1.0 else x * jnp.asarray(by, x.dtype)


def latent_project(cfg: HybridMoEConfig, p, h, positions, kind: str = "latent"):
    """A latent layer's projections of the normed ``h`` [B, T, H] at
    ``positions`` [B, T] (``position="none"``: unused, may be None): ``q_nope``
    [B, T, NH, nope], ``q_rope`` [B, T, NH, rope], and what the layer keeps of
    each token, ``[c_kv ; k_rope]`` [B, T, kv_lora_rank + rope]: the normed
    latent and the one key part all heads share; ``q_rope`` and ``k_rope``
    rotated under ``position="rope"``, as projected under ``"none"``. The query
    through its low rank and norm, or, with ``q_lora_rank`` 0, ``h Wq``. The
    widths are the ``kind``'s (``cfg.latent_dims``); under
    ``latent_lora_rescale`` both normed low ranks are scaled."""
    from deepspeed_tpu.models.transformer import _rope

    d = cfg.latent_dims(kind)
    if d.q_rank:
        q = qmatmul(latent_query_rank(cfg, p, h, kind), p["wq_b"])
    else:
        # the head split kept apart from the matmul (as ``decode._paged_layers.project`` keeps a softmax layer's): folded into
        # it, the compiler wants ``wq`` head-major and copies the layer's 28 MB to that layout every step
        q = jax.lax.optimization_barrier(qmatmul(h, p["wq"]))
    q = q.reshape(h.shape[:-1] + (d.heads, d.nope + d.rope))
    # the entry spelled out here and not through ``latent_entry``: the accepted latent models' steps trace these operations in
    # this order (``test_accepted_programs_guard.py``: a fingerprint a family)
    kv = qmatmul(h, p["wkv_a"])
    c_kv = scaled(_norm(kv[..., : d.kv_rank], p["kv_norm_scale"], None, "rmsnorm", cfg.norm_eps), d.kv_rescale)
    if cfg.position != "rope":  # nothing is rotated: the shared features as projected
        return q[..., : d.nope], q[..., d.nope :], jnp.concatenate([c_kv, kv[..., d.kv_rank :]], axis=-1)
    k_rope = _rope(kv[..., None, d.kv_rank :], positions, d.theta)[..., 0, :]
    return q[..., : d.nope], _rope(q[..., d.nope :], positions, d.theta), jnp.concatenate([c_kv, k_rope], axis=-1)


def latent_query_rank(cfg: HybridMoEConfig, p, h, kind: str = "latent"):
    """``c_q = s_q RMSNorm(h Wq_a)`` [..., q_lora_rank]: the query's low rank, which a sparse layer's indexer reads too."""
    return scaled(_norm(qmatmul(h, p["wq_a"]), p["q_norm_scale"], None, "rmsnorm", cfg.norm_eps), cfg.latent_dims(kind).q_rescale)


def latent_queries(cfg: HybridMoEConfig, p, c_q, positions, kind: str = "latent"):
    """``c_q Wq_b`` as heads [..., NH, nope + rope], the rope part rotated at ``positions`` [B, T] under ``position="rope"``."""
    from deepspeed_tpu.models.transformer import _rope

    d = cfg.latent_dims(kind)
    # the head split kept apart from the matmul (as ``latent_project`` keeps ``wq``'s): folded into it, the compiler wants the
    # stack head-major and copied a window model's six ``wq_b`` whole, 201 MB, every step (0.6 ms of 21: PERF.md, PR 66)
    q = jax.lax.optimization_barrier(qmatmul(c_q, p["wq_b"])).reshape(c_q.shape[:-1] + (d.heads, d.nope + d.rope))
    if cfg.position != "rope":
        return q
    return jnp.concatenate([q[..., : d.nope], _rope(q[..., d.nope :], positions, d.theta)], axis=-1)


def latent_entry(cfg: HybridMoEConfig, p, h, positions, kind: str = "latent"):
    """What a latent layer keeps of a token: ``[s_kv RMSNorm(c) ; k_rope]`` [..., kv_lora_rank + rope] of ``[c ; r] = h
    Wkv_a``: the norm over the latent alone; ``r`` rotated under ``position="rope"``, as projected under ``"none"``."""
    from deepspeed_tpu.models.transformer import _rope

    d = cfg.latent_dims(kind)
    kv = qmatmul(h, p["wkv_a"])
    c_kv = scaled(_norm(kv[..., : d.kv_rank], p["kv_norm_scale"], None, "rmsnorm", cfg.norm_eps), d.kv_rescale)
    shared = kv[..., d.kv_rank :] if cfg.position != "rope" else _rope(kv[..., None, d.kv_rank :], positions, d.theta)[..., 0, :]
    return jnp.concatenate([c_kv, shared], axis=-1)


def latent_absorb(cfg: HybridMoEConfig, p, q_nope, q_rope, kind: str = "latent"):
    """The query against the stored latent: ``[q_nope Wk_b,h^T ; q_rope]``
    [..., NH, kv_lora_rank + rope], so that its product with ``[c_kv ; k_rope]``
    is the head's score."""
    d = cfg.latent_dims(kind)
    wk_b = p["wk_b"].reshape(d.kv_rank, d.heads, d.nope)
    return jnp.concatenate([jnp.einsum("...hd,chd->...hc", q_nope, wk_b.astype(q_nope.dtype)), q_rope], axis=-1)


def latent_output(cfg: HybridMoEConfig, p, o, kind: str = "latent", h=None):
    """``o`` [..., NH, kv_lora_rank], a head's weights over the latents: its
    values' sum ``o Wv_b,h``, the head gate of a layer that has one (from the
    normed ``h`` [..., H] the queries came from), then the output projection.
    [..., H]."""
    d = cfg.latent_dims(kind)
    wv_b = p["wv_b"].reshape(d.kv_rank, d.heads, d.v)
    attn = jnp.einsum("...hc,chv->...hv", o, wv_b.astype(o.dtype)).reshape(o.shape[:-2] + (d.heads * d.v,))
    return qmatmul(output_gate(p, h, attn) if "wg_head" in p else attn, p["wo"])


def index_queries(cfg: HybridMoEConfig, p, c_q, h, positions):
    """A sparse layer's indexer, the query's side: ``qI = c_q WI_qb`` [B, T,
    IH, ID] from the query's low rank, its leading ``qk_rope_head_dim``
    features rotated at ``positions`` [B, T] with the layer's theta, and the
    weight a head ``w = (h WI_w) IH^-0.5 ID^-0.5`` [B, T, IH] float32."""
    from deepspeed_tpu.models.transformer import _rope

    IH, ID = cfg.index_num_heads, cfg.index_head_dim
    q = _rope(qmatmul(c_q, p["wi_qb"]).reshape(c_q.shape[:-1] + (IH, ID)), positions, float(cfg.rope_theta), cfg.qk_rope_head_dim)
    return q, qmatmul(h, p["wi_w"]).astype(jnp.float32) * float(IH ** -0.5 * ID ** -0.5)


def index_key(cfg: HybridMoEConfig, p, h, positions):
    """The key's side, what a sparse layer keeps of a token beside its latent:
    ``kI = LayerNorm(h WI_k)`` [B, T, ID] (scale and bias, eps ``norm_eps``),
    its leading ``qk_rope_head_dim`` features rotated."""
    from deepspeed_tpu.models.transformer import _rope

    k = _norm(qmatmul(h, p["wi_k"]), p["wi_k_norm_scale"], p["wi_k_norm_bias"], "layernorm", cfg.norm_eps)
    return _rope(k[..., None, :], positions, float(cfg.rope_theta), cfg.qk_rope_head_dim)[..., 0, :]


def index_scores(q, w, k):
    """``I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])`` in float32: ``q``
    [..., T, IH, ID], ``w`` [..., T, IH] float32, ``k`` [..., S, ID] -> [..., T, S]."""
    dots = jnp.einsum("...tjd,...sd->...tjs", q, k.astype(q.dtype), preferred_element_type=jnp.float32)
    # the heads' weighted sum as a float32 product and sum, not a matmul: a device's default matmul rounds float32
    # operands to bfloat16, and a selection turns on the order of nearly equal scores
    return jnp.sum(jax.nn.relu(dots) * w[..., None], axis=-2)


def chosen_keys(scores, seen, k: int):
    """The selection as a mask: of each query's keys that it may see (``seen``
    [..., T, S] bool) the ``k`` of largest ``scores`` [..., T, S] float32, ties
    to the lower position; all of them where there are ``k`` at most. EXACT,
    and without a sort: a float32's bits, the sign bit flipped (a negative's
    other bits too), order as unsigned integers as the floats do, so the
    ``k``-th largest is built bit by bit from the top, 32 counts of the scores
    at or above a candidate (a pass over the scores each; a sort of 512 x
    16,384 scores is ~100 such passes), and a tie at it is resolved by
    counting positions."""
    S = scores.shape[-1]
    if S <= k:
        return seen
    scores = jnp.where(seen & (scores != 0), scores, jnp.where(seen, 0.0, -jnp.inf))  # no negative zero: its bits order below zero's
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def bit(i, least):
        candidate = least | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(jnp.sum(keys >= candidate, axis=-1, keepdims=True) >= k, candidate, least)

    least = jax.lax.fori_loop(0, 32, bit, jnp.zeros(scores.shape[:-1] + (1,), jnp.uint32))  # the k-th largest
    above, tied = keys > least, keys == least
    room = k - jnp.sum(above, axis=-1, keepdims=True)  # places left for the keys tied at it, lowest positions first
    return seen & (above | (tied & (jnp.cumsum(tied, axis=-1) <= room)))


def moe_ffn(cfg: HybridMoEConfig, p, h, live=None, experts=None, group_offset=0):
    """The routed FFN of one layer for a normed slab ``h`` [B, T, H]: the
    router over its whole width in float32, the held experts' part
    (``moe/routed_ffn.py``, ``held``), the shared expert once. ``live``,
    ``experts`` and ``group_offset`` as ``inference/decode.py::_moe_ffn``.
    Returns (out [B, T, H], the held experts' assignment counts [E])."""
    from deepspeed_tpu.moe.experts import apply_dense_ffn
    from deepspeed_tpu.moe.routed_ffn import routed_ffn

    B, T, H = h.shape
    tokens = h.reshape(-1, H)
    logits = tokens.astype(jnp.float32) @ p["gate"]["wg"].astype(jnp.float32)
    out, counts, _ = routed_ffn(
        p["experts"] if experts is None else experts, tokens, logits, k=cfg.moe_top_k, activation=cfg.activation,
        norm_topk_prob=cfg.moe_norm_topk_prob, live=None if live is None else live.reshape(-1),
        group_offset=group_offset, scoring=cfg.moe_scoring, select_bias=p["gate"].get("bias"),
        held=cfg.held_experts,
    )
    if cfg.moe_routed_scaling != 1.0:
        out = out * jnp.asarray(cfg.moe_routed_scaling, out.dtype)
    if "shared" in p:
        with jax.named_scope("moe_shared"):
            out = out + apply_dense_ffn(p["shared"], tokens, cfg.activation)
    return out.reshape(B, T, H), counts


class HybridMoETransformerLM(MoETransformerLM):
    """``init`` and a cache-free ``apply`` (logits of a whole sequence; with
    labels, the loss). Serving goes through ``init_inference`` and the paged
    server (``inference/hybrid_decode.py``)."""

    def stream_fns(self):
        raise NotImplementedError("offload_param layer streaming does not support hybrid (multi-kind) layer stacks")

    def tp_partition_rules(self, params_shapes=None):
        if params_shapes is None:
            return None
        raise NotImplementedError("hybrid layer stacks have no tensor- or expert-parallel partition rules yet")

    def keep_fp32_params(self, params_shapes=None):
        return None

    def init(self, rng, batch) -> Dict[str, Any]:
        cfg = self.config
        H, L, V = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
        NH, D, Dv = cfg.num_heads, cfg.head_dim, cfg.v_head_dim
        LH, LD, r, K = cfg.linear_num_heads, cfg.linear_head_dim, cfg.linear_gate_rank, cfg.linear_conv_kernel
        E, ER, I = cfg.num_experts, cfg.moe_router_experts, cfg.expert_intermediate_size
        NP, period = cfg.num_periods, cfg.period
        n = cfg.ffns_per_period
        gated = cfg.activation == "swiglu"  # three matrices an FFN; a pointwise activation has two, ``w_in`` and ``w_out``
        keys = iter(jax.random.split(rng, 40 + 24 * (cfg.leading_dense_layers + len(cfg.remainder))))
        std, out_std = 0.02, 0.02 / np.sqrt(2 * L)

        def dense(shape, s=std):
            return jax.random.normal(next(keys), shape, jnp.float32) * s

        def mixer(kind, *lead):
            """The leaves of one kind of mixer, each behind the axes ``lead``."""
            if kind == "linear":
                C = LH * LD
                # the decay's initial rate and bias as the family's modelling code draws
                # them: a rate of 1..16 a head, a step of 1e-3..1e-1 a channel
                dt = jnp.exp(jax.random.uniform(next(keys), lead + (C,), minval=np.log(1e-3), maxval=np.log(1e-1)))
                return {
                    "attn_norm_scale": jnp.ones(lead + (H,)),
                    "wq": dense(lead + (H, C)),
                    "wk": dense(lead + (H, C)),
                    "wv": dense(lead + (H, C)),
                    "conv_q": dense(lead + (K, C), 0.5),
                    "conv_k": dense(lead + (K, C), 0.5),
                    "conv_v": dense(lead + (K, C), 0.5),
                    "wf_down": dense(lead + (H, r)),
                    "wf_up": dense(lead + (r, C)),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                    "A_log": jnp.log(jax.random.uniform(next(keys), lead + (LH,), minval=1.0, maxval=16.0)),
                    "wb": dense(lead + (H, LH)),
                    "wg_down": dense(lead + (H, r)),
                    "wg_up": dense(lead + (r, C)),
                    "o_norm_scale": jnp.ones(lead + (LD,)),
                    "wo": dense(lead + (C, H), out_std),
                }
            if kind == "ssm":
                SH, inner, SC, SK = cfg.ssm_num_heads, cfg.ssm_inner, cfg.ssm_conv_channels, cfg.ssm_conv_kernel
                # the family's modelling code: a rate of 1..16 a head, a step (after softplus) of 1e-3..1e-1, D ones
                dt = jnp.exp(jax.random.uniform(next(keys), lead + (SH,), minval=np.log(1e-3), maxval=np.log(1e-1)))
                return {
                    "attn_norm_scale": jnp.ones(lead + (H,)),
                    # the published in_proj [H, z ; x B C ; dt], its three parts apart (a projection reads its matrix
                    # where it lies: of one matrix of 8,512 columns, no whole lane tiles, each layer's slice is written out)
                    "w_z": dense(lead + (H, inner)),
                    "w_xbc": dense(lead + (H, SC)),
                    "w_dt": dense(lead + (H, SH)),
                    "conv_w": dense(lead + (SK, SC), 0.5),
                    "conv_b": dense(lead + (SC,)),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                    "A_log": jnp.log(jax.random.uniform(next(keys), lead + (SH,), minval=1.0, maxval=16.0)),
                    "D": jnp.ones(lead + (SH,)),
                    "o_norm_scale": jnp.ones(lead + (inner,)),
                    "wo": dense(lead + (inner, H), out_std),
                }
            if kind == "conv":
                return {
                    "attn_norm_scale": jnp.ones(lead + (H,)),
                    # the published in_proj [H, B ; C ; x~] WHOLE: 3 H columns are whole lane tiles wherever H is, so the
                    # three parts are slices of one product at tile boundaries, and a decode row's mixer is one read of
                    # one matrix and one launch (granite's in_proj is in parts because ITS parts are no whole tiles)
                    "w_in": dense(lead + (H, 3 * H)),
                    "conv_w": dense(lead + (cfg.conv_kernel, H), 0.5),
                    "wo": dense(lead + (H, H), out_std),
                }
            if kind in LATENT_KINDS:
                d = cfg.latent_dims(kind)
                if d.q_rank:
                    query = {"wq_a": dense(lead + (H, d.q_rank)), "q_norm_scale": jnp.ones(lead + (d.q_rank,)),
                             "wq_b": dense(lead + (d.q_rank, d.heads * (d.nope + d.rope)))}
                else:  # no low rank: one matrix, no query norm
                    query = {"wq": dense(lead + (H, d.heads * (d.nope + d.rope)))}
                latent = {
                    "attn_norm_scale": jnp.ones(lead + (H,)),
                    **query,
                    "wkv_a": dense(lead + (H, d.width)),
                    "kv_norm_scale": jnp.ones(lead + (d.kv_rank,)),
                    "wk_b": dense(lead + (d.kv_rank, d.heads * d.nope)),
                    "wv_b": dense(lead + (d.kv_rank, d.heads * d.v)),
                    "wo": dense(lead + (d.heads * d.v, H), out_std),
                }
                if cfg.attn_head_gate:
                    latent["wg_head"] = dense(lead + (H, d.heads))
                if kind == "sparse_latent":  # the indexer: queries from the query's low rank, one key and a weight a head from h
                    IH, ID = cfg.index_num_heads, cfg.index_head_dim
                    latent.update(wi_qb=dense(lead + (d.q_rank, IH * ID)), wi_k=dense(lead + (H, ID)),
                                  wi_k_norm_scale=jnp.ones(lead + (ID,)), wi_k_norm_bias=dense(lead + (ID,)),
                                  wi_w=dense(lead + (H, IH)))
                return latent
            NQ, NKV = cfg.heads_of(kind), cfg.kv_heads_of(kind)
            attn = {
                "attn_norm_scale": jnp.ones(lead + (H,)),
                "wq": dense(lead + (H, NQ * D)),
                "wk": dense(lead + (H, NKV * D)),
                "wv": dense(lead + (H, NKV * Dv)),
                "wo": dense(lead + (NQ * Dv, H), out_std),
            }
            if cfg.qk_norm == "head":
                attn.update(q_norm_scale=jnp.ones(lead + (D,)), k_norm_scale=jnp.ones(lead + (D,)))
            if cfg.attn_output_gate:
                attn["wg"] = dense(lead + (H, NQ * Dv))
            if cfg.attn_head_gate:
                attn["wg_head"] = dense(lead + (H, NQ))
            if kind == "window" and cfg.window_sinks:
                attn["sinks"] = dense(lead + (NQ,))
            return attn

        periods: Dict[str, Any] = {kind: mixer(kind, NP, period.count(kind)) for kind in LAYER_KINDS if kind in period}
        Id = cfg.intermediate_size

        def ffn(width, *lead, routed=False):
            """One FFN's matrices behind the axes ``lead``: the input side at
            ``lead + (H, width)``; a ROUTED expert of two matrices keeps its
            input matrix by its output rows, ``w_in_t`` ``lead + (width, H)``
            (the published ``up_proj``'s own layout): a width of no whole
            lane tiles (1,856) on the minor axis of a stack is an array the
            device keeps H-minor, and the grouped matmul would be handed a
            transposed copy of all of it, every call
            (``grouped_matmul``'s ``transposed``)."""
            if gated:
                into = {"w_gate": dense(lead + (H, width)), "w_up": dense(lead + (H, width))}
            else:
                into = {"w_in_t": dense(lead + (width, H))} if routed else {"w_in": dense(lead + (H, width))}
            return {**into, "w_out": dense(lead + (width, H), out_std)}

        dense_ffn = lambda *lead: {"mlp_norm_scale": jnp.ones(lead + (H,)), **ffn(Id, *lead)}

        def routed_ffn(*lead):
            moe = {
                "mlp_norm_scale": jnp.ones(lead + (H,)),
                "gate": {"wg": dense(lead + (H, ER))},
                "experts": ffn(I, *lead, E, routed=True),
            }
            if cfg.moe_select_bias:
                moe["gate"]["bias"] = dense(lead + (ER,))
            if cfg.moe_shared_experts:
                moe["shared"] = ffn(I * cfg.moe_shared_experts, *lead)
            return moe

        # a layer's FFN under the key the period's stacks hold it by: routed, or (no expert anywhere) a dense one
        feed_forward = (lambda *lead: {"moe": routed_ffn(*lead)}) if E else (lambda *lead: {"ffn": dense_ffn(*lead)})
        periods.update(feed_forward(NP, n))
        params = {"embed": {"tokens": dense((V, H))}, "periods": periods, "final_norm_scale": jnp.ones((H,))}
        if cfg.leading_dense_layers:
            params["leading"] = [{"mixer": mixer(kind), "ffn": dense_ffn()} for kind in cfg.layer_types[: cfg.leading_dense_layers]]
        if cfg.remainder:  # the layers behind the last whole period, as the leading ones: leaves of their own
            params["trailing"] = [{"mixer": mixer(kind), **feed_forward()} for kind in cfg.remainder]
        if not cfg.tie_embeddings:
            params["lm_head"] = dense((H, V))
        return params

    # --- the cache-free forward ------------------------------------------
    def _attention_mixer(self, kind, p, h):
        cfg = self.config
        B, T, _ = h.shape
        NH, NKV, D, Dv = cfg.heads_of(kind), cfg.kv_heads_of(kind), cfg.head_dim, cfg.v_head_dim
        pos = jnp.arange(T, dtype=jnp.int32)
        q, k, v = attn_heads(cfg, kind, *attn_project(p, h), jnp.broadcast_to(pos, (B, T)), p)
        q = q.reshape(B, T, NKV, NH // NKV, D)
        scale = cfg.attn_softmax_scale if cfg.attn_softmax_scale is not None else D ** -0.5
        scores = jnp.einsum("btkgd,bskd->bkgts", q, k).astype(jnp.float32) * scale
        seen = pos[:, None] >= pos[None, :]
        if kind == "window":
            seen &= pos[:, None] - pos[None, :] < cfg.window
        scores = jnp.where(seen, scores, -1e30)
        if "sinks" in p:  # one more column a head, dropped after the softmax
            sink = jnp.broadcast_to(p["sinks"].astype(jnp.float32).reshape(1, NKV, NH // NKV, 1, 1), scores.shape[:-1] + (1,))
            probs = jax.nn.softmax(jnp.concatenate([scores, sink], axis=-1), axis=-1)[..., :-1]
        else:
            probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bkgts,bskd->btkgd", probs.astype(v.dtype), v)
        return qmatmul(output_gate(p, h, attn.reshape(B, T, NH * Dv)), p["wo"])

    def _latent_mixer(self, p, h, kind="latent"):
        """The published (expanded) form: every head's keys and values made from
        the latents; a ``window_latent`` layer's query sees the newest
        ``window`` keys, a ``sparse_latent`` layer's the ``index_topk`` its
        indexer scores highest (``chosen_keys``)."""
        cfg = self.config
        B, T, _ = h.shape
        d = cfg.latent_dims(kind)
        NH, C = d.heads, d.kv_rank
        pos = jnp.arange(T, dtype=jnp.int32)
        positions = jnp.broadcast_to(pos, (B, T))
        # the plain kind through the four-argument call it always was: tests and the logits tools stand in for it by that signature
        q_nope, q_rope, latent = latent_project(cfg, p, h, positions, *(() if kind == "latent" else (kind,)))
        c_kv, k_rope = latent[..., :C], latent[..., C:]
        k_nope = qmatmul(c_kv, p["wk_b"]).reshape(B, T, NH, d.nope)
        v = qmatmul(c_kv, p["wv_b"]).reshape(B, T, NH, d.v)
        scale = cfg.attn_softmax_scale if cfg.attn_softmax_scale is not None else d.scale
        scores = (jnp.einsum("bthd,bshd->bhts", q_nope, k_nope) + jnp.einsum("bthd,bsd->bhts", q_rope, k_rope)).astype(jnp.float32)
        seen = pos[:, None] >= pos[None, :]
        if kind == "window_latent":
            seen &= pos[:, None] - pos[None, :] < cfg.window
        if kind == "sparse_latent":
            qi, w = index_queries(cfg, p, latent_query_rank(cfg, p, h, kind), h, positions)
            seen = chosen_keys(index_scores(qi, w, index_key(cfg, p, h, positions)), jnp.broadcast_to(seen, (B, T, T)), cfg.index_topk)[:, None]
        scores = jnp.where(seen, scores * scale, -1e30)
        attn = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1).astype(v.dtype), v).reshape(B, T, NH * d.v)
        return qmatmul(output_gate(p, h, attn) if "wg_head" in p else attn, p["wo"])

    def _linear_mixer(self, p, h):
        from deepspeed_tpu.ops.transformer.linear_attention import kda_chunked

        cfg = self.config
        B, T, _ = h.shape
        NH, D, K = cfg.linear_num_heads, cfg.linear_head_dim, cfg.linear_conv_kernel
        qkv, log_a, beta = linear_inputs(cfg, p, h)
        q, k, v = linear_qkv(cfg, short_conv(p, jnp.zeros((B, K - 1, qkv.shape[-1]), qkv.dtype), qkv))
        o, _ = kda_chunked(q, k, v, log_a.reshape(B, T, NH, D), beta, jnp.zeros((B, NH, D, D), jnp.float32))
        return linear_output(cfg, p, h, o)

    def _ssm_mixer(self, p, h):
        from deepspeed_tpu.ops.transformer.state_space import ssd_chunked

        cfg = self.config
        B, T, _ = h.shape
        z, xbc, dt = ssm_inputs(cfg, p, h)
        x, Bm, Cm = ssm_split(cfg, ssm_conv(p, jnp.zeros((B, cfg.ssm_conv_kernel - 1, xbc.shape[-1]), xbc.dtype), xbc))
        state = jnp.zeros((B, cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32)
        y, _ = ssd_chunked(x, Bm, Cm, dt, -jnp.exp(p["A_log"].astype(jnp.float32)), p["D"].astype(jnp.float32), state)
        return ssm_output(cfg, p, z, y.reshape(B, T, cfg.ssm_inner))

    def _conv_mixer(self, p, h):
        u, c = conv_inputs(p, h)
        return conv_output(p, c, gated_conv(p, jnp.zeros((h.shape[0], self.config.conv_kernel - 1, u.shape[-1]), u.dtype), u))

    def apply(self, params, batch, *, rngs=None, train: bool = False, pld_theta=None, ltd_idx=None):
        from deepspeed_tpu.models.transformer import _split_batch, cross_entropy_loss
        from deepspeed_tpu.moe.experts import apply_dense_ffn

        if train:
            raise NotImplementedError("training a hybrid (linear-attention or state-space) model is not supported: apply is the eval forward")
        cfg = self.config
        tokens, labels = _split_batch(batch)
        x = scaled(params["embed"]["tokens"].astype(self.dtype)[tokens], cfg.embedding_multiplier)
        branch = functools.partial(scaled, by=cfg.residual_multiplier)

        def mix(x, kind, mixer):
            h = _norm(x, mixer["attn_norm_scale"], None, "rmsnorm", cfg.norm_eps)
            with jax.named_scope(SCOPES[kind]):
                if kind == "linear":
                    out = self._linear_mixer(mixer, h)
                elif kind in LATENT_KINDS:
                    out = self._latent_mixer(mixer, h, kind)
                elif kind == "ssm":
                    out = self._ssm_mixer(mixer, h)
                elif kind == "conv":
                    out = self._conv_mixer(mixer, h)
                else:
                    out = self._attention_mixer(kind, mixer, h)
            return x + branch(out.astype(x.dtype))

        def dense(x, p):
            with jax.named_scope("mlp"):
                h = _norm(x, p["mlp_norm_scale"], None, "rmsnorm", cfg.norm_eps)
                return x + branch(apply_dense_ffn(p, h, cfg.activation).astype(x.dtype))

        for kind, p in zip(cfg.layer_types, params.get("leading", ())):
            x = dense(mix(x, kind, p["mixer"]), p["ffn"])

        def feed_forward(x, p, j=None):
            """The period's ``j``-th FFN (None: ``p`` holds ONE layer's, a trailing layer's own): dense out of
            ``p["ffn"]`` (``num_experts`` 0), else routed."""
            own = (lambda tree: tree) if j is None else functools.partial(jax.tree_util.tree_map, lambda a: a[j])
            if "ffn" in p:
                return dense(x, own(p["ffn"]))
            moe = own(p["moe"])
            with jax.named_scope("mlp"):
                out, _ = moe_ffn(cfg, moe, _norm(x, moe["mlp_norm_scale"], None, "rmsnorm", cfg.norm_eps))
            return x + branch(out.astype(x.dtype))

        def period_step(x, p):
            at = {kind: 0 for kind in LAYER_KINDS + (FFN_BLOCK,)}
            for j, kind in enumerate(cfg.period):
                if kind == FFN_BLOCK:  # a block that is the FFN alone
                    x = feed_forward(x, p, at[kind])
                else:
                    x = mix(x, kind, jax.tree_util.tree_map(lambda a: a[at[kind]], p[kind]))
                    if not cfg.single_sublayer:  # a layer is a mixer AND an FFN
                        x = feed_forward(x, p, j)
                at[kind] += 1
            return x, None

        x, _ = jax.lax.scan(period_step, x, params["periods"])
        for kind, p in zip(cfg.remainder, params.get("trailing", ())):
            x = feed_forward(mix(x, kind, p["mixer"]), p)
        x = _norm(x, params["final_norm_scale"], None, "rmsnorm", cfg.norm_eps)
        head = params["embed"]["tokens"].T if cfg.tie_embeddings else params["lm_head"]
        logits = scaled(qmatmul(x, head.astype(x.dtype)), 1.0 / cfg.logits_scaling)
        return logits if labels is None else cross_entropy_loss(logits, labels)


def solar_open2_config(size: str = "250b", **overrides) -> HybridMoEConfig:
    """Solar-Open2-250B (``upstage/Solar-Open2-250B`` ``config.json``,
    ``model_type: solar_open2``): 48 layers, every fourth (0, 4, 8, ...) gated
    NoPE GQA of 64 query heads over 8 KV heads of 128, the rest gated
    delta-rule linear attention of 64 heads of 128 with a 4-tap short
    convolution and ``allow_neg_eigval``; every layer 320 SwiGLU experts of
    1,280, 8 a token by sigmoid scores with a selection bias, gates
    normalised, one shared expert. ``250b`` is the published model whole;
    ``tiny`` a toy of one chip's share (2 of 4 experts held... of 8 at the
    router) for tests."""
    presets = {
        "tiny": dict(hidden_size=64, num_layers=4, num_heads=4, num_kv_heads=2, head_dim=16, vocab_size=512,
                     max_seq_len=256, intermediate_size=32, linear_num_heads=8, linear_head_dim=16, linear_gate_rank=8,
                     num_experts=4, moe_router_experts=8, moe_expert_share=(0, 2), moe_top_k=3),
        "250b": dict(hidden_size=4096, num_layers=48, num_heads=64, num_kv_heads=8, head_dim=128, vocab_size=196608,
                     max_seq_len=131072, intermediate_size=1280, linear_num_heads=64, linear_head_dim=128,
                     linear_gate_rank=128, num_experts=320, moe_top_k=8),
    }
    base = dict(
        norm="rmsnorm", norm_eps=1e-5, position="none", activation="swiglu", use_bias=False, tie_embeddings=False,
        attn_output_gate=True, linear_conv_kernel=4, linear_allow_neg_eigval=True,
        moe_layer_freq=1, moe_drop_tokens=False, moe_norm_topk_prob=True, moe_scoring="sigmoid",
        moe_select_bias=True, moe_shared_experts=1, moe_routed_scaling=1.0,
    )
    base.update(presets[size])
    base.update(overrides)
    if "layer_types" not in base:
        base["layer_types"] = ["softmax" if i % 4 == 0 else "linear" for i in range(base["num_layers"])]
    return HybridMoEConfig(**base)


def mimo_v2_config(size: str = "v2.5", **overrides) -> HybridMoEConfig:
    """MiMo-V2.5's language model (``XiaomiMiMo/MiMo-V2.5`` ``config.json``,
    ``model_type: mimo_v2``): 48 layers, layers 0, 5, 11, 17, ... full causal
    GQA of 64 query heads over 4 KV heads, the others over a sliding window of
    128 with 8 KV heads and a learned sink a head; keys of 192 (the leading 64
    rotated, theta 1e7 full / 1e4 window), values of 128 scaled by 0.707;
    layer 0 a dense SwiGLU FFN of 16,384, layers 1-47 256 SwiGLU experts of
    2,048, 8 a token by sigmoid scores with a selection bias, gates
    normalised, no shared expert. The vision and audio towers and the three
    multi-token-prediction layers are not part of this model. ``v2.5`` is the
    published model whole; ``tiny`` a toy of one chip's share (4 of 16 experts
    held) with one leading dense layer and one period for tests."""
    presets = {
        "tiny": dict(hidden_size=64, num_layers=7, num_heads=8, num_kv_heads=2, window_num_kv_heads=4, head_dim=24,
                     v_head_dim=16, rope_dim=8, window=8, vocab_size=512, max_seq_len=256, intermediate_size=96,
                     expert_intermediate_size=32, num_experts=4, moe_router_experts=16, moe_expert_share=(0, 4),
                     moe_top_k=3, layer_types=["softmax"] + ["window"] * 5 + ["softmax"]),
        "v2.5": dict(hidden_size=4096, num_layers=48, num_heads=64, num_kv_heads=4, window_num_kv_heads=8, head_dim=192,
                     v_head_dim=128, rope_dim=64, window=128, vocab_size=152576, max_seq_len=1048576,
                     intermediate_size=16384, expert_intermediate_size=2048, num_experts=256, moe_top_k=8),
    }
    base = dict(
        norm="rmsnorm", norm_eps=1e-5, position="rope", rope_theta=1e7, window_rope_theta=1e4, activation="swiglu",
        use_bias=False, tie_embeddings=False, attn_value_scale=0.707, window_sinks=True, leading_dense_layers=1,
        moe_layer_freq=1, moe_drop_tokens=False, moe_norm_topk_prob=True, moe_scoring="sigmoid",
        moe_select_bias=True, moe_shared_experts=0, moe_routed_scaling=1.0,
    )
    base.update(presets[size])
    base.update(overrides)
    if "layer_types" not in base:
        # hybrid_layer_pattern: full at 0, 5 and then every sixth
        base["layer_types"] = ["softmax" if i == 0 or i % 6 == 5 else "window" for i in range(base["num_layers"])]
    return HybridMoEConfig(**base)


def glm4_moe_lite_config(size: str = "4.7-flash", **overrides) -> HybridMoEConfig:
    """GLM-4.7-Flash (``zai-org/GLM-4.7-Flash`` ``config.json``, ``model_type:
    glm4_moe_lite``): 47 layers of latent attention, 20 heads with a query and
    key of 192 unrotated + 64 rotated features (theta 1e6) and a value of 256
    over a latent of 512 (queries through a low rank of 768); layer 0 a dense
    SwiGLU FFN of 10,240, layers 1-46 64 SwiGLU experts of 1,536, 4 a token by
    sigmoid scores with a selection bias, gates normalised and times 1.8, one
    shared expert. The multi-token-prediction layer is not part of this model.
    ``4.7-flash`` is the published model whole; ``tiny`` a toy of one chip's
    share (4 of 16 experts held) with one leading dense layer for tests."""
    presets = {
        "tiny": dict(hidden_size=64, num_layers=4, num_heads=4, head_dim=24, v_head_dim=16, q_lora_rank=48,
                     kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, vocab_size=512, max_seq_len=256,
                     intermediate_size=96, expert_intermediate_size=32, num_experts=4, moe_router_experts=16,
                     moe_expert_share=(0, 4), moe_top_k=3),
        "4.7-flash": dict(hidden_size=2048, num_layers=47, num_heads=20, head_dim=256, v_head_dim=256, q_lora_rank=768,
                          kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64, vocab_size=154880,
                          max_seq_len=202752, intermediate_size=10240, expert_intermediate_size=1536, num_experts=64,
                          moe_top_k=4),
    }
    base = dict(
        norm="rmsnorm", norm_eps=1e-5, position="rope", rope_theta=1e6, activation="swiglu", use_bias=False,
        tie_embeddings=False, leading_dense_layers=1, moe_layer_freq=1, moe_drop_tokens=False, moe_norm_topk_prob=True,
        moe_scoring="sigmoid", moe_select_bias=True, moe_shared_experts=1, moe_routed_scaling=1.8,
    )
    base.update(presets[size])
    base.update(overrides)
    base.setdefault("layer_types", ["latent"] * base["num_layers"])
    return HybridMoEConfig(**base)


def laguna_config(size: str = "s-2.1", **overrides) -> HybridMoEConfig:
    """Laguna-S-2.1 (``poolside/Laguna-S-2.1`` ``config.json``, ``model_type:
    laguna``): 48 layers, layers 0, 4, 8, ... full causal GQA of 48 query heads,
    the others 72 query heads over a sliding window of 512, both over 8 KV
    heads of 128; a full layer rotates the leading 64 features with YaRN
    frequencies (theta 5e5, factor 128 over 8,192 positions, cos and sin times
    1.4852), a window layer all 128 with plain ones (theta 1e4); one sigmoid
    gate a head on every layer's attention output; layer 0 a dense SwiGLU FFN
    of 12,288, layers 1-47 256 SwiGLU experts of 1,024, 10 a token by softmax
    scores, gates normalised and times 2.5, one shared expert. ``s-2.1`` is the
    published model whole; ``tiny`` a toy of one chip's share (4 of 16 experts
    held) with one leading dense layer and two periods for tests: groups of 6
    and 9 query heads a KV head as published, and a YaRN ramp that its short
    contexts reach."""
    presets = {
        "tiny": dict(hidden_size=64, num_layers=9, num_heads=12, window_num_heads=18, num_kv_heads=2, head_dim=16,
                     rope_dim=8, window_rope_dim=16, rope_theta=100.0, rope_yarn_factor=8.0, rope_yarn_original_positions=32,
                     rope_yarn_beta_fast=4.0, rope_yarn_beta_slow=0.5, window=8, vocab_size=512, max_seq_len=256,
                     intermediate_size=96, expert_intermediate_size=32, num_experts=4, moe_router_experts=16,
                     moe_expert_share=(0, 4), moe_top_k=3),
        "s-2.1": dict(hidden_size=3072, num_layers=48, num_heads=48, window_num_heads=72, num_kv_heads=8, head_dim=128,
                      rope_dim=64, window_rope_dim=128, rope_theta=5e5, rope_yarn_factor=128.0,
                      rope_yarn_original_positions=8192, rope_yarn_beta_fast=32.0, rope_yarn_beta_slow=1.0,
                      rope_yarn_attention_factor=1.4852030263919618, window=512, vocab_size=100352, max_seq_len=1048576,
                      intermediate_size=12288, expert_intermediate_size=1024, num_experts=256, moe_top_k=10),
    }
    base = dict(
        norm="rmsnorm", norm_eps=1e-6, position="rope", window_rope_theta=1e4, activation="swiglu", use_bias=False,
        tie_embeddings=False, attn_head_gate=True, leading_dense_layers=1, moe_layer_freq=1, moe_drop_tokens=False,
        moe_norm_topk_prob=True, moe_scoring="softmax", moe_select_bias=False, moe_shared_experts=1, moe_routed_scaling=2.5,
    )
    base.update(presets[size])
    base.update(overrides)
    if "layer_types" not in base:
        # layer_types: full_attention at 0 and then every fourth
        base["layer_types"] = ["softmax" if i % 4 == 0 else "window" for i in range(base["num_layers"])]
    return HybridMoEConfig(**base)


def kimi_linear_config(size: str = "48b-a3b", **overrides) -> HybridMoEConfig:
    """Kimi-Linear-48B-A3B (``moonshotai/Kimi-Linear-48B-A3B-Instruct``
    ``config.json``, ``model_type: kimi_linear``): 27 layers, published layers
    4, 8, ..., 24 and 27 (counted from 1) latent attention of 32 heads with a
    query and key of 128 + 64 features, NONE rotated (``mla_use_nope``), a value
    of 128 over a latent of 512 and a query with no low rank (``q_lora_rank``
    null); the other 20 gated delta-rule linear attention of 32 heads of 128
    with a 4-tap short convolution, ``b = sigmoid`` (no ``allow_neg_eigval``);
    layer 1 a dense SwiGLU FFN of 9,216 under a LINEAR mixer, layers 2-27 256
    SwiGLU experts of 1,024, 8 a token by sigmoid scores with a selection bias,
    gates normalised and times 2.446, one shared expert. ``48b-a3b`` is the
    published model whole, which no whole number of periods makes (26 layers
    behind the leading one, the last two a part of a period): ``period`` is
    then the 26 layers themselves, one trip of the scan; a benchmark
    configuration holds 1 + 4 n layers. ``tiny`` a toy of one chip's share (4
    of 16 experts held) with the leading layer and two periods for tests."""
    presets = {
        "tiny": dict(hidden_size=64, num_layers=9, num_heads=4, num_kv_heads=4, head_dim=24, v_head_dim=16,
                     kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, linear_num_heads=4, linear_head_dim=16,
                     linear_gate_rank=8, vocab_size=512, max_seq_len=256, intermediate_size=96,
                     expert_intermediate_size=32, num_experts=4, moe_router_experts=16, moe_expert_share=(0, 4), moe_top_k=3),
        "48b-a3b": dict(hidden_size=2304, num_layers=27, num_heads=32, num_kv_heads=32, head_dim=192, v_head_dim=128,
                        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, linear_num_heads=32,
                        linear_head_dim=128, linear_gate_rank=128, vocab_size=163840, max_seq_len=1048576,
                        intermediate_size=9216, expert_intermediate_size=1024, num_experts=256, moe_top_k=8),
    }
    base = dict(
        norm="rmsnorm", norm_eps=1e-5, position="none", activation="swiglu", use_bias=False, tie_embeddings=False,
        q_lora_rank=0, linear_conv_kernel=4, linear_allow_neg_eigval=False, leading_dense_layers=1, moe_layer_freq=1,
        moe_drop_tokens=False, moe_norm_topk_prob=True, moe_scoring="sigmoid", moe_select_bias=True,
        moe_shared_experts=1, moe_routed_scaling=2.446,
    )
    base.update(presets[size])
    base.update(overrides)
    if "layer_types" not in base:
        full_attn_layers = (4, 8, 12, 16, 20, 24, 27)  # counted from 1, as published; the others are kda_layers
        base["layer_types"] = ["latent" if i + 1 in full_attn_layers else "linear" for i in range(base["num_layers"])]
    return HybridMoEConfig(**base)


def granite_hybrid_config(size: str = "4.0-h-micro", **overrides) -> HybridMoEConfig:
    """granite-4.0-h-micro (``ibm-granite/granite-4.0-h-micro`` ``config.json``,
    ``model_type: granitemoehybrid``): 40 layers, layers 5, 15, 25 and 35 causal
    GQA of 32 query heads over 8 KV heads of 64 with NO positional term and a
    softmax scale of 1/64 (``attention_multiplier``), the other 36 Mamba-2
    mixers of 64 heads of 64 over a state of 128 (one group, a 4-tap
    convolution with a bias); a dense SwiGLU FFN of 8,192 in EVERY layer
    (``num_local_experts`` 0: ``num_experts`` 0 here); the embedding times 12,
    both branches of every layer times 0.22, the logits divided by 8, the
    embedding tied to the head. ``4.0-h-micro`` is the published model whole;
    ``tiny`` a toy of two periods ``[ssm, ssm, softmax]`` for tests, its
    state-space layer at the kernel's own tiles (heads of 64, a state of 128)."""
    presets = {
        "tiny": dict(hidden_size=64, num_layers=6, num_heads=4, num_kv_heads=2, head_dim=16, vocab_size=512, max_seq_len=256,
                     intermediate_size=96, ssm_num_heads=2, ssm_head_dim=64, ssm_state=128, attn_softmax_scale=0.0625,
                     layer_types=["ssm", "ssm", "softmax"] * 2),
        "4.0-h-micro": dict(hidden_size=2048, num_layers=40, num_heads=32, num_kv_heads=8, head_dim=64, vocab_size=100352,
                            max_seq_len=131072, intermediate_size=8192, ssm_num_heads=64, ssm_head_dim=64, ssm_state=128,
                            attn_softmax_scale=0.015625),
    }
    base = dict(
        norm="rmsnorm", norm_eps=1e-5, position="none", activation="swiglu", use_bias=False, tie_embeddings=True,
        ssm_groups=1, ssm_conv_kernel=4, embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=8.0,
        num_experts=0, moe_top_k=0, moe_layer_freq=1, moe_drop_tokens=False,
    )
    base.update(presets[size])
    base.update(overrides)
    if "layer_types" not in base:
        # layer_types: attention at 5 and then every tenth, mamba elsewhere
        base["layer_types"] = ["softmax" if i % 10 == 5 else "ssm" for i in range(base["num_layers"])]
    return HybridMoEConfig(**base)


def lfm2_moe_config(size: str = "24b-a2b", **overrides) -> HybridMoEConfig:
    """LFM2-24B-A2B (``LiquidAI/LFM2-24B-A2B`` ``config.json``, ``model_type:
    lfm2_moe``): 40 layers, layers 2, 6, 10, ..., 38 causal GQA of 32 query
    heads over 8 KV heads of 64 with an RMSNorm over each head's features of q
    and of k before a rotation of all 64 (theta 1e6), the other 30 gated short
    convolutions (``conv_L_cache`` 3 taps over the 2,048 channels of ``B *
    x~``, no bias, no activation: the only thing a row carries is the last two
    products); layers 0 and 1 a dense SwiGLU FFN of 11,776, layers 2-39 64
    SwiGLU experts of 1,536, 4 a token by sigmoid scores with a selection
    bias, gates normalised, no shared expert; the embedding tied to the head.
    Behind the two leading layers the list is ``[attn conv conv conv] x 9 +
    [attn conv]``: nine scanned periods and a remainder of two. ``24b-a2b`` is
    the published model whole; ``tiny`` a toy of one chip's share (2 of 16
    experts held) with the two leading layers, two periods and the remainder
    for tests."""
    presets = {
        "tiny": dict(hidden_size=128, num_layers=12, num_heads=4, num_kv_heads=2, head_dim=32, vocab_size=512, max_seq_len=256,
                     intermediate_size=96, expert_intermediate_size=32, num_experts=2, moe_router_experts=16,
                     moe_expert_share=(0, 8), moe_top_k=4),
        "24b-a2b": dict(hidden_size=2048, num_layers=40, num_heads=32, num_kv_heads=8, head_dim=64, vocab_size=65536,
                        max_seq_len=128000, intermediate_size=11776, expert_intermediate_size=1536, num_experts=64, moe_top_k=4),
    }
    base = dict(
        norm="rmsnorm", norm_eps=1e-5, position="rope", rope_theta=1e6, activation="swiglu", use_bias=False,
        tie_embeddings=True, qk_norm="head", conv_kernel=3, leading_dense_layers=2, moe_layer_freq=1, moe_drop_tokens=False,
        moe_norm_topk_prob=True, moe_scoring="sigmoid", moe_select_bias=True, moe_shared_experts=0, moe_routed_scaling=1.0,
    )
    base.update(presets[size])
    base.update(overrides)
    if "layer_types" not in base:
        # layer_types: full_attention at 2 and then every fourth, conv elsewhere
        base["layer_types"] = ["softmax" if i % 4 == 2 else "conv" for i in range(base["num_layers"])]
    return HybridMoEConfig(**base)


def dots3_note_config(size: str = "note-prev", **overrides) -> HybridMoEConfig:
    """dots3-note-prev's language model (``dots-studio/dots3-note-prev``
    ``config.json``, ``model_type: dots3_note``): 46 layers of latent
    attention of TWO kinds. Layers 0, 1, 5, 9, ... (``full_attention``) 128
    heads with a query and key of 128 unrotated + 64 rotated features (theta
    8e7) and a value of 128 over a latent of 512, queries through a low rank
    of 1,024, attending the ``index_topk`` 2,048 causal keys that an indexer
    of 64 heads of 128 scores highest (``sparse_latent``); the others
    (``sliding_attention``) 64 heads of 192 + 64 / 128 over a latent of 1,024
    of their own, queries through 1,024, theta 5e4, over the newest 513 keys
    (``window_latent``); both low ranks times (5120 / rank)^0.5 behind their
    norms (``apply_mla_qkv_lora_rescale``), one sigmoid gate a head on both
    kinds' output; layer 0 a dense SwiGLU FFN of 13,824, layers 1-45 256
    SwiGLU experts of 1,536, 8 a token by sigmoid scores with a selection
    bias, gates normalised, one shared expert. The vision and audio towers and
    the multi-token-prediction layer are not part of this model.
    ``note-prev`` is the published model whole (behind the leading layer the
    list repeats ``[full window window window]``, eleven periods and a
    remainder of one); ``tiny`` a toy of one chip's share (4 of 16 experts
    held) with the leading layer and two periods for tests, whose
    ``index_topk`` and window its short contexts pass."""
    presets = {
        "tiny": dict(hidden_size=64, num_layers=9, num_heads=4, num_kv_heads=4, head_dim=24, v_head_dim=16, q_lora_rank=48,
                     kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, window_num_heads=2, window_num_kv_heads=2, window_q_lora_rank=40,
                     window_kv_lora_rank=48, window_qk_nope_head_dim=24, window_qk_rope_head_dim=8, window_v_head_dim=16,
                     window=9, index_num_heads=4, index_head_dim=16, index_topk=8, vocab_size=512, max_seq_len=256,
                     intermediate_size=96, expert_intermediate_size=32, num_experts=4, moe_router_experts=16,
                     moe_expert_share=(0, 4), moe_top_k=3),
        "note-prev": dict(hidden_size=5120, num_layers=46, num_heads=128, num_kv_heads=128, head_dim=192, v_head_dim=128,
                          q_lora_rank=1024, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, window_num_heads=64,
                          window_num_kv_heads=64, window_q_lora_rank=1024, window_kv_lora_rank=1024, window_qk_nope_head_dim=192,
                          window_qk_rope_head_dim=64, window_v_head_dim=128, window=513, index_num_heads=64, index_head_dim=128,
                          index_topk=2048, vocab_size=152064, max_seq_len=524288, intermediate_size=13824,
                          expert_intermediate_size=1536, num_experts=256, moe_top_k=8),
    }
    base = dict(
        norm="rmsnorm", norm_eps=1e-5, position="rope", rope_theta=8e7, window_rope_theta=5e4, activation="swiglu",
        use_bias=False, tie_embeddings=False, attn_head_gate=True, latent_lora_rescale=True, leading_dense_layers=1,
        moe_layer_freq=1, moe_drop_tokens=False, moe_norm_topk_prob=True, moe_scoring="sigmoid", moe_select_bias=True,
        moe_shared_experts=1, moe_routed_scaling=1.0,
    )
    base.update(presets[size])
    base.update(overrides)
    if "layer_types" not in base:
        # layer_types: full_attention at 0, at 1 and then every fourth
        base["layer_types"] = ["sparse_latent" if i == 0 or i % 4 == 1 else "window_latent" for i in range(base["num_layers"])]
    return HybridMoEConfig(**base)


def layer_types_of_pattern(pattern: str):
    """A ``hybrid_override_pattern`` as ``layer_types``: ``M`` a Mamba-2 mixer alone, ``*`` an attention mixer alone, ``E`` the expert FFN alone."""
    return [{"M": "ssm", "*": "softmax", "E": FFN_BLOCK}[letter] for letter in pattern]


def nemotron_h_config(size: str = "3-nano-30b-a3b", **overrides) -> HybridMoEConfig:
    """NVIDIA-Nemotron-3-Nano-30B-A3B (``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``
    ``config.json``, ``model_type: nemotron_h``): 52 blocks of ONE sublayer
    each, ``hybrid_override_pattern`` ``MEMEM*EMEMEM*...``: 23 ``M`` Mamba-2
    mixers of 64 heads of 64 over a state of 128 with EIGHT groups of ``B``
    and ``C`` (a 4-tap convolution with a bias over 6,144 channels, the gated
    norm over each group of 512 features apart), 6 ``*`` causal GQA of 32
    query heads over 2 KV heads of 128 with no positional term, 23 ``E``
    expert FFNs: 128 experts of TWO matrices with ``relu2`` between them
    (2,688 -> 1,856 -> 2,688), 6 a token by sigmoid scores with a selection
    bias, gates normalised and times 2.5, one shared expert of 3,712 (two
    expert widths). ``layer_types`` names the blocks ``ssm`` / ``softmax`` /
    ``ffn`` (``layer_types_of_pattern``). ``3-nano-30b-a3b`` is the published
    model whole, whose list repeats nothing: ``period`` is then the 52 blocks
    themselves, one trip of the scan; ``tiny`` a toy of one chip's share (4 of
    8 experts held) of the leading 16 blocks ``MEMEM*EMEMEM*EME`` for tests,
    its state-space layer at the kernel's own tiles with two groups of two
    heads."""
    presets = {
        "tiny": dict(hidden_size=64, num_layers=16, num_heads=4, num_kv_heads=2, head_dim=16, vocab_size=512, max_seq_len=256,
                     intermediate_size=32, expert_intermediate_size=32, num_experts=4, moe_router_experts=8,
                     moe_expert_share=(0, 2), moe_top_k=3, ssm_num_heads=4, ssm_head_dim=64, ssm_state=128, ssm_groups=2),
        "3-nano-30b-a3b": dict(hidden_size=2688, num_layers=52, num_heads=32, num_kv_heads=2, head_dim=128, vocab_size=131072,
                               max_seq_len=262144, intermediate_size=1856, expert_intermediate_size=1856, num_experts=128,
                               moe_top_k=6, ssm_num_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=8),
    }
    base = dict(
        norm="rmsnorm", norm_eps=1e-5, position="none", activation="relu2", use_bias=False, tie_embeddings=False,
        ssm_conv_kernel=4, moe_layer_freq=1, moe_drop_tokens=False, moe_norm_topk_prob=True, moe_scoring="sigmoid",
        moe_select_bias=True, moe_shared_experts=2, moe_routed_scaling=2.5,
    )
    base.update(presets[size])
    base.update(overrides)
    if "layer_types" not in base:
        published = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
        base["layer_types"] = layer_types_of_pattern(published[: base["num_layers"]])
    return HybridMoEConfig(**base)
