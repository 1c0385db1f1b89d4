"""Mixture-of-Experts (expert parallelism).

TPU-native counterpart of ``deepspeed/moe/``: top-1/top-2 gating with
capacity + load-balance loss, expert dispatch over the ``expert`` mesh axis
(GSPMD all-to-all), stacked-expert FFNs, PR-MoE residual; and dropless top-k
routing for any k, tokens sorted by expert and multiplied group by group
(``moe/routed_ffn.py``, ``moe/grouped_matmul.py``), which is what ``drop_tokens=False`` means.
"""

from deepspeed_tpu.moe.layer import MoE
from deepspeed_tpu.moe.experts import (
    apply_expert_ffn,
    expert_partition_rules,
    init_expert_ffn,
)
from deepspeed_tpu.moe.sharded_moe import (
    combine,
    dispatch,
    multiplicative_jitter,
    top1gating,
    top2gating,
    topkgating,
)

__all__ = [
    "MoE",
    "top1gating",
    "top2gating",
    "topkgating",
    "dispatch",
    "combine",
    "multiplicative_jitter",
    "init_expert_ffn",
    "apply_expert_ffn",
    "expert_partition_rules",
]
