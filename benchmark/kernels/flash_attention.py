"""Operations and bytes the causal flash-attention kernels of
``ops/transformer/flash_attention.py`` need, from shapes alone, and how their
events are named in a device trace.

One invocation covers ``bn`` (batch x heads) sequences of ``t`` positions
with head size ``d``; causal, so ``t (t + 1) / 2`` query-key pairs. A matrix
product over the pairs is ``2 d`` operations a pair.

* forward: S = Q K^T and O = P V, 2 products; reads q k v, writes o and the
  row log-sum-exp (4 bytes a row).
* backward dq: recomputes S, dP = dO V^T, dQ = dS K, 3 products; reads
  q k v do and the two row statistics, writes dq.
* backward dkv: recomputes S and dP, dV = P^T dO, dK = dS^T Q, 4 products;
  reads the same, writes dk dv.

The split backward recomputes S and dP twice (7 products where a fused
backward needs 5); each kernel is held to its own job here, and the
duplicate shows in the kernels' share of the step, not in their roofline.
"""

# How the kernels' events read on this runtime (PR 22 trace): the event's name
# is the custom call's HLO text and says nothing of the kernel, so each is told
# by its signature once the layouts are taken out: the forward returns (o, f32
# lse) from 3 operands, dq one tensor from 6 (q k v do lse delta), dkv two
# tensors from 6. A kernel `name=` in the program would make this a plain match
# (PERF.md section 7, for the tracing issue).
_ARG = r"\w+\[[\d,]+\] %[\w.-]+(, )?"
_TAIL = r'\), custom_call_target="tpu_custom_call"'
EVENTS = {
    "forward": r"= \(\w+\[[\d,]+\], f32\[[\d,]+\]\) custom-call\((" + _ARG + "){3}" + _TAIL,
    "backward_dq": r"= \w+\[[\d,]+\] custom-call\((?!s32)(" + _ARG + "){6}" + _TAIL,
    "backward_dkv": r"= \(\w+\[[\d,]+\], (?!f32)\w+\[[\d,]+\]\) custom-call\((" + _ARG + "){6}" + _TAIL,
}


def calls_per_step(num_layers: int, remat: bool):
    """Kernel calls in one optimizer step: one of each a layer, and the
    forward once more where the backward pass recomputes the layer."""
    return {"forward": num_layers * (2 if remat else 1), "backward_dq": num_layers, "backward_dkv": num_layers}


PRODUCTS = {"forward": 2, "backward_dq": 3, "backward_dkv": 4}
TENSORS_MOVED = {"forward": 4, "backward_dq": 5, "backward_dkv": 6}
ROW_STATS = {"forward": 1, "backward_dq": 2, "backward_dkv": 2}


def ops_and_bytes(kind: str, bn: int, t: int, d: int, itemsize: int = 2):
    pairs = t * (t + 1) // 2
    ops = PRODUCTS[kind] * 2 * d * pairs * bn
    moved = (TENSORS_MOVED[kind] * t * d * itemsize + ROW_STATS[kind] * t * 4) * bn
    return ops, moved


def min_seconds(kind: str, bn: int, t: int, d: int, peak, itemsize: int = 2):
    """The least time one invocation can take on this chip, and which peak
    bounds it."""
    ops, moved = ops_and_bytes(kind, bn, t, d, itemsize)
    by_ops, by_bytes = ops / peak["bf16_flops"], moved / peak["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("compute" if by_ops >= by_bytes else "memory")
