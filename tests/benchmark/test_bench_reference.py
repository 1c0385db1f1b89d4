"""The plain reference against the program's model on seeded random weights,
at a small size on the CPU, for both published blocks the benchmark runs.

Tolerance: both sides compute in float32 here (``dtype="float32"``), so they
differ only by the order of float32 sums: 2e-4 on logits of order 1. Running
either side in bfloat16 moves logits by ~1e-2 and fails."""

import jax
import numpy as np
import pytest

from benchmark.reference import dense_decoder
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.config import TransformerConfig

BLOCKS = {
    "gpt2": dict(vocab_size=128, hidden_size=32, num_layers=2, num_heads=4, max_seq_len=32, norm="layernorm", norm_eps=1e-5,
                 position="learned", activation="gelu", use_bias=True, tie_embeddings=True),
    "mistral": dict(vocab_size=128, hidden_size=32, intermediate_size=48, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
                    max_seq_len=32, norm="rmsnorm", norm_eps=1e-5, position="rope", rope_theta=1e6, activation="swiglu",
                    use_bias=False, tie_embeddings=False),
}


def randomised(params, seed):
    """The model's init leaves biases at 0 and norm scales at 1: perturb every
    leaf so that a dropped bias or scale would show."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [a + 0.05 * jax.random.normal(k, a.shape) for a, k in zip(leaves, keys)])


@pytest.mark.parametrize("block", sorted(BLOCKS))
@pytest.mark.parametrize("flash", [False, True])
def test_program_logits_match_the_reference(block, flash):
    kwargs = dict(BLOCKS[block], dtype="float32", remat=False, flash_attention=flash)
    model = TransformerLM(TransformerConfig(**kwargs))
    tokens = np.random.default_rng(0).integers(0, 128, (2, 16), dtype=np.int32)
    params = randomised(model.init(jax.random.PRNGKey(1), tokens), seed=2)
    with jax.default_matmul_precision("highest"):
        ours = np.asarray(model.apply(params, tokens, train=False))
    ref = np.asarray(dense_decoder.logits({"kwargs": kwargs}, params, tokens))
    assert ours.shape == ref.shape == (2, 16, 128)
    np.testing.assert_allclose(ours, ref, atol=2e-4, rtol=0)
    assert np.abs(ref).max() > 0.1  # not a comparison of zeros


def test_reference_loss_is_the_mean_next_token_cross_entropy():
    kwargs = dict(BLOCKS["gpt2"], dtype="float32")
    model = TransformerLM(TransformerConfig(**kwargs, remat=False, flash_attention=False))
    tokens = np.random.default_rng(1).integers(0, 128, (2, 17), dtype=np.int32)
    params = randomised(model.init(jax.random.PRNGKey(3), tokens[:, :-1]), seed=4)
    model_section = {"kwargs": kwargs}
    lg = np.asarray(dense_decoder.logits(model_section, params, tokens[:, :-1]), np.float64)
    logp = lg - np.log(np.exp(lg).sum(-1, keepdims=True))
    by_hand = -np.mean(np.take_along_axis(logp, tokens[:, 1:, None].astype(np.int64), axis=-1))
    assert float(dense_decoder.loss(model_section, params, tokens)) == pytest.approx(by_hand, abs=1e-5)
    with jax.default_matmul_precision("highest"):
        ours = float(model.apply(params, {"input_ids": tokens[:, :-1], "labels": tokens[:, 1:]}, train=True))
    assert ours == pytest.approx(by_hand, abs=2e-4)


def test_reference_refuses_a_block_it_does_not_describe():
    with pytest.raises(ValueError):
        dense_decoder.arch_of({"kwargs": dict(BLOCKS["gpt2"], position="alibi")})
