"""Random layerwise token dropping (random-LTD).

Counterpart of the reference's ``deepspeed/runtime/data_pipeline/data_routing/``
(``basic_layer.py RandomLayerTokenDrop`` + the native gather/scatter kernels
``csrc/random_ltd/``): during training, middle layers process only a random
subset of tokens; the untouched tokens bypass the layer and are scattered
back — cutting per-layer FLOPs while the schedule grows the kept-token count
to full length by the end of training.

On TPU the gather/scatter is ``jnp.take_along_axis`` /
``.at[].set`` — static kept-count per compiled program (the scheduler's
values bucket compilation, like the reference's seqlen schedule).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


class RandomLTDScheduler:
    """Kept-token schedule (reference ``scheduler.py``): linear increase
    from ``start_token_num`` to the full ``max_token_num`` over
    ``total_layer_token_steps``."""

    def __init__(self, start_token_num: int, max_token_num: int, total_steps: int, step_size: int = 16):
        self.start = start_token_num
        self.max = max_token_num
        self.total = max(total_steps, 1)
        self.step_size = step_size
        self.current = start_token_num

    def update(self, global_step: int) -> int:
        frac = min(1.0, global_step / self.total)
        if frac >= 1.0:
            # snap to full length even when max is not a step_size multiple
            self.current = self.max
            return self.current
        n = self.start + (self.max - self.start) * frac
        n = int(n // self.step_size) * self.step_size
        self.current = max(self.start, min(self.max, n))
        return self.current

    def state_dict(self) -> Dict[str, Any]:
        return {"current": self.current}

    def load_state_dict(self, sd: Dict[str, Any]) -> None:
        self.current = sd["current"]


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def sample_layer_token_indices(rng, n_layers: int, batch: int, seq_len: int, kept: int) -> jnp.ndarray:
    """[n_layers, B, kept] sorted random token indices — each LTD layer
    draws its OWN subset (the 'layerwise' in random-LTD; sorted so position
    order — and causality — is preserved, the reference's token_sort.cu).
    One fused program: a per-layer host loop would cost n_layers
    dispatches per step."""
    scores = jax.random.uniform(rng, (n_layers, batch, seq_len))
    _, idx = jax.lax.top_k(-scores, kept)
    return jnp.sort(idx, axis=-1).astype(jnp.int32)


def random_token_select(rng, seq_len: int, kept: int, batch: int) -> jnp.ndarray:
    """[B, kept] single-layer form of ``sample_layer_token_indices``."""
    return sample_layer_token_indices(rng, 1, batch, seq_len, kept)[0]


def gather_tokens(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """[B, T, H] × [B, kept] → [B, kept, H] (csrc/random_ltd/gather_scatter.cu)."""
    return jnp.take_along_axis(x, idx[..., None], axis=1)


def scatter_tokens(full: jnp.ndarray, processed: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Write processed tokens back at their positions; untouched tokens keep
    the bypass value."""
    B = full.shape[0]
    b_idx = jnp.arange(B)[:, None]
    return full.at[b_idx, idx].set(processed)


class RandomLayerTokenDrop:
    """Wrap a layer fn so it runs on a random token subset
    (reference ``basic_layer.py RandomLayerTokenDrop``)."""

    def __init__(self, layer_fn, scheduler: RandomLTDScheduler):
        self.layer_fn = layer_fn
        self.scheduler = scheduler

    def __call__(self, params, x: jnp.ndarray, rng, train: bool = True, **kwargs):
        kept = self.scheduler.current
        T = x.shape[1]
        if not train or kept >= T:
            return self.layer_fn(params, x, **kwargs)
        idx = random_token_select(rng, T, kept, x.shape[0])
        sub = gather_tokens(x, idx)
        out = self.layer_fn(params, sub, **kwargs)
        return scatter_tokens(x, out, idx)
