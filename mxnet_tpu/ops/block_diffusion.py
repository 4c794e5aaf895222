"""The noising of block diffusion (``_contrib_block_diffusion_noise``).

Block diffusion (Arriola et al., "Block Diffusion", arXiv:2503.09573;
BD3-LM) cuts a sequence into blocks of ``block`` tokens and replaces each
token of block ``j`` by the mask id with probability ``t_j``, a noise level
drawn for the block, uniform in ``[T_MIN, 1]`` (the linear schedule of
masked diffusion, ``alpha_t = 1 - t``).  The draw is a function of the
batch alone: the key is ``fold_in(PRNGKey(seed), sum of the ids)``, so that
the same batch is noised alike wherever it is (the program, the loss, the
plain reference) and another batch otherwise.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register

T_MIN = 1e-3        # the least noise level a block is drawn at


def noise(tokens, block, mask_id, seed):
    """``(x_t, masked, t)`` of ``tokens (B, T)``: the noised ids (the
    tokens' dtype), which positions were masked (float32 0 / 1) and the
    noise level of each position's block (float32), all ``(B, T)``."""
    b, t = tokens.shape
    if t % block:
        raise ValueError("block_diffusion_noise: %d tokens are no whole "
                         "number of blocks of %d" % (t, block))
    ids = tokens.astype(jnp.int32)
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed)),
                             jnp.sum(ids, dtype=jnp.int32))
    k_level, k_mask = jax.random.split(key)
    level = jax.random.uniform(k_level, (b, t // block), jnp.float32,
                               T_MIN, 1.0)
    level = jnp.repeat(level, block, axis=1)
    masked = jax.random.uniform(k_mask, (b, t), jnp.float32) < level
    x_t = jnp.where(masked, jnp.int32(mask_id), ids).astype(tokens.dtype)
    return x_t, masked.astype(jnp.float32), level


@register("_contrib_block_diffusion_noise", num_outputs=3,
          inputs=("tokens",))
def block_diffusion_noise(tokens, block=4, mask_id=0, seed=0):
    """``(x_t, masked, t)`` as ``noise`` gives them; no gradient flows (the
    ids are integers)."""
    x_t, masked, level = noise(jax.lax.stop_gradient(tokens), int(block),
                               int(mask_id), int(seed))
    return x_t, masked, level
