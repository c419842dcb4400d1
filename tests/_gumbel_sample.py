"""The loop ``sample_decode`` was up to PR 39: Gumbel-max over the
vocabulary, K x B x V draws a step from ``fold_in(fold_in(rng, k), t)``.

The ``fused_decode`` family (decoding/fused.py, the stride kernel, the
serving engine) still selects this way, on this key stream, and its sampled
lanes are pinned bit-exact against this loop (tests/test_decoding.py,
tests/test_rl.py, tests/test_ops_decode_pallas.py). ``sample_decode`` itself
now draws one uniform a lane (``common.sample_lanes``): the same
distribution, another stream.
"""

import jax
import jax.numpy as jnp

from cst_captioning_tpu.config.config import BOS_ID, PAD_ID
from cst_captioning_tpu.decoding.common import (
    apply_min_len,
    forbid_special,
    gumbel_step_noise,
    lane_decode_step,
    rollout_step_keys,
    scan_until_finished,
    selected_logprob,
    step_outputs,
)
from cst_captioning_tpu.models.captioner import CaptionModel


def gumbel_sample_decode(model, params, feats, masks, rng, num_rollouts=1,
                         temperature=1.0, max_len=None, min_len=0,
                         batch_axes=()):
    """-> (tokens [K, B, T], logprobs [K, B, T]), as ``sample_decode``."""
    T = max_len or model.cfg.max_len
    K = num_rollouts
    enc = model.apply(params, feats, masks, method=CaptionModel.encode)
    B = enc.memory.shape[0]
    step_keys = rollout_step_keys(rng, K, T)

    def step(state, t):
        carry, token, finished = state
        carry, logits = lane_decode_step(model, params, carry, token, enc)
        logits = apply_min_len(forbid_special(logits), t, min_len)
        tl = logits / temperature
        noise = gumbel_step_noise(step_keys[t], tl.shape[1:], tl.dtype)
        nxt = jnp.argmax(tl + noise, axis=-1).astype(jnp.int32)
        lp = selected_logprob(logits, nxt)
        nxt, lp, finished = step_outputs(nxt, lp, finished)
        return (carry, nxt, finished), (nxt, lp)

    init = (
        jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (K,) + x.shape), enc.carry
        ),
        jnp.full((K, B), BOS_ID, jnp.int32),
        jnp.zeros((K, B), bool),
    )
    _, (tokens, logprobs) = scan_until_finished(
        step, init, T, lambda s: s[2], (PAD_ID, 0.0), batch_axes
    )
    return tokens.transpose(1, 2, 0), logprobs.transpose(1, 2, 0)
