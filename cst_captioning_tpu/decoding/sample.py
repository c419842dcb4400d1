"""Multinomial sampling with K Monte-Carlo rollouts per clip.

Reference behavior: ``model.sample(feats, multinomial × K)`` — temperature
sampling, K rollouts per video for the consensus reward (SURVEY.md §3.2,
BASELINE config 4). The encoder pass is shared across rollouts (computed
once, closed over by the rollout-vmapped decode step); all K×B sequences
decode in ONE XLA program — the fused "one launch" design of §7 step 5 —
whose loop exits as soon as every rollout of every clip has emitted EOS.

RNG discipline: rollout k at step t uses ``fold_in(fold_in(key, k), t)``
for ONE uniform a row, [B] of them, and each row takes its token by the
inverse CDF of ``softmax(masked logits / temperature)``
(``common.sample_lanes``): K x B draws a step where Gumbel-max over the
vocabulary made K x B x V (the gauge ``rl.decode.sampler_draws``, set when
the loop is traced, says how many) — reproducible regardless of batch
sharding or rollout count. The whole [T, K] key array is precomputed
outside the scan (``rollout_step_keys``); the step body gathers row ``t``
instead of re-folding K keys per iteration — the same stream bit-for-bit.
The ``fused_decode`` family (decoding/fused.py) folds the same keys but
draws Gumbel noise from them, which it needs as data: the two draw from
the same distribution, not the same tokens.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cst_captioning_tpu import obs
from cst_captioning_tpu.config.config import BOS_ID, PAD_ID
from cst_captioning_tpu.decoding.common import (
    apply_min_len,
    forbid_special,
    lane_decode_step,
    rollout_step_keys,
    sample_lanes,
    scan_until_finished,
    selected_logprob,
    step_outputs,
)
from cst_captioning_tpu.models.captioner import CaptionModel, EncoderOutput


def sample_decode(
    model: CaptionModel,
    params,
    feats: dict[str, jnp.ndarray],
    masks: dict[str, jnp.ndarray],
    rng: jax.Array,
    num_rollouts: int = 1,
    temperature: float = 1.0,
    max_len: int | None = None,
    min_len: int = 0,
    batch_axes: tuple[str, ...] = (),
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """-> (tokens [K, B, T], logprobs [K, B, T]); PAD/0 after EOS.

    ``logprobs`` are the *untempered* model logprobs of the sampled tokens
    (the REINFORCE estimator needs log p_model, not log p_temperature).
    """
    T = max_len or model.cfg.max_len
    K = num_rollouts
    enc: EncoderOutput = model.apply(params, feats, masks, method=CaptionModel.encode)
    B = enc.memory.shape[0]

    # the decode step is lane-batched over the rollout axis with the encoder
    # output CLOSED OVER (unbatched): XLA reads the memory bank once per
    # step and fuses the additive-attention broadcast across rollouts. (A
    # flat [K*B]-row layout with tiled memory was measured 80% slower at the
    # flagship dims, round 5 — the tile defeats that fusion.)
    step_keys = rollout_step_keys(rng, K, T)  # [T, K]
    obs.gauge("rl.decode.sampler_draws").set(float(K * B))

    def step(state, t):
        carry, token, finished = state  # carry leaves [K, B, ...]; [K, B]
        carry, logits = lane_decode_step(model, params, carry, token, enc)
        logits = apply_min_len(forbid_special(logits), t, min_len)  # [K,B,V]
        nxt = sample_lanes(step_keys[t], logits / temperature)
        lp = selected_logprob(logits, nxt)
        nxt, lp, finished = step_outputs(nxt, lp, finished)
        return (carry, nxt, finished), (nxt, lp)

    init = (
        # broadcast (no reshape): stays a view for the vmapped step
        jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (K,) + x.shape), enc.carry
        ),
        jnp.full((K, B), BOS_ID, jnp.int32),
        jnp.zeros((K, B), bool),
    )
    _, (tokens, logprobs) = scan_until_finished(
        step, init, T, lambda s: s[2], (PAD_ID, 0.0), batch_axes
    )
    # ys stack on axis 0: [T, K, B] -> [K, B, T]
    return tokens.transpose(1, 2, 0), logprobs.transpose(1, 2, 0)
