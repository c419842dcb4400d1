"""Fused one-loop RL decode: greedy baseline + K rollouts in ONE scan.

The SCST decode program used to run ``greedy_decode`` then ``sample_decode``
as two *sequential* ``scan_until_finished`` loops inside one jitted program
(rl/scst.py pre-PR 4) — two encoder passes, two T-step while loops, and per
step two separate attention/LSTM dispatches over the same memory bank.
Round-5 bench put that program at 85.1% of sequential RL step time at MFU
0.010: the loop is latency-bound, so its cost scales with *steps
dispatched*, not FLOPs.

Here the greedy baseline is folded in as lane 0 of a single (1+K)-lane
scan: lane 0 takes the argmax of its untempered logits, lanes 1..K sample
``categorical(fold_in(fold_in(rng, k), t), logits/temperature)`` in its
bit-identical Gumbel-max form (``gumbel_step_noise``), so the same streams
drive every path below: the noise is data here (gathered through the
compaction permutation, argmaxed inside the stride kernel). The keys are
``sample_decode``'s (``rollout_step_keys``), the draws are not: since PR 39
``sample_decode`` draws one uniform a lane and takes the token by the
inverse CDF (``common.sample_lanes``), which the RL decode without a greedy
lane runs, so the two families sample the same distribution with different
tokens under one key. One encoder pass feeds all lanes; the loop exits once
EVERY lane of every clip has emitted EOS. Pinned bit-exact in
tests/test_decoding.py and tests/test_rl.py: the greedy lane against
``greedy_decode``, the sampled lanes against the Gumbel-max loop
``sample_decode`` was (tests/_gumbel_sample.py).

On top of the one-loop structure sit the two decode-endgame levers
(``ModelConfig.decode_stride`` / ``decode_compact``):

- **stride**: the driving while loop advances ``S`` time steps per
  iteration instead of one. On the XLA path that is an inner ``lax.scan``
  chunk (the early-exit check amortizes over S steps); with
  ``decode_impl="pallas"`` each chunk is ONE launch of the multi-step
  stride kernel (ops/decode_pallas.py: token selection + next-token embed
  lookup in-kernel, decoder weights VMEM-resident across the whole
  stride).
- **compaction**: between strides, batch columns whose every lane has
  finished are permuted out of a dense still-active prefix
  (``jnp.argsort`` stable: active columns keep their order), the stride
  steps the permuted state, and outputs scatter back through the inverse
  permutation. Per-row math is position-independent, so the round trip is
  token- and logprob-exact (pinned in tests/test_decoding.py); the
  compute win is the stride kernel's, which skips whole blocks past the
  ``n_active`` prefix. The while loop's all-finished exit replaces the
  fixed budget either way.

Every (stride, compact) combination is token- and logprob-exact vs the
stride-1 uncompacted loop under a fixed rng — selection noise is always
drawn in ORIGINAL batch order and gathered through the compaction
permutation, so a row's RNG stream follows it through the shuffle.

The serving engine (cst_captioning_tpu/serving/engine.py) drives the SAME
stride machinery as an always-on service: its admission loop re-packs the
active prefix between strides exactly like the compaction here, but with
per-REQUEST RNG streams and a paged encoder bank gathered per stride —
``fused_decode_stride``'s ``mem_lens`` argument carries the per-row ragged
lengths; the offline paths below pass none (uniform M), which compiles to
the identical program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cst_captioning_tpu.config.config import BOS_ID, EOS_ID, PAD_ID
from cst_captioning_tpu.decoding.common import (
    apply_min_len,
    forbid_special,
    gumbel_step_noise,
    lane_decode_step,
    npad_best_lane,
    pcast_varying,
    rollout_step_keys,
    scan_until_finished,
    selected_logprob,
    step_outputs,
)
from cst_captioning_tpu.models.captioner import CaptionModel, EncoderOutput


def _sel_step(model, params, enc_c, step_keys, B, V, temperature, min_len,
              perm):
    """The (1+K)-lane decode step with fused token selection.

    ``perm`` (compaction permutation, or None) maps the state's column
    order back to original batch order: Gumbel noise is drawn for ORIGINAL
    columns and gathered through it, so a clip's sampling stream is
    independent of where compaction moved it.
    """

    def step(state, t):
        carry, token, finished = state  # carry leaves [1+K, B, ...]; [1+K, B]
        carry, logits = lane_decode_step(model, params, carry, token, enc_c)
        logits = apply_min_len(forbid_special(logits), t, min_len)  # [1+K,B,V]
        g_nxt = jnp.argmax(logits[0], axis=-1)
        tl = logits[1:] / temperature
        noise = gumbel_step_noise(step_keys[t], (B, V), tl.dtype)
        if perm is not None:
            noise = noise[:, perm, :]
        s_nxt = jnp.argmax(tl + noise, axis=-1)
        nxt = jnp.concatenate([g_nxt[None], s_nxt], axis=0).astype(jnp.int32)
        lp = selected_logprob(logits, nxt)
        nxt, lp, finished = step_outputs(nxt, lp, finished)
        return (carry, nxt, finished), (nxt, lp)

    return step


def _kernel_stride(model, params, state_c, enc_c, noise, t, S, n_active,
                   temperature, min_len):
    """One stride via the multi-step Pallas kernel -> (state', toks, lps)."""
    from cst_captioning_tpu.ops.decode_pallas import fused_decode_stride

    carry, token, finished = state_c
    new_carry, toks, lps = fused_decode_stride(
        params["params"]["cell"], carry, token, finished,
        enc_c.memory, enc_c.memory_proj, enc_c.memory_mask,
        noise, t, n_active, steps=S, temperature=temperature,
        min_len=min_len, num_layers=model.cfg.num_layers,
    )
    # the kernel emits the frozen-token stream; the carried token is the
    # last emission and finished accumulates any EOS in the chunk — the
    # exact state the XLA step chain would carry
    finished = finished | jnp.any(toks == EOS_ID, axis=0)
    return (new_carry, toks[-1], finished), toks, lps


def _stride_decode(model, params, enc: EncoderOutput, step_keys, B, T, S, K,
                   temperature, min_len, compact, batch_axes):
    """The strided driving loop (module docstring): while over S-step
    chunks, optional finished-column compaction between chunks, all-
    finished early exit. Returns (tokens [P,1+K,B], logprobs [P,1+K,B])
    already sliced to the T budget."""
    G = 1 + K
    V = model.cfg.vocab_size
    padded = -(-T // S) * S
    use_kernel = getattr(model.cfg, "decode_impl", "xla") == "pallas"

    init = (
        jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (G,) + x.shape), enc.carry
        ),
        jnp.full((G, B), BOS_ID, jnp.int32),
        jnp.zeros((G, B), bool),
    )
    bufs = (
        jnp.full((padded, G, B), PAD_ID, jnp.int32),
        jnp.zeros((padded, G, B), jnp.float32),
    )
    init = pcast_varying(init, batch_axes)
    bufs = pcast_varying(bufs, batch_axes)

    def count_unfinished(finished):
        n = jnp.sum(jnp.logical_not(finished).astype(jnp.int32))
        for ax in batch_axes:
            n = jax.lax.psum(n, ax)
        return n

    def cond(loop):
        t, _, _, unfinished = loop
        return (t < T) & (unfinished > 0)

    def body(loop):
        t, state, (tok_buf, lp_buf), _ = loop
        carry, token, finished = state
        if compact:
            # stable sort keeps active columns in original relative order,
            # so the prefix is a gather, not a shuffle
            col_done = jnp.all(finished, axis=0)                    # [B]
            perm = jnp.argsort(col_done, stable=True)
            inv = jnp.argsort(perm, stable=True)
            n_active = B - jnp.sum(col_done.astype(jnp.int32))
            carry = jax.tree.map(lambda x: jnp.take(x, perm, axis=1), carry)
            token = jnp.take(token, perm, axis=1)
            finished = jnp.take(finished, perm, axis=1)
            enc_c = enc.take_batch(perm)
            # materialize the gathered operands: without the barrier XLA
            # fuses the gather into the step's consumers, changing the
            # generated code and drifting logits by ULPs vs the uncompacted
            # loop — with it, the step body sees plain arrays and compiles
            # to the exact same program, which is what makes compaction
            # bit-exact rather than merely close (a gather is a copy
            # anyway, so the barrier costs nothing extra). The state and
            # the encoder bank go through barriers of their own: one barrier
            # types every output varying over the union of its operands'
            # axes, and under sequence parallelism the frame-sharded bank
            # would mark the 'seq'-replicated loop state as 'seq'-varying
            carry, token, finished = jax.lax.optimization_barrier(
                (carry, token, finished)
            )
            enc_c = jax.lax.optimization_barrier(enc_c)
        else:
            perm = None
            n_active = jnp.int32(B)
            enc_c = enc
        state_c = (carry, token, finished)

        if use_kernel:
            # the kernel's whole-stride noise, drawn in original column
            # order from the exact rollout_step_keys streams (overhang rows
            # past T clamp to row T-1; their emissions never leave the
            # sliced-off buffer tail)
            keys_chunk = step_keys[t + jnp.arange(S)]               # [S, K]
            noise = jax.vmap(
                lambda ks: gumbel_step_noise(ks, (B, V), jnp.float32)
            )(keys_chunk)
            if compact:
                noise = noise[:, :, perm, :]
            state_c, tok_chunk, lp_chunk = _kernel_stride(
                model, params, state_c, enc_c, noise, t, S, n_active,
                temperature, min_len,
            )
        else:
            step = _sel_step(
                model, params, enc_c, step_keys, B, V, temperature, min_len,
                perm,
            )
            state_c, (tok_chunk, lp_chunk) = jax.lax.scan(
                step, state_c, t + jnp.arange(S)
            )

        carry, token, finished = state_c
        if compact:
            carry = jax.tree.map(lambda x: jnp.take(x, inv, axis=1), carry)
            token = jnp.take(token, inv, axis=1)
            finished = jnp.take(finished, inv, axis=1)
            tok_chunk = jnp.take(tok_chunk, inv, axis=2)
            lp_chunk = jnp.take(lp_chunk, inv, axis=2)
        tok_buf = jax.lax.dynamic_update_slice_in_dim(tok_buf, tok_chunk, t, 0)
        lp_buf = jax.lax.dynamic_update_slice_in_dim(lp_buf, lp_chunk, t, 0)
        return (
            t + S,
            (carry, token, finished),
            (tok_buf, lp_buf),
            count_unfinished(finished),
        )

    # overhang steps past T (S not dividing T, final chunk only) need no
    # state freeze: finished is monotonic, the loop cond exits on t >= T
    # regardless, and the final state is discarded — only the buffer rows
    # below T survive
    _, _, (tok_buf, lp_buf), _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), init, bufs, count_unfinished(init[2]))
    )
    return tok_buf[:T], lp_buf[:T]


def fused_decode(
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
    decode_stride: int | None = None,
    compact: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """-> (greedy [B,T], greedy_lp [B,T], tokens [K,B,T], logprobs [K,B,T]).

    Lane 0 is the greedy baseline (argmax of untempered logits, no RNG
    consumed); lanes 1..K are the Monte-Carlo rollouts on ``sample_decode``'s
    exact key stream. ``logprobs`` are untempered model logprobs of the
    chosen tokens (``selected_logprob``); PAD/0 after EOS on every lane.

    ``decode_stride`` / ``compact`` default from ``model.cfg``
    (``decode_stride`` / ``decode_compact``); pass explicit values to
    override per call (the parity tests do). Stride 1
    without compaction is the per-step loop every other combination is
    pinned token/logprob-exact against.
    """
    T = max_len or model.cfg.max_len
    K = num_rollouts
    S = (
        decode_stride if decode_stride is not None
        else getattr(model.cfg, "decode_stride", 1)
    )
    S = max(1, min(int(S), T))
    if compact is None:
        compact = bool(getattr(model.cfg, "decode_compact", False))
    if S == 1:
        # compaction only pays between strides (the per-step kernel takes
        # no active-prefix, and permuting between every step buys nothing);
        # stride 1 therefore always means the plain per-step loop
        compact = False
    enc: EncoderOutput = model.apply(
        params, feats, masks, method=CaptionModel.encode
    )
    B = enc.memory.shape[0]
    step_keys = rollout_step_keys(rng, K, T)  # [T, K] — lane 0 never draws

    if S == 1 and not compact:
        # the per-step loop: scan_until_finished's fine-grained early exit
        # (exit check every ~5 steps), the exactness baseline
        step = _sel_step(
            model, params, enc, step_keys, B, model.cfg.vocab_size,
            temperature, min_len, None,
        )
        init = (
            jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (1 + K,) + x.shape),
                enc.carry,
            ),
            jnp.full((1 + K, B), BOS_ID, jnp.int32),
            jnp.zeros((1 + K, B), bool),
        )
        _, (tokens, logprobs) = scan_until_finished(
            step, init, T, lambda s: s[2], (PAD_ID, 0.0), batch_axes
        )
    else:
        tokens, logprobs = _stride_decode(
            model, params, enc, step_keys, B, T, S, K, temperature, min_len,
            compact, batch_axes,
        )
    # ys stack on axis 0: [T, 1+K, B] -> [1+K, B, T]
    tokens = tokens.transpose(1, 2, 0)
    logprobs = logprobs.transpose(1, 2, 0)
    return tokens[0], logprobs[0], tokens[1:], logprobs[1:]


def npad_decode(
    model: CaptionModel,
    params,
    feats: dict[str, jnp.ndarray],
    masks: dict[str, jnp.ndarray],
    rng: jax.Array,
    num_lanes: int = 4,
    temperature: float = 1.0,
    max_len: int | None = None,
    min_len: int = 0,
    batch_axes: tuple[str, ...] = (),
    decode_stride: int | None = None,
    compact: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Noisy Parallel Approximate Decoding -> (tokens [B, T], scores [B]).

    arXiv 1605.03835: decode the greedy lane plus ``num_lanes`` noise-
    perturbed lanes IN PARALLEL (they drop into the fused loop's (1+K)-lane
    pool, so the marginal cost over greedy is one wider lane axis, not M
    sequential decodes), then answer with the highest-sum-logprob lane.
    The anytime property the evaluator's NPAD mode leans on: lane 0 is the
    unperturbed greedy lane and argmax ties break toward it, so the answer
    is >= greedy by construction (pinned in tests/test_decoding.py) at a
    latency near greedy's — the budget-friendly stand-in for beam search.
    ``scores`` are the winning lane's sum-logprobs (PAD rows contribute
    0.0, so it is exactly the sequence logprob, the beam ranking scale).
    """
    g_tok, g_lp, s_tok, s_lp = fused_decode(
        model, params, feats, masks, rng, num_rollouts=num_lanes,
        temperature=temperature, max_len=max_len, min_len=min_len,
        batch_axes=batch_axes, decode_stride=decode_stride, compact=compact,
    )
    tokens = jnp.concatenate([g_tok[None], s_tok], axis=0)     # [1+M, B, T]
    logprobs = jnp.concatenate([g_lp[None], s_lp], axis=0)
    return npad_best_lane(tokens, logprobs)
