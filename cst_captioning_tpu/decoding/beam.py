"""Batched fixed-shape beam search under ``lax.scan`` (eval config 5).

Reference behavior: ``model.sample(feats, beam_size=5)`` per-step topk over
beam×vocab (SURVEY.md §3.3). The classic tricky kernel (§7 "hard parts"):
everything is static-shape —

- finished beams may only "continue" with PAD at logprob 0, so their score is
  frozen while still participating in top-k,
- beam 0 alone is live at t=0 (others start at -1e9) so the first expansion
  doesn't pick W copies of the same token,
- one ``top_k`` over the flattened ``W*V`` axis per step; parent beams are
  gathered with ``take_along_axis`` over every carry leaf.

Two implementations share that candidate math (``_topk_expand``):

- ``beam_impl="reference"`` — the original sequential spelling: beams are
  flattened into the batch (state carry ``[B*W, ...]``) and every step runs
  one ``model.decode_step`` over the tiled batch. Kept verbatim as the
  bit-parity oracle.
- ``beam_impl="lanes"`` (default) — beams ride the shared (1+K)-lane decode
  step from decoding/fused.py (``lane_decode_step``): state carry is lane-
  major ``[W, B, ...]``, one lane per beam, so beam search reuses the exact
  step program the fused RL loop and the serving engine compile — including
  the fused Pallas step kernel when ``model.cfg.decode_impl == "pallas"``,
  where the per-step top-k itself moves in-kernel
  (``ops.decode_pallas.fused_beam_step``: blocked online logsumexp + blocked
  top-W over (lane, vocab-block)). Beam-hypothesis reordering is a cross-
  lane gather and therefore happens OUTSIDE the kernel, between launches —
  the seam where decoding/fused.py compacts finished columns.

Lane-vs-reference token- and score-bit-exactness at beam∈{1,3,5} is pinned
in tests/test_decoding.py. The guarantee rests on per-row bit-
stability of the decode step across batch layouts (vmap lanes over [B] vs
one flat [B*W] batch) — the same property that makes the fused loop's
greedy lane bit-exact against the two-loop reference.

Correctness is also pinned by tests: beam=1 ≡ greedy, and a brute-force
enumeration oracle on a tiny vocab (SURVEY.md §4 item 2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cst_captioning_tpu.config.config import BOS_ID, EOS_ID, PAD_ID
from cst_captioning_tpu.decoding.common import (
    apply_min_len,
    carry_tally,
    forbid_special,
    lane_decode_step,
    row_logprobs,
    scan_until_finished,
)
from cst_captioning_tpu.models.captioner import CaptionModel, EncoderOutput

_NEG = -1.0e9

BEAM_IMPLS = ("lanes", "reference")


def _tile_beam(tree, beam: int):
    """[B, ...] leaves -> [B*beam, ...] (each row repeated beam times)."""
    return jax.tree.map(
        lambda x: jnp.repeat(x, beam, axis=0), tree
    )


def _gather_beams(tree, parent: jnp.ndarray, batch: int, beam: int):
    """Select parent beams: leaves [B*W, ...] indexed by parent [B, W]."""
    flat_idx = (jnp.arange(batch)[:, None] * beam + parent).reshape(-1)  # [B*W]
    return jax.tree.map(lambda x: x[flat_idx], tree)


def _gather_lanes(tree, parent: jnp.ndarray):
    """Select parent beams on LANE-major leaves: [W, B, ...] by parent [B, W].

    ``out[w, b] = leaf[parent[b, w], b]`` — the beam-hypothesis reorder as a
    cross-lane gather, the lane layout's spelling of ``_gather_beams``.
    """
    pT = parent.T  # [W, B]
    return jax.tree.map(
        lambda x: jnp.take_along_axis(
            x, pT.reshape(pT.shape + (1,) * (x.ndim - 2)), axis=0
        ),
        tree,
    )


def _pad_row(V: int) -> jnp.ndarray:
    """Continuation row for finished beams: logp 0 at PAD, -1e9 else."""
    return jnp.full((V,), _NEG).at[PAD_ID].set(0.0)


def _topk_expand(scores, finished, logp, pad_row, B: int, W: int, V: int):
    """The per-step beam expansion both impls share.

    (scores [B,W], finished [B,W], logp [B,W,V]) ->
    (top_scores [B,W], parent [B,W], tok [B,W]) — finished beams continue
    with the PAD-only row, one ``top_k`` over the flattened W*V candidates
    (ties break toward the lower flat index = lower beam, then lower token).
    """
    cont = jnp.where(finished[:, :, None], pad_row[None, None, :], logp)
    total = scores[:, :, None] + cont                      # [B, W, V]
    top_scores, flat = jax.lax.top_k(total.reshape(B, W * V), W)
    parent = flat // V                                     # [B, W]
    tok = (flat % V).astype(jnp.int32)
    return top_scores, parent, tok


def _state0(carry0, B: int, W: int, T: int, tally):
    """(carry, tokens, scores, finished, last, tally): beam 0 alone live at
    t=0; ``tally`` is what the encoder pass counted (``carry_tally``: ``()``
    for a decoder that counts nothing)."""
    return (
        carry0,
        jnp.full((B, W, T), PAD_ID, jnp.int32),
        jnp.concatenate([jnp.zeros((B, 1)), jnp.full((B, W - 1), _NEG)], axis=1),
        jnp.zeros((B, W), bool),
        jnp.full((B, W), BOS_ID, jnp.int32),
        tally,
    )


def _add(tally, more):
    return jax.tree.map(jnp.add, tally, more)


def _run_reference(model, params, enc, B, V, W, T, min_len, batch_axes):
    """The sequential spelling: beams flattened into the batch ([B*W] rows)."""
    tally0 = carry_tally(enc.carry)         # once a clip, before the tiling
    enc_tiled = _tile_beam(enc, W)          # leaves [B*W, ...]
    carry0 = enc_tiled.carry
    enc_tiled = EncoderOutput(
        enc_tiled.memory, enc_tiled.memory_proj, enc_tiled.memory_mask, carry=()
    )
    pad_row = _pad_row(V)

    def step(state, t):
        carry, tokens, scores, finished, last, tally = state
        carry, logits = model.apply(
            params,
            carry,
            last.reshape(B * W),
            enc_tiled,
            method=CaptionModel.decode_step,
        )
        tally = _add(tally, carry_tally(carry))
        logits = apply_min_len(forbid_special(logits), t, min_len)
        logp = row_logprobs(logits).reshape(B, W, V)
        top_scores, parent, tok = _topk_expand(
            scores, finished, logp, pad_row, B, W, V
        )

        carry = _gather_beams(carry, parent, B, W)
        tokens = jnp.take_along_axis(tokens, parent[:, :, None], axis=1)
        finished = jnp.take_along_axis(finished, parent, axis=1)
        tok = jnp.where(finished, jnp.full_like(tok, PAD_ID), tok)
        tokens = tokens.at[:, :, t].set(tok)
        finished = finished | (tok == EOS_ID)
        return (carry, tokens, top_scores, finished, tok, tally), None

    # Early exit once every beam of every row is finished — bit-identical to
    # the full T-step unroll: with all beams finished, every continuation row
    # is the PAD-only ``pad_row``, so the per-beam top candidate is its own
    # frozen score at PAD, and since top_k returned ``scores`` DESCENDING on
    # the step that finished the last beam (ties broken toward lower flat
    # index = lower beam), the next top_k re-selects the beams in their
    # current order: parent is the identity, tok is PAD everywhere, and the
    # whole state is a fixed point of ``step``.
    (_, tokens, scores, _, _, tally), _ = scan_until_finished(
        step, _state0(carry0, B, W, T, tally0), T, lambda s: s[3], None,
        batch_axes
    )
    return tokens, scores, tally


def _run_lanes(model, params, enc, B, V, W, T, min_len, batch_axes):
    """Beams on decode lanes: carry [W, B, ...], one shared-step lane per beam."""
    carry0 = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (W,) + x.shape), enc.carry
    )
    pad_row = _pad_row(V)
    use_kernel = getattr(model.cfg, "decode_impl", "xla") == "pallas"

    def step(state, t):
        carry, tokens, scores, finished, last, tally = state  # carry [W, B, ...]
        if use_kernel:
            from cst_captioning_tpu.ops.decode_pallas import fused_beam_step

            # step + candidate selection in ONE launch: blocked online
            # logsumexp and blocked top-W per (lane, vocab-block), cross-
            # lane merge in-kernel; only the hypothesis reorder (a cross-
            # lane gather) stays out here at the seam between launches
            carry, top_scores, flat = fused_beam_step(
                params["params"]["cell"], carry, last, finished.T,
                scores.T.astype(jnp.float32), enc.memory, enc.memory_proj,
                enc.memory_mask, t=t, min_len=min_len,
                num_layers=model.cfg.num_layers,
            )
            parent = flat // V
            tok = (flat % V).astype(jnp.int32)
            top_scores = top_scores.astype(scores.dtype)
        else:
            carry, logits = lane_decode_step(model, params, carry, last, enc)
            tally = _add(tally, carry_tally(carry))
            logits = apply_min_len(forbid_special(logits), t, min_len)
            logp = row_logprobs(logits).transpose(1, 0, 2)   # [B, W, V]
            top_scores, parent, tok = _topk_expand(
                scores, finished, logp, pad_row, B, W, V
            )

        carry = _gather_lanes(carry, parent)
        tokens = jnp.take_along_axis(tokens, parent[:, :, None], axis=1)
        finished = jnp.take_along_axis(finished, parent, axis=1)
        tok = jnp.where(finished, jnp.full_like(tok, PAD_ID), tok)
        tokens = tokens.at[:, :, t].set(tok)
        finished = finished | (tok == EOS_ID)
        return (carry, tokens, top_scores, finished, tok.T, tally), None

    # the lane-major state0: last tokens live as [W, B]
    carry, tokens, scores, finished, last, tally = _state0(
        carry0, B, W, T, carry_tally(enc.carry)
    )
    state0 = (carry, tokens, scores, finished, last.T, tally)
    # same all-finished fixed point as the reference (see _run_reference)
    (_, tokens, scores, _, _, tally), _ = scan_until_finished(
        step, state0, T, lambda s: s[3], None, batch_axes
    )
    return tokens, scores, tally


def beam_search(
    model: CaptionModel,
    params,
    feats: dict[str, jnp.ndarray],
    masks: dict[str, jnp.ndarray],
    beam_size: int = 5,
    max_len: int | None = None,
    min_len: int = 0,
    length_penalty: float = 0.0,
    return_all: bool = False,
    batch_axes: tuple[str, ...] = (),
    beam_impl: str = "lanes",
    return_tally: bool = False,
    enc: EncoderOutput | None = None,
):
    """-> (tokens [B, T], scores [B]) — or [B, W, T] / [B, W] if return_all.

    ``enc`` is the encoder pass where the caller has run it already
    (``CaptionModel.encode`` as a program of its own: ``feats`` and ``masks``
    are then not read).

    ``return_tally`` appends what the decoder counted over the search
    (``decoding.common.carry_tally`` summed over the encoder pass and every
    step run: a routed-expert decoder's token-expert assignments, this
    shard's; ``()`` for the LSTM).

    ``length_penalty`` α rescales final scores by ``1/len^α`` (α=0 matches the
    reference's pure sum-logprob ranking). ``beam_impl`` picks the lane-
    batched fast path ("lanes", default) or the sequential bit-parity
    reference ("reference") — token- and score-bit-exact against each other
    (module docstring).
    """
    if beam_impl not in BEAM_IMPLS:
        raise ValueError(
            f"beam_impl must be one of {BEAM_IMPLS}, got {beam_impl!r}"
        )
    W = beam_size
    T = max_len or model.cfg.max_len
    if enc is None:
        enc = model.apply(params, feats, masks, method=CaptionModel.encode)
    B = enc.memory_mask.shape[0]
    V = model.cfg.vocab_size

    run = _run_lanes if beam_impl == "lanes" else _run_reference
    tokens, scores, tally = run(
        model, params, enc, B, V, W, T, min_len, batch_axes
    )
    more = (tally,) if return_tally else ()

    if length_penalty > 0.0:
        lengths = jnp.maximum((tokens != PAD_ID).sum(axis=-1), 1).astype(jnp.float32)
        ranked = scores / (lengths**length_penalty)
    else:
        ranked = scores
    if return_all:
        order = jnp.argsort(-ranked, axis=1)
        return (
            jnp.take_along_axis(tokens, order[:, :, None], axis=1),
            jnp.take_along_axis(ranked, order, axis=1),
            *more,
        )
    best = jnp.argmax(ranked, axis=1)                           # [B]
    best_tokens = jnp.take_along_axis(tokens, best[:, None, None], axis=1)[:, 0]
    best_scores = jnp.take_along_axis(ranked, best[:, None], axis=1)[:, 0]
    return (best_tokens, best_scores, *more)
