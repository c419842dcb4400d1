"""Shared decode-loop plumbing."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cst_captioning_tpu.config.config import BOS_ID, EOS_ID, PAD_ID


def selected_logprob(logits: jnp.ndarray, token: jnp.ndarray) -> jnp.ndarray:
    """Logprob of ``token`` under softmax(logits) — [..., V], [...] -> [...].

    ``logit - logsumexp(logits)`` on the selected row only: one [.., V]
    reduction plus a gather, instead of materializing the full ``[.., V]``
    ``log_softmax`` output just to gather one column from it (one fewer
    full-vocab pass per decode step). Matches ``log_softmax`` + gather to
    float association order.
    """
    lse = jax.nn.logsumexp(logits, axis=-1)
    sel = jnp.take_along_axis(logits, token[..., None], axis=-1)[..., 0]
    return sel - lse


def row_logprobs(logits: jnp.ndarray) -> jnp.ndarray:
    """Full log-softmax row in the :func:`selected_logprob` association.

    ``logits - logsumexp(logits, keepdims=True)`` — gathering a column of
    this row is BITWISE equal to ``selected_logprob(logits, token)`` (same
    subtraction, same operand order), which is what lets the beam loops
    score whole rows while the greedy/sampling loops score one token, with
    one shared primitive. Note this differs from ``jax.nn.log_softmax`` by
    float association (``x - (max + log s)`` vs ``(x - max) - log s``), so
    every decoder that wants cross-impl bit-parity must route through here.
    """
    return logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)


def npad_best_lane_index(logprobs) -> jnp.ndarray:
    """[G, .., T] per-token logprobs -> [..] best lane per row (NPAD pick).

    Noisy Parallel Approximate Decoding (arXiv 1605.03835): run the greedy
    lane plus M noise-perturbed lanes, answer with the highest-sum-logprob
    lane. Post-EOS emissions carry logprob 0.0 (``step_outputs``), so the
    sum is exactly the sequence logprob. argmax ties break toward the
    LOWEST lane — lane 0 is the unperturbed greedy lane, so the anytime
    answer degrades to greedy, never below it. Backend-agnostic on purpose
    (pure array methods): the serving engine calls it on host numpy
    tickets ([G, T] -> scalar), the evaluator on device arrays
    ([G, B, T] -> [B]).
    """
    return logprobs.sum(axis=-1).argmax(axis=0)


def npad_best_lane(tokens: jnp.ndarray, logprobs: jnp.ndarray):
    """Select the NPAD answer: ([G, B, T], [G, B, T]) -> ([B, T], [B]).

    Returns the best lane's token rows and their sum-logprob scores,
    gathered with ``take_along_axis`` so the whole selection stays on
    device (one scalar readback for the caller, not G of them).
    """
    best = npad_best_lane_index(logprobs)                       # [B]
    idx = best[None, :, None]                                   # [1, B, 1]
    best_tokens = jnp.take_along_axis(tokens, idx, axis=0)[0]   # [B, T]
    best_scores = jnp.take_along_axis(
        logprobs.sum(axis=-1), best[None, :], axis=0
    )[0]                                                        # [B]
    return best_tokens, best_scores


def rollout_step_keys(rng: jax.Array, num_rollouts: int, length: int) -> jax.Array:
    """[T, K] typed key array with ``keys[t, k] == fold_in(fold_in(rng, k), t)``.

    The sampling loops' per-step RNG discipline, precomputed OUTSIDE the
    scan: the step body gathers row ``t`` (one dynamic slice of K keys)
    instead of re-folding K keys every iteration — bit-identical streams by
    construction (same fold chain), asserted in tests/test_decoding.py.
    Steps past ``length`` (the early-exit loop's overhang, see
    :func:`scan_until_finished`) clamp to row T-1; their draws are
    select-frozen out of the outputs, so the clamped reuse is unobservable.
    """
    keys = jax.vmap(lambda k: jax.random.fold_in(rng, k))(
        jnp.arange(num_rollouts)
    )
    return jax.vmap(
        lambda t: jax.vmap(lambda key: jax.random.fold_in(key, t))(keys)
    )(jnp.arange(length))


def gumbel_step_noise(step_keys_t: jax.Array, shape: tuple[int, ...],
                      dtype) -> jax.Array:
    """[K] keys -> [K, *shape] Gumbel noise — ``jax.random.categorical``'s
    internals, reified.

    ``categorical(key, logits)`` is by definition
    ``argmax(logits + gumbel(key, logits.shape, logits.dtype))`` (the Gumbel
    -max trick; jax implements it literally), and IEEE addition is
    commutative, so selecting via this precomputed noise is BIT-IDENTICAL
    to the categorical call it replaces (pinned in tests/test_decoding.py).
    Reifying the noise is what lets (a) the compacted decode draw in
    ORIGINAL batch order and gather rows through the compaction permutation
    — drawing after the gather would pair rows with different streams — and
    (b) the Pallas stride kernel select tokens in-kernel on the exact same
    RNG streams (the noise is data; the argmax moves inside).
    """
    return jax.vmap(lambda k: jax.random.gumbel(k, shape, dtype))(step_keys_t)


def _cdf_block(V: int) -> int:
    """Columns a block of :func:`inverse_cdf_index`, from the shape: the
    largest multiple of 8 up to 128 that divides V (9000 = 75 x 120), so
    that cutting V into blocks is a view of the logits where V runs along
    the tiles' 8 sublanes (rows on the 128 lanes); where V has no such
    divisor, ``min(128, V)``, and the last block is padded."""
    for w in range(128, 7, -8):
        if V % w == 0:
            return w
    return min(128, V)


def _first_over(mass: jnp.ndarray, cum: jnp.ndarray,
                target: jnp.ndarray) -> jnp.ndarray:
    """[..., N] terms and their cumulative sums, [...] targets -> [...]
    int32: the first position that HAS mass and whose cumulative sum passes
    the target; where none does, the last position that has mass. The test
    on the term itself is what makes "never a term of zero mass" hold by
    construction, whatever order a parallel prefix sum associated in."""
    n = mass.shape[-1]
    pos = jnp.arange(n, dtype=jnp.int32)
    has = mass > 0
    over = has & (cum > target[..., None])
    first = jnp.min(jnp.where(over, pos, n), axis=-1)
    last = jnp.max(jnp.where(has, pos, 0), axis=-1)
    return jnp.where(first < n, first, last)


# what the running maximum of :func:`_max_and_sumexp` starts from, and what
# pads a short last block: finite, so that the difference of two of them is
# 0.0 and not the NaN two infinities give
_LOWEST = -3.0e38


def _max_and_sumexp(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[..., w] -> ([...] max, [...] sum of ``exp(x - max)``) over the last
    axis in ONE reduction: the pair (m, s) is carried and rescaled as the
    maximum rises, ``(m1, s1) + (m2, s2) = (m, s1 e^(m1 - m) + s2 e^(m2 -
    m))`` with ``m = max(m1, m2)``, so the logits are read once where a
    maximum and then a sum read them twice."""

    def merge(a, b):
        (m1, s1), (m2, s2) = a, b
        m = jnp.maximum(m1, m2)
        return m, s1 * jnp.exp(m1 - m) + s2 * jnp.exp(m2 - m)

    return jax.lax.reduce(
        (x, jnp.ones_like(x)),
        (jnp.asarray(_LOWEST, x.dtype), jnp.asarray(0.0, x.dtype)),
        merge, (x.ndim - 1,),
    )


def inverse_cdf_index(tl: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """[..., V] masked, tempered logits and [...] uniforms in [0, 1) ->
    [...] int32: each row's sample of ``softmax(tl)`` by the inverse CDF,
    the first column whose cumulative probability passes ``u``.

    One draw a row where Gumbel-max (:func:`gumbel_step_noise`) makes V.
    Two levels, so that nothing of the logits' size is written and no scan
    runs over V, and two reads of the logits in all. First each block of
    consecutive columns (:func:`_cdf_block`) gives its maximum and its sum
    of exponentials (:func:`_max_and_sumexp`); scaled to the row's maximum
    these are the block sums of ``e = exp(tl - max)``, whose cumulative sum
    gives ``target = u * total`` and the block it falls in. Then that one
    block of *logits* a row is exponentiated again and summed cumulatively
    from the block's base. Both reads take the logits as ``[..., blocks,
    width]``, a view of them as the chip lays them out at the RL decode's
    batch (rows on the minor-most axis, V along the tiles), and do their
    arithmetic there, so each is one fused reduction; the row's block is
    picked out by a masked sum over the blocks and not by a gather, which
    along V would first copy all the logits into another layout.

    Guarantees: the column returned has ``e > 0``, so a column masked to
    ``-1e9`` (``forbid_special``, ``apply_min_len``) is never emitted; and
    where rounding between a block's sum and the sum inside it would run
    past the block's end, the row takes the block's last column with
    ``e > 0`` (:func:`_first_over`, at both levels). ``u < 1`` keeps
    ``target`` under the total.

    Fidelity: exact to the resolution of one f32 uniform. The grain is
    2^-24 of the total, so no token of probability under about 6e-8 is
    drawn, which Gumbel-max from f32 uniforms does not do either (its noise
    is capped near 16.6 nats). No precision is lowered and no row skipped.
    """
    V = tl.shape[-1]
    w = _cdf_block(V)
    blocks = -(-V // w)
    if blocks * w != V:
        tl = jnp.pad(tl, [(0, 0)] * (tl.ndim - 1) + [(0, blocks * w - V)],
                     constant_values=_LOWEST)
    x = tl.reshape(tl.shape[:-1] + (blocks, w))
    block_max, block_sum = _max_and_sumexp(x)
    m = jnp.max(block_max, axis=-1, keepdims=True)
    sums = block_sum * jnp.exp(block_max - m)
    cum = jnp.cumsum(sums, axis=-1)
    target = u * cum[..., -1]
    block = _first_over(sums, cum, target)
    at = jnp.arange(blocks, dtype=jnp.int32) - block[..., None]
    base = jnp.sum(jnp.where(at == -1, cum, 0.0), axis=-1)
    e_in = jnp.exp(
        jnp.sum(jnp.where((at == 0)[..., None], x, 0.0), axis=-2) - m
    )
    inside = _first_over(e_in, base[..., None] + jnp.cumsum(e_in, axis=-1),
                         target)
    return block * w + inside


def sample_lanes(step_keys_t: jax.Array, tl: jnp.ndarray) -> jnp.ndarray:
    """[K] keys and [K, B, V] masked, tempered logits -> [K, B] int32
    tokens: rollout ``k`` draws ONE uniform a row from its key and takes the
    row's token by :func:`inverse_cdf_index`, so a row's stream depends on
    its key and its place among the rows it was drawn with, never on their
    logits."""
    u = jax.vmap(
        lambda k: jax.random.uniform(k, tl.shape[1:-1], tl.dtype)
    )(step_keys_t)
    return inverse_cdf_index(tl, u)


def lane_decode_step(model, params, carry, token, enc):
    """One decoder step over a LANE-batched state: [G, B, ...] -> [G, B, V].

    The shared step of every decode loop (greedy runs G=1, K-rollout
    sampling G=K, the fused RL loop G=1+K — all lanes share the encoder
    output, closed over unbatched so XLA reads the memory bank once per
    step). Dispatches on ``model.cfg.decode_impl``: "xla" vmaps
    ``CaptionModel.decode_step``; "pallas" calls the fused decode-step
    kernel (ops/decode_pallas.py — attention + LSTM stack + out_proj in one
    launch, weights resident in VMEM across the row grid). Decode is
    inference-only, so the kernel needs no VJP. A decoder kind that takes
    its step for all lanes at once (``models.captioner.ALL_LANES``) is
    called so and not vmapped.
    """
    if getattr(model.cfg, "decode_impl", "xla") == "pallas":
        from cst_captioning_tpu.ops.decode_pallas import fused_decode_step

        return fused_decode_step(
            params["params"]["cell"], carry, token,
            enc.memory, enc.memory_proj, enc.memory_mask,
            num_layers=model.cfg.num_layers,
        )

    from cst_captioning_tpu.models.captioner import ALL_LANES, CaptionModel

    if model.cfg.decoder in ALL_LANES:
        # the kind's own step over all lanes at once: G x B rows as one list
        # through its routed experts, attention grouped by clip
        return model.apply(
            params, carry, token, enc, method=CaptionModel.decode_lanes
        )

    def one_lane(carry_k, token_k):
        return model.apply(
            params, carry_k, token_k, enc, method=CaptionModel.decode_step
        )

    return jax.vmap(one_lane)(carry, token)


def carry_tally(carry):
    """What a decoder's last call counted for the decode loop to sum, off a
    carry whose leaves lead with batch (and lane) axes: the ``routed``
    leaf of a routed-expert decoder's state (models/latent_moe.py: token-
    expert assignments, rows a held expert) or the ``counted`` leaf of the
    sparse/linear decoder's (models/sparse_linear.py: keys its sparse layers'
    queries saw and attended to) and of the EVA decoder's (models/eva.py:
    exact keys and summaries a query attended to, windows a caption
    entered), of the window/full decoder's (models/window_moe.py: pairs
    a window and a full layer attended) and of the compressed-latent
    decoder's (models/cca_moe.py: pairs attended, rows that chose no
    expert), summed over every axis but its last
    two; ``()`` for a carry that counts nothing (the LSTM's), which adds no leaf
    to the loop's state."""
    found = [counts.sum(axis=tuple(range(counts.ndim - 2)))
             for counts in (getattr(carry, leaf, None)
                            for leaf in ("routed", "counted"))
             if counts is not None]
    # one leaf as it is; both (models/window_moe.py, models/cca_moe.py:
    # routed experts and attended pairs) as the pair (routed, counted)
    return found[0] if len(found) == 1 else tuple(found)


def pcast_varying(tree, axes: tuple[str, ...]):
    """pcast every leaf to "varying" over ``axes`` it isn't already varying on.

    Inside ``shard_map(..., check_vma=True)`` loop-carried state must keep one
    varying-axis type across iterations; decode inits mix device-invariant
    constants (BOS tokens, zero buffers) with already-varying encoder state,
    so only the missing axes are cast (pcast of an already-varying leaf would
    be rejected). No-op outside shard_map (``axes`` empty).
    """
    if not axes:
        return tree

    def cast(x):
        vma = jax.typeof(x).vma
        for a in axes:
            if a not in vma:
                x = jax.lax.pcast(x, a, to="varying")
        return x

    return jax.tree.map(cast, tree)


def _exit_stride(length: int) -> int:
    """Steps per exit check: a divisor of ``length`` near 5 when one exists.

    The while condition forces a scalar-core sync per iteration (~0.2-0.3ms
    pipeline bubble on TPU, measured round 5); checking every ~5 steps
    amortizes it to noise while keeping the exit granularity fine enough
    that converged policies (captions well under T) still skip most of the
    tail. A divisor avoids overhang steps in the never-finishing case.
    """
    for c in (5, 6, 4, 3, 7, 2):
        if length % c == 0:
            return c
    return min(4, length)


def scan_until_finished(step, init, length: int, get_finished, y_fills,
                        batch_axes: tuple[str, ...] = ()):
    """``lax.scan(step, init, jnp.arange(length))`` with EOS early exit.

    Runs ``step`` in stride-sized ``lax.scan`` chunks under a
    ``lax.while_loop`` that stops once every row has finished (or ``length``
    steps ran) — the decode loops spend most of a T=30 budget emitting
    post-EOS padding on converged policies, and the while loop skips exactly
    that tail while keeping every shape static.

    Bit-exactness contract (the caller's to uphold): once
    ``get_finished(state)`` is all-True, ``step`` must be an identity on
    the OUTPUT-RELEVANT state components (whatever ``get_finished`` and
    the emitted ys read — finished flags, tokens, beam bookkeeping) and
    emit exactly ``y_fills`` — true for the EOS-frozen decode loops here
    (PAD token / 0.0 logprob emission; the beam step degenerates to the
    identity permutation, see beam.py). Under that contract the early exit
    returns ``ys`` bit-identical to the full scan: the y-buffers are
    pre-filled with the post-finish emission, and any overhang step past
    ``length`` (non-divisor stride only) is select-frozen out of the state.

    The returned ``final_state`` is NOT covered by that guarantee: the
    decode steps keep evolving their LSTM carries on post-finish steps, so
    under early exit the carry differs from the full scan's (every caller
    here discards it). A future caller wanting the final carry must either
    freeze it in ``step`` once finished or decode without early exit.

    ``batch_axes`` names the mesh axes the batch dim is sharded over (when
    called inside ``shard_map``). The unfinished-row count is psum'd over
    them in the loop BODY, so (a) every shard exits on the same step —
    uniform control flow — and (b) the while condition reads an invariant
    carried scalar, keeping ``check_vma=True`` sound (collectives stay out
    of the cond computation). The rest of the carry is pcast to varying over
    the same axes so its type is loop-invariant.

    ``y_fills``: pytree of scalars matching the step's y output structure.
    Returns ``(final_state, ys)`` with ys stacked on axis 0, like scan.
    """
    stride = _exit_stride(length)
    padded = -(-length // stride) * stride

    def count_unfinished(state):
        n = jnp.sum(jnp.logical_not(get_finished(state)).astype(jnp.int32))
        for ax in batch_axes:
            n = jax.lax.psum(n, ax)
        return n

    y_aval = jax.eval_shape(lambda s: step(s, jnp.int32(0))[1], init)
    ys0 = jax.tree.map(
        lambda av, fill: jnp.full((padded,) + av.shape, fill, av.dtype),
        y_aval, y_fills,
    )
    init = pcast_varying(init, batch_axes)
    ys0 = pcast_varying(ys0, batch_axes)

    def cond(loop):
        t, _, _, unfinished = loop
        return (t < length) & (unfinished > 0)

    def inner(state, t):
        state2, y = step(state, t)
        if padded != length:
            # overhang steps past `length` must not mutate the state (the
            # beam carry IS the result) — freeze them; their y rows are
            # sliced off below, the select just keeps dtypes aligned
            live = t < length
            state2 = jax.tree.map(
                lambda a, b: jnp.where(live, a, b), state2, state
            )
        return state2, y

    def body(loop):
        t, state, ys, _ = loop
        state, chunk = jax.lax.scan(inner, state, t + jnp.arange(stride))
        ys = jax.tree.map(
            lambda buf, c: jax.lax.dynamic_update_slice_in_dim(buf, c, t, 0),
            ys, chunk,
        )
        return t + stride, state, ys, count_unfinished(state)

    _, state, ys, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), init, ys0, count_unfinished(init))
    )
    if padded != length:
        ys = jax.tree.map(lambda buf: buf[:length], ys)
    return state, ys


def forbid_special(logits: jnp.ndarray) -> jnp.ndarray:
    """Mask PAD/BOS columns to -inf for decoding.

    The reference's vocab overloads id 0 as its pad/end token, so sampling it
    means "stop"; here PAD and EOS are distinct ids, so decoders must never
    *emit* PAD or BOS — EOS is the only way to end a caption.
    """
    neg = jnp.full_like(logits[..., :1], -1e9)
    return logits.at[..., PAD_ID].set(neg[..., 0]).at[..., BOS_ID].set(neg[..., 0])


def step_outputs(
    token: jnp.ndarray,      # [B] token chosen this step
    logprob: jnp.ndarray,    # [B] its logprob
    finished: jnp.ndarray,   # [B] bool: sequence already emitted EOS
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Force PAD / zero-logprob after EOS; returns (token, logprob, finished')."""
    token = jnp.where(finished, jnp.full_like(token, PAD_ID), token)
    logprob = jnp.where(finished, jnp.zeros_like(logprob), logprob)
    finished = finished | (token == EOS_ID)
    return token, logprob, finished


def mask_from_tokens(tokens: jnp.ndarray) -> jnp.ndarray:
    """[.., T] decoded tokens -> float mask counting real tokens incl. EOS."""
    return (tokens != PAD_ID).astype(jnp.float32)


def caption_depth(tokens: jnp.ndarray) -> jnp.ndarray:
    """[.., T] decoded tokens -> int32 scalar: positions up to and including
    the last one at which any row holds a token (:func:`mask_from_tokens`
    is 0.0 at every position from there on); 0 when every row is PAD. For
    left-aligned captions it is the longest caption's length, EOS included.
    """
    T = tokens.shape[-1]
    held = jnp.any(tokens.reshape(-1, T) != PAD_ID, axis=0)
    return jnp.max(jnp.where(held, jnp.arange(1, T + 1, dtype=jnp.int32), 0))


def apply_min_len(logits: jnp.ndarray, t, min_len: int) -> jnp.ndarray:
    """Suppress EOS while step ``t`` < ``min_len`` (prevents empty captions).

    The reference ranks beams by pure sum-logprob, which lets EOS-first beams
    win on weak models; a min caption length is the standard guard. No-op for
    ``min_len`` 0 (reference behavior).
    """
    if min_len <= 0:
        return logits
    blocked = logits.at[..., EOS_ID].set(-1.0e9)
    return jnp.where(t < min_len, blocked, logits)
