"""SCST orchestration: fused decode dispatch -> host reward -> REINFORCE update.

The throughput-critical path (SURVEY.md §3.2, the north-star metric). Design
vs the reference's per-batch host↔device ping-pong:

1. ``make_rl_decode``   — ONE jitted program, ONE scan loop: the greedy
   baseline rides as lane 0 of the (1+K)-lane rollout scan
   (decoding/fused.py), sharing the encoder pass and every per-step
   attention/LSTM dispatch with the K multinomial rollouts (the reference
   runs two separate ``model.sample`` calls; the pre-PR-4 build ran two
   sequential scan loops in one program — kept behind ``fused=False`` as
   the bit-exactness reference).
2. Host: ``RewardComputer`` scores rollouts + greedy against the consensus
   pools (vectorized numpy, precomputed df); advantage = reward − baseline
   (greedy SCST or self-consensus SCB).
3. ``make_rl_update``   — second jitted program teacher-forces the sampled
   tokens to get *differentiable* logprobs and applies the REINFORCE grad
   (psum-DP in the parallel variant).

Two dispatches, not ``io_callback``, exactly per SURVEY.md §7 step 5: the
reward stays debuggable on host, the device work stays fused.
"""

from __future__ import annotations

import itertools
from typing import Any, Callable, Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, PartitionSpec as P

from cst_captioning_tpu import obs
from cst_captioning_tpu.config.config import PAD_ID, RLConfig
from cst_captioning_tpu.decoding import fused_decode, greedy_decode, sample_decode
from cst_captioning_tpu.decoding.common import _exit_stride, mask_from_tokens
from cst_captioning_tpu.obs import flops as _flops
from cst_captioning_tpu.losses import reinforce_loss, sequence_log_probs
from cst_captioning_tpu.models.captioner import CaptionModel, scan_positions
from cst_captioning_tpu.parallel.comms import local_params, reduce_tree
from cst_captioning_tpu.parallel.compile import CompilePlan, compile_fn
from cst_captioning_tpu.resilience import chaos
from cst_captioning_tpu.resilience.health import collective_span
from cst_captioning_tpu.resilience.retry import RetryPolicy, retry_call
from cst_captioning_tpu.rl.rewards import RewardComputer, scb_baseline
from cst_captioning_tpu.train.state import TrainState
from cst_captioning_tpu.train.steps import _apply


def compaction_stats(greedy_np, samples_np, stride: int, budget: int,
                     compact: bool = True) -> dict:
    """Host-side decode ledger from already-decoded tokens (no device reads).

    -> ``{depth, lanes_stepped, lanes_skipped}``: the scan depth the
    early-exit loop ran (next ``stride`` multiple of the longest row,
    capped at the padded budget), and how many (lane, batch-column) steps
    the compacted decode computed vs skipped. A lane stops computing after
    its own (EOS-inclusive) length: compaction packs still-active columns
    into a dense prefix and the stride kernel additionally skips a lane's
    batch block once every row in it is finished, so the row-granular
    ledger here is ``sum(min(len, depth))`` stepped out of ``G*B*depth``
    total (block granularity makes the realized kernel savings slightly
    lower — a block dies only when its last row does). Without ``compact``
    every lane rides to the global early exit. Feeds ``SCSTTrainer``'s
    ``rl.decode.compaction`` counter pair.
    """
    lanes = []
    if greedy_np is not None and np.asarray(greedy_np).size:
        lanes.append(np.asarray(greedy_np)[None])
    if samples_np is not None and np.asarray(samples_np).size:
        lanes.append(np.asarray(samples_np))
    if not lanes:
        return {"depth": 0, "lanes_stepped": 0, "lanes_skipped": 0}
    toks = np.concatenate(lanes, axis=0)                      # [G, B, T]
    G, B, _ = toks.shape
    stride = max(int(stride), 1)
    padded = -(-int(budget) // stride) * stride
    lens = (toks != PAD_ID).sum(axis=-1)                      # [G, B]
    depth = min(
        padded, stride * -(-max(int(lens.max()), 1) // stride)
    )
    total = G * B * depth
    if compact:
        stepped = int(np.minimum(lens, depth).sum())
    else:
        stepped = total
    return {
        "depth": int(depth),
        "lanes_stepped": stepped,
        "lanes_skipped": int(total - stepped),
    }


def sample_entropy(samples_np) -> float:
    """Empirical token-distribution entropy (nats) of the sampled lanes,
    from the already-on-host tokens — the flight recorder's entropy-collapse
    signal (a policy converging onto a few captions drives this toward 0
    while the reward mean can still look healthy). Pad tokens are excluded
    so short captions don't masquerade as low entropy."""
    toks = np.asarray(samples_np).ravel()
    toks = toks[toks != PAD_ID]
    if toks.size == 0:
        return 0.0
    counts = np.bincount(toks)
    p = counts[counts > 0] / toks.size
    return float(-(p * np.log(p)).sum())


def make_rl_decode(model, num_rollouts: int, temperature: float = 1.0,
                   max_len: int | None = None,
                   with_greedy: bool = True, fused: bool = True) -> Callable:
    """Jitted: (params, feats, masks, rng) -> (greedy [B,T], samples [K,B,T]).

    ``fused=True`` (default): ONE scan produces greedy and samples — the
    greedy baseline is lane 0 of the (1+K)-lane rollout scan
    (decoding/fused.py), eliminating the second loop's encoder pass, its
    per-step fixed overhead, and the duplicate attention/LSTM dispatch.
    ``fused=False`` is the two-loop reference the fused path is pinned
    bit-exact against (tests/test_rl.py).

    ``with_greedy=False`` skips the greedy rollout (``greedy`` is None):
    only the 'greedy' baseline consumes it, so the scb/none baselines save
    one of the K+1 decoded rows per clip plus its host transfer + reward
    (already one loop — ``fused`` changes nothing there)."""

    def decode(params, feats, masks, rng):
        if with_greedy and fused:
            greedy, _, samples, _ = fused_decode(
                model, params, feats, masks, rng,
                num_rollouts=num_rollouts, temperature=temperature,
                max_len=max_len,
            )
            return greedy, samples
        greedy = None
        if with_greedy:
            greedy, _ = greedy_decode(
                model, params, feats, masks, max_len=max_len
            )
        samples, _ = sample_decode(
            model, params, feats, masks, rng,
            num_rollouts=num_rollouts, temperature=temperature, max_len=max_len,
        )
        return greedy, samples

    return compile_fn(decode, CompilePlan())


def make_parallel_rl_decode(model, mesh: Mesh, num_rollouts: int,
                            temperature: float = 1.0,
                            max_len: int | None = None,
                            axis: str = "data",
                            with_greedy: bool = True,
                            fused: bool = True) -> Callable:
    """shard_map decode: batch sharded over the mesh, the dominant RL cost
    scales with chips (SURVEY.md §3.2/§7 step 6) instead of running on one.

    Decode has no cross-example interaction, so each device decodes its own
    batch shard. The greedy path is deterministic — sharded output equals the
    single-device decode of the concatenated batch (pinned by
    tests/test_rl.py). Sampling folds ``axis_index`` into the rollout key so
    shards draw independent streams.
    """

    def device_decode(params, feats, masks, rng):
        local_rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
        if with_greedy and fused:
            greedy, _, samples, _ = fused_decode(
                model, params, feats, masks, local_rng,
                num_rollouts=num_rollouts, temperature=temperature,
                max_len=max_len, batch_axes=(axis,),
            )
            return greedy, samples
        greedy = None
        if with_greedy:
            greedy, _ = greedy_decode(
                model, params, feats, masks, max_len=max_len,
                batch_axes=(axis,),
            )
        samples, _ = sample_decode(
            model, params, feats, masks, local_rng,
            num_rollouts=num_rollouts, temperature=temperature, max_len=max_len,
            batch_axes=(axis,),
        )
        return greedy, samples

    # check_vma stays ON (VERDICT r4 weak #3 closed): the decode loops pcast
    # their device-invariant inits (BOS tokens, output buffers) to varying
    # over ``batch_axes`` and psum the early-exit row count over it, so the
    # compiler verifies the per-shard/collective split instead of a comment
    # promising the exactness tests will.
    return compile_fn(device_decode, CompilePlan(
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P()),
        out_specs=(P(axis), P(None, axis)),
    ))


def _tile_enc(enc, K):
    """EncoderOutput [B, ...] -> [K*B, ...] (rollout-major tiling to match
    ``samples.reshape``).

    Tiling the ENCODED memory instead of the raw features lets the update
    run the encoder once per clip instead of once per rollout row — the
    encoder is ~12% of the update FLOPs at the flagship dims, and gradients
    flow through the tile as a sum over the K copies (same math as the
    feature-tiled computation up to float summation order)."""
    t = lambda x: jnp.tile(x, (K,) + (1,) * (x.ndim - 1))
    return jax.tree.map(t, enc)


def _decode_loss_sums(model, params, enc_tiled, tokens_flat, advantage_flat,
                      valid_tiled):
    """``(numerator, (denominator, positions))``: the REINFORCE sums from
    tiled encoder output, and beside them what teacher forcing ran for
    these rows (``models.captioner.scan_positions``: int32 ``[positions
    run, positions]``).

    ``valid_tiled`` zeroes wrap-padded duplicate rows from short final
    batches so they carry no gradient weight and don't dilute the
    normalization. Uses the ``teacher_force_logps`` path: each step's
    logits are reduced to the target-token logprob in the step, in the
    forward pass and again in the backward pass, which keeps the carries and
    no ``[T, rows, V]`` residual; and neither pass runs a position past the
    last at which one of THESE rows holds a token, so the bound is per
    rollout chunk, per row block and, inside ``shard_map``, per shard, with
    no collective. The word embedding is outside both loops: these rows'
    input tokens are looked up once before the forward loop and their
    cotangents summed into the table once after the backward loop
    (``models.captioner._bounded_logps``); the gauge
    ``rl.update.embed_rows`` says how many, positions x rows."""

    obs.gauge("rl.update.embed_rows").set(float(tokens_flat.size))
    logp = model.apply(
        params, enc_tiled, tokens_flat, method=CaptionModel.teacher_force_logps
    )
    mask = mask_from_tokens(tokens_flat) * valid_tiled[:, None]
    den = jnp.sum(mask)
    num = reinforce_loss(logp, mask, advantage_flat) * jnp.maximum(den, 1.0)
    return num, (den, scan_positions(tokens_flat))


# Teacher-forced rows of one rollout chunk that a device runs as one block.
# The update's backward loop keeps two accumulators for the cotangent of the
# attention bank, bf16 [rows, slots, d] (`memory`) and [rows, slots, d_att]
# (`memory_proj`), read, added to and written at every position it runs; the
# rows decide whether the compiler holds them in the chip's fast memory or
# in HBM. The weight gradients' f32 accumulators cost the same whatever the
# rows and are paid a position once more with every block, `out_proj`'s
# [d, V] first among them; the word embedding's [V, d] is no longer one of
# them (PR 41: its rows are summed into it once a block after the loop, so a
# smaller block pays one more scatter of the same rows in all, not one more
# dense table a position). Readings of `scripts/update_row_sweep.py` on one
# TPU v5e, the update alone at B=1792, K=5, update_chunks=5, preset 4's
# widths, every block at the depth (my chip runs, PR 41; PERF.md section
# 6), block rows -> ms an update at depth 30 / 20 of 30, the two
# accumulators' memory (`memory` / `memory_proj`):
#   1792 -> 334.0 / 231.0 (hbm / hbm)     896 -> 286.1 / 199.7 (hbm / hbm)
#    448 -> 251.9 / 176.9 (fast / fast)   224 -> 224.8 / 160.3 (fast / fast)
# (before PR 41, the dense table a position: 448 -> 311.3 / 213.4, and at
# depth 20 1792 -> 242.0, 896 -> 211.6, 224 -> 187.9: my chip runs, PRs 37
# and 41.) 448 it is: a data-parallel shard of 448 rows (B=1792 on four
# chips) keeps the program it had, and one chip runs that program four
# times. 224 reads 9 % better still (it was 12 %: a block of 224 no longer
# pays the embedding's accumulator a second time); it would cut the
# four-chip program too (ROADMAP S1(c)).
_ROW_BLOCK_CAP = 448


def _zero_tally():
    """What :func:`_decode_loss_sums` returns beside the numerator, at zero:
    the denominator and the teacher-forcing scan's ``[positions run,
    positions]``."""
    return jnp.zeros(()), jnp.zeros((2,), jnp.int32)


def _positions(metrics: dict, positions) -> dict:
    """The update's metrics with the scan's tally beside them, one scalar
    each so that whatever reads a step's metrics reads them too."""
    return {**metrics, "positions_run": positions[0],
            "positions": positions[1]}


def _varying(tree, axis: str | None):
    """Every leaf typed varying over the shard_map ``axis``; the tree as it
    is outside shard_map (``axis`` None)."""
    if axis is None:
        return tree
    return jax.tree.map(
        lambda x: jax.lax.pcast(x, axis, to="varying"), tree
    )


def _row_block(rows: int, cap: int) -> int:
    """Rows of one block: ``rows`` itself when it is at or under ``cap``,
    else the largest divisor of ``rows`` in (cap/2, cap] (blocks are equal),
    else ``rows`` again: a row count with no such divisor is not cut."""
    if rows <= cap:
        return rows
    for block in range(cap, cap // 2, -1):
        if rows % block == 0:
            return block
    return rows


def _chunked_loss_grads(model, params, feats, masks, samples, advantage,
                        valid, chunks: int, vary_axis: str | None = None,
                        comm=None):
    """REINFORCE loss sums + gradients of one update: ``(num, den, g_sum)``,
    accumulated over ``chunks`` slices of the K rollout axis
    (:func:`_block_loss_grads`, which has the mechanism and the ``comm``
    overlap contract) and, where the shape asks for it, over blocks of rows.

    ``rl.update_chunks`` divides K, not the batch: a chunk teacher-forces
    K/chunks rollouts of EVERY row the device holds. Rows never interact
    before the final sums (encoder, teacher forcing, loss numerator and
    denominator are all per row), so when a chunk's K/chunks x B rows exceed
    ``_ROW_BLOCK_CAP`` the whole computation, encoder included, runs over
    consecutive equal row blocks (:func:`_row_block`) in a ``lax.scan`` and
    the blocks' f32 sums are added: the same mathematics in another
    summation order, the very program a data-parallel shard of that many
    rows runs, less the all-reduce. The block comes from the traced shapes
    alone; there is no knob. Rows at or under the cap, and row counts with
    no divisor between half the cap and the cap, take exactly the unblocked
    path (the same lowered text as before row blocks existed). The gauges
    ``rl.update.row_blocks`` / ``rl.update.block_rows`` are set when the
    program is traced and say what was chosen.

    With ``comm`` overlap each block reduces its own chunks' gradients
    inside its scan and hands back reduced gradients, and the sum of the
    blocks' reduced gradients is the reduced sum: the row loop composes
    with the per-chunk reduction ("defer" stays bit-equal to "eager"), at
    ``blocks`` times the reductions.
    """
    K, B, T = samples.shape
    if K % chunks:
        raise ValueError(f"update_chunks {chunks} must divide K={K} rollouts")
    overlap = comm is not None and comm.overlap != "off"
    if overlap and vary_axis is None:
        raise ValueError(
            "comm overlap needs vary_axis (the per-chunk reduction runs "
            "inside shard_map); single-device updates have nothing to "
            "overlap"
        )
    if vary_axis is not None:
        # per-shard LOCAL grads below; the caller (or the overlap path
        # there) owns the one explicit reduction over the axis
        params = local_params(params, vary_axis)

    block = _row_block(B, max(_ROW_BLOCK_CAP // (K // chunks), 1))
    blocks = B // block
    obs.gauge("rl.update.row_blocks").set(float(blocks))
    obs.gauge("rl.update.block_rows").set(float(block))

    def run(f, m, s, a, v):
        return _block_loss_grads(
            model, params, f, m, s, a, v, chunks, vary_axis, comm
        )

    if blocks == 1:
        return run(feats, masks, samples, advantage, valid)

    rows = lambda x: x.reshape((blocks, block) + x.shape[1:])
    cols = lambda x: jnp.moveaxis(
        x.reshape((K, blocks, block) + x.shape[2:]), 1, 0
    )

    def body(acc, x):
        return jax.tree.map(jnp.add, acc, run(*x)), None

    # the carry's types: a block's sums vary over the batch axis, and so do
    # its gradients unless the overlap path has reduced them already
    num, tally = _varying((jnp.zeros(()), _zero_tally()), vary_axis)
    g_sum = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), params)
    if not overlap:
        g_sum = _varying(g_sum, vary_axis)
    xs = (
        jax.tree.map(rows, feats), jax.tree.map(rows, masks),
        cols(samples), cols(advantage), rows(valid),
    )
    acc, _ = jax.lax.scan(body, (num, tally, g_sum), xs)
    return acc


def _block_loss_grads(model, params, feats, masks, samples, advantage,
                      valid, chunks: int, vary_axis: str | None = None,
                      comm=None):
    """REINFORCE loss sums + gradients, accumulated over ``chunks`` slices
    of the K rollout axis — with ONE encoder pass shared by every chunk.

    Teacher-forcing all K*B sequences at once is the HBM ceiling on batch
    size (VERDICT r2 weak #1); chunking bounds the live activation footprint
    to K/chunks rollouts. The encoder runs once on the B clip rows
    (``jax.vjp`` keeps its backward); each scanned chunk differentiates the
    decode w.r.t. (params, encoder output), the encoder-output cotangents
    accumulate in f32 across chunks, and one ``enc_vjp`` call at the end
    folds them into the parameter gradients. Same total gradient as the
    feature-tiled computation up to float summation order.

    ``comm`` (parallel/comms.CommConfig) with ``overlap != "off"`` moves the
    cross-device grad allreduce INSIDE the scan (needs ``vary_axis``): each
    chunk's parameter grads are reduced per chunk instead of accumulate-
    then-reduce, so the collective can run while the next chunk's backward
    computes. Two spellings, bit-identical to each other at f32:

    - ``"defer"`` — the production overlap: a double-buffered carry holds
      the PREVIOUS chunk's unreduced grads; iteration *i* issues the psum
      of chunk *i-1*'s grads alongside chunk *i*'s forward+backward, giving
      the scheduler a full chunk of compute to hide each collective behind
      (one flush reduction after the scan drains the buffer).
    - ``"eager"`` — reduce each chunk's grads in its own iteration; no
      buffering, nothing to overlap. Float-order-identical to "defer"
      (defer merely adds a leading ``+ psum(zeros)``, a bitwise no-op), so
      it serves as its bit-exact parity reference in tests.

    When overlap is active the returned gradients are ALREADY reduced over
    ``vary_axis`` (axis-invariant); the caller must not psum them again —
    only the scalar num/den sums still need their reduction. Note the
    per-chunk reductions move (chunks+1)x the payload of the single fused
    reduction (each chunk reduces a full params-shaped tree, plus the
    encoder-cotangent fold at the end) — that is the latency-for-bandwidth
    trade, accounted by ``parallel.comms.ledger``.
    """

    K, B, T = samples.shape
    kc = K // chunks

    def enc_fn(p):
        e = model.apply(p, feats, masks, method=CaptionModel.encode)
        if vary_axis is not None:
            # inside shard_map, outputs that don't depend on the sharded
            # inputs (e.g. the meanpool encoder's all-ones memory_mask) are
            # device-INVARIANT, and the vjp would then reject the varying
            # per-shard cotangents accumulated below. Adding a varying zero
            # to every leaf makes the whole output uniformly varying; its
            # transpose lands in the (discarded) feats cotangent, so the
            # parameter gradients are untouched.
            zv = jnp.sum(jax.tree.leaves(feats)[0]) * 0.0
            e = jax.tree.map(lambda x: x + zv.astype(x.dtype), e)
        return e

    enc, enc_vjp = jax.vjp(enc_fn, params)
    valid_f = jnp.tile(valid, (kc,))
    sam = samples.reshape(chunks, kc * B, T)
    adv = advantage.reshape(chunks, kc * B)

    def sums_fn(p, e, tokens, a):
        return _decode_loss_sums(
            model, p, _tile_enc(e, kc), tokens, a, valid_f
        )

    overlap = comm is not None and comm.overlap != "off"

    def chunk_grads(x):
        return jax.value_and_grad(sums_fn, argnums=(0, 1), has_aux=True)(
            params, enc, *x
        )

    def accum_ge(ge_acc, ge):
        # f32 accumulation: the cotangents arrive in the model dtype
        # (bf16 on the flagship config) and 8 mantissa bits across
        # `chunks` additions is avoidable error
        return jax.tree.map(lambda a_, g: a_ + g.astype(a_.dtype), ge_acc, ge)

    # from shapes, not zeros_like: the (varying) params would hand their
    # type to the zeros, and the reduced-grad accumulator must be invariant
    zeros_p = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), params)
    zeros_e = jax.tree.map(
        lambda x: jnp.zeros(x.shape, jnp.promote_types(x.dtype, jnp.float32)),
        enc,
    )
    # inside shard_map the per-chunk grads/sums vary over the batch axis;
    # the scan carry init must carry the same varying-axis type
    vary = lambda t: _varying(t, vary_axis)

    if overlap:
        # gp_acc accumulates the REDUCED (axis-invariant) per-chunk grads;
        # gp_pend is the double buffer holding the previous chunk's
        # unreduced (varying) grads, drained one iteration late so its
        # psum can fly while this iteration's backward computes
        def body(acc, x):
            gp_acc, gp_pend, ge_acc, num_acc, tally_acc = acc
            if comm.overlap == "defer":
                gp_acc = jax.tree.map(
                    jnp.add, gp_acc, reduce_tree(gp_pend, vary_axis, comm)
                )
            (num, tally), (gp, ge) = chunk_grads(x)
            if comm.overlap == "eager":
                gp_acc = jax.tree.map(
                    jnp.add, gp_acc, reduce_tree(gp, vary_axis, comm)
                )
                gp = gp_pend  # buffer unused: stays the zeros it came in as
            return (
                gp_acc, gp, accum_ge(ge_acc, ge),
                num_acc + num, jax.tree.map(jnp.add, tally_acc, tally),
            ), None

        init = (
            zeros_p, vary(zeros_p), vary(zeros_e),
            vary(jnp.zeros(())), vary(_zero_tally()),
        )
        (gp, gp_pend, ge, num, tally), _ = jax.lax.scan(
            body, init, (sam, adv)
        )
        if comm.overlap == "defer":
            # flush: the last chunk's grads are still in the buffer ("defer"
            # is bit-equal to "eager" — its extra leading `+ psum(zeros)`
            # adds +0.0, a bitwise no-op)
            gp = jax.tree.map(
                jnp.add, gp, reduce_tree(gp_pend, vary_axis, comm)
            )
    else:
        def body(acc, x):
            gp_acc, ge_acc, num_acc, tally_acc = acc
            (num, tally), (gp, ge) = chunk_grads(x)
            return (
                jax.tree.map(jnp.add, gp_acc, gp), accum_ge(ge_acc, ge),
                num_acc + num, jax.tree.map(jnp.add, tally_acc, tally),
            ), None

        init = vary((zeros_p, zeros_e, jnp.zeros(()), _zero_tally()))
        (gp, ge, num, tally), _ = jax.lax.scan(body, init, (sam, adv))

    # vjp cotangents must match the primal dtype
    ge = jax.tree.map(lambda g, x: g.astype(x.dtype), ge, enc)
    (g_enc,) = enc_vjp(ge)
    if overlap:
        # keep the already-reduced invariant: fold the encoder grads in
        # reduced too, so the caller skips its own grad psum entirely
        g_enc = reduce_tree(g_enc, vary_axis, comm)
    g_sum = jax.tree.map(jnp.add, gp, g_enc)
    return num, tally, g_sum


def make_rl_update(model, chunks: int = 1, donate: bool = False,
                   guard: bool = False, comm=None,
                   stats: bool = False) -> Callable:
    """Jitted: (state, feats, masks, samples [K,B,T], adv [K,B]) -> (state, metrics).

    ``chunks > 1`` accumulates gradients over slices of the rollout axis
    (same total gradient, K/chunks of the activation memory — see
    :func:`_chunked_loss_grads`). ``donate=True`` donates the input state's
    buffers (params + Adam moments update in place; the passed-in state is
    consumed — rebind, never reuse); off by default so exactness tests can
    replay one state through several update variants. ``guard=True``
    suppresses non-finite updates on device (resilience/guard.py) and adds
    a ``nonfinite`` metric. ``comm`` (parallel/comms.CommConfig) is accepted
    for factory-signature symmetry and ignored: no collectives here.
    ``stats=True`` adds the flight recorder's per-family update-ratio
    metrics (train/steps._update_ratios) — extra outputs only, params
    bit-identical.
    """
    del comm  # no cross-device reduction on this path

    def update(state: TrainState, feats, masks, samples, advantage, valid):
        if chunks > 1:
            num, (den, positions), g_sum = _chunked_loss_grads(
                model, state.params, feats, masks, samples, advantage, valid,
                chunks,
            )
            den = jnp.maximum(den, 1.0)
            loss = num / den
            grads = jax.tree.map(lambda g: g / den, g_sum)
        else:

            K, B, T = samples.shape
            tokens = samples.reshape(K * B, T)
            adv = advantage.reshape(K * B)
            valid_f = jnp.tile(valid, (K,))

            def loss_fn(p):
                # one encoder pass per clip; memory tiled over rollouts
                enc = model.apply(p, feats, masks, method=CaptionModel.encode)
                num, (den, positions) = _decode_loss_sums(
                    model, p, _tile_enc(enc, K), tokens, adv, valid_f
                )
                return num / jnp.maximum(den, 1.0), positions

            (loss, positions), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.params)
        gnorm = optax.global_norm(grads)
        state, metrics = _apply(state, grads, loss, gnorm, guard,
                                key="rl_loss", stats=stats)
        return state, _positions(metrics, positions)

    return compile_fn(
        update, CompilePlan(donate_argnums=(0,) if donate else ())
    )


def make_parallel_rl_update(model, mesh: Mesh, axis: str = "data",
                            chunks: int = 1, donate: bool = False,
                            guard: bool = False, comm=None,
                            stats: bool = False) -> Callable:
    """shard_map variant: batch axis sharded, exact global normalization.
    ``chunks`` / ``donate`` / ``guard`` / ``stats`` exactly like
    :func:`make_rl_update`.

    ``comm`` (parallel/comms.CommConfig) selects the grad-allreduce
    spelling: None keeps the original per-leaf psum; otherwise bucketed
    (and optionally bf16) reduction, and with ``comm.overlap != "off"`` the
    per-chunk reduction runs inside the update scan so it can hide behind
    the next chunk's backward (see :func:`_chunked_loss_grads` — the
    chunked path then returns already-reduced grads).
    """
    overlap = comm is not None and comm.overlap != "off"
    if overlap and chunks < 2:
        raise ValueError(
            "comm overlap requires chunks >= 2: the rl.update_chunks "
            "boundary is the overlap seam (config validation enforces the "
            f"same; got chunks={chunks})"
        )

    def device_update(state, feats, masks, samples, advantage, valid):
        if chunks > 1:
            num, (den, positions), grads_num = _chunked_loss_grads(
                model, state.params, feats, masks, samples, advantage, valid,
                chunks, vary_axis=axis, comm=comm,
            )
        else:

            K, Bl, T = samples.shape
            tokens = samples.reshape(K * Bl, T)
            adv = advantage.reshape(K * Bl)
            valid_f = jnp.tile(valid, (K,))

            def local_num(p):
                enc = model.apply(p, feats, masks, method=CaptionModel.encode)
                return _decode_loss_sums(
                    model, p, _tile_enc(enc, K), tokens, adv, valid_f
                )

            (num, (den, positions)), grads_num = jax.value_and_grad(
                local_num, has_aux=True
            )(local_params(state.params, axis))
        den_total = jax.lax.psum(den, axis)
        loss = jax.lax.psum(num, axis) / jnp.maximum(den_total, 1.0)
        if not overlap:
            # the chunked-overlap path hands back already-reduced grads;
            # everything else reduces here, after the full local backward
            grads_num = reduce_tree(grads_num, axis, comm)
        grads = jax.tree.map(
            lambda g: g / jnp.maximum(den_total, 1.0), grads_num
        )
        gnorm = optax.global_norm(grads)
        # psum'd grads/loss are device-invariant: the guarded select picks
        # the same branch on every shard, so state stays replicated
        state, metrics = _apply(state, grads, loss, gnorm, guard,
                                key="rl_loss", stats=stats)
        # each shard bounds its own rows: the tally is the shards' sum
        return state, _positions(metrics, jax.lax.psum(positions, axis))

    return compile_fn(device_update, CompilePlan(
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(None, axis), P(None, axis), P(axis)),
        out_specs=(P(), P()),
        donate_argnums=(0,) if donate else (),
    ))


def _close(batches) -> None:
    """Close an epoch's batches where they can be closed (a generator over
    a feed, whose worker retires then; a plain iterator has nothing to)."""
    close = getattr(batches, "close", None)
    if close is not None:
        close()


class PrimedEpoch(NamedTuple):
    """What a pipelined :meth:`SCSTTrainer.train_epoch` that ran on over its
    epoch's end leaves for the next call (:attr:`SCSTTrainer.primed`): the
    next epoch, opened, with its first two batches decoded. The caller
    passes ``batches`` and ``rng`` as that call's; the call itself takes the
    pair over."""

    batches: Iterator   # the next epoch's batches after its first two
    rng: Any            # its key after those two splits
    scored: tuple | None    # batch 0': ``_apply``'s arguments (None: an
    #                         epoch of one batch, which is ``decoded``)
    decoded: tuple      # batch 1' (or 0'): ``_score``'s arguments
    first: tuple        # batch 0''s decoded (greedy, samples), its video ids


class SCSTTrainer:
    """Per-batch CST step: decode -> consensus reward -> REINFORCE update.

    ``baseline``: 'greedy' (SCST / CST_GT_None), 'scb' (self-consensus across
    the other K-1 rollouts, CST_MS_SCB), or 'none'.

    With a mesh, BOTH dispatches are shard_map-parallel — decode (the dominant
    cost) and update shard the batch over 'data'; host reward stays per-host.

    :meth:`train_step` is the strict sequential step. :meth:`train_epoch`
    is the pipelined loop (SURVEY.md §7 "hard parts"): the host scores batch
    *i* while the device decodes batch *i+1*.
    """

    # what the last train_epoch call left for the next (PrimedEpoch), or None
    primed: PrimedEpoch | None = None

    def __init__(
        self,
        model,
        reward: RewardComputer,
        cfg: RLConfig,
        mesh: Mesh | None = None,
        max_len: int | None = None,
        donate: bool = False,
        guard: bool = False,
        retry: RetryPolicy | None = None,
        on_event: Callable | None = None,
        comm=None,
        stats: bool = False,
    ):
        """``donate=True`` makes the REINFORCE update consume its input state
        (buffer donation — see :func:`make_rl_update`); the production
        Trainer path enables it, tests that replay a state don't.
        ``guard=True`` adds the on-device non-finite update guard.
        ``retry`` is the backoff policy for the (host-side, fallible in
        production) reward scorer; ``on_event(event, **fields)`` receives
        ``reward_retry`` events (an EventLogger.log works as-is).
        ``comm`` (parallel/comms.CommConfig) selects the update's grad
        allreduce spelling (None = original per-leaf psum); the Trainer
        builds it from the ``train.comm_*`` knobs. ``stats=True`` builds
        the update with the flight recorder's per-family update-ratio
        outputs (train/steps._update_ratios)."""
        self.model = model
        self.reward = reward
        self.cfg = cfg
        self.mesh = mesh
        self.comm = comm
        self.retry = retry or RetryPolicy()
        self.on_event = on_event or (lambda event, **fields: None)
        # analytic per-clip FLOPs (obs/flops.py) for the run report's MFU
        # column, plus the early-exit depth accounting (budget + stride) —
        # all host-side constants, nothing here touches a device value
        mc = model.cfg
        dims = dict(
            F=mc.max_frames, d_embed=mc.d_embed, d_hidden=mc.d_hidden,
            d_att=mc.d_att, V=mc.vocab_size,
            feat_dims=tuple(d for _, d in mc.modalities),
            num_layers=mc.num_layers,
        )
        self._depth_budget = max_len or mc.max_len
        # exit-check granularity of the decode actually dispatched: the
        # strided driver checks every decode_stride steps; the stride-1
        # uncompacted loop keeps scan_until_finished's ~5-step divisor
        decode_stride = max(
            1, min(int(getattr(mc, "decode_stride", 1)), self._depth_budget)
        )
        self._compact = bool(getattr(mc, "decode_compact", False))
        self._depth_stride = (
            decode_stride if decode_stride > 1 or self._compact
            else _exit_stride(self._depth_budget)
        )
        self._decode_flops_per_clip = _flops.decode_flops_per_clip(
            K=cfg.num_rollouts, T=self._depth_budget,
            with_greedy=(cfg.baseline == "greedy"),
            stride=self._depth_stride, **dims,
        )
        self._update_flops_per_clip = _flops.update_flops_per_clip(
            K=cfg.num_rollouts, T=self._depth_budget, **dims,
        )
        # compile-time update cost (obs/flops.compiled_cost), resolved
        # lazily at the first dispatch when obs is on: None = not yet
        # probed, False = XLA exposed no cost (analytic fallback), float =
        # whole-update FLOPs from the compiled program
        self._update_cost = None
        # the updates' positions tallies, device scalars until
        # observe_update_positions reads them (never in _apply)
        self._positions_pending = []
        obs.gauge("rl.decode.budget").set(float(self._depth_budget))
        # decode FLOPs are always the analytic per-clip model (the early-exit
        # loop's realized cost isn't a fixed compiled number)
        obs.gauge("flops.backend.rl.decode").set(0.0)
        # only the 'greedy' baseline consumes the greedy rollout: scb/none
        # skip its decode, host transfer, and reward scoring entirely (one
        # of the K+1 decoded rows per clip on the flagship config)
        wg = cfg.baseline == "greedy"
        if mesh is not None and "seq" in mesh.axis_names:
            # DP x SP (MeshConfig.seq_devices > 1): frames shard over 'seq'
            # with the collective attention softmax, batch over 'data'
            from cst_captioning_tpu.parallel import (
                make_sp_decode, make_sp_rl_update, sp_model,
            )

            spm = model if model.cfg.seq_axis else sp_model(model.cfg)
            self.decode = make_sp_decode(
                spm, mesh, cfg.num_rollouts, cfg.temperature, max_len,
                data_axis="data", with_greedy=wg,
            )
            self.update = make_sp_rl_update(
                spm, mesh, chunks=cfg.update_chunks, donate=donate,
                guard=guard, comm=comm, stats=stats,
            )
        elif mesh is not None:
            self.decode = make_parallel_rl_decode(
                model, mesh, cfg.num_rollouts, cfg.temperature, max_len,
                with_greedy=wg,
            )
            self.update = make_parallel_rl_update(
                model, mesh, chunks=cfg.update_chunks, donate=donate,
                guard=guard, comm=comm, stats=stats,
            )
        else:
            self.decode = make_rl_decode(
                model, cfg.num_rollouts, cfg.temperature, max_len,
                with_greedy=wg,
            )
            self.update = make_rl_update(
                model, chunks=cfg.update_chunks, donate=donate, guard=guard,
                comm=comm, stats=stats,
            )

    # ---- reward / advantage (host) ------------------------------------------

    def _reward_call(self, video_ids, rows):
        """The reward scorer behind jittered-backoff retries: in-process
        numpy never fails, but the production deployment scores against a
        service — transient failures are retried, not fatal (and the chaos
        ``reward.call`` point lets tests inject both)."""

        def call():
            chaos.visit("reward.call")
            return self.reward(video_ids, rows)

        return retry_call(
            call,
            policy=self.retry,
            on_retry=lambda info: self.on_event("reward_retry", **info),
        )

    def _advantage(self, greedy, samples_np, video_ids, valid_np):
        """-> (advantage [K,B] np, metrics dict). Blocks on decode transfer."""
        K = self.cfg.num_rollouts
        B = samples_np.shape[1]
        r_samples = self._reward_call(video_ids, samples_np.reshape(K * B, -1))
        r_kb = r_samples.reshape(K, B)

        if self.cfg.baseline == "greedy":
            if greedy is None:
                raise ValueError(
                    "baseline='greedy' needs the greedy rollout; the decode "
                    "was built with with_greedy=False"
                )
            r_greedy = self._reward_call(video_ids, np.asarray(greedy))
            baseline = np.broadcast_to(r_greedy[None, :], (K, B))
        elif self.cfg.baseline == "scb":
            baseline = scb_baseline(r_kb)
        elif self.cfg.baseline == "none":
            baseline = np.zeros_like(r_kb)
        else:
            raise ValueError(f"unknown baseline {self.cfg.baseline!r}")

        advantage = (r_kb - baseline) * valid_np[None, :]
        n_valid = max(valid_np.sum(), 1.0)
        v = valid_np[None, :]
        r_valid = r_kb[:, valid_np > 0]
        a_valid = advantage[:, valid_np > 0]
        has_valid = valid_np.sum() > 0
        metrics = {
            "reward_mean": float((r_kb * v).sum() / (K * n_valid)),
            "reward_std": float(r_valid.std()) if has_valid else 0.0,
            # reward tails (flight recorder): collapse shows up as p90
            # pinning to p10 long before the mean moves
            "reward_p10": (
                float(np.percentile(r_valid, 10.0)) if has_valid else 0.0
            ),
            "reward_p90": (
                float(np.percentile(r_valid, 90.0)) if has_valid else 0.0
            ),
            "baseline_mean": float((np.asarray(baseline) * v).sum() / (K * n_valid)),
            "advantage_mean": float(advantage.sum() / (K * n_valid)),
            # advantage spread — the REINFORCE gradient's variance driver
            "advantage_std": float(a_valid.std()) if has_valid else 0.0,
            # rows behind reward_mean: lets epoch/cross-host aggregation weight
            # steps exactly (wrap-padded final batches have fewer valid rows)
            "valid_rows": float(valid_np.sum()),
        }
        return advantage, metrics

    def _score(self, greedy, samples, feats, masks, video_ids, valid_np):
        """Host half of the step: read the decoded tokens back and compute
        the advantage. Returns the argument tuple for :meth:`_apply`.

        Multi-host: ``video_ids``/``valid_np`` are THIS process's rows (the
        host-sharded Batcher), so the decoded tokens come back per-host
        (``to_host_local``), the reward is computed on local rows only, and
        the local advantage is re-assembled into a global sharded array for
        the update — host scoring never crosses DCN (SURVEY.md §5).
        """
        from cst_captioning_tpu.train import multihost

        # the rl.reward span covers the device->host token readback AND the
        # consensus scoring: this is the host half the pipeline must hide,
        # so its p95 against rl.decode/rl.update is THE pipelining health
        # signal in the run report
        with obs.span("rl.reward"):
            # its three parts, one span each a batch: the blocking read-back
            # (the wait for the decode ends here), tracing's own accounting
            # (real work only while obs is on), the scoring
            with obs.span("rl.reward.readback"):
                samples_np = multihost.to_host_local(      # [K, B_local, T]
                    samples, self.mesh, P(None, "data")
                ) if self.mesh is not None else np.asarray(samples)
                greedy_np = None
                if greedy is not None:
                    greedy_np = multihost.to_host_local(
                        greedy, self.mesh, P("data")
                    ) if self.mesh is not None else np.asarray(greedy)
            with obs.span("rl.reward.observe"):
                entropy = self._observe_decode(greedy_np, samples_np)
            with obs.span("rl.reward.score"):
                advantage, host_metrics = self._advantage(
                    greedy_np, samples_np, video_ids, valid_np
                )
            if entropy is not None:
                host_metrics["sample_entropy"] = entropy
        return (advantage, host_metrics, samples, feats, masks, valid_np)

    # depth buckets sized to caption-length budgets (T <= ~64), not the
    # default latency buckets
    _DEPTH_BUCKETS = (2.0, 4.0, 6.0, 8.0, 10.0, 12.0, 16.0, 20.0, 24.0,
                      28.0, 32.0, 40.0, 48.0, 64.0)

    def _observe_decode(self, greedy_np, samples_np) -> float | None:
        """Decode accounting from the already-on-host tokens: the analytic
        FLOPs counter behind the report's MFU column, the early-exit depth
        histogram (scan steps the while loop actually ran vs the T budget),
        and the ``rl.decode.compaction`` counter pair — (lane, column)
        steps the compacted driver computed vs skipped (what finished-lane
        compaction saves per batch; ``cli.obs_report`` surfaces the pair).
        All derived from this process's local rows; no device reads.
        Returns the sampled-lane entropy (:func:`sample_entropy`) for the
        flight recorder's step record, or None when obs is off."""
        obs.counter("flops.rl.decode").inc(
            samples_np.shape[1] * self._decode_flops_per_clip
        )
        if not obs.enabled():
            return None
        # rows finish at their (EOS-inclusive) length; the loop checks the
        # exit every `stride` steps, so it runs to the next stride multiple
        # of the longest row, capped at the padded budget
        stats = compaction_stats(
            greedy_np, samples_np, self._depth_stride, self._depth_budget,
            compact=self._compact,
        )
        obs.histogram("rl.decode.depth", self._DEPTH_BUCKETS).observe(
            stats["depth"]
        )
        obs.counter("rl.decode.compaction.lanes_stepped").inc(
            stats["lanes_stepped"]
        )
        obs.counter("rl.decode.compaction.lanes_skipped").inc(
            stats["lanes_skipped"]
        )
        return sample_entropy(samples_np)

    def _update_flops_inc(self, n_rows, args) -> float:
        """Per-process FLOPs to count for one update dispatch. Prefers the
        COMPILED program's own cost (obs/flops.compiled_cost); falls back
        to the analytic per-clip model when XLA exposes
        no cost or obs is off (probing forces a lower+compile walk — free
        on the hot path only because the jit cache already holds this
        program, so don't pay it when nothing reads the counter). Either
        way the per-process streams sum to the global total: the compiled
        number is the whole (global-batch) program split evenly across
        processes; the analytic one is counted over this host's rows."""
        if self._update_cost is None and obs.enabled():
            cost = _flops.compiled_cost(self.update, *args)
            self._update_cost = cost["flops"] if cost else False
            # probe ledger: the degraded-mesh continuation rebuilds this
            # trainer and must re-probe (tested); the backend gauge labels
            # the report's MFU rows compiled-vs-analytic
            obs.counter("obs.flops.probes").inc()
            obs.gauge("flops.backend.rl.update").set(
                1.0 if self._update_cost else 0.0
            )
        if self._update_cost:
            return self._update_cost / jax.process_count()
        return n_rows * self._update_flops_per_clip

    def _apply(self, state, advantage, host_metrics, samples, feats, masks,
               valid_np):
        """Device half: upload the advantage, dispatch the REINFORCE update."""
        from cst_captioning_tpu.train import multihost

        # host time only: the update is dispatched, never waited on here
        with obs.span("rl.update"):
            # host numpy goes straight to its TARGET sharding (explicit
            # placement): converting to a single-device jnp array first
            # would leave the sharded update to re-scatter it implicitly
            # on every dispatch
            adv = np.asarray(advantage, np.float32)
            valid = np.asarray(valid_np, np.float32)
            if self.mesh is not None:
                adv = multihost.from_host_local(adv, self.mesh, P(None, "data"))
                valid = multihost.from_host_local(valid, self.mesh, P("data"))
            else:
                adv = jnp.asarray(adv, jnp.float32)
                valid = jnp.asarray(valid)
            args = (state, feats, masks, samples, adv, valid)
            obs.counter("flops.rl.update").inc(
                self._update_flops_inc(len(valid_np), args)
            )
            if self.mesh is not None and self.comm is not None:
                # the update carries the grad allreduce: ledger its dispatch
                # under the DCN/ICI collective span (PR 6 machinery) so
                # stalls surface in the same place multihost barriers do
                with collective_span("rl.update.allreduce"):
                    state, metrics = self.update(*args)
            else:
                state, metrics = self.update(*args)
        metrics = dict(metrics)
        if obs.enabled():
            self._positions_pending.append(
                (metrics["positions_run"], metrics["positions"])
            )
        metrics.update(host_metrics)
        return state, metrics

    def observe_update_positions(self) -> None:
        """Count what the updates' teacher-forcing scans ran since the last
        call: ``rl.update.positions.run`` of ``rl.update.positions``, summed
        over rollout chunks, row blocks and devices (``cli.obs_report``'s
        ``update row blocks:`` line has the share). One read of the
        scalars the updates returned beside their metrics, so call it where
        the steps' metrics are read already (the Trainer: in the epoch's
        drain, after the sentinel's flush has waited for the last update);
        nothing is held while obs is off."""
        pending, self._positions_pending = self._positions_pending, []
        if not pending:
            return
        run, total = np.sum(jax.device_get(pending), axis=0)
        obs.counter("rl.update.positions.run").inc(float(run))
        obs.counter("rl.update.positions").inc(float(total))

    def _finish(self, state, greedy, samples, feats, masks, video_ids, valid_np):
        """Score a decoded batch and apply the REINFORCE update."""
        return self._apply(
            state,
            *self._score(greedy, samples, feats, masks, video_ids, valid_np),
        )

    @staticmethod
    def _valid_np(valid, B):
        return (
            np.ones((B,), np.float32) if valid is None
            else np.asarray(valid, np.float32)
        )

    # ---- strict sequential step ---------------------------------------------

    def train_step(self, state: TrainState, feats, masks, video_ids, rng,
                   valid=None):
        with obs.span("rl.decode"):
            greedy, samples = self.decode(state.params, feats, masks, rng)
        # sized from the LOCAL row count (== global single-host; under
        # multi-host, samples is a global array but the reward rows are ours)
        valid_np = self._valid_np(valid, len(video_ids))
        return self._finish(
            state, greedy, samples, feats, masks, video_ids, valid_np
        )

    # ---- drain-aware seam (pipelined preemption) ---------------------------

    def _seam_capture(self, decoded_pair, video_ids) -> dict:
        """Host copies of a decoded-but-unscored batch's tokens — the
        rollout/update SEAM of the pipelined loop. Gathered globally so any
        surviving process can replay them (single-process: plain asarray)."""
        from cst_captioning_tpu.train import multihost

        greedy, samples = decoded_pair
        all_ids = [
            i for sub in multihost.allgather_pyobj(list(video_ids))
            for i in sub
        ]
        out = {
            "samples": multihost.allgather_to_host(samples),
            "video_ids": all_ids,
        }
        if greedy is not None:
            out["greedy"] = multihost.allgather_to_host(greedy)
        return out

    def _seam_tokens_to_device(self, seam: dict):
        """Persisted seam tokens -> device arrays in the decode's output
        layout (greedy [B,T] over 'data', samples [K,B,T] over (None,'data'))
        so the resumed pipeline is indistinguishable from a live decode."""
        from cst_captioning_tpu.train import multihost

        samples = np.asarray(seam["samples"])
        greedy = seam.get("greedy")
        if self.mesh is not None:
            from jax.sharding import NamedSharding

            samples = multihost.put_full_global(
                NamedSharding(self.mesh, P(None, "data")), samples
            )
            if greedy is not None:
                greedy = multihost.put_full_global(
                    NamedSharding(self.mesh, P("data")), np.asarray(greedy)
                )
        else:
            samples = jnp.asarray(samples)
            if greedy is not None:
                greedy = jnp.asarray(np.asarray(greedy))
        return greedy, samples

    @staticmethod
    def _seam_matches(seam: dict, video_ids) -> bool:
        from cst_captioning_tpu.train import multihost

        ids = [
            i for sub in multihost.allgather_pyobj(list(video_ids))
            for i in sub
        ]
        return list(seam.get("video_ids", [])) == ids

    # ---- pipelined epoch ----------------------------------------------------

    def _replicated_key(self, rng):
        """An epoch's key, replicated onto the mesh ONCE: the sharded decode
        takes its rng replicated (in_specs P()), and a single-device key
        would otherwise be implicitly re-replicated device-to-device on
        EVERY batch's dispatch (the sanitizer gate's transfer_guard vetoes
        that); every split of it inherits the replicated placement.
        Bit-identical — placement only. A mesh that spans processes takes
        the key through multihost's global placement (device_put refuses
        devices of another process); one process gets the same device_put
        as ever."""
        if self.mesh is None:
            return rng
        from cst_captioning_tpu.train.mesh import replicate

        return replicate(self.mesh, rng)

    def drop_primed(self) -> None:
        """Forget what :meth:`train_epoch` left for the next call: the
        opened batches are closed (a feed behind them retires its worker and
        starts over) and the pair's features are let go. For a caller whose
        next epoch is no longer the one that was opened: a rollback, a lost
        or rejoined peer, the phase's end."""
        primed, self.primed = self.primed, None
        if primed is not None:
            _close(primed.batches)

    def primed_seam(self) -> dict | None:
        """Host copies of the tokens of the next epoch's first batch, where
        the last call primed it (else None): what a checkpoint of the state
        that call returned needs beside it, since that batch was decoded one
        update before that state. ``next_epoch=True`` names their position
        (the next epoch's batch 0), as a stop while priming does."""
        if self.primed is None:
            return None
        return dict(self._seam_capture(*self.primed.first), next_epoch=True)

    def train_epoch(self, state: TrainState, batches, rng, on_step=None,
                    pipelined: bool = True, should_stop=None,
                    seam: dict | None = None,
                    seam_sink: dict | None = None,
                    next_epoch: Callable[[], tuple | None] | None = None):
        """SCST over an epoch of batches.

        ``should_stop()`` (optional) is polled once per batch; when it turns
        True the epoch stops consuming batches and the pipeline DRAINS —
        every batch already decoded gets its update applied, so the returned
        state corresponds to exactly ``len(metrics)`` completed steps (the
        preemption-save path depends on this invariant).

        ``seam_sink`` (pipelined only) opts into the DRAIN-AWARE stop order:
        instead of discarding the batch fetched when ``should_stop`` fired,
        the loop runs that iteration's schedule prefix — update(i-2) ->
        decode(i) — captures the freshly decoded tokens into ``seam_sink``
        (via :meth:`_seam_capture`), then scores+applies the final pending
        batch. The caller persists the sink next to the checkpoint; a resume
        that passes it back as ``seam`` replays those tokens for its first
        batch instead of re-decoding — the decode then used params from the
        exact pipeline schedule position, so a pipelined mid-epoch resume is
        BIT-IDENTICAL to the uninterrupted run (previously the seam batch
        was re-decoded against params one update fresher).

        ``seam`` (pipelined only): tokens for the first batch this call
        decodes, from a prior ``seam_sink``. Ignored (with a live decode
        fallback) when the batch identity check fails — a changed data order
        must never silently marry old tokens to new features.

        ``batches`` yields ``(feats, masks, video_ids, valid)`` with arrays
        already on device.

        ``pipelined=True`` (default): two-stage software pipeline. Per
        iteration the dispatch order is **update(i-2) -> decode(i) ->
        host-score(i-1)** — the update that became ready from the previous
        iteration's scoring is dispatched *before* the host starts scoring
        the next batch, so the device always has ~a full step of queued work
        (one update + one decode) while the host computes the consensus
        reward, and never idles on it (VERDICT r3: the 1-deep
        score-then-update order left the device idle for the reward tail).
        The decoded policy is ONE update stale — identical to the plain
        decode-then-score-then-update pipelining (update *i-1* cannot be
        ready before decode *i* is dispatched without serializing on the
        host), and the parameter/rng/metric sequence is bit-identical to
        it; with the RL learning rate (~2e-5) the one-step policy drift is
        negligible (measured vs strict in BASELINE.md), and the REINFORCE
        logprobs are recomputed from the *current* params in the update, so
        the gradient estimator itself stays well-formed. HBM note: three
        batches' features are live at once (scored, decoded-awaiting-score,
        current) vs two in the strict loop.

        ``next_epoch`` (pipelined, with a ``seam_sink``) PRIMES the pipeline
        across the epoch's end. Called once, when ``batches`` has ended and
        no stop was asked for, it opens the epoch after this one and returns
        ``(batches', rng')``, or None where there is none. The schedule then
        runs on over the cut, with n this epoch's batches and i' the next
        epoch's::

            update(n-2) -> decode(0') -> host-score(n-1)
            update(n-1) -> decode(1') -> host-score(0')

        and the call returns with the device still holding update(n-1) and
        decode(1'). The returned state has this epoch's n updates and none of
        the next epoch's (a decode reads parameters and donates nothing), and
        ``metrics`` has n entries. What the next call needs is kept in
        :attr:`primed` (a :class:`PrimedEpoch`): the rest of ``batches'``
        and ``rng'`` after its two splits, which the caller passes as that
        call's ``batches`` and ``rng``, and the scored batch 0' and the
        decoded batch 1', which that call takes over by itself. It begins by
        dispatching update(0'), before it takes a batch, and goes on at
        decode(2') -> host-score(1'): no epoch begins with an empty
        pipeline, and EVERY batch is decoded one update stale, an epoch's
        first included (it used to read fresh parameters only because the
        loop happened to start there). Every rollout is still scored once
        and applied once, in the same order, from the same per-epoch keys;
        the trajectory is that of ONE pipelined loop over the phase's
        batches end to end, so it differs from a phase cut into calls of one
        epoch each (cold every time) by that first batch's staleness. Batch
        0' was decoded one update before the returned state: a checkpoint of
        that state carries its tokens (:meth:`primed_seam`), and a resume
        replays them as ``seam`` (0' from the file, 1' decoded from the
        saved state with the saved key), bit-identical to the primed run. A
        caller whose next call is not that epoch calls :meth:`drop_primed`.
        A stop that arrives while priming ends it there: this epoch's
        updates are applied, batch 0''s tokens go to ``seam_sink`` with
        ``next_epoch=True`` (their position is the next epoch's batch 0),
        ``batches'`` is closed and nothing is kept. Counters
        ``rl.epoch.primed`` / ``rl.epoch.cold``: calls that began with a
        primed pair / with an empty pipeline.

        ``pipelined=False``: strict on-policy SCST — :meth:`train_step` per
        batch with the same rng stream (the reference's loop, SURVEY.md
        §3.2). It never primes.

        Returns ``(state, metrics_list)``; ``on_step(metrics)`` fires per batch.
        """
        primed, self.primed = self.primed, None
        obs.counter(
            "rl.epoch.cold" if primed is None else "rl.epoch.primed"
        ).inc()
        if primed is None:
            rng = self._replicated_key(rng)
        out = []

        def emit(m):
            out.append(m)
            if on_step is not None:
                on_step(m)

        if should_stop is None:
            should_stop = lambda: False     # noqa: E731

        if not pipelined:
            for feats, masks, video_ids, valid in batches:
                if should_stop():
                    break
                rng, srng = jax.random.split(rng)
                state, m = self.train_step(
                    state, feats, masks, video_ids, srng, valid
                )
                emit(m)
            return state, out

        scored = None     # _apply args: advantage ready, update not dispatched
        decoded = None    # _score args: decode dispatched, not yet scored

        def update():
            """Dispatch the oldest pending batch's update."""
            nonlocal state, scored, decoded
            if scored is None:
                scored, decoded = self._score(*decoded), None
            state, m = self._apply(state, *scored)
            scored = None
            emit(m)

        def slot(batch, score=True):
            """One slot of the schedule: update(i-2) -> decode(i) ->
            host-score(i-1), the host scoring while the device runs the two
            just queued. Returns decode(i)'s tokens. ``score=False`` is the
            drain-aware stop: the slot's device work and no more, so that
            the seam batch is decoded against the params the uninterrupted
            pipeline would have used; the caller captures its tokens for the
            checkpoint instead of scoring it."""
            nonlocal rng, seam, scored, decoded
            if scored is not None:
                update()
            # split even for a replayed batch, so that later batches'
            # streams stay aligned with the uninterrupted run
            rng, srng = jax.random.split(rng)
            feats, masks, video_ids, valid = batch
            replay, seam = seam, None
            if replay is not None and self._seam_matches(replay, video_ids):
                # the call's first decode, resumed: the tokens persisted
                # before the stop at this exact schedule position
                d = self._seam_tokens_to_device(replay)
            else:
                with obs.span("rl.decode"):
                    d = self.decode(state.params, feats, masks, srng)
                    for arr in d:
                        # start the device->host token transfer NOW, so it
                        # overlaps this decode — by the time _score reads
                        # the tokens they are already on host. greedy is
                        # None for the scb/none baselines (no greedy
                        # rollout); multi-host global arrays are not fully
                        # addressable here and their reads go through
                        # to_host_local.
                        if arr is not None and arr.is_fully_addressable:
                            arr.copy_to_host_async()
            if score:
                if decoded is not None:
                    scored = self._score(*decoded)
                valid_np = self._valid_np(valid, len(video_ids))
                decoded = (*d, feats, masks, video_ids, valid_np)
            return d

        if primed is not None:
            # the pair the epoch before this one decoded inside its drain.
            # Batch 0''s update goes first of all, before a batch is taken
            # from the feed (whose worker wakes on that and takes the
            # interpreter lock for its next collate): the device has only
            # decode(1') left of what that call queued
            scored, decoded = primed.scored, primed.decoded
            del primed      # the pair's features die with their updates
            if scored is not None:
                update()
        stopped = False
        for batch in batches:
            if should_stop():
                stopped = True
                if seam_sink is not None:
                    seam_sink.update(self._seam_capture(
                        slot(batch, score=False), batch[2]
                    ))
                break
            slot(batch)

        # this epoch's updates, once the pending ones are applied
        n = len(out) + (scored is not None) + (decoded is not None)
        ahead = None
        if not stopped and next_epoch is not None and seam_sink is not None:
            ahead = next_epoch()
        first = None      # batch 0' of the next epoch: (its tokens, its ids)
        opened = None     # the next epoch's batches, while they are ours
        try:
            if ahead is not None:
                # the next epoch's fill inside this epoch's drain
                opened, rng = ahead[0], self._replicated_key(ahead[1])
                for batch in itertools.islice(opened, 2):
                    stopped = should_stop()
                    if stopped and first is not None:
                        break       # 0' is the seam: 1' is never decoded
                    d = slot(batch, score=not stopped)
                    first = first or (d, batch[2])
                    if stopped:
                        break
            # drain in order: update(n-2), then score+update(n-1)
            while len(out) < n:
                update()
            if first is not None and not stopped:
                self.primed = PrimedEpoch(opened, rng, scored, decoded, first)
                opened = None
        finally:
            if opened is not None:
                _close(opened)
        if first is not None and stopped:
            # 0' was decoded one update before the state this call returns
            seam_sink.update(self._seam_capture(*first), next_epoch=True)
        return state, out
