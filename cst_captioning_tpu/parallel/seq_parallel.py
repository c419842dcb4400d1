"""Frame-axis sequence parallelism: shard the video, psum the attention.

Design (SURVEY.md §5 long-context row, "one-step ring"): every op in the
caption model is frame-local EXCEPT the attention softmax and the carry-init
pooling. With ``ModelConfig.seq_axis`` set, those two become collective
(``pmax`` + ``psum`` over the mesh axis — see ``models/attention.py``), so the
model body runs unchanged inside ``shard_map`` with ``feats``/``masks``
sharded on their frame axis. Everything downstream of the psums is
device-invariant, which means:

- decode (greedy / K-rollout sampling / beam) works as-is — every device
  steps the same replicated LSTM against its own frame shard;
- training gradients are taken OUTSIDE the shard_map: JAX's varying-axis
  machinery (check_vma) transposes the collectives correctly, producing
  global grads — frame-sharded params (encoder embeds, attention memory
  projection) get their partial contributions summed, replicated-path params
  (LSTM, output projection) stay exact. Pinned against single-device grads
  in tests/test_seq_parallel.py.

Composition with data parallelism: a 2-D ``Mesh(('data', 'seq'))`` shards the
batch over 'data' and frames over 'seq'; the XE step psums the loss over
'data' exactly like train/steps.py does.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cst_captioning_tpu.config.config import ModelConfig
from cst_captioning_tpu.parallel.compile import CompilePlan, compile_fn, partition
from cst_captioning_tpu.decoding import fused_decode, greedy_decode, sample_decode
from cst_captioning_tpu.losses import masked_cross_entropy
from cst_captioning_tpu.models import CaptionModel
from cst_captioning_tpu.train.steps import _apply
from cst_captioning_tpu.train.state import TrainState


def sp_model(cfg: ModelConfig, seq_axis: str = "seq") -> CaptionModel:
    """A CaptionModel whose frame-axis reductions are collective over ``seq_axis``.

    Parameters are layout-identical to the unsharded model — checkpoints
    trained one way load the other way.
    """
    return CaptionModel(dataclasses.replace(cfg, seq_axis=seq_axis))


def sp_batch_specs(cfg: ModelConfig, data_axis: str = "",
                   seq_axis: str = "seq"):
    """(feats_specs, masks_specs): frame axis on ``seq_axis``, batch axis on
    ``data_axis`` (or replicated when empty)."""
    b = data_axis if data_axis else None
    feats = {name: P(b, seq_axis) for name, _ in cfg.modalities}
    masks = {name: P(b, seq_axis) for name, _ in cfg.modalities}
    return feats, masks


def make_sp_forward(model: CaptionModel, mesh: Mesh, data_axis: str = "",
                    seq_axis: str = "seq") -> Callable:
    """Jitted teacher-forced forward: (params, feats, masks, labels) -> logits.

    Logits replicate over 'seq' (they sit downstream of the attention psum)
    and shard over ``data_axis`` when given.
    """
    f_spec, m_spec = sp_batch_specs(model.cfg, data_axis, seq_axis)
    b = data_axis if data_axis else None

    def fwd(params, feats, masks, labels):
        return model.apply(params, feats, masks, labels)

    return compile_fn(fwd, CompilePlan(
        mesh=mesh,
        in_specs=(P(), f_spec, m_spec, P(b)),
        out_specs=P(b),
    ))


def make_sp_decode(model: CaptionModel, mesh: Mesh, num_rollouts: int = 0,
                   temperature: float = 1.0, max_len: int | None = None,
                   seq_axis: str = "seq", data_axis: str = "",
                   with_greedy: bool = True, fused: bool = True) -> Callable:
    """Jitted SP decode: (params, feats, masks, rng) -> (greedy, samples|None).

    The long-video RL/eval decode: frames sharded over ``seq_axis``; the
    batch replicates, or shards over ``data_axis`` when given (DP x SP —
    the product layout for ``MeshConfig.seq_devices > 1``). With
    ``num_rollouts=0`` only the greedy decode runs (eval path);
    ``with_greedy=False`` skips the greedy rollout (greedy is None — the
    scb/none baselines never consume it, see make_rl_decode). When both run,
    ``fused=True`` (default) folds the greedy baseline in as lane 0 of the
    rollout scan — one loop, one encoder pass (decoding/fused.py), pinned
    bit-exact against the two-loop ``fused=False`` reference.

    The fused loop's stride/compaction knobs (``model.decode_stride`` /
    ``decode_compact``) compose with SP: the compaction permutation is
    derived from ``finished``, which sits downstream of the attention psum
    and is therefore 'seq'-invariant — every frame shard gathers the same
    batch columns, and the frame-sharded memory follows the gather
    unchanged. Under DP x SP the permutation varies over 'data' only (each
    batch shard compacts its own columns) and the early-exit count psums
    over 'data', exactly like the 1-D path. ``decode_impl="pallas"``
    remains excluded here (config validation): the stride kernel's
    in-kernel softmax cannot express the collective 'seq' reduction.
    """
    f_spec, m_spec = sp_batch_specs(model.cfg, data_axis, seq_axis)
    b = data_axis if data_axis else None
    if not num_rollouts and not with_greedy:
        raise ValueError("nothing to decode: num_rollouts=0 and no greedy")

    # batch varying over 'data' when DP x SP; the decode loops pcast their
    # invariant inits over it and psum the early-exit count, so check_vma
    # stays ON and verifies the 'seq' attention collectives against the
    # per-shard batch loop (VERDICT r4 weak #3 closed)
    bx = (data_axis,) if data_axis else ()

    def dec(params, feats, masks, rng):
        if data_axis:
            # independent sampling streams per batch shard
            rng = jax.random.fold_in(rng, jax.lax.axis_index(data_axis))
        if with_greedy and num_rollouts and fused:
            greedy, _, samples, _ = fused_decode(
                model, params, feats, masks, rng,
                num_rollouts=num_rollouts, temperature=temperature,
                max_len=max_len, batch_axes=bx,
            )
            return greedy, samples
        greedy = None
        if with_greedy:
            greedy, _ = greedy_decode(
                model, params, feats, masks, max_len=max_len, batch_axes=bx
            )
        if num_rollouts:
            samples, _ = sample_decode(
                model, params, feats, masks, rng,
                num_rollouts=num_rollouts, temperature=temperature,
                max_len=max_len, batch_axes=bx,
            )
        else:
            samples = greedy  # stable output structure for jit
        return greedy, samples

    return compile_fn(dec, CompilePlan(
        mesh=mesh,
        in_specs=(P(), f_spec, m_spec, P()),
        out_specs=(P(b), P(None, b) if num_rollouts else P(b)),
    ))


def make_sp_xe_step(model: CaptionModel, mesh: Mesh,
                    label_smoothing: float = 0.0, data_axis: str = "",
                    seq_axis: str = "seq", donate: bool = False,
                    guard: bool = False, comm=None,
                    stats: bool = False) -> Callable:
    """Jitted SP (optionally DP x SP) XE train step.

    The loss is computed inside shard_map (loss psum'd over ``data_axis``
    when sharded); ``value_and_grad`` wraps the WHOLE sharded computation, so
    the collective transposes produce exact global gradients.

    ``comm`` (parallel/comms.CommConfig) is accepted for factory-signature
    symmetry and IGNORED: gradients here are taken outside shard_map — the
    collective transposes already yield global grads, so there is no grad
    allreduce to bucket, compress, or overlap (ExperimentConfig rejects
    bf16/overlap knobs on the seq-parallel path for the same reason).

    ``stats=True`` adds the flight recorder's per-family update-ratio
    metrics (train/steps._update_ratios) — extra outputs only, params
    bit-identical.
    """
    del comm  # no grad allreduce on this path — see docstring
    f_spec, m_spec = sp_batch_specs(model.cfg, data_axis, seq_axis)
    b = data_axis if data_axis else None

    def sharded_loss(params, feats, masks, labels, mask, weights, drng):
        if data_axis:
            drng = jax.random.fold_in(drng, jax.lax.axis_index(data_axis))
        # the seq index is deliberately NOT folded in (ADVICE r2 reviewed and
        # declined): every dropout site sits on the REPLICATED path (the
        # decoder input/hidden, downstream of the attention psum — there is
        # no frame-sharded dropout in this model), so identical masks across
        # 'seq' devices are what keep the replicated activations replicated;
        # folding the seq index would desynchronize them and break the
        # out_specs invariance.
        logits = model.apply(
            params, feats, masks, labels, train=True, rngs={"dropout": drng}
        )
        w_mask = mask * weights[:, None]
        den = jnp.sum(w_mask)
        num = masked_cross_entropy(
            logits, labels, mask, weights=weights,
            label_smoothing=label_smoothing,
        ) * den
        if data_axis:
            num = jax.lax.psum(num, data_axis)
            den = jax.lax.psum(den, data_axis)
        return num / jnp.maximum(den, 1.0)

    sm = partition(sharded_loss, CompilePlan(
        mesh=mesh,
        in_specs=(P(), f_spec, m_spec, P(b), P(b), P(b), P()),
        out_specs=P(),
    ))

    def step(state: TrainState, feats, masks, labels, mask, weights):
        drng = jax.random.fold_in(state.rng, state.step)

        def loss_fn(p):
            return sm(p, feats, masks, labels, mask, weights, drng)

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        gnorm = optax.global_norm(grads)
        return _apply(state, grads, loss, gnorm, guard, stats=stats)

    return compile_fn(
        step, CompilePlan(donate_argnums=(0,) if donate else ())
    )


def make_sp_rl_update(model: CaptionModel, mesh: Mesh, data_axis: str = "data",
                      seq_axis: str = "seq", chunks: int = 1,
                      donate: bool = False, guard: bool = False,
                      comm=None, stats: bool = False) -> Callable:
    """Jitted DP x SP REINFORCE update (the SCST update on a 2-D mesh).

    Same structure as :func:`make_sp_xe_step`: the (numerator, denominator)
    sums of the teacher-forced REINFORCE loss are computed inside shard_map
    (psum'd over ``data_axis``); ``value_and_grad`` wraps the whole sharded
    computation so the 'seq' attention collectives transpose to exact global
    gradients. Mirrors rl/scst.py's ``make_parallel_rl_update`` semantics
    (valid-row exclusion included). ``chunks > 1`` scans over slices of the
    rollout axis at the jit level — one value_and_grad per chunk, gradients
    accumulated, normalized once by the global token count — producing the
    same total gradient in K/chunks of the activation memory (the same
    HBM-ceiling lever as ``rl.update_chunks`` on the 1-D mesh).

    ``comm`` is accepted for factory-signature symmetry and IGNORED — same
    reason as :func:`make_sp_xe_step`: grads are taken outside shard_map,
    there is no grad allreduce to shape.
    """
    del comm  # no grad allreduce on this path — see docstring
    from cst_captioning_tpu.models.captioner import EncoderOutput
    # lazy like sharded_sums' import below: scst imports this package
    from cst_captioning_tpu.rl.scst import _positions, _zero_tally

    f_spec, m_spec = sp_batch_specs(model.cfg, data_axis, seq_axis)
    b = data_axis if data_axis else None
    # EncoderOutput partition specs: memory/proj/mask keep their frame shard,
    # the carry ((c, h) per LSTM layer, downstream of the attention psum)
    # shards over the batch only — structure is static given the config
    enc_spec = EncoderOutput(
        P(b, seq_axis), P(b, seq_axis), P(b, seq_axis),
        tuple((P(b), P(b)) for _ in range(model.cfg.num_layers)),
    )

    def sharded_encode(params, feats, masks):
        # one sharded encoder program: memory/proj/mask keep their frame
        # shard, the carry (downstream of the attention psum) shards over the
        # batch only. Frame-axis leaves that don't depend on the sharded
        # feats (e.g. an all-ones memory_mask) are device-invariant and would
        # violate their varying out_specs — the varying-zero trick from
        # rl/scst._block_loss_grads makes those three leaves uniformly
        # varying (zv carries exactly the feats' vma = the f_spec axes); its
        # transpose lands in the discarded feats cotangent. The carry is NOT
        # touched: its out_spec is batch-only (it sits downstream of the
        # 'seq' attention psum) and zv would wrongly make it frame-varying.
        enc = model.apply(params, feats, masks, method=CaptionModel.encode)
        zv = jnp.sum(jax.tree.leaves(feats)[0]) * 0.0
        return type(enc)(
            enc.memory + zv.astype(enc.memory.dtype),
            enc.memory_proj + zv.astype(enc.memory_proj.dtype),
            enc.memory_mask + zv.astype(enc.memory_mask.dtype),
            enc.carry,
        )

    def sharded_sums(params, enc, samples, advantage, valid):
        # the single source of truth for tiling + REINFORCE loss sums lives
        # in rl/scst.py (import here: scst's own parallel import is lazy, so
        # there is no module-level cycle). Same shape as the DP update: tile
        # the ENCODED memory over rollouts and compute target logps inside
        # the teacher-forcing scan — the [K*Bl, T, V] logits stack never
        # materializes, which matters most here (long-context SP exists
        # because memory is tight). The encoder runs OUTSIDE this program
        # (sharded_encode + jax.vjp below), so with chunks>1 its forward AND
        # backward run once instead of once per chunk (ADVICE r4: the
        # per-chunk encoder backward could not be hoisted by XLA — the
        # cotangents differ per chunk — but summing the enc cotangents first
        # and running one backward is the same linear algebra).
        from cst_captioning_tpu.rl.scst import _decode_loss_sums, _tile_enc

        K, Bl, T = samples.shape
        # tally: the denominator and teacher forcing's positions (run, all);
        # the tokens, hence the time loops' trip count, are the same on
        # every 'seq' shard, so the shards that meet in the attention's
        # reduction over 'seq' run the same positions
        num, tally = _decode_loss_sums(
            model, params, _tile_enc(enc, K),
            samples.reshape(K * Bl, T),
            advantage.reshape(K * Bl),
            jnp.tile(valid, (K,)),
        )
        if data_axis:
            num, tally = jax.lax.psum((num, tally), data_axis)
        return num, tally

    def update(state: TrainState, feats, masks, samples, advantage, valid):
        K = samples.shape[0]

        # gradients are taken OUTSIDE the shard_maps (module docstring): the
        # collective transposes produce exact global grads — frame-sharded
        # params sum their partials, replicated-path params stay exact
        def enc_fn(p):
            return partition(sharded_encode, CompilePlan(
                mesh=mesh,
                in_specs=(P(), f_spec, m_spec), out_specs=enc_spec,
            ))(p, feats, masks)

        def sums(p, e, sam_c, adv_c):
            return partition(sharded_sums, CompilePlan(
                mesh=mesh,
                in_specs=(P(), enc_spec, P(None, b), P(None, b), P(b)),
                out_specs=(P(), (P(), P())),
            ))(p, e, sam_c, adv_c, valid)

        if chunks > 1:
            if K % chunks:
                raise ValueError(
                    f"update_chunks {chunks} must divide K={K} rollouts"
                )
            kc = K // chunks
            sam = samples.reshape((chunks, kc) + samples.shape[1:])
            adv = advantage.reshape((chunks, kc) + advantage.shape[1:])
            enc, enc_vjp = jax.vjp(enc_fn, state.params)

            def body(acc, x):
                gp_acc, ge_acc, num_acc, tally_acc = acc
                (num, tally), (gp, ge) = jax.value_and_grad(
                    sums, argnums=(0, 1), has_aux=True
                )(state.params, enc, *x)
                return (
                    jax.tree.map(jnp.add, gp_acc, gp),
                    # f32 accumulation of the (possibly bf16) enc cotangents
                    jax.tree.map(
                        lambda a_, g: a_ + g.astype(a_.dtype), ge_acc, ge
                    ),
                    num_acc + num,
                    jax.tree.map(jnp.add, tally_acc, tally),
                ), None

            init = (
                jax.tree.map(jnp.zeros_like, state.params),
                jax.tree.map(
                    lambda x: jnp.zeros(
                        x.shape, jnp.promote_types(x.dtype, jnp.float32)
                    ),
                    enc,
                ),
                jnp.zeros(()),
                _zero_tally(),
            )
            (gp, ge, num, (den, positions)), _ = jax.lax.scan(
                body, init, (sam, adv)
            )
            ge = jax.tree.map(lambda g, x: g.astype(x.dtype), ge, enc)
            (g_enc,) = enc_vjp(ge)
            g_sum = jax.tree.map(jnp.add, gp, g_enc)
            den = jnp.maximum(den, 1.0)
            loss = num / den
            grads = jax.tree.map(lambda g: g / den, g_sum)
        else:
            def loss_fn(p):
                num, (den, positions) = sums(p, enc_fn(p), samples, advantage)
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


def sp_batch_shardings(mesh: Mesh, cfg: ModelConfig, data_axis: str = "data",
                       seq_axis: str = "seq") -> tuple:
    """``jax.device_put`` shardings for the XE batch tuple
    ``(feats, masks, labels, mask, weights, valid)`` on a 2-D mesh:
    frame axis over ``seq_axis``, batch axis over ``data_axis``."""
    f_spec, m_spec = sp_batch_specs(cfg, data_axis, seq_axis)
    d = NamedSharding(mesh, P(data_axis))
    return (
        {k: NamedSharding(mesh, s) for k, s in f_spec.items()},
        {k: NamedSharding(mesh, s) for k, s in m_spec.items()},
        d, d, d, d,
    )
