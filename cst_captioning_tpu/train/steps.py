"""Jitted XE train steps: single-device and mesh-parallel (shard_map).

The whole reference inner loop — forward, masked (weighted) XE, backward,
global-norm clip, allreduce, Adam update (SURVEY.md §3.1) — compiles to one
XLA program. Data parallelism is explicit shard_map over ``Mesh('data')``:

- the batch arrives sharded on axis 0 (``shard_batch``), params replicated,
- each device computes grads of its *local loss numerator* (sum of per-token
  losses) plus its local token count,
- one ``psum`` over 'data' reduces both; grads divide by the GLOBAL token
  count, so the parallel step is bit-comparable to the single-device step on
  the concatenated batch (asserted by the 8-fake-device test, SURVEY.md §4
  item 4) — not just approximately data-parallel,
- the update then runs identically on every device, keeping state replicated
  without a broadcast.

RNG: dropout key = fold_in(fold_in(state.rng, step), device_index) — distinct
per step and per shard, reproducible under resharding.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from cst_captioning_tpu.losses import masked_cross_entropy
from cst_captioning_tpu.resilience.guard import guarded_apply_gradients
from cst_captioning_tpu.train.state import TrainState


def _update_ratios(old_params, new_params) -> dict:
    """Per-family relative update magnitude, computed on device.

    For each top-level parameter family ``fam`` (the module groups under
    ``params``): ``upd_ratio/<fam> = ||new - old|| / max(||old||, eps)``,
    plus the all-params ``upd_ratio/global``. The classic LR-health signal:
    a healthy Adam step sits around 1e-3; a family pinned at ~0 is frozen,
    one at ~1 is being rewritten every step. Flight-recorder food — only
    traced when a step factory is built with ``stats=True``."""
    op = old_params.get("params", old_params)
    np_ = new_params.get("params", new_params)

    def ratio(o, n):
        delta = optax.global_norm(jax.tree.map(lambda a, b: b - a, o, n))
        return delta / jnp.maximum(optax.global_norm(o), 1e-12)

    out = {f"upd_ratio/{fam}": ratio(op[fam], np_[fam]) for fam in op}
    out["upd_ratio/global"] = ratio(op, np_)
    return out


def _apply(state, grads, loss, gnorm, guard: bool, key: str = "loss",
           stats: bool = False):
    """Optionally-guarded update; metrics grow a ``nonfinite`` flag when
    guarded (see resilience/guard.py — bit-identical on finite steps).
    ``key`` names the loss metric ("loss" for XE steps, "rl_loss" for the
    REINFORCE updates). ``stats=True`` (flight recorder on) additionally
    returns the per-family update ratios (:func:`_update_ratios`) — extra
    metric outputs only; the parameter math is untouched, and the default
    ``stats=False`` program is literally the pre-stats one."""
    old_params = state.params if stats else None
    if not guard:
        new_state = state.apply_gradients(grads)
        metrics = {key: loss, "grad_norm": gnorm}
    else:
        new_state, nonfinite = guarded_apply_gradients(
            state, grads, loss, gnorm
        )
        metrics = {key: loss, "grad_norm": gnorm, "nonfinite": nonfinite}
    if stats:
        metrics.update(_update_ratios(old_params, new_state.params))
    return new_state, metrics


def _local_loss_sums(model, params, feats, masks, labels, mask, weights,
                     dropout_rng, label_smoothing):
    """(numerator, denominator) of the masked XE on this shard."""
    logits = model.apply(
        params, feats, masks, labels, train=True, rngs={"dropout": dropout_rng}
    )
    w_mask = mask * weights[:, None]
    den = jnp.sum(w_mask)
    # masked_cross_entropy normalizes internally; recover the sum form so the
    # global normalization can happen after the cross-device reduce
    num = masked_cross_entropy(
        logits, labels, mask, weights=weights, label_smoothing=label_smoothing
    ) * den
    return num, den


def make_xe_step(model, label_smoothing: float = 0.0, donate: bool = False,
                 guard: bool = False, comm=None, stats: bool = False):
    """Single-device jitted step: (state, batch arrays) -> (state, metrics).

    ``donate=True`` donates the input ``state`` buffers to the output state
    (params + Adam moments update in place instead of double-buffering —
    free HBM headroom on the production path). The caller must then treat
    the passed-in state as consumed: rebind, never reuse. Off by default so
    exactness tests can replay one state through several step variants.

    ``guard=True`` suppresses non-finite updates on device and adds a
    ``nonfinite`` metric (resilience/guard.py); finite steps are bit-equal
    to the unguarded program.

    ``comm`` (parallel/comms.CommConfig) is accepted for factory-signature
    symmetry and ignored: the single-device step has no collectives.

    ``stats=True`` adds the flight recorder's per-family update-ratio
    metrics (:func:`_update_ratios`) — pure extra outputs, bit-identical
    params; note the old params stay live past the update, so the param
    buffers can't be donation-reused on stats builds.
    """
    del comm  # no cross-device reduction on this path
    # lazy for the same cycle reason as reduce_tree below
    from cst_captioning_tpu.parallel.compile import CompilePlan, compile_fn

    def step(state: TrainState, feats, masks, labels, mask, weights):
        drng = jax.random.fold_in(state.rng, state.step)

        def loss_fn(p):
            num, den = _local_loss_sums(
                model, p, feats, masks, labels, mask, weights, drng, label_smoothing
            )
            return num / jnp.maximum(den, 1.0)

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        gnorm = optax.global_norm(grads)
        return _apply(state, grads, loss, gnorm, guard, stats=stats)

    return compile_fn(
        step, CompilePlan(donate_argnums=(0,) if donate else ())
    )


def make_parallel_xe_step(model, mesh: Mesh, label_smoothing: float = 0.0,
                          axis: str = "data", donate: bool = False,
                          guard: bool = False, comm=None,
                          stats: bool = False):
    """shard_map data-parallel step, exact-equivalent to the fused batch.
    ``donate`` / ``guard`` / ``stats``: see :func:`make_xe_step`. The stats
    ratios are computed from psum'd (device-invariant) grads, so they stay
    replicated like the state.

    ``comm`` (parallel/comms.CommConfig) selects the grad-allreduce spelling:
    None keeps the original per-leaf psum; otherwise the reduction buckets
    (and optionally bf16-compresses) per the config. f32 configs are
    bit-identical to ``comm=None`` — psum is elementwise (tests/test_comms).
    """
    # imported lazily: parallel/__init__ -> seq_parallel imports this module,
    # so a module-level import here would close the cycle mid-initialization
    from cst_captioning_tpu.parallel.comms import local_params, reduce_tree
    from cst_captioning_tpu.parallel.compile import CompilePlan, compile_fn

    def device_step(state: TrainState, feats, masks, labels, mask, weights):
        drng = jax.random.fold_in(
            jax.random.fold_in(state.rng, state.step), jax.lax.axis_index(axis)
        )

        def local_num(p):
            num, den = _local_loss_sums(
                model, p, feats, masks, labels, mask, weights, drng, label_smoothing
            )
            return num, den

        (num, den), grads_num = jax.value_and_grad(local_num, has_aux=True)(
            local_params(state.params, axis)
        )
        den_total = jax.lax.psum(den, axis)
        num_total = jax.lax.psum(num, axis)
        grads = jax.tree.map(
            lambda g: g / jnp.maximum(den_total, 1.0),
            reduce_tree(grads_num, axis, comm),
        )
        loss = num_total / jnp.maximum(den_total, 1.0)
        gnorm = optax.global_norm(grads)
        # grads/loss are psum'd (device-invariant), so the guard's where()
        # selects identically on every shard — state stays replicated
        return _apply(state, grads, loss, gnorm, guard, stats=stats)

    return compile_fn(device_step, CompilePlan(
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(), P()),
        donate_argnums=(0,) if donate else (),
    ))


def batch_arrays(batch) -> tuple[Any, ...]:
    """Batch -> (feats, masks, labels, mask, weights) jnp pytrees."""
    return (
        {k: jnp.asarray(v) for k, v in batch.feats.items()},
        {k: jnp.asarray(v) for k, v in batch.feat_masks.items()},
        jnp.asarray(batch.labels),
        jnp.asarray(batch.mask),
        jnp.asarray(batch.weights),
    )
