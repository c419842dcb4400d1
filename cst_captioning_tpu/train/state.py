"""TrainState: params + optimizer state + step + RNG, one pytree.

The reference scatters this across the torch module, the optimizer object and
an ``infos`` pickle (SURVEY.md §3.5); here it is a single flax.struct pytree
so the whole training state shards/replicates/checkpoints as one unit.
"""

from __future__ import annotations

from typing import Any, Callable

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax


def device_key(seed: int) -> jax.Array:
    """``jax.random.key`` with the seed compiled in as a static constant.

    Eager ``jax.random.key(int)`` stages the seed through an implicit
    host->device transfer (vetoed by the sanitizer gate's
    ``jax.transfer_guard("disallow")``); jitted with a static seed, the key
    materializes on device with no runtime transfer at all. The seed is one
    value a process, so static costs one program and every later call is a
    hit in the jit cache: what changes from epoch to epoch (the epoch, the
    rollback salt) goes through :func:`device_fold_in` instead."""
    return jax.jit(jax.random.key, static_argnums=0)(seed)


# one program for every folded integer (device_fold_in says why)
_fold_in = jax.jit(jax.random.fold_in)


def device_fold_in(key: jax.Array, n: int) -> jax.Array:
    """``jax.random.fold_in`` of ``n`` in ``[0, 2**32)``, ``n`` traced.

    ``n`` (an epoch, a rollback salt) takes a new value every call, so it is
    an argument of one compiled program and never a static one: compiled in,
    every epoch was a program of its own to trace, lower and load on the main
    thread. It reaches the device as a ``uint32`` scalar by an EXPLICIT
    ``device_put`` onto the key's own placement: handed to the program as a
    host value it would be an implicit host->device transfer on every call,
    once per epoch inside the sanitized RL loop, which
    ``jax.transfer_guard("disallow")`` vetoes. The key goes through the same
    ``device_put`` (no copy: it is there already) so that both arguments are
    committed to that placement whether the caller's key was (a folded key)
    or not (``device_key``'s), and one cached program serves both.
    ``fold_in`` converts its data to ``uint32`` itself, so the key is
    bit-identical to the eager spelling and to the one with ``n`` compiled
    in."""
    return _fold_in(*jax.device_put((key, np.uint32(n)), key.sharding))


@flax.struct.dataclass
class TrainState:
    step: jnp.ndarray                 # scalar int32
    params: Any
    opt_state: Any
    rng: jax.Array                    # base RNG key (folded per step/device)
    tx: optax.GradientTransformation = flax.struct.field(pytree_node=False)
    apply_fn: Callable = flax.struct.field(pytree_node=False)

    def apply_gradients(self, grads) -> "TrainState":
        updates, new_opt_state = self.tx.update(grads, self.opt_state, self.params)
        return self.replace(
            step=self.step + 1,
            params=optax.apply_updates(self.params, updates),
            opt_state=new_opt_state,
        )


def create_train_state(
    model,
    tx: optax.GradientTransformation,
    sample_batch: tuple,
    seed: int = 0,
) -> TrainState:
    """Initialize params from a sample (feats, masks, labels) batch."""
    feats, masks, labels = sample_batch
    rng = device_key(seed)
    init_rng, state_rng = jax.random.split(rng)
    params = model.init(init_rng, feats, masks, labels)
    return TrainState(
        # device_put, not jnp.zeros: eager creation of the step counter is
        # a host->device transfer, and the sanitizer gate
        # (jax.transfer_guard("disallow")) holds setup to EXPLICIT ones
        step=jax.device_put(np.zeros((), np.int32)),
        params=params,
        opt_state=tx.init(params),
        rng=state_rng,
        tx=tx,
        apply_fn=model.apply,
    )
