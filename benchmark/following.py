"""The plain reference following a training job's first steps, and the
comparison of what the program's own steps did with it.

The job taps the very ``update`` the window goes on to drive (``Tap``): for
the run's first steps, in set-up, it keeps host copies of each update's own
arguments (the batch's features, the samples the trainer's own ``decode``
produced, the advantages, the valid mask), of the parameters before the first
and of Adam's first moment after it, and a few scalars off the program's state
(:func:`leaf_norms`). Once the
window has closed, the peak has been read and the program's state is freed,
:func:`follow` starts from those parameters and takes the same steps with the
configuration's reference: REINFORCE loss from the reference's
log-probabilities, its gradient by ``jax.grad``, the clip and Adam written
out here, float32 throughout, in blocks of clips. Nothing of the program is
imported. :func:`compare` then holds, each to a limit of its own from the
configuration's ``checks``:

- every followed step's loss (a part of the batch left out, a wrong
  normaliser, other rows or weights);
- the first gradient as the optimizer gets it, read from Adam's first moment
  after one step, ``mu / (1 - b1)``: its norm by the worst leaf (a gradient
  summed over the chips and not averaged, a chunk or a part of the batch left
  out), and the norm of its difference from the reference's over the
  reference's norm, over the whole tree (a coarser forward or backward pass:
  rounding errors turn the gradient and hardly change its length, so the
  norms' gap does not see a lower precision and this number does);
- the parameters' change after the steps, by the worst leaf (a step that
  returns its state unchanged, another optimizer or rate).

"By the worst leaf": the gap between the program's norm of a leaf and the
reference's, not the norm of their difference, over the reference's norm of
that leaf or of the median leaf, whichever is larger (some gradients are all
but zero).
"""

from __future__ import annotations

import time

import numpy as np

PAD_ID = 0


class Tap:
    """A callable of the program with the benchmark listening: ``call(fn,
    *args)`` runs in its place, and every other attribute (``lower``, which
    the program's cost probe asks for) is the callable's own."""

    def __init__(self, fn, call):
        self._fn, self._call = fn, call

    def __call__(self, *args):
        return self._call(self._fn, *args)

    def __getattr__(self, name):
        return getattr(self._fn, name)


def adam_moment(opt_state):
    """The first moment (``mu``) inside an optax optimizer's state."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for part in opt_state:
            found = adam_moment(part)
            if found is not None:
                return found
    return None


def leaf_norms(tree, minus=None) -> dict:
    """``{leaf's path: its l2 norm}`` as device scalars, of ``tree`` or of
    ``tree - minus``: one small program, dispatched and not waited for."""
    import jax
    import jax.numpy as jnp

    def norms(t, m):
        if m is not None:
            t = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b, t, m)
        flat, _ = jax.tree_util.tree_flatten_with_path(t)
        return {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(jnp.square(
            v.astype(jnp.float32)))) for k, v in flat}

    return jax.jit(norms)(tree, minus)


def follow(reference, model: dict, optimizer: dict, params0, steps: list[dict],
           rows: int, precision: str = "float32", log=None) -> dict:
    """Take ``steps`` from ``params0`` with ``reference.token_logprobs``:
    ``{"loss": [..], "grad_norm": [..], "grad": the first clipped gradient on
    the host, "grad_leaf": {path: its norm}, "change_leaf": {path: norm of the
    parameters' change}}``.
    ``steps``: ``feats``, ``masks`` (dicts of [B, ...]), ``samples`` [K, B, T],
    ``advantage`` [K, B], ``valid`` [B], all on the host; ``rows`` clips a
    block. ``optimizer``: ``name`` (adam), ``lr``, ``b1``, ``b2``, ``eps``,
    ``grad_clip`` (by global norm, 0 for none)."""
    import jax
    import jax.numpy as jnp

    if optimizer["name"] != "adam":
        raise SystemExit(f"the reference follows adam, not {optimizer['name']!r}")
    lr, b1, b2, eps, clip = (float(optimizer[k]) for k in
                             ("lr", "b1", "b2", "eps", "grad_clip"))

    def block_sums(params, feats, masks, tokens, adv, valid):
        K, Bb, T = tokens.shape
        tile = lambda x: jnp.tile(x, (K,) + (1,) * (x.ndim - 1))  # noqa: E731
        flat = tokens.reshape(K * Bb, T)
        logp = reference.token_logprobs(
            params, model, jax.tree.map(tile, feats), jax.tree.map(tile, masks),
            flat, precision=precision)
        mask = (flat != PAD_ID).astype(jnp.float32) * tile(valid)[:, None]
        return -jnp.sum(adv.reshape(-1)[:, None] * logp * mask), jnp.sum(mask)

    grad_block = jax.jit(jax.value_and_grad(block_sums, has_aux=True))

    @jax.jit
    def apply(params, mu, nu, count, g_sum, num, den):
        den = jnp.maximum(den, 1.0)
        grads = jax.tree.map(lambda g: g / den, g_sum)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree.leaves(grads)))
        if clip > 0:
            scale = jnp.where(gnorm < clip, 1.0, clip / gnorm)
            grads = jax.tree.map(lambda g: g * scale, grads)
        count = count + 1
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        step = jax.tree.map(
            lambda m, v: -lr * (m / (1 - b1 ** count))
            / (jnp.sqrt(v / (1 - b2 ** count)) + eps), mu, nu)
        params = jax.tree.map(jnp.add, params, step)
        return params, mu, nu, count, num / den, gnorm, grads

    t0 = time.perf_counter()
    first = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params0)
    params = first
    mu = jax.tree.map(jnp.zeros_like, first)
    nu = jax.tree.map(jnp.zeros_like, first)
    count = jnp.zeros((), jnp.float32)
    out = {"loss": [], "grad_norm": []}
    for i, s in enumerate(steps):
        B = s["samples"].shape[1]
        g_sum, num, den = None, 0.0, 0.0
        for a in range(0, B, rows):
            cut = lambda x: x[a:a + rows]  # noqa: E731
            (n, d), g = grad_block(
                params, jax.tree.map(cut, s["feats"]),
                jax.tree.map(cut, s["masks"]), s["samples"][:, a:a + rows],
                s["advantage"][:, a:a + rows], s["valid"][a:a + rows])
            g_sum = g if g_sum is None else jax.tree.map(jnp.add, g_sum, g)
            num, den = num + n, den + d
        params, mu, nu, count, loss, gnorm, grads = apply(
            params, mu, nu, count, g_sum, num, den)
        if i == 0:
            out["grad_leaf"] = host(leaf_norms(grads))
            out["grad"] = jax.device_get(grads)
        out["loss"].append(float(loss))
        out["grad_norm"].append(float(gnorm))
    out["change_leaf"] = host(leaf_norms(params, first))
    if log:
        log(f"the reference followed {len(steps)} steps of "
            f"{steps[0]['samples'].shape[1]} clips x {steps[0]['samples'].shape[0]} "
            f"rollouts in blocks of {rows} clips, precision {precision}: "
            f"{time.perf_counter() - t0:.2f}s")
    return out


def host(tree) -> dict:
    """``leaf_norms``' scalars as floats on the host."""
    import jax

    return {k: float(v) for k, v in jax.device_get(tree).items()}


def worst_leaf_gap(program: dict, reference: dict) -> tuple[float, str]:
    """(the largest gap, its leaf) between two ``{path: norm}`` of one tree."""
    if set(program) != set(reference):
        raise SystemExit("the program's and the reference's trees have "
                         f"different leaves: {sorted(set(program) ^ set(reference))}")
    median = float(np.median(list(reference.values())))
    gaps = {k: abs(program[k] - reference[k]) / max(reference[k], median, 1e-30)
            for k in reference}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def rel_diff(program, reference) -> float:
    """Norm of the difference of two trees over the norm of the second."""
    import jax

    a, b = jax.tree.leaves(program), jax.tree.leaves(reference)
    diff = sum(float(np.sum(np.square(np.asarray(x, np.float64) - y)))
               for x, y in zip(a, b))
    ref = sum(float(np.sum(np.square(np.asarray(y, np.float64)))) for y in b)
    return float(np.sqrt(diff / max(ref, 1e-300)))


def compare(compared, program: dict, reference: dict, limits: dict) -> dict:
    """Hold the program's readings of its first steps (``loss`` a step,
    ``grad``, ``grad_leaf``, ``change_leaf``) to the reference's, into
    ``compared``; returns what a log line wants to say. ``limits``:
    ``rl_loss_abs_tol``, ``grad_leaf_gap_tol``, ``grad_rel_diff_tol``,
    ``change_leaf_gap_tol``."""
    said = {"loss_program": program["loss"], "loss_reference": reference["loss"]}
    for i, (got, want) in enumerate(zip(program["loss"], reference["loss"]), 1):
        compared.at_most(f"rl_loss_step{i}_abs_diff", abs(got - want),
                         limits["rl_loss_abs_tol"])
    gap, leaf = worst_leaf_gap(program["grad_leaf"], reference["grad_leaf"])
    compared.at_most("first_grad_worst_leaf_gap", gap, limits["grad_leaf_gap_tol"])
    said["first_grad_worst_leaf"] = leaf
    compared.at_most("first_grad_rel_diff",
                     rel_diff(program["grad"], reference["grad"]),
                     limits["grad_rel_diff_tol"])
    gap, leaf = worst_leaf_gap(program["change_leaf"], reference["change_leaf"])
    compared.at_most("param_change_worst_leaf_gap", gap,
                     limits["change_leaf_gap_tol"])
    said["param_change_worst_leaf"] = leaf
    return said
