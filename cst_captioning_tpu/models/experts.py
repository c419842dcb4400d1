"""The routed-expert FFN of one chip's share (expert parallelism without its
exchange), shared by the decoder kinds that have one: ``latent_moe``
(models/latent_moe.py: a shared expert beside the routed ones),
``window_moe`` (models/window_moe.py: none) and ``cca_moe`` (models/
cca_moe.py: none, and a router of its own, :func:`route_mlp`).

A float32 sigmoid router over all ``n_routed_experts``; the chosen are the
``num_experts_per_tok`` largest of ``score + bias`` (the bias steers the
choice only), ``w = score / sum(chosen scores) * routed_scaling_factor``. The
layer holds ``experts_held`` consecutive experts (``expert_share_index`` says
which), routes over all of them, normalises over all chosen, and computes the
chosen experts it holds for every token routed to them: tokens are sorted by
held expert and each expert walks its own rows in blocks, as many as it has
(:func:`held_experts`), so there is no capacity and no dropped token. What the
absent experts would add is left out and the partial result goes on; nothing
stands in for the other chips.

:func:`route_mlp` is the other router (ZAYA1's): a stream of its own across
depth (this layer's down-projection plus a learned share of the layer
before's), an MLP over it, a softmax over the experts and one output more,
and the one largest: a token may choose no expert at all.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def gated(x, gate, up, down):
    """The gated FFN ``(silu(x W_g) * x W_u) W_d`` in ``x``'s dtype."""
    dt = x.dtype
    return (jax.nn.silu(x @ gate.astype(dt)) * (x @ up.astype(dt))) @ down.astype(dt)


def route(x, gate, bias, k: int, scale: float):
    """Float32 sigmoid router over every expert: -> (chosen [N, k] expert
    ids, weights [N, k] float32). ``bias`` moves the choice, never the
    weights; the weights are normalised over all ``k`` chosen."""
    s = jax.nn.sigmoid(jnp.dot(
        x, gate.astype(x.dtype), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, picked / (picked.sum(-1, keepdims=True) + 1e-20) * scale


def route_mlp(p, x, r_prev, eps: float):
    """The MLP router, float32 from its down-projection on: x [N, h], r_prev
    [N, R] (the layer before's stream; zeros before the first) -> (r [N, R]:
    ``x W_down + gamma r_prev``, chosen [N], weight [N] float32).
    ``softmax(W3 gelu(W2 gelu(W1 norm(r) + b1) + b2) + b3)`` has one output
    more than there are experts; the last is "no expert". The
    choice is the largest of ``p + bias`` (the bias steers the choice only),
    the weight the chosen output's own probability."""
    f32, best = jnp.float32, jax.lax.Precision.HIGHEST
    r = jnp.dot(x, p["router_down"].astype(x.dtype), preferred_element_type=f32,
                precision=best) + p["router_eda"].astype(f32) * r_prev
    y = r * jax.lax.rsqrt(jnp.mean(r * r, axis=-1, keepdims=True) + eps) \
        * p["router_norm"].astype(f32)
    for i in (1, 2):
        y = jax.nn.gelu(jnp.dot(y, p[f"router_w{i}"].astype(f32), precision=best)
                        + p[f"router_b{i}"].astype(f32), approximate=False)
    prob = jax.nn.softmax(jnp.dot(y, p["router_w3"].astype(f32), precision=best)
                          + p["router_b3"].astype(f32), axis=-1)
    chosen = jnp.argmax(prob + p["router_bias"].astype(f32), axis=-1)
    return r, chosen, jnp.take_along_axis(prob, chosen[:, None], axis=-1)[:, 0]


def expert_block_rows(n_tokens: int, k: int, n_experts: int) -> int:
    """Rows an expert walks at a time: the smallest multiple of 128 that
    holds twice the rows an expert expects under uniform routing, at most
    1024 (and never more than the tokens there are, rounded up to 8)."""
    expected = n_tokens * k / max(n_experts, 1)
    rows = min(-(-int(2 * expected) // 128) * 128 or 128, 1024)
    return min(rows, -(-n_tokens // 8) * 8)


def held_experts(x, chosen, weights, live, gate_w, up_w, down_w, lo: int,
                 n_experts: int, differentiable: bool, layer=None):
    """The held experts' part of ``sum_e w_e expert_e(x)`` for ``x [N, h]``.

    Tokens are sorted by held expert (a stable argsort a column) and every
    held expert walks the rows routed to it in blocks of
    :func:`expert_block_rows`, as many blocks as it has rows: a
    ``fori_loop`` with a traced trip count, so an expert nobody chose costs
    nothing and one everybody chose takes all of them — no capacity, no
    dropped token. ``differentiable`` (teacher forcing, which a loss may
    differentiate) spells the same walk as a static number of blocks under
    ``lax.cond``, since a loop with a traced trip count has no transpose.
    With ``layer`` (a traced index) the weights are every layer's, stacked
    ``[layers, held, ...]``, and an expert's are taken where a block uses
    them: a stack scanned over its layers hands the walk no copy of a
    layer's experts.
    -> (out [N, h] float32, tally [N, held + 1] int32: a token's rows on
    each held expert, and its assignments over all experts)."""
    N, k = chosen.shape
    held = gate_w.shape[0 if layer is None else 1]
    of = (lambda w, e: w[e]) if layer is None else (lambda w, e: w[layer, e])
    local = chosen - lo
    onehot = (local[:, :, None] == jnp.arange(held)) & live[:, None, None]
    hit = onehot.any(axis=1)                                       # [N, held]
    wt = (onehot * weights[:, :, None]).sum(axis=1)                # [N, held]
    counts = hit.sum(axis=0).astype(jnp.int32)
    rows_a_block = expert_block_rows(N, k, n_experts)
    order = jnp.argsort(jnp.logical_not(hit), axis=0, stable=True)  # hits first
    blocks = -(-N // rows_a_block)
    order = jnp.pad(order, ((0, blocks * rows_a_block - N), (0, 0)))
    # the experts' weighted outputs are summed in float32 on purpose
    out = jnp.zeros((N, x.shape[-1]), jnp.float32)  # graftlint: disable=GL005
    for e in range(held):
        def block(b, acc, e=e):
            start = b * rows_a_block
            rows = jax.lax.dynamic_slice_in_dim(order[:, e], start, rows_a_block)
            ok = start + jnp.arange(rows_a_block) < counts[e]
            y = gated(x[rows], of(gate_w, e), of(up_w, e), of(down_w, e))
            y = y.astype(jnp.float32) * jnp.where(ok, wt[rows, e], 0.0)[:, None]
            return acc.at[jnp.where(ok, rows, N)].add(y, mode="drop")

        n_blocks = -(-counts[e] // rows_a_block)
        if differentiable:
            for b in range(blocks):
                out = jax.lax.cond(b < n_blocks, lambda a, b=b: block(b, a),
                                   lambda a: a, out)
        else:
            out = jax.lax.fori_loop(0, n_blocks, block, out)
    assigned = jnp.where(live, k, 0).astype(jnp.int32)
    return out, jnp.concatenate([hit.astype(jnp.int32), assigned[:, None]], 1)


def expert_shapes(cfg, init) -> dict:
    """The parameters of one routed-expert FFN, name -> (initializer, shape):
    the router, the held experts stacked, and the shared expert where the
    configuration has one (``n_shared_experts`` 0 declares none)."""
    h, m, held = cfg.hidden_size, cfg.moe_intermediate_size, cfg.experts_held
    shapes = {
        "gate": (init, (h, cfg.n_routed_experts)),
        "experts_gate_proj": (init, (held, h, m)),
        "experts_up_proj": (init, (held, h, m)),
        "experts_down_proj": (init, (held, m, h)),
    }
    if cfg.n_shared_experts:
        ms = m * cfg.n_shared_experts
        shapes.update(shared_gate_proj=(init, (h, ms)),
                      shared_up_proj=(init, (h, ms)),
                      shared_down_proj=(init, (ms, h)))
    return shapes


def check_share(cfg) -> None:
    """Raise unless the held experts are a share of the routed ones."""
    held, share = cfg.experts_held, cfg.expert_share_index
    if not 0 < held <= cfg.n_routed_experts or not (
            0 <= share * held <= cfg.n_routed_experts - held):
        raise ValueError(
            f"experts_held {held} at expert_share_index {share} is no "
            f"share of n_routed_experts {cfg.n_routed_experts}")
    if not 0 < cfg.num_experts_per_tok <= cfg.n_routed_experts:
        raise ValueError(
            f"num_experts_per_tok {cfg.num_experts_per_tok} must be in "
            f"1..n_routed_experts {cfg.n_routed_experts}")


def expert_ffn(cfg, p, bias, x, live, differentiable: bool):
    """x [N, h], live [N] -> (out [N, h], tally [N, held + 1]): the shared
    expert (where ``p`` holds one) plus the held experts' part of the routed
    sum, from the parameters :func:`expert_shapes` names and the router's
    ``bias``."""
    chosen, weights = route(x, p["gate"], bias, cfg.num_experts_per_tok,
                            cfg.routed_scaling_factor)
    routed, tally = held_experts(
        x, chosen, weights, live, p["experts_gate_proj"],
        p["experts_up_proj"], p["experts_down_proj"],
        cfg.expert_share_index * cfg.experts_held, cfg.n_routed_experts,
        differentiable)
    if "shared_gate_proj" in p:
        shared = gated(x, p["shared_gate_proj"], p["shared_up_proj"],
                       p["shared_down_proj"])
        routed = shared.astype(jnp.float32) + routed
    return routed.astype(x.dtype), tally
