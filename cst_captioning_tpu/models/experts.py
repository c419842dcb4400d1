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
chosen experts it holds for every token routed to them: the (token, expert)
pairs are sorted once by held expert and one grouped product walks the row
tiles that hold a pair, as many as an expert has, or (several experts a token
over many rows) every held expert walks its own rows in blocks
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

from cst_captioning_tpu.ops import grouped_ffn
from cst_captioning_tpu.ops.grouped_ffn import gated  # noqa: F401  (the dense FFNs')

# the most the grouped product's sorted copy of ``x`` may hold where a token
# has several experts (:func:`held_experts`)
CHUNK_BYTES = 32 * 1024 * 1024


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


def expert_tile_rows(n_tokens: int, k: int, n_experts: int) -> int:
    """Rows of a tile of the grouped product: the power of two that holds
    four times the rows an expert expects under uniform routing, from 16 (a
    beam step's few rows: a tile reads an expert's bytes once) to 512 (a
    prefix: the matrix unit outruns the matrices' reads)."""
    rows = 16
    while rows < min(4 * n_tokens * k / max(n_experts, 1), 512):
        rows *= 2
    return rows


def expert_block_rows(n_tokens: int, k: int, n_experts: int) -> int:
    """Rows an expert walks at a time where the experts are walked one by one
    (:func:`held_experts`): the smallest multiple of 128 that holds twice the
    rows an expert expects under uniform routing, at most 1024 (and never
    more than the tokens there are, rounded up to 8)."""
    expected = n_tokens * k / max(n_experts, 1)
    rows = min(-(-int(2 * expected) // 128) * 128 or 128, 1024)
    return min(rows, -(-n_tokens // 8) * 8)


def held_experts(x, chosen, weights, live, gate_w, up_w, down_w, lo: int,
                 n_experts: int, differentiable: bool, layer=None):
    """The held experts' part of ``sum_e w_e expert_e(x)`` for ``x [N, h]``.

    **One grouped product** (ops/grouped_ffn.py) wherever a token has one
    expert (``k`` = 1) or the sorted copy of ``x`` with every pair held fits
    ``CHUNK_BYTES`` (a beam step's few rows): the ``N x k`` (token, expert)
    pairs are sorted once by held expert (not held, not live and "no expert"
    behind them), each expert's pairs laid out from a tile boundary on in
    tiles of :func:`expert_tile_rows`, and the product walks the tiles that
    hold a pair; a token's (at most ``k``) outputs are gathered back from
    the slots its pairs took and summed in float32 in the order it chose
    them: no scatter, no loop a held expert. ``differentiable`` (teacher
    forcing, which a loss may differentiate) takes this path with the
    product's compiled map, since a kernel has no transpose.
    **An expert at a time** elsewhere (``k`` > 1 over many rows: the sorted
    copy would be ``k`` times ``x``, and summing ``k`` gathered copies or
    scatter-adding a copy's rows costs more than the product wins: measured,
    PERF.md section 6, PR 51): tokens sorted by held expert (a stable argsort
    a column) and every held expert walks its rows in blocks of
    :func:`expert_block_rows`, a ``fori_loop`` with a traced trip count,
    each block's outputs scatter-added to their tokens' float32 sums.
    Either way an expert nobody chose costs nothing and one everybody chose
    takes all its rows: no capacity, no dropped token.
    The weights are ``[held, ...]`` or, with ``layer`` (a traced index),
    every layer's stacked ``[layers, held, ...]``: an expert's matrices are
    read where they lie, so a stack scanned over its layers hands neither
    path a copy of a layer's experts.
    -> (out [N, h] float32, tally [N, held + 1] int32: a token's rows on
    each held expert, and its assignments over all experts)."""
    N, k = chosen.shape
    h = x.shape[-1]
    if layer is None:
        layer, gate_w, up_w, down_w = 0, gate_w[None], up_w[None], down_w[None]
    held = gate_w.shape[1]
    gate_w, up_w, down_w = (w.reshape((-1,) + w.shape[2:])
                            for w in (gate_w, up_w, down_w))
    local = chosen - lo
    key = jnp.where((local >= 0) & (local < held) & live[:, None], local, held)
    onehot = key[:, :, None] == jnp.arange(held)                # [N, k, held]
    hit = onehot.any(axis=1)
    counts = hit.sum(axis=0, dtype=jnp.int32)
    tile = expert_tile_rows(N, k, n_experts)
    most = min(N * k, N * k // tile + held)     # tiles, if every pair is held
    # the experts' weighted outputs are summed in float32 on purpose
    out = jnp.zeros((N, h), jnp.float32)  # graftlint: disable=GL005
    if k == 1 or differentiable or \
            most * tile * h * x.dtype.itemsize <= CHUNK_BYTES:
        out = _grouped(x, key, jnp.where(key < held, weights, 0.0), onehot,
                       counts, layer * held, (gate_w, up_w, down_w), tile, most,
                       "xla" if differentiable else grouped_ffn.impl(), out)
    else:
        wt = (onehot * weights[:, :, None]).sum(axis=1)         # [N, held]
        rows_a_block = expert_block_rows(N, k, n_experts)
        order = jnp.argsort(jnp.logical_not(hit), axis=0, stable=True)
        order = jnp.pad(order, ((0, -N % rows_a_block), (0, 0)))
        for e in range(held):
            def block(b, acc, e=e):
                start = b * rows_a_block
                rows = jax.lax.dynamic_slice_in_dim(order[:, e], start, rows_a_block)
                ok = start + jnp.arange(rows_a_block) < counts[e]
                y = gated(x[rows], *(w[layer * held + e]
                                     for w in (gate_w, up_w, down_w)))
                y = y.astype(jnp.float32) * jnp.where(ok, wt[rows, e], 0.0)[:, None]
                return acc.at[jnp.where(ok, rows, N)].add(y, mode="drop")

            out = jax.lax.fori_loop(0, -(-counts[e] // rows_a_block), block, out)
    assigned = jnp.where(live, k, 0).astype(jnp.int32)
    return out, jnp.concatenate([hit.astype(jnp.int32), assigned[:, None]], 1)


def _grouped(x, key, weights, onehot, counts, base, stack, tile: int, most: int,
             impl: str, out):
    """:func:`held_experts` as one grouped product: key [N, k] (a pair's
    held expert, ``held`` for none), weights [N, k] (0 where none), onehot
    [N, k, held], counts [held]; ``stack`` the experts' three matrices
    ``[groups, ...]`` of which this layer's begin at ``base``; ``most`` row
    tiles of ``tile`` at most."""
    N, k = key.shape
    held = counts.shape[0]
    onehot = onehot.reshape(N * k, held)
    order = jnp.argsort(key.reshape(-1), stable=True)           # the one sort
    # a pair's place among its expert's, in the pairs' order (the sort's)
    rank = ((jnp.cumsum(onehot, axis=0, dtype=jnp.int32) - 1) * onehot).sum(axis=1)
    tiles = -(-counts // tile)
    tile_end = jnp.cumsum(tiles)
    n_tiles, first_tile = tile_end[-1], tile_end - tiles
    t = jnp.arange(most)
    expert = (jnp.minimum(t, n_tiles - 1)[:, None] >= tile_end).sum(axis=1)
    # the pair a slot of the layout holds: its expert's first + its place (a
    # slot past its expert's pairs holds some pair, whose output nothing reads)
    place = ((t - first_tile[expert]) * tile)[:, None] + jnp.arange(tile)
    first = jnp.cumsum(counts) - counts
    pair = order[jnp.minimum(first[expert][:, None] + place, N * k - 1)]
    ys = grouped_ffn.grouped_gated(
        x[pair.reshape(-1) // k], base + expert, n_tiles, *stack, tile=tile,
        impl=impl)
    # a token's outputs, from the slots its pairs took, in the order it chose
    slot = first_tile[jnp.minimum(key, held - 1)] * tile + rank.reshape(N, k)
    for j in range(k):
        out = out + weights[:, j, None] * ys[slot[:, j]]
    return out


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
