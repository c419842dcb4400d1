"""The plain reference of the compressed-convolutional-attention, top-1-expert
caption decoder (``configs/zaya1_8b_20l.json``): float32 at ``highest`` matmul
precision, no kernel, no cache across steps, the convolutions as shifted adds
over the whole sequence, causal attention as a mask over a dense product
against ALL keys in blocks of query rows, every held expert over every row
masked by the choice. Written from the layer equations the configuration's
file states (ZAYA1-8B's config.json and arXiv:2510.04476) and independent of
the program: it imports nothing of ``cst_captioning_tpu`` and reads the
parameter tree as stored (every layer's leaf stacked on a leading axis),
``model`` being the configuration file's ``model`` dict. bfloat16 values are
exact in float32, so the parameters come as stored and are raised where they
are used, a layer and an expert at a time.

The layer (``norm(x; g) = x / sqrt(mean x^2 + eps) * g``; ``H`` query heads,
``G`` key/value heads, ``d = head_dim``; ``a_{-1} = 0`` for every sequence
``a``)::

    u = norm(x; g1)
    q~_t = u_t Wq in [H, d];  k~_t = u_t Wk in [G, d]
    v_t = [u_t Wv ; u_{t-1} Wv']           (each half G d / 2 channels)
    c_t = [q~_t ; k~_t]                    ((H + G) d channels, H + G heads)
    a_t[c] = w0[0, c] c_{t-1}[c] + w0[1, c] c_t[c] + b0[c]        (depthwise)
    m_t[g] = a_{t-1}[g] W1[0, g] + a_t[g] W1[1, g] + b1[g]   (a head a group)
    q_t[h] = m_t[h] + (q~_t[h] + k~_t[h // (H / G)]) / 2
    k_t[g] = m_t[H + g] + (mean_{h in g} q~_t[h] + k~_t[g]) / 2
    q <- sqrt(d) q / |q|;  k <- tau_g sqrt(d) k / |k|          (a head each)
    rope on the first int(d partial_rotary_factor) dims of q and k, pairs
        (i, i + half), base rope_theta
    s_ij = q_i . k_j / sqrt(d), j <= i;  head h reads key/value head h // (H / G)
    x = (a1 x + b1) + (a2 (concat_h sum_j softmax_j(s_ij) v_j) Wo + b2)
    z = norm(x; g2)
    r_l = z Wdown + gamma_l r_{l-1}        (r of the layer before the first = 0)
    p = softmax(gelu(gelu(norm(r_l; gr) W1 + c1) W2 + c2) W3 + c3) in [E + 1]
    e = argmax(p + bias);  f = p_e Expert_e(z) if e < E and e is held, else 0
    x = (a3 x + b3) + (a4 f + b4)
    logits = norm(x; g) E^T                (E: the token embedding, tied)

So that 16 k positions fit, the work is cut in ways that change no number's
meaning: a clip's prefix is computed once a call (:func:`prefix_block`: its
attention in blocks of query rows over all the prefix's keys, its experts in
blocks of rows, an expert's weights raised one expert at a time) and leaves
each layer what a caption behind it reads: its keys and values, and of its
last position ``n - 1`` the three things a convolution of width 2 and the
value's shift reach back for (``c``, ``a`` and ``u Wv'``). The caption's
positions are recomputed whole from that block at every call
(:func:`caption_logits`), so the beam search runs one caption forward a step
and keeps nothing between steps. :func:`forward_whole` is the same stack over
prefix and caption as ONE sequence, with nothing handed over: the tests hold
the two to each other.

The rules this repository adds to the published layers, each in the
configuration's ``assumed``: the video prefix (patch features through a linear
projector ``embed_<m>``, no bias; **a clip's valid slots are moved to the
front in their order and the missing ones are as if they were not there**:
with ``n`` valid slots, slot ``i`` of them is position ``i`` and the caption's
token ``t`` position ``n + t``, BOS first); the held experts (the router
scores all ``n_routed_experts`` and the no-expert output, only the experts
``expert_share_index * experts_held ...`` are computed, what an absent one
would add is left out and the partial result goes on).

``precision`` (``bfloat16``, ``float8_e4m3fn``) rounds the operands of every
matrix product, the attention's and the convolutions' included: only the
controls use it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PAD_ID, BOS_ID, EOS_ID = 0, 1, 2
QUERY_BLOCK, ROW_BLOCK = 128, 2048
_NEG = -1.0e30
NORM_FLOOR = 1e-12      # under the root of a head's squared length


def rounder(precision: str):
    """x -> x rounded to ``precision`` (a one-byte type after scaling to the
    tensor's largest magnitude) and back to float32."""
    if precision == "float32":
        return lambda x: x
    dtype = jnp.dtype(precision)
    top = float(jnp.finfo(dtype).max)

    def rounded(x):
        x0 = jax.lax.stop_gradient(x)
        if dtype.itemsize > 1:
            y = x0.astype(dtype).astype(jnp.float32)
        else:
            s = jnp.maximum(jnp.max(jnp.abs(x0)), 1e-30) / top
            y = (x0 / s).astype(dtype).astype(jnp.float32) * s
        return x + (y - x0)

    return rounded


# ---- the pieces ---------------------------------------------------------------


def norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, positions, theta, rot: int):
    """x [..., heads, d]: the first ``rot`` dims rotated at ``positions``
    [...], pairs (i, i + rot/2); the others pass."""
    inv_freq = float(theta) ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angle = positions[..., None, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = jnp.split(x[..., :rot], 2, axis=-1)
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def _in_blocks(fn, x, block: int):
    """``fn`` over blocks of ``x``'s leading axis, the results joined."""
    N = x.shape[0]
    if N <= block:
        return fn(x)
    pad = (-N) % block
    xp = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    out = jax.lax.map(fn, xp.reshape((-1, block) + x.shape[1:]))
    return out.reshape((-1,) + out.shape[2:])[:N]


def shifted(a, first):
    """``a [B, S, ...]`` one position late: entry ``t`` is ``a[t - 1]`` and
    entry 0 is ``first [B, ...]`` (zeros at a sequence's start; behind a
    prefix, what its last position left)."""
    return jnp.concatenate([first[:, None], a[:, :-1]], axis=1)


def value(p, u, first, r):
    """v_t = [u_t Wv ; u_{t-1} Wv'] -> (v [B, S, G d], u Wv' [B, S, G d / 2]:
    what the next position's second half is)."""
    late = r(u) @ r(p["v_shift_proj"])
    return jnp.concatenate([r(u) @ r(p["v_proj"]), shifted(late, first)], -1), late


def mix(p, c, first_c, first_a, heads: int, r):
    """The two causal convolutions of width 2 over ``c [B, S, heads x d]``:
    depthwise along the sequence, then a head a group -> (m, a)."""
    B, S, C = c.shape
    w0 = p["conv0_w"]
    a = r(w0[0]) * r(shifted(c, first_c)) + r(w0[1]) * r(c) + p["conv0_b"]
    grouped = lambda x, w: jnp.einsum(  # noqa: E731
        "bsgi,gio->bsgo", r(x.reshape(B, S, heads, -1)), r(w)).reshape(B, S, C)
    w1 = p["conv1_w"]
    m = grouped(shifted(a, first_a), w1[0]) + grouped(a, w1[1]) + p["conv1_b"]
    return m, a


def qk_mean(m_q, m_k, q0, k0):
    """The mean of the pre-convolution latents added to the mixed ones: q0
    [B, S, H, d], k0 [B, S, G, d]; a query head takes its key head's, a key
    head the mean of its query heads'."""
    B, S, H, d = q0.shape
    G = k0.shape[2]
    q = m_q + (q0 + jnp.repeat(k0, H // G, axis=2)) / 2
    k = m_k + (q0.reshape(B, S, G, H // G, d).mean(axis=3) + k0) / 2
    return q, k


def unit(x, d: int):
    """A head scaled to length sqrt(d)."""
    return x * math.sqrt(d) * jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + NORM_FLOOR)


def attend(q, keys, values, mask, r):
    """Masked softmax attention from its definition: q [B, Q, H, d], keys
    [B, K, G, d], values [B, K, G, d], mask [B, Q, K] -> [B, Q, H, d]."""
    B, Q, H, d = q.shape
    G = keys.shape[2]
    qg = q.reshape(B, Q, G, H // G, d)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", r(qg), r(keys)) / math.sqrt(d)
    ok = mask[:, None, None]
    top = jnp.max(jnp.where(ok, s, _NEG), axis=-1, keepdims=True)
    e = jnp.where(ok, jnp.exp(jnp.where(ok, s, _NEG) - top), 0.0)
    # a query with no visible key (a slot past a clip's ``n``, which nothing
    # reads) gets zeros, not 0 / 0
    below = jnp.maximum(e.sum(axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", r(e / below), r(values))
    return out.reshape(B, Q, H, d)


def gated(x, gate, up, down, r):
    return r(jax.nn.silu(r(x) @ r(gate)) * (r(x) @ r(up))) @ r(down)


def route(p, model: dict, z, r_prev, r):
    """The router's stream and the combine weights: z [N, h], r_prev [N, R]
    -> (r_l [N, R], w [N, n_routed_experts]: ``p_e`` on the chosen expert, 0
    elsewhere, all 0 where the no-expert output was chosen). The balancing
    bias moves the choice, never the weight."""
    E = model["n_routed_experts"]
    gelu = lambda x: jax.nn.gelu(x, approximate=False)  # noqa: E731
    r_l = r(z) @ r(p["router_down"]) + p["router_eda"] * r_prev
    y = norm(r_l, p["router_norm"], model["rms_norm_eps"])
    y = gelu(r(y) @ r(p["router_w1"]) + p["router_b1"])
    y = gelu(r(y) @ r(p["router_w2"]) + p["router_b2"])
    prob = jax.nn.softmax(r(y) @ r(p["router_w3"]) + p["router_b3"], axis=-1)
    chosen = jnp.argmax(prob + p["router_bias"], axis=-1)
    return r_l, (jax.nn.one_hot(chosen, E + 1) * prob)[:, :E]


def experts(stacked, model: dict, z, w, r):
    """The held experts' part of ``sum_e w_e expert_e(z)`` for z [N, h]: every
    held expert over every row, times its combine weight (0 where the row did
    not choose it). ``stacked`` holds a layer's experts as stored; an expert's
    weights are raised when its turn comes."""
    held = model["experts_held"]
    lo = model["expert_share_index"] * held
    w = w[:, lo:lo + held]

    def one(e, out):
        wg, wu, wd = (a[e].astype(jnp.float32) for a in stacked)
        rows = lambda a: gated(a, wg, wu, wd, r)  # noqa: E731
        return out + jax.lax.dynamic_slice_in_dim(w, e, 1, 1) \
            * _in_blocks(rows, z, ROW_BLOCK)

    return jax.lax.fori_loop(0, held, one, jnp.zeros_like(z))


def merge(p, name: str, x, f):
    """(a x + b) + (a' f + b'): the residual's and the branch's learned
    scales and offsets of sublayer ``name``."""
    return (p[name + "_res_scale"] * x + p[name + "_res_bias"]) \
        + (p[name + "_out_scale"] * f + p[name + "_out_bias"])


def _raised(stored):
    """A layer's parameters in float32 but for the stacked experts (raised an
    expert at a time, :func:`experts`)."""
    return {k: v if k.startswith("experts_") else v.astype(jnp.float32)
            for k, v in stored.items()}


def layer(p, model: dict, x, r_prev, positions, before, attention, r):
    """One block over x [B, S, h] at ``positions`` [B, S], the router's
    stream r_prev [B S, R]. ``before``: what stands ahead of position 0 of
    these sequences, ``(c, a, u Wv')`` of the position before (zeros at a
    sequence's start). ``attention(q, k, v) -> [B, S, H, d]`` is given the
    layer's queries, keys and values and answers with the heads' outputs (it
    knows the mask and whatever keys lie before). -> (x, r_l, k, v, the three
    of each position: ``(c, a, u Wv')`` [B, S, ...])."""
    H, G, d = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    B, S, h = x.shape
    first_c, first_a, first_v = before
    u = norm(x, p["input_layernorm"], model["rms_norm_eps"])
    q0 = r(u) @ r(p["q_proj"])
    k0 = r(u) @ r(p["k_proj"])
    v, late = value(p, u, first_v, r)
    c = jnp.concatenate([q0, k0], axis=-1)
    m, a = mix(p, c, first_c, first_a, H + G, r)
    heads = lambda t, n: t.reshape(B, S, n, d)  # noqa: E731
    q, k = qk_mean(heads(m[..., :H * d], H), heads(m[..., H * d:], G),
                   heads(q0, H), heads(k0, G))
    rot = int(d * model["partial_rotary_factor"])
    q = rope(unit(q, d), positions, model["rope_theta"], rot)
    k = rope(unit(k, d) * p["temp"][:, None], positions, model["rope_theta"], rot)
    v = heads(v, G)
    attn = attention(q, k, v)
    x = merge(p, "attn", x, r(attn.reshape(B, S, H * d)) @ r(p["o_proj"]))
    z = norm(x, p["post_attention_layernorm"], model["rms_norm_eps"])
    z = z.reshape(B * S, h)
    r_l, w = route(p, model, z, r_prev, r)
    stacked = (p["experts_gate_proj"], p["experts_up_proj"],
               p["experts_down_proj"])
    x = merge(p, "moe", x, experts(stacked, model, z, w, r).reshape(B, S, h))
    return x, r_l, k, v, (c, a, late)


def _compact(params, model: dict, feats, masks, r):
    """-> (x [B, P, h]: each clip's valid slots first, n [B])."""
    dec = params["params"]["decoder"]
    names = [m for m, _ in model["modalities"]]
    valid = jnp.concatenate([jnp.asarray(masks[m]) > 0 for m in names], axis=1)
    x = jnp.concatenate([
        r(jnp.asarray(feats[m], jnp.float32))
        @ r(dec["embed_" + m].astype(jnp.float32)) for m in names], axis=1)
    order = jnp.argsort(jnp.logical_not(valid), axis=1, stable=True)
    x = jnp.take_along_axis(x, order[..., None], axis=1)
    n = valid.sum(axis=1).astype(jnp.int32)
    return x * (jnp.arange(x.shape[1])[None] < n[:, None])[..., None], n


def _zeros_before(model: dict, B: int):
    C = (model["num_attention_heads"] + model["num_key_value_heads"]) \
        * model["head_dim"]
    half = model["num_key_value_heads"] * model["head_dim"] // 2
    return (jnp.zeros((B, C)), jnp.zeros((B, C)), jnp.zeros((B, half)))


def _causal_in_blocks(q, k, v, positions, exists, r):
    """Queries in blocks of rows over all of the sequence's keys, query ``i``
    seeing the keys ``j <= i`` that exist."""
    B, P = positions.shape

    def queries(block):
        qb, pos = block                         # [Qb, B, H, d], [Qb, B]
        qb, pos = jnp.swapaxes(qb, 0, 1), jnp.swapaxes(pos, 0, 1)
        ok = (positions[:, None, :] <= pos[..., None]) & exists[:, None, :]
        return jnp.swapaxes(attend(qb, k, v, ok, r), 0, 1)

    Qb = min(QUERY_BLOCK, P)
    edge = (-P) % Qb
    by_pos = lambda a: jnp.pad(  # noqa: E731
        jnp.swapaxes(a, 0, 1), [(0, edge)] + [(0, 0)] * (a.ndim - 1))
    qs, ps = by_pos(q), by_pos(positions)
    attn = jax.lax.map(queries, (qs.reshape((-1, Qb) + qs.shape[1:]),
                                 ps.reshape((-1, Qb) + ps.shape[1:])))
    return jnp.swapaxes(attn.reshape((-1,) + attn.shape[2:])[:P], 0, 1)


def _whole(params, model: dict, x, n_exist, r, last=None):
    """The stack over whole sequences x [B, S, h] whose first ``n_exist`` [B]
    positions exist, nothing before them -> (x, what each layer leaves:
    keys, values [L, B, S, G, d] and, where ``last`` [B] names a position,
    ``(c, a, u Wv')`` [L, B, ...] of that one)."""
    dec = params["params"]["decoder"]
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    exists = positions < n_exist[:, None]
    before = _zeros_before(model, B)

    def block(state, stored):
        x, r_prev = state
        attention = lambda q, k, v: _causal_in_blocks(  # noqa: E731
            q, k, v, positions, exists, r)
        x, r_l, k, v, each = layer(_raised(stored), model, x, r_prev,
                                   positions, before, attention, r)
        at_last = () if last is None else tuple(
            jnp.take_along_axis(a, last[:, None, None], axis=1)[:, 0]
            for a in each)
        return (x, r_l), (k, v, at_last)

    r0 = jnp.zeros((B * S, model["router_hidden_size"]))
    (x, _), left = jax.lax.scan(block, (x, r0), dec["layers"])
    return x, left


def prefix_block(params, model: dict, feats, masks, r):
    """The prefix through the stack, once a clip -> (what each layer leaves
    the caption, stacked over the layers: the prefix's keys and values [L, B,
    P, G, d] and ``(c, a, u Wv')`` of its last position ``n - 1`` [L, B,
    ...], zeros for a clip without a slot; n [B])."""
    x, n = _compact(params, model, feats, masks, r)
    _, (k, v, tail) = _whole(params, model, x, n, r, last=jnp.maximum(n - 1, 0))
    return (k, v, tuple(a * (n > 0)[None, :, None] for a in tail)), n


def _head(dec, model: dict, x, r):
    x = norm(x, dec["norm"].astype(jnp.float32), model["rms_norm_eps"])
    return r(x) @ r(dec["embed_tokens"].astype(jnp.float32)).T


def caption_logits(params, model: dict, left, n, tokens_in, r):
    """Logits [B, T, V] of the caption's positions under inputs ``tokens_in``
    [B, T], every position recomputed from the prefix's block."""
    dec = params["params"]["decoder"]
    B, T = tokens_in.shape
    x = dec["embed_tokens"][tokens_in].astype(jnp.float32)
    positions = n[:, None] + jnp.arange(T)[None, :]
    P = left[0].shape[2]
    before_pos = jnp.broadcast_to(jnp.arange(P), (B, P))
    key_pos = jnp.concatenate([before_pos, positions], axis=1)
    key_ok = jnp.concatenate(
        [before_pos < n[:, None], jnp.ones((B, T), bool)], axis=1)
    ok = (key_pos[:, None, :] <= positions[..., None]) & key_ok[:, None, :]

    def block(state, xs):
        x, r_prev = state
        stored, (before_k, before_v, before) = xs
        attention = lambda q, k, v: attend(  # noqa: E731
            q, jnp.concatenate([before_k, k], axis=1),
            jnp.concatenate([before_v, v], axis=1), ok, r)
        x, r_l, *_ = layer(_raised(stored), model, x, r_prev, positions,
                           before, attention, r)
        return (x, r_l), None

    r0 = jnp.zeros((B * T, model["router_hidden_size"]))
    (x, _), _ = jax.lax.scan(block, (x, r0), (dec["layers"], left))
    return _head(dec, model, x, r)


def forward(params, model: dict, feats, masks, tokens_in, r):
    left, n = prefix_block(params, model, feats, masks, r)
    return caption_logits(params, model, left, n, tokens_in, r)


def forward_whole(params, model: dict, feats, masks, tokens_in, r):
    """The same logits from ONE sequence a clip, the caption's tokens written
    behind the prefix's ``n`` positions and nothing handed from one part to
    the other: what :func:`forward` is held to (tests)."""
    dec = params["params"]["decoder"]
    x, n = _compact(params, model, feats, masks, r)
    B, T = tokens_in.shape
    at = n[:, None] + jnp.arange(T)[None, :]
    x = jnp.pad(x, ((0, 0), (0, T), (0, 0))).at[
        jnp.arange(B)[:, None], at].set(
            dec["embed_tokens"][tokens_in].astype(jnp.float32))
    x, _ = _whole(params, model, x, n + T, r)
    return _head(dec, model, jnp.take_along_axis(x, at[..., None], axis=1), r)


def _inputs(tokens):
    """``tokens`` shifted right behind BOS: what the decoder reads."""
    bos = jnp.full((tokens.shape[0], 1), BOS_ID, jnp.int32)
    return jnp.concatenate([bos, tokens[:, :-1]], axis=1)


def _forbid(logits):
    return logits.at[..., PAD_ID].set(-1.0e9).at[..., BOS_ID].set(-1.0e9)


def _alive(tokens):
    """[B, T] True up to and including a row's first EOS (or PAD)."""
    ended = (tokens == EOS_ID) | (tokens == PAD_ID)
    return jnp.cumsum(ended, axis=1) - ended == 0


# ---- what the harness calls ---------------------------------------------------


def token_logprobs(params, model: dict, feats, masks, tokens,
                   forbid_special: bool = False, precision: str = "float32"):
    """Per-position log-probability of ``tokens`` [B, T] under teacher
    forcing; positions after a row's EOS read 0."""
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits = forward(params, model, feats, masks, _inputs(tokens),
                         rounder(precision))
        if forbid_special:
            logits = _forbid(logits)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
        return jnp.where(_alive(tokens), picked, 0.0)


def beam_logprobs(params, model: dict, feats, masks, tokens, beam: int,
                  precision: str = "float32"):
    """``(logp, edge)``, each [B, T] and 0 after a row's EOS, along
    ``tokens`` under teacher forcing with PAD and BOS forbidden: the token's
    log-probability, and that of the ``beam``-th most probable token there."""
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits = _forbid(forward(params, model, feats, masks, _inputs(tokens),
                                 rounder(precision)))
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
        edge = jax.lax.top_k(logp, beam)[0][..., -1]
        alive = _alive(tokens)
        return jnp.where(alive, picked, 0.0), jnp.where(alive, edge, 0.0)


def beam_search(params, model: dict, feats, masks, beam: int, max_len: int,
                length_penalty: float = 0.0, precision: str = "float32"):
    """The plain beam search: every clip keeps ``beam`` hypotheses; the
    prefix's block is computed once a clip and read by all of them, and a
    step is one forward over each hypothesis' whole caption (the positions
    behind the newest are PAD and in no mask of it), read at the newest
    position (PAD and BOS forbidden); a hypothesis that has ended goes on
    with PAD at no cost; the ``beam`` best of ``beam * V`` candidates are
    kept; the first step has one live hypothesis. -> (tokens [B, max_len],
    PAD after a caption's EOS; score [B])."""
    r = rounder(precision)
    W = int(beam)
    with jax.default_matmul_precision("highest"):
        left, n = prefix_block(params, model, feats, masks, r)
        B = n.shape[0]

        def step(state, t):
            score, done, tokens = state         # [B, W], [B, W], [B, W, T]
            # every hypothesis over the clip's one block
            logits = jax.vmap(
                lambda toks: caption_logits(params, model, left, n,
                                            _inputs(toks), r)[:, t])(
                jnp.swapaxes(tokens, 0, 1))                     # [W, B, V]
            logp = jax.nn.log_softmax(_forbid(jnp.swapaxes(logits, 0, 1)),
                                      axis=-1)
            V = logp.shape[-1]
            ended = jnp.full((V,), -1.0e9).at[PAD_ID].set(0.0)
            logp = jnp.where(done[:, :, None], ended, logp)
            score, flat = jax.lax.top_k(
                (score[:, :, None] + logp).reshape(B, W * V), W)
            parent, tok = flat // V, (flat % V).astype(jnp.int32)
            tokens = jnp.take_along_axis(tokens, parent[:, :, None], axis=1)
            tokens = tokens.at[:, :, t].set(tok)
            done = jnp.take_along_axis(done, parent, axis=1) | (tok == EOS_ID)
            return (score, done, tokens), None

        state = (jnp.full((B, W), -1.0e9).at[:, 0].set(0.0),
                 jnp.zeros((B, W), bool),
                 jnp.full((B, W, max_len), PAD_ID, jnp.int32))
        (score, _, tokens), _ = jax.lax.scan(step, state, jnp.arange(max_len))
        if length_penalty > 0.0:
            length = jnp.maximum((tokens != PAD_ID).sum(-1), 1)
            score = score / length.astype(jnp.float32) ** length_penalty
        best = jnp.argmax(score, axis=1)
        return (jnp.take_along_axis(tokens, best[:, None, None], axis=1)[:, 0],
                jnp.take_along_axis(score, best[:, None], axis=1)[:, 0])
