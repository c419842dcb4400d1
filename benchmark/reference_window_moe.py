"""The plain reference of the window/full-attention, routed-expert caption
decoder (``configs/mimo_v2_5_ep16.json``): float32 at ``highest`` matmul
precision, no kernel, no cache across steps, the visible keys as a mask over
a dense product against ALL keys in blocks of query rows, the sink from its
definition, the held experts as a plain loop with a dense choice. Written
from the layer equations the configuration's file states (MiMo-V2.5's
config.json) and independent of the program: it imports nothing of
``cst_captioning_tpu`` and reads the parameter tree as stored, ``model``
being the configuration file's ``model`` dict. bfloat16 values are exact in
float32, so the parameters come as stored and are raised where they are used.

The layer ``l`` (kind from ``mixer_types[l]``; ``norm(x; g) = x / sqrt(mean
x^2 + eps) * g``)::

    y = norm(x; g1)
    q = y Wq in [H, 192];  k = y Wk in [G, 192];  v = scale * y Wv in [G, 128]
    rope on the first 64 dims of q and k, pairs (i, i + 32), base theta_kind
    head h reads key/value head h // (H / G);  s_ij = q_i . k_j / sqrt(192)
    full:    visible j <= i;               p_ij = softmax_j s_ij
    window:  visible i - window < j <= i;  p_ij = exp s_ij / (exp b_h + sum_j' exp s_ij')
    x += concat_h(sum_j p_ij v_j) Wo
    z = norm(x; g2)
    dense (published layer 0):  x += (silu(z Wg) * z Wu) Wd
    experts:  s = sigmoid(z Wr);  chosen = top8(s + b);  w_e = s_e / sum_chosen s
              x += sum over the HELD chosen e of w_e Expert_e(z)
    logits = norm(x; g) W_head

So that 16 k positions fit, the work is cut in ways that change no number's
meaning: a clip's prefix is computed once a call (:func:`prefix_block`: its
attention in blocks of query rows over all the prefix's keys, its FFNs in
blocks of rows, an expert's weights raised one expert at a time) and leaves
each layer what a caption behind it can see: a full layer's keys and values
whole, a window layer's last ``window`` positions (an earlier key is in no
caption query's band). The caption's positions are recomputed whole from that
block at every call (:func:`caption_logits`), so the beam search runs one
caption forward a step and keeps nothing between steps.

The rules this repository adds to the published layers, each in the
configuration's ``assumed``: the video prefix (patch features through a linear
projector ``embed_<m>``, no bias; **a clip's valid slots are moved to the
front in their order and the missing ones are as if they were not there**:
with ``n`` valid slots, slot ``i`` of them is position ``i`` and the caption's
token ``t`` position ``n + t``, BOS first); the sliced head (the softmax is
over the held ``vocab_size`` rows); the held experts (the router scores all
``n_routed_experts`` and normalises over all chosen, only the experts
``expert_share_index * experts_held ...`` are computed, what the absent ones
would add is left out and the partial result goes on).

``precision`` (``bfloat16``, ``float8_e4m3fn``) rounds the operands of every
matrix product, the attention's included: only the controls use it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PAD_ID, BOS_ID, EOS_ID = 0, 1, 2
QUERY_BLOCK, ROW_BLOCK = 128, 2048
_NEG = -1.0e30


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


def _f32(p, x):
    """Parameters raised to float32 where they are used: the barrier ties the
    conversion to the input, so that the compiler can neither hoist it out of
    a loop over steps nor keep every layer's float32 copy alive at once."""
    p, x = jax.lax.optimization_barrier((p, x))
    return jax.tree.map(lambda w: w.astype(jnp.float32), p), x


def _in_blocks(fn, x, block: int):
    """``fn`` over blocks of ``x``'s leading axis, the results joined."""
    N = x.shape[0]
    if N <= block:
        return fn(x)
    pad = (-N) % block
    xp = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    out = jax.lax.map(fn, xp.reshape((-1, block) + x.shape[1:]))
    return out.reshape((-1,) + out.shape[2:])[:N]


def is_window(model: dict, i: int) -> bool:
    return model["mixer_types"][i] == "window"


def is_dense(model: dict, i: int) -> bool:
    return model["first_layer_index"] + i < model["first_k_dense_replace"]


def qkv(p, model: dict, i: int, x, positions, r):
    """The stream x [B, Q, h] -> q [B, Q, H, dk], k [B, Q, G, dk], v
    [B, Q, G, dv] of layer ``i``."""
    window = is_window(model, i)
    H = model["num_attention_heads"]
    G = model["swa_num_key_value_heads" if window else "num_key_value_heads"]
    theta = model["swa_rope_theta" if window else "rope_theta"]
    rot = int(model["head_dim"] * model["partial_rotary_factor"])
    y = norm(x, p["input_layernorm"], model["rms_norm_eps"])
    heads = lambda a, n: a.reshape(a.shape[:-1] + (n, -1))  # noqa: E731
    q = rope(heads(r(y) @ r(p["q_proj"]), H), positions, theta, rot)
    k = rope(heads(r(y) @ r(p["k_proj"]), G), positions, theta, rot)
    v = model["attention_value_scale"] * heads(r(y) @ r(p["v_proj"]), G)
    return q, k, v


def attend(q, keys, values, mask, sink, r):
    """Masked softmax attention from its definition: q [B, Q, H, dk], keys
    [B, K, G, dk], values [B, K, G, dv], mask [B, Q, K], sink [H] or None
    (``exp(sink)`` joins the denominator) -> [B, Q, H, dv]."""
    B, Q, H, dk = q.shape
    G = keys.shape[2]
    qg = q.reshape(B, Q, G, H // G, dk)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", r(qg), r(keys)) / math.sqrt(dk)
    ok = mask[:, None, None]
    top = jnp.max(jnp.where(ok, s, _NEG), axis=-1, keepdims=True)
    if sink is not None:
        b = sink.reshape(G, H // G)[None, :, :, None, None]
        top = jnp.maximum(top, b)
    e = jnp.where(ok, jnp.exp(jnp.where(ok, s, _NEG) - top), 0.0)
    below = e.sum(axis=-1, keepdims=True)
    if sink is not None:
        below = below + jnp.exp(b - top)
    # a query with no visible key (a slot past a clip's ``n``, which nothing
    # reads) gets zeros, not 0 / 0
    out = jnp.einsum("bgrqk,bkgd->bqgrd", r(e / jnp.maximum(below, 1e-30)),
                     r(values))
    return out.reshape(B, Q, H, -1)


def gated(x, gate, up, down, r):
    return r(jax.nn.silu(r(x) @ r(gate)) * (r(x) @ r(up))) @ r(down)


def route(p, model: dict, x, r):
    """-> combine weights [N, n_routed_experts]: ``s[e] / sum(s[chosen])``
    (times ``routed_scaling_factor``) on the chosen experts, 0 elsewhere; the
    choice is the ``num_experts_per_tok`` largest of ``s + bias``."""
    s = jax.nn.sigmoid(r(x) @ r(p["gate"]))
    _, chosen = jax.lax.top_k(s + p["e_score_correction_bias"],
                              model["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * model["routed_scaling_factor"]
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], chosen].set(w)


def expert_ffn(p, model: dict, x, r):
    """The held experts' part of ``sum_e w_e expert_e(x)`` for x [N, h]: every
    held expert over every row, times its combine weight (0 where the row did
    not choose it). ``p`` holds the router as float32 and the experts as
    stored; an expert's weights are raised when its turn comes."""
    lo = model["expert_share_index"] * model["experts_held"]
    w = route(p, model, x, r)[:, lo:lo + model["experts_held"]]      # [N, held]
    stacked = (p["experts_gate_proj"], p["experts_up_proj"], p["experts_down_proj"])
    out = jnp.zeros_like(x)
    for e in range(model["experts_held"]):
        # the barrier ties expert e's slices to the sum so far: one expert's
        # float32 weights are alive at a time, not every expert's of the layer
        stacked, (x, out) = jax.lax.optimization_barrier((stacked, (x, out)))
        wg, wu, wd = (a[e].astype(jnp.float32) for a in stacked)
        rows = lambda a, wg=wg, wu=wu, wd=wd: gated(a, wg, wu, wd, r)  # noqa: E731
        out = out + w[:, e:e + 1] * _in_blocks(rows, x, ROW_BLOCK)
    return out


def ffn(p, model: dict, i: int, x, r):
    """The stream x [B, Q, h] -> the FFN branch of layer ``i``."""
    z = norm(x, p["post_attention_layernorm"], model["rms_norm_eps"])
    z = z.reshape(-1, x.shape[-1])
    if is_dense(model, i):
        rows = lambda a: gated(a, p["gate_proj"], p["up_proj"],  # noqa: E731
                               p["down_proj"], r)
        return _in_blocks(rows, z, ROW_BLOCK).reshape(x.shape)
    return expert_ffn(p, model, z, r).reshape(x.shape)


def _layer(dec, i: int, x):
    """Layer ``i``'s parameters, float32 but for the stacked experts (raised
    an expert at a time, :func:`expert_ffn`)."""
    stored = dec[f"layers_{i}"]
    small, x = _f32({k: v for k, v in stored.items()
                     if not k.startswith("experts_")}, x)
    return {**stored, **small}, x


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


def visible(model: dict, i: int, q_pos, key_pos, key_ok):
    """The mask of layer ``i``: queries at ``q_pos`` [B, Q] over keys at
    ``key_pos`` [B, K] that exist where ``key_ok`` [B, K] -> [B, Q, K]."""
    ok = (key_pos[:, None, :] <= q_pos[..., None]) & key_ok[:, None, :]
    if is_window(model, i):
        ok &= key_pos[:, None, :] > q_pos[..., None] - model["sliding_window"]
    return ok


def prefix_block(params, model: dict, feats, masks, r):
    """The prefix through the stack, once a clip -> (what each layer leaves
    the caption: ``(keys, values, their positions [B, K])``, a full layer's
    whole prefix, a window layer's last ``sliding_window`` positions; n [B])."""
    dec = params["params"]["decoder"]
    window = model["sliding_window"]
    x, n = _compact(params, model, feats, masks, r)
    B, P, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(P), (B, P))
    exists = positions < n[:, None]
    tail = n[:, None] - window + jnp.arange(window)[None]          # [B, window]
    depth, left = model["num_hidden_layers"], []
    for i in range(depth):
        p, x = _layer(dec, i, x)
        q, k, v = qkv(p, model, i, x, positions, r)
        if is_window(model, i):
            take = lambda a: jnp.take_along_axis(  # noqa: E731
                a, jnp.clip(tail, 0, P - 1)[..., None, None], axis=1)
            kept = (take(k), take(v), tail)
        else:
            kept = (k, v, positions)
        # the barrier has the compiler cut what the caption reads out of this
        # layer's keys here, and not keep every layer's until the caption runs
        kept, x = jax.lax.optimization_barrier((kept, x))
        left.append(kept)
        if i + 1 == depth:
            break       # the last layer's output over the prefix feeds nothing
        sink = p.get("attention_sink_bias")

        def queries(block, k=k, v=v, sink=sink, i=i):
            qb, pos = block                     # [Qb, B, H, d], [Qb, B]
            qb, pos = jnp.swapaxes(qb, 0, 1), jnp.swapaxes(pos, 0, 1)
            return jnp.swapaxes(attend(
                qb, k, v, visible(model, i, pos, positions, exists), sink, r), 0, 1)

        Qb = min(QUERY_BLOCK, P)
        edge = (-P) % Qb
        by_pos = lambda a: jnp.pad(  # noqa: E731
            jnp.swapaxes(a, 0, 1), [(0, edge)] + [(0, 0)] * (a.ndim - 1))
        qs, ps = by_pos(q), by_pos(positions)
        attn = jax.lax.map(queries, (qs.reshape((-1, Qb) + qs.shape[1:]),
                                     ps.reshape((-1, Qb) + ps.shape[1:])))
        attn = jnp.swapaxes(attn.reshape((-1,) + attn.shape[2:])[:P], 0, 1)
        x = x + r(attn.reshape(B, P, -1)) @ r(p["o_proj"])
        x = x + ffn(p, model, i, x, r)
    return left, n


def caption_logits(params, model: dict, left, n, tokens_in, r):
    """Logits [B, T, V] of the caption's positions under inputs ``tokens_in``
    [B, T], every position recomputed from the prefix's block."""
    dec = params["params"]["decoder"]
    B, T = tokens_in.shape
    x = dec["embed_tokens"].astype(jnp.float32)[tokens_in]
    positions = n[:, None] + jnp.arange(T)[None, :]
    for i in range(model["num_hidden_layers"]):
        p, x = _layer(dec, i, x)
        q, k, v = qkv(p, model, i, x, positions, r)
        before_k, before_v, before_pos = left[i]
        key_pos = jnp.concatenate([before_pos, positions], axis=1)
        key_ok = jnp.concatenate(
            [(before_pos >= 0) & (before_pos < n[:, None]),
             jnp.ones((B, T), bool)], axis=1)
        attn = attend(q, jnp.concatenate([before_k, k], axis=1),
                      jnp.concatenate([before_v, v], axis=1),
                      visible(model, i, positions, key_pos, key_ok),
                      p.get("attention_sink_bias"), r)
        x = x + r(attn.reshape(B, T, -1)) @ r(p["o_proj"])
        x = x + ffn(p, model, i, x, r)
    x = norm(x, dec["norm"].astype(jnp.float32), model["rms_norm_eps"])
    return r(x) @ r(dec["lm_head"].astype(jnp.float32))


def forward(params, model: dict, feats, masks, tokens_in, r):
    left, n = prefix_block(params, model, feats, masks, r)
    return caption_logits(params, model, left, n, tokens_in, r)


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
            # a hypothesis at a time over the clip's one block
            logits = jax.vmap(
                lambda toks: caption_logits(params, model, left, n,
                                            _inputs(toks), r),
                in_axes=1, out_axes=1)(tokens)                  # [B, W, T, V]
            logp = jax.nn.log_softmax(_forbid(logits[:, :, t]), axis=-1)
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
