"""The plain reference of the EVA-attention byte-level caption decoder
(``configs/evabyte_8l.json``): float32 at ``highest`` matmul precision, no
kernel, no cache across steps, the visible sets as a mask over a dense
product in blocks of query rows, the summaries from their definition. Written
from the layer equations the configuration's file states (EvaByte's
config.json; the EVA estimator of arXiv:2302.04542 for the sets; the family's
released form for the pooling the config does not state) and independent of
the program: it imports nothing of ``cst_captioning_tpu`` and reads the
parameter tree as stored, ``model`` being the configuration file's ``model``
dict. A layer's weights are raised to float32 where the layer uses them.

The layer, ``h`` the float32 residual stream, ``norm(x; g) = x / rms(x) *
(1 + g)``::

    y = norm(h; g1);  q_i = rope(y_i Wq, i), k_i = rope(y_i Wk, i), v_i = y_i Wv
    chunk c = positions [chunk c, chunk c + chunk), head h with phi_h, mu_h:
        a_j = softmax over j in c of (k_j . phi_h)
        k~_c = sum_j a_j k_j + mu_h        v~_c = sum_j a_j v_j
    query i, window w = i // window:  E_i = {j : window w <= j <= i},
                                      S_i = {c : c < (window / chunk) w}
        o_i = softmax over E_i and S_i together of (q_i . key / sqrt d), times the values
    h += o Wo;   h += (silu(z Wg) * (z Wu)) Wd  with z = norm(h; g2)
    logits = norm(h; g) W_head[:, 0:vocab_size]

So that 16 k positions fit, the work is cut in ways that change no number's
meaning: a clip's prefix is computed once a call (:func:`prefix_block`: its
attention as a mask over ALL the prefix's keys and chunk summaries, in blocks
of query rows; its FFN in blocks of rows) and leaves each layer what a caption
behind it can see by the sets above, the prefix's chunk summaries and the keys
of its last ``window`` positions (a caption's queries lie in the window
position ``n`` lies in or a later one, and exact keys of an earlier window are
in no ``E_i``); the caption's positions are recomputed whole from that block
at every call (:func:`caption_logits`), with the positions from that window's
start on laid out by position, prefix then caption, and every chunk of them
summarised from its definition. The beam search runs one caption forward a
step, over the positions run so far rounded up to a segment.

The rules this repository adds to the published layers, each in the
configuration's ``assumed``: the video prefix (patch features through a linear
projector ``embed_<m>``, no bias; **a clip's valid slots are moved to the
front in their order and the missing ones are as if they were not there**:
with ``n`` valid slots, slot ``i`` of them is position ``i`` and the caption's
token ``t`` position ``n + t``, BOS first); the pooling above; the head's
``num_pred_heads`` blocks of which block 0 is read.

``precision`` (``bfloat16``, ``float8_e4m3fn``) rounds the operands of every
matrix product, the attention's included: only the controls use it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PAD_ID, BOS_ID, EOS_ID = 0, 1, 2
QUERY_BLOCK, ROW_BLOCK, SEARCH_SEGMENT = 128, 2048, 32
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
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g)


def rope(x, positions, theta):
    """x [..., H, d] rotated at ``positions`` [...]: pairs (i, i + d/2)."""
    d = x.shape[-1]
    inv_freq = float(theta) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions[..., None, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _layer_f32(p, x):
    """A layer's parameters raised to float32 where the layer uses them (the
    barrier keeps the compiler from raising every layer at once)."""
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


def qkv(p, model: dict, x, positions, r):
    """The stream x [B, Q, h] -> q, k, v [B, Q, H, d]."""
    H = model["num_attention_heads"]
    y = norm(x, p["input_layernorm"], model["rms_norm_eps"])
    heads = lambda a: a.reshape(a.shape[:-1] + (H, -1))  # noqa: E731
    q = rope(heads(r(y) @ r(p["q_proj"])), positions, model["rope_theta"])
    k = rope(heads(r(y) @ r(p["k_proj"])), positions, model["rope_theta"])
    return q, k, heads(r(y) @ r(p["v_proj"]))


def ffn(p, model: dict, x, r):
    """The stream x [B, Q, h] -> the FFN branch, in blocks of rows."""
    def rows(x):
        z = norm(x, p["post_attention_layernorm"], model["rms_norm_eps"])
        return r(jax.nn.silu(r(z) @ r(p["gate_proj"])) * (r(z) @ r(p["up_proj"]))) \
            @ r(p["down_proj"])

    return _in_blocks(rows, x.reshape(-1, x.shape[-1]), ROW_BLOCK).reshape(x.shape)


def summaries(p, model: dict, k, v):
    """k, v [B, N, H, d] with ``chunk | N`` -> each chunk's summary,
    [B, N / chunk, H, d]: the definition above."""
    chunk = model["chunk_size"]
    B, N, H, d = k.shape
    kc, vc = (x.reshape(B, N // chunk, chunk, H, d) for x in (k, v))
    a = jax.nn.softmax(jnp.einsum("bcjhd,hd->bcjh", kc, p["phi"]), axis=2)
    return (jnp.einsum("bcjh,bcjhd->bchd", a, kc) + p["mu"],
            jnp.einsum("bcjh,bcjhd->bchd", a, vc))


def attend(q, keys, values, mask, r):
    """Masked softmax attention: q [B, Q, H, d], keys/values [B, K, H, d],
    mask [B, Q, K] -> [B, Q, H, d]."""
    s = jnp.einsum("bqhd,bkhd->bhqk", r(q), r(keys)) / math.sqrt(q.shape[-1])
    prob = jax.nn.softmax(jnp.where(mask[:, None], s, _NEG), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", r(prob), r(values))


def visible(model: dict, q_pos, key_pos, first_chunk: int, chunks: int):
    """The sets as a mask: queries at ``q_pos`` [B, Q] over exact keys at
    positions ``key_pos`` [B, K] and the summaries of chunks ``first_chunk ..
    first_chunk + chunks`` -> ([B, Q, K], [B, Q, chunks])."""
    window, chunk = model["window_size"], model["chunk_size"]
    w = q_pos // window
    exact = (key_pos[:, None, :] // window == w[..., None]) \
        & (key_pos[:, None, :] <= q_pos[..., None])
    pooled = (first_chunk + jnp.arange(chunks))[None, None, :] \
        < (window // chunk) * w[..., None]
    return exact, pooled


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


def prefix_block(params, model: dict, feats, masks, r):
    """The prefix through the stack, once a clip -> (what each layer leaves
    the caption: ``(summary keys, summary values, tail keys, tail values)``,
    the summaries of the prefix's chunks and the keys of the ``window``
    positions from ``base`` on; base [B]: the first position of the window
    position ``n`` lies in; n [B])."""
    dec = params["params"]["decoder"]
    window, chunk = model["window_size"], model["chunk_size"]
    x, n = _compact(params, model, feats, masks, r)
    B, P, _ = x.shape
    pad = (-P) % chunk      # zeros from n on: in no set of a position under n
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    N = P + pad
    positions = jnp.broadcast_to(jnp.arange(N), (B, N))
    base = (n // window) * window
    tail = jnp.clip(base[:, None] + jnp.arange(window)[None], 0, N - 1)
    depth, left = model["num_hidden_layers"], []
    for i in range(depth):
        p, x = _layer_f32(dec[f"layers_{i}"], x)
        q, k, v = qkv(p, model, x, positions, r)
        sk, sv = summaries(p, model, k, v)
        take = lambda a: jnp.take_along_axis(a, tail[..., None, None], axis=1)  # noqa: E731
        # the barrier has the compiler cut what the caption reads out of this
        # layer's keys here, and not keep every layer's until the caption runs
        kept, x = jax.lax.optimization_barrier(((sk, sv, take(k), take(v)), x))
        left.append(kept)
        if i + 1 == depth:
            break

        keys, values = (jnp.concatenate(both, axis=1) for both in ((k, sk), (v, sv)))

        def queries(block, keys=keys, values=values):
            qb, pos = block                     # [Qb, B, H, d], [Qb, B]
            qb, pos = jnp.swapaxes(qb, 0, 1), jnp.swapaxes(pos, 0, 1)
            exact, pooled = visible(model, pos, positions, 0, N // chunk)
            return jnp.swapaxes(attend(
                qb, keys, values, jnp.concatenate([exact, pooled], axis=-1),
                r), 0, 1)

        Qb = min(QUERY_BLOCK, N)
        edge = (-N) % Qb
        by_pos = lambda a: jnp.pad(  # noqa: E731
            jnp.swapaxes(a, 0, 1), [(0, edge)] + [(0, 0)] * (a.ndim - 1))
        qs, ps = by_pos(q), by_pos(positions)
        attn = jax.lax.map(queries, (qs.reshape((-1, Qb) + qs.shape[1:]),
                                     ps.reshape((-1, Qb) + ps.shape[1:])))
        attn = jnp.swapaxes(attn.reshape((-1,) + attn.shape[2:])[:N], 0, 1)
        x = x + r(attn.reshape(B, N, -1)) @ r(p["o_proj"])
        x = x + ffn(p, model, x, r)
    return left, base, n


def caption_logits(params, model: dict, left, base, n, tokens_in, r):
    """Logits [B, T, V] of the caption's positions under inputs ``tokens_in``
    [B, T], every position recomputed from the prefix's block."""
    dec = params["params"]["decoder"]
    window, chunk = model["window_size"], model["chunk_size"]
    B, T = tokens_in.shape
    x = dec["embed_tokens"].astype(jnp.float32)[tokens_in]
    positions = n[:, None] + jnp.arange(T)[None, :]
    # the positions from ``base`` on, by position: the prefix's, then the
    # caption's; a whole number of chunks
    R = -(-(window + T) // chunk) * chunk
    region = base[:, None] + jnp.arange(R)[None, :]                 # [B, R]
    in_prefix, in_caption = region < n[:, None], \
        (region >= n[:, None]) & (region < n[:, None] + T)
    from_tail = jnp.clip(region - base[:, None], 0, window - 1)
    from_caption = jnp.clip(region - n[:, None], 0, T - 1)
    for i in range(model["num_hidden_layers"]):
        p, x = _layer_f32(dec[f"layers_{i}"], x)
        q, k, v = qkv(p, model, x, positions, r)
        sk, sv, tail_k, tail_v = left[i]

        def by_position(tail, own):
            take = lambda a, idx: jnp.take_along_axis(  # noqa: E731
                a, idx[..., None, None], axis=1)
            return jnp.where(in_prefix[..., None, None], take(tail, from_tail),
                             jnp.where(in_caption[..., None, None],
                                       take(own, from_caption), 0.0))

        rk, rv = by_position(tail_k, k), by_position(tail_v, v)
        rsk, rsv = summaries(p, model, rk, rv)
        # a chunk of the region counts from ``base / chunk``; the prefix's
        # chunks before it are all of earlier windows than any query here
        exact, _ = visible(model, positions, region, 0, 0)
        exact = exact & (in_prefix | in_caption)[:, None, :]
        before = jnp.arange(sk.shape[1])[None, None, :] \
            < (base // chunk)[:, None, None]
        w = positions // window
        pooled = ((base // chunk)[:, None, None] + jnp.arange(R // chunk)) \
            < (window // chunk) * w[..., None]
        attn = attend(
            q, jnp.concatenate([rk, sk, rsk], axis=1),
            jnp.concatenate([rv, sv, rsv], axis=1),
            jnp.concatenate([exact, jnp.broadcast_to(
                before, (B, T, sk.shape[1])), pooled], axis=-1), r)
        x = x + r(attn.reshape(B, T, -1)) @ r(p["o_proj"])
        x = x + ffn(p, model, x, r)
    x = norm(x, dec["norm"].astype(jnp.float32), model["rms_norm_eps"])
    head = dec["lm_head"].astype(jnp.float32)[:, :model["vocab_size"]]
    return r(x) @ r(head)


def forward(params, model: dict, feats, masks, tokens_in, r):
    left, base, n = prefix_block(params, model, feats, masks, r)
    return caption_logits(params, model, left, base, n, tokens_in, r)


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
    step is one forward over each hypothesis' caption so far (the positions
    up to the end of the step's segment of ``SEARCH_SEGMENT``: those behind
    are PAD and in no set of the newest), read at the newest position (PAD
    and BOS forbidden); a hypothesis that has ended goes on with PAD at no
    cost; the ``beam`` best of ``beam * V`` candidates are kept; the first
    step has one live hypothesis. -> (tokens [B, max_len], PAD after a
    caption's EOS; score [B])."""
    r = rounder(precision)
    W = int(beam)
    with jax.default_matmul_precision("highest"):
        left, base, n = prefix_block(params, model, feats, masks, r)
        B = n.shape[0]

        def step(state, t, length):
            score, done, tokens = state         # [B, W], [B, W], [B, W, T]
            # a hypothesis at a time over the clip's one block
            logits = jax.vmap(
                lambda toks: caption_logits(params, model, left, base, n,
                                            _inputs(toks[:, :length]), r),
                in_axes=1, out_axes=1)(tokens)                  # [B, W, L, V]
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
        for lo in range(0, max_len, SEARCH_SEGMENT):
            hi = min(lo + SEARCH_SEGMENT, max_len)
            state, _ = jax.lax.scan(
                lambda s, t, hi=hi: step(s, t, hi), state, jnp.arange(lo, hi))
        score, _, tokens = state
        if length_penalty > 0.0:
            length = jnp.maximum((tokens != PAD_ID).sum(-1), 1)
            score = score / length.astype(jnp.float32) ** length_penalty
        best = jnp.argmax(score, axis=1)
        return (jnp.take_along_axis(tokens, best[:, None, None], axis=1)[:, 0],
                jnp.take_along_axis(score, best[:, None], axis=1)[:, 0])
