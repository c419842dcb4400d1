"""The plain reference of the sparse-softmax / linear-attention caption decoder
(``configs/minicpm_sala_8l.json``): float32 at ``highest`` matmul precision,
no kernel, no cache across steps, the block selection as a mask over a dense
product, the linear attention as its recurrence, one position after another.
Written from the layer equations the configuration's file states (MiniCPM-SALA's
config.json; MiniCPM4's ``sparse_config`` and the lightning-attention family's
slopes for what it lacks) and independent of the program: it imports nothing of
``cst_captioning_tpu`` and reads the parameter tree as stored, ``model`` being
the configuration file's ``model`` dict. A layer's weights are raised to
float32 where the layer uses them.

So that 16 k positions fit, the work is cut in blocks that change no number's
meaning: a clip's prefix is computed once a call (:func:`prefix_block`: the
sparse layers' keys, values and compressed keys, the linear layers' states
after the prefix; its softmax attention in blocks of query positions, its FFN
in blocks of rows), and the caption's positions are recomputed whole from
that block at every call (:func:`caption_logits`), so the beam search runs
one full caption forward a step.

The rules this repository adds to the published layers, each in the
configuration's ``assumed``:

- the video prefix: frame features through a linear projector (``embed_<m>``,
  no bias), one slot a frame; **a clip's valid slots are moved to the front
  in their order and the missing ones are as if they were not there**: with
  ``n`` valid slots, slot ``i`` of them is position ``i`` and the caption's
  token ``t`` position ``n + t``, BOS first;
- compressed keys pool windows of the video prefix alone (window ``c`` covers
  positions ``[c stride, c stride + kernel)`` and exists once it lies wholly
  among the ``n``): a caption's at most ``max_len`` positions are inside the
  window of the last ``window`` positions and need no selecting;
- a block's score is the maximum over the compressed keys whose window meets
  the block; a query that sees fewer than ``dense_len`` keys attends to all;
- linear layer ``l`` (its index in the published model) of ``L`` forgets at
  ``slope_h = 2^(-8 (h + 1) / H) (1 - l / (L - 1) + 1e-5)``.

``precision`` (``bfloat16``, ``float8_e4m3fn``) rounds the operands of every
matrix product, both attentions' included: only the controls use it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PAD_ID, BOS_ID, EOS_ID = 0, 1, 2
SPARSE, LINEAR = "minicpm4", "lightning-attn"
QUERY_BLOCK, ROW_BLOCK = 256, 2048
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


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, positions, theta):
    """x [..., H, d] rotated at ``positions`` [...]: pairs (i, i + d/2)."""
    d = x.shape[-1]
    inv_freq = float(theta) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions[..., None, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def slopes(model: dict, layer: int):
    H = model["lightning_nh"]
    depth = model["first_layer_index"] + layer
    base = 2.0 ** (-8.0 * jnp.arange(1, H + 1, dtype=jnp.float32) / H)
    return base * (1.0 - depth / max(model["published_layers"] - 1, 1) + 1e-5)


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


def qkv(p, model: dict, kind: str, y, positions, r):
    """y [B, Q, h] -> q [B, Q, H, d], k, v [B, Q, G, d]."""
    eps = model["rms_norm_eps"]
    if kind == SPARSE:
        H, G = model["num_attention_heads"], model["num_key_value_heads"]
    else:
        H = G = model["lightning_nh"]
    heads = lambda x, n: x.reshape(x.shape[:-1] + (n, -1))  # noqa: E731
    q = rms_norm(heads(r(y) @ r(p["q_proj"]), H), p["q_norm"], eps)
    k = rms_norm(heads(r(y) @ r(p["k_proj"]), G), p["k_norm"], eps)
    v = heads(r(y) @ r(p["v_proj"]), G)
    if kind == LINEAR:
        q = rope(q, positions, model["rope_theta"])
        k = rope(k, positions, model["rope_theta"])
    return q, k, v


def branch(p, model: dict, kind: str, y, attn, r):
    """The mixer's branch from its heads' outputs [B, Q, H, d]."""
    attn = attn.reshape(attn.shape[:-2] + (-1,))
    if kind == LINEAR:
        attn = rms_norm(attn, p["o_norm"], model["rms_norm_eps"])
    return r(attn * jax.nn.sigmoid(r(y) @ r(p["o_gate"]))) @ r(p["o_proj"])


def ffn(p, model: dict, x, r):
    """x [B, Q, h] -> the FFN branch, in blocks of rows."""
    def rows(y):
        y = rms_norm(y, p["post_attention_layernorm"], model["rms_norm_eps"])
        return r(jax.nn.silu(r(y) @ r(p["gate_proj"])) * (r(y) @ r(p["up_proj"]))) \
            @ r(p["down_proj"])

    return _in_blocks(rows, x.reshape(-1, x.shape[-1]), ROW_BLOCK).reshape(x.shape)


def compressed_keys(model: dict, k):
    """k [B, P, G, d] -> (window means [B, C, G, d], each window's end [C])."""
    kernel, stride = model["sparse_kernel_size"], model["sparse_kernel_stride"]
    C = max((k.shape[1] - kernel) // stride + 1, 0)
    at = jnp.arange(C)[:, None] * stride + jnp.arange(kernel)[None, :]
    return k[:, at].mean(axis=2), jnp.arange(C) * stride + kernel


def key_mask(model: dict, q, ck, ends, q_pos, n, P: int, r):
    """Which of a row's P prefix keys each query attends to: q [B, Q, H, d]
    at positions q_pos [B, Q] -> [B, G, Q, P] bool."""
    block, topk = model["sparse_block_size"], model["sparse_topk"]
    ratio = block // model["sparse_kernel_stride"]
    B, Q, H, d = q.shape
    G, C = ck.shape[2], ck.shape[1]
    nb = -(-P // block)
    if C:
        s = jnp.einsum("bqghd,bcgd->bghqc", r(q.reshape(B, Q, G, H // G, d)),
                       r(ck)) / math.sqrt(d)
        seen = (ends[None, None] <= q_pos[..., None] + 1) \
            & (ends[None, None] <= n[:, None, None])            # [B, Q, C]
        prob = jax.nn.softmax(jnp.where(seen[:, None, None], s, _NEG), axis=-1)
        score = jnp.where(seen[:, None], jnp.where(
            seen[:, None, None], prob, 0.0).sum(axis=2), -1.0)  # [B, G, Q, C]
        meets = jnp.arange(nb)[:, None] * ratio - 1 + jnp.arange(ratio + 1)
        inside = (meets >= 0) & (meets < C)
        by_block = jnp.where(inside, score[..., jnp.clip(meets, 0, C - 1)],
                             -1.0).max(axis=-1)                 # [B, G, Q, nb]
        _, best = jax.lax.top_k(by_block, min(topk, nb))
        chosen = jax.nn.one_hot(best, nb, dtype=jnp.int32).sum(axis=-2) > 0
    else:
        chosen = jnp.ones((B, G, Q, nb), bool)
    chosen = chosen | (jnp.arange(nb) < model["sparse_init_blocks"]) \
        | (q_pos + 1 < model["sparse_dense_len"])[:, None, :, None]
    j = jnp.arange(P)
    pos = q_pos[:, None, :, None]
    return (chosen[..., j // block] | (j > pos - model["sparse_window_size"])) \
        & (j <= pos) & (j < n[:, None, None, None])


def attend(q, k, v, mask, r):
    """Masked softmax attention, grouped-query: q [B, Q, H, d], k/v
    [B, K, G, d], mask [B, G, Q, K] -> [B, Q, H, d]."""
    B, Q, H, d = q.shape
    G = k.shape[2]
    s = jnp.einsum("bqghd,bkgd->bghqk", r(q.reshape(B, Q, G, H // G, d)),
                   r(k)) / math.sqrt(d)
    prob = jax.nn.softmax(jnp.where(mask[:, :, None], s, _NEG), axis=-1)
    return jnp.einsum("bghqk,bkgd->bqghd", r(prob), r(v)).reshape(B, Q, H, d)


def recurrence(q, k, v, slope, state, live, r):
    """``S_t = lam S_{t-1} + k_t^T v_t``, ``o_t = (q_t / sqrt(d)) S_t`` over
    q/k/v [B, Q, H, d] from ``state`` [B, H, d, d]; a position that is not
    ``live`` [B, Q] leaves the state as it is -> (out, the last state)."""
    d = q.shape[-1]
    lam = jnp.exp(-slope)[None, :, None, None]

    def step(S, x):
        q_t, k_t, v_t, live_t = x
        S = jnp.where(live_t[:, None, None, None],
                      lam * S + jnp.einsum("bhd,bhe->bhde", r(k_t), r(v_t)), S)
        return S, jnp.einsum("bhd,bhde->bhe", r(q_t), r(S)) / math.sqrt(d)

    swap = lambda x: jnp.swapaxes(x, 0, 1)  # noqa: E731
    state, out = jax.lax.scan(step, state, (swap(q), swap(k), swap(v), swap(live)))
    return swap(out), state


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
    the caption: ``(k, v, ck, ends)`` of a sparse layer, the state of a linear
    one; n [B])."""
    dec = params["params"]["decoder"]
    x, n = _compact(params, model, feats, masks, r)
    B, P, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(P), (B, P))
    live = positions < n[:, None]
    scale = model["scale_depth"] / math.sqrt(model["published_layers"])
    kinds, left = model["mixer_types"], []
    for i, kind in enumerate(kinds):
        p, x = _layer_f32(dec[f"layers_{i}"], x)
        y = rms_norm(x, p["input_layernorm"], model["rms_norm_eps"])
        q, k, v = qkv(p, model, kind, y, positions, r)
        last = i + 1 == len(kinds)
        if kind == SPARSE:
            ck, ends = compressed_keys(model, k)
            left.append((k, v, ck, ends))
            if last:
                break

            def queries(block, k=k, v=v, ck=ck, ends=ends):
                qb, pos = block                     # [Qb, B, H, d], [Qb, B]
                qb, pos = jnp.swapaxes(qb, 0, 1), jnp.swapaxes(pos, 0, 1)
                mask = key_mask(model, qb, ck, ends, pos, n, P, r)
                return jnp.swapaxes(attend(qb, k, v, mask, r), 0, 1)

            pad = (-P) % min(QUERY_BLOCK, P)
            by_pos = lambda a: jnp.pad(  # noqa: E731
                jnp.swapaxes(a, 0, 1), [(0, pad)] + [(0, 0)] * (a.ndim - 1))
            Qb = min(QUERY_BLOCK, P)
            qs, ps = by_pos(q), by_pos(positions)
            attn = jax.lax.map(queries, (qs.reshape((-1, Qb) + qs.shape[1:]),
                                         ps.reshape((-1, Qb) + ps.shape[1:])))
            attn = jnp.swapaxes(attn.reshape((-1,) + attn.shape[2:])[:P], 0, 1)
        else:
            H, d = q.shape[2], q.shape[3]
            attn, state = recurrence(q, k, v, slopes(model, i),
                                     jnp.zeros((B, H, d, d), jnp.float32), live, r)
            left.append(state)
            if last:
                break
        x = x + scale * branch(p, model, kind, y, attn, r)
        x = x + scale * ffn(p, model, x, r)
    return left, n


def caption_logits(params, model: dict, left, n, tokens_in, r):
    """Logits [B, T, V] of the caption's positions under inputs ``tokens_in``
    [B, T], every position recomputed from the prefix's block."""
    dec = params["params"]["decoder"]
    B, T = tokens_in.shape
    scale = model["scale_depth"] / math.sqrt(model["published_layers"])
    x = model["scale_emb"] * dec["embed_tokens"].astype(jnp.float32)[tokens_in]
    positions = n[:, None] + jnp.arange(T)[None, :]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    for i, kind in enumerate(model["mixer_types"]):
        p, x = _layer_f32(dec[f"layers_{i}"], x)
        y = rms_norm(x, p["input_layernorm"], model["rms_norm_eps"])
        q, k, v = qkv(p, model, kind, y, positions, r)
        if kind == SPARSE:
            pk, pv, ck, ends = left[i]
            P, G = pk.shape[1], pk.shape[2]
            mask = jnp.concatenate([
                key_mask(model, q, ck, ends, positions, n, P, r),
                jnp.broadcast_to(causal, (B, G, T, T))], axis=-1)
            attn = attend(q, jnp.concatenate([pk, k], axis=1),
                          jnp.concatenate([pv, v], axis=1), mask, r)
        else:
            attn, _ = recurrence(q, k, v, slopes(model, i), left[i],
                                 jnp.ones((B, T), bool), r)
        x = x + scale * branch(p, model, kind, y, attn, r)
        x = x + scale * ffn(p, model, x, r)
    x = rms_norm(x, dec["norm"].astype(jnp.float32), model["rms_norm_eps"])
    return r(x) @ r(dec["lm_head"].astype(jnp.float32)) \
        / (model["hidden_size"] / model["dim_model_base"])


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
    prefix's block is computed once a clip, and a step is one forward over
    each hypothesis' caption so far, read at the newest position (PAD and
    BOS forbidden); a hypothesis that has ended goes on with PAD at no cost;
    the ``beam`` best of ``beam * V`` candidates are kept; the first step
    has one live hypothesis. -> (tokens [B, max_len], PAD after a caption's
    EOS; score [B])."""
    r = rounder(precision)
    W = int(beam)
    with jax.default_matmul_precision("highest"):
        left, n = prefix_block(params, model, feats, masks, r)
        tile = lambda tree: jax.tree.map(  # noqa: E731
            lambda x: jnp.repeat(x, W, axis=0) if x.ndim > 1 else x, tree)
        left, n = tile(left), jnp.repeat(n, W)
        B = n.shape[0] // W

        def step(state, t):
            score, done, tokens = state         # [B, W], [B, W], [B, W, T]
            logits = caption_logits(params, model, left, n,
                                    _inputs(tokens.reshape(B * W, max_len)), r)
            logp = jax.nn.log_softmax(_forbid(logits[:, t]), axis=-1)
            V = logp.shape[-1]
            ended = jnp.full((V,), -1.0e9).at[PAD_ID].set(0.0)
            logp = jnp.where(done[:, :, None], ended, logp.reshape(B, W, V))
            score, flat = jax.lax.top_k(
                (score[:, :, None] + logp).reshape(B, W * V), W)
            parent, tok = flat // V, (flat % V).astype(jnp.int32)
            tokens = jnp.take_along_axis(tokens, parent[:, :, None], axis=1)
            tokens = tokens.at[:, :, t].set(tok)
            done = jnp.take_along_axis(done, parent, axis=1) | (tok == EOS_ID)
            return (score, done, tokens), None

        start = (jnp.full((B, W), -1.0e9).at[:, 0].set(0.0),
                 jnp.zeros((B, W), bool),
                 jnp.full((B, W, max_len), PAD_ID, jnp.int32))
        (score, _, tokens), _ = jax.lax.scan(step, start, jnp.arange(max_len))
        if length_penalty > 0.0:
            length = jnp.maximum((tokens != PAD_ID).sum(-1), 1)
            score = score / length.astype(jnp.float32) ** length_penalty
        best = jnp.argmax(score, axis=1)
        return (jnp.take_along_axis(tokens, best[:, None, None], axis=1)[:, 0],
                jnp.take_along_axis(score, best[:, None], axis=1)[:, 0])
