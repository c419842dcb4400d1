"""The plain reference of the latent-attention, routed-expert caption decoder
(``configs/kimi_k2_ep32.json``): float32 at ``highest`` matmul precision, no
cache, no kernel, no batching trick. Every call is a full forward over the
video prefix and the caption's tokens so far; its beam search runs one such
forward a step. Written from the equations of the published architecture
(DeepSeek-V3's modelling code, which the Kimi-K2 config.json names) and
independent of the program: it imports nothing of ``cst_captioning_tpu`` and
reads the parameter tree as stored, ``model`` being the configuration file's
``model`` dict. bfloat16 values are exact in float32, so the parameters come
as stored and a layer's weights are raised where the layer uses them
(``_layer_f32``): at the published widths the reference needs the stored
parameters and one layer in float32, not the whole model twice.

Departures from the published description, each because this repository's
job is captioning frame features and one chip holds a share of the model:

- the video prefix: each modality's frame features go through a linear
  projector of this repository's own (``embed_<modality>``, no bias) into 28
  prefix slots a modality; slot index = position; a missing frame's slot is
  zero and masked out of every attention. The caption's token ``t`` sits at
  position ``n_prefix + t``, BOS first;
- the sliced head: embedding and head hold ``vocab_size`` rows of the
  published 163840, and the softmax is over the slice;
- the held experts: the router scores all ``n_routed_experts`` and
  normalises over all ``num_experts_per_tok`` chosen, but only the experts
  ``expert_share_index * experts_held ...`` (``experts_held`` of them) are
  computed; what the absent experts would add is left out and the partial
  result goes on to the next layer;
- RoPE rotates the pairs ``(i, i + d/2)`` of the 64 rope dimensions (the
  published code reaches the same rotation from an interleaved storage
  order; with seeded weights the order carries no meaning).

``precision`` (``bfloat16``, ``float8_e4m3fn``) rounds the operands of every
matrix product, attention's two included: only the controls use it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PAD_ID, BOS_ID, EOS_ID = 0, 1, 2


def rounder(precision: str):
    """x -> x rounded to ``precision`` (a one-byte type after scaling to the
    tensor's largest magnitude) and back to float32; the gradient passes
    straight through."""
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


def yarn_inv_freq(model: dict) -> jnp.ndarray:
    """The ``qk_rope_head_dim / 2`` rotation frequencies under YaRN: the
    plain ``theta^(-2i/d)`` where a dimension turns more than ``beta_fast``
    times in the original context, that over ``factor`` where it turns fewer
    than ``beta_slow`` times, a linear ramp between."""
    d, base = model["qk_rope_head_dim"], float(model["rope_theta"])
    ys = dict(model["rope_scaling"])
    plain = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)

    def dim_of(turns):      # the dimension that makes ``turns`` turns
        return d * math.log(ys["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim_of(ys["beta_fast"])), 0)
    high = min(math.ceil(dim_of(ys["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return plain / ys["factor"] * ramp + plain * (1.0 - ramp)


def softmax_scale(model: dict) -> float:
    """``(nope + rope)^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``."""
    ys = dict(model["rope_scaling"])
    m = 0.1 * ys["mscale_all_dim"] * math.log(ys["factor"]) + 1.0 \
        if ys["factor"] > 1 else 1.0
    return (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5 * m * m


def rope(x, positions, inv_freq):
    """x [..., P, H?, d] rotated at ``positions`` [..., P]: pairs (i, i+d/2)."""
    angle = positions[..., None].astype(jnp.float32) * inv_freq   # [..., P, d/2]
    while angle.ndim < x.ndim:
        angle = angle[..., None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _layer_f32(p, x):
    """A layer's parameters raised to float32 where the layer uses them: the
    barrier ties the conversion to the layer's input, so that the compiler
    can neither hoist it out of a loop over steps nor keep every layer's
    float32 copy alive at once."""
    p, x = jax.lax.optimization_barrier((p, x))
    return jax.tree.map(lambda w: w.astype(jnp.float32), p), x


def attention(p, model, x, positions, mask, r):
    """Latent attention, expanded: x [B, P, h], mask [B, P, P] -> [B, P, h]."""
    H, eps = model["num_attention_heads"], model["rms_norm_eps"]
    nope, rot, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    rank = model["kv_lora_rank"]
    B, P, _ = x.shape
    inv_freq = yarn_inv_freq(model)
    c_q = rms_norm(r(x) @ r(p["q_a_proj"]), p["q_a_layernorm"], eps)
    q = (r(c_q) @ r(p["q_b_proj"])).reshape(B, P, H, nope + rot)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], positions, inv_freq)],
                        axis=-1)
    kv = r(x) @ r(p["kv_a_proj_with_mqa"])                   # [B, P, rank + rot]
    c_kv = rms_norm(kv[..., :rank], p["kv_a_layernorm"], eps)
    k_r = rope(kv[..., rank:], positions, inv_freq)          # one for all heads
    kvb = (r(c_kv) @ r(p["kv_b_proj"])).reshape(B, P, H, nope + vd)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_r[:, :, None], (B, P, H, rot))], -1)
    v = kvb[..., nope:]
    scores = jnp.einsum("bihd,bjhd->bhij", r(q), r(k)) * softmax_scale(model)
    scores = jnp.where(mask[:, None], scores, -1.0e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhij,bjhd->bihd", r(probs), r(v)).reshape(B, P, H * vd)
    return r(out) @ r(p["o_proj"])


def gated(x, gate, up, down, r):
    return r(jax.nn.silu(r(x) @ r(gate)) * (r(x) @ r(up))) @ r(down)


def route(p, model, x, r):
    """-> combine weights [N, n_routed_experts]: ``s[e] / sum(s[chosen]) *
    routed_scaling_factor`` on the chosen experts, 0 elsewhere; the choice is
    the ``num_experts_per_tok`` largest of ``s + bias``."""
    k = model["num_experts_per_tok"]
    s = jax.nn.sigmoid(r(x) @ r(p["gate"]))
    _, chosen = jax.lax.top_k(s + p["e_score_correction_bias"], k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * model["routed_scaling_factor"]
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], chosen].set(w)


def expert_ffn(p, model, x, r):
    """shared(x) + the held experts' part of sum_e w_e expert_e(x)."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    out = gated(x, p["shared_gate_proj"], p["shared_up_proj"],
                p["shared_down_proj"], r)
    lo = model["expert_share_index"] * model["experts_held"]
    w = route(p, model, x, r)[:, lo:lo + model["experts_held"]]     # [N, held]
    for e in range(model["experts_held"]):
        out = out + w[:, e:e + 1] * gated(
            x, p["experts_gate_proj"][e], p["experts_up_proj"][e],
            p["experts_down_proj"][e], r)
    return out.reshape(shape)


def forward(params, model: dict, feats, masks, tokens_in, r):
    """Logits [B, T, V] of the caption positions under inputs ``tokens_in``
    [B, T] behind the video prefix: one full forward, causal over prefix and
    caption, missing frames' slots masked out."""
    dec = params["params"]["decoder"]
    names = [n for n, _ in model["modalities"]]
    valid = jnp.concatenate([jnp.asarray(masks[n], jnp.float32) for n in names], 1)
    prefix = jnp.concatenate([
        r(jnp.asarray(feats[n], jnp.float32))
        @ r(dec["embed_" + n].astype(jnp.float32)) for n in names], axis=1)
    prefix = prefix * valid[..., None]
    B, n_prefix = valid.shape
    T = tokens_in.shape[1]
    x = jnp.concatenate(
        [prefix, dec["embed_tokens"].astype(jnp.float32)[tokens_in]], axis=1)
    P = n_prefix + T
    positions = jnp.broadcast_to(jnp.arange(P), (B, P))
    key_ok = jnp.concatenate([valid > 0, jnp.ones((B, T), bool)], axis=1)
    mask = (jnp.arange(P)[None, :, None] >= jnp.arange(P)[None, None, :]) \
        & key_ok[:, None, :]
    eps = model["rms_norm_eps"]
    for i in range(model["num_hidden_layers"]):
        p, x = _layer_f32(dec[f"layers_{i}"], x)
        x = x + attention(p, model, rms_norm(x, p["input_layernorm"], eps),
                          positions, mask, r)
        y = rms_norm(x, p["post_attention_layernorm"], eps)
        if i < model["first_k_dense_replace"]:
            x = x + gated(y, p["gate_proj"], p["up_proj"], p["down_proj"], r)
        else:
            x = x + expert_ffn(p, model, y, r)
    x = rms_norm(x[:, n_prefix:], dec["norm"].astype(jnp.float32), eps)
    return r(x) @ r(dec["lm_head"].astype(jnp.float32))


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
    log-probability, and that of the ``beam``-th most probable token there.
    A beam of that width keeps only tokens with ``logp >= edge``."""
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
    """The plain beam search: every clip keeps ``beam`` hypotheses; a step is
    one full forward over the prefix and each hypothesis' tokens so far, read
    at the newest position (PAD and BOS forbidden); a hypothesis that has
    ended goes on with PAD at no cost; the ``beam`` best of ``beam * V``
    candidates are kept; the first step has one live hypothesis. ->
    (tokens [B, max_len], PAD after a caption's EOS; score [B])."""
    r = rounder(precision)
    W = int(beam)
    with jax.default_matmul_precision("highest"):
        tile = lambda tree: jax.tree.map(  # noqa: E731
            lambda x: jnp.repeat(jnp.asarray(x), W, axis=0), tree)
        feats, masks = tile(feats), tile(masks)
        B = next(iter(jax.tree.leaves(masks))).shape[0] // W

        def step(state, t):
            score, done, tokens = state         # [B, W], [B, W], [B, W, T]
            logits = forward(params, model, feats, masks,
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
