"""Compressed-convolutional-attention, top-1-expert caption decoder
(``ModelConfig.decoder = "cca_moe"``): a pre-norm residual stack behind a long
video prefix whose layers attend in a latent narrower than the stream, its
queries and keys mixed along the sequence before the scores
(ops/window_attention.py runs the attention itself), and whose FFN is one
expert of sixteen, or none, chosen by an MLP router that is a stream of its
own across depth (models/experts.py).

The sixth decoder kind, reached through the same :class:`~cst_captioning_tpu.
models.captioner.CaptionModel` methods as the other five. The sizes are
fields of ``ModelConfig`` under the key names of the published ``config.json``
they are read from (ZAYA1-8B; benchmark/configs/zaya1_8b_20l.json); what that
file has no key for is built as the family's convention has it, each form in
the configuration's ``assumed``.

**The layer** (``norm(x; g) = x / sqrt(mean x^2 + eps) * g``; ``H`` query
heads over ``G`` key/value heads of ``d = head_dim``; ``a_{-1} = 0`` for
every sequence ``a``)::

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

bfloat16 parameters and compute; float32 for the norms' statistics, the
heads' lengths and the rope, attention scores and softmax, the router from its
down-projection on, the experts' weighted sum and the logits.

- **Prefix.** As the sparse/linear decoder's (models/sparse_linear.py): each
  modality's features through its linear projection (``embed_<name>``, no
  bias), a clip's valid slots moved to the front in their order, slot ``i`` of
  the ``n`` valid ones at position ``i`` and caption token ``t`` at ``n + t``,
  BOS first; nothing from position ``n`` of the prefix on exists.
- **One block, scanned.** Every layer is of one kind, so every parameter is
  one leaf stacked over the layers (``layers/<name> [L, ...]``) and the stack
  is a ``lax.scan`` of one block whose carry is the pair ``(x, r)``: the
  stream and the router's. A program traces and compiles one layer. A layer's
  leaves are taken by index inside the block, an expert's where a tile of
  its rows uses them (``experts.held_experts(layer=...)``).
- **Two streams.** ``r_l`` feeds the next layer's router, so a block returns
  two values; the stream's residual merge has learned scales and offsets.
- **The head is the embedding**: no head leaf among the parameters.

**Four kinds of state in one beam.** What a clip's lanes share rides in
``EncoderOutput.memory`` and is held once a clip: every layer's prefix keys
and values in the latent, ``[B, L, G, P, d]`` head-major (an eighth of what
full heads at the stream's width would hold). What a lane owns rides in
:class:`CCAMoECarry`: its caption's keys and values in every layer
(``max_len`` positions), and the **convolution tail**: of the last position,
per layer, the pre-convolution latent ``c``, the first convolution's output
``a`` and the value's late half ``u Wv'``, which the next position's two
convolutions of width 2 and its value reach back for. At a caption's first
step the tail is the clip's (prefix position ``n - 1``; :meth:`prefill` puts
it into the carry a clip hands its lanes), from the second on the lane's own,
and the beam gathers it by parent like the caption's keys: every leaf is
batch-major.

**A step for all lanes at once** (:meth:`CCAMoEDecoder.step_lanes`): the
beam's ``[lanes, clips]`` tokens go through projections, mixing, router and
experts as one list of ``lanes x clips`` rows (sequences of one position whose
"position before" is the tail; the held experts are one grouped product a
layer over all of them), and attend grouped by clip over the shared keys. :meth:`step` is the
same code with one lane.

The last layer's attention output and FFN over the prefix feed nothing and
are not run: the prefix leaves that layer its keys, values and tail only.
"""

from __future__ import annotations

import math

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp

from cst_captioning_tpu.config.config import BOS_ID, ModelConfig
from cst_captioning_tpu.models.experts import (
    check_share,
    held_experts,
    route_mlp,
)
from cst_captioning_tpu.models.latent_moe import rms_norm, rope
from cst_captioning_tpu.models.sparse_linear import compact_prefix, mixer_impl
from cst_captioning_tpu.ops import window_attention as wa

NORM_FLOOR = 1e-12      # under the root of a head's squared length


@flax.struct.dataclass
class CCAMoECarry:
    """What one lane owns; every leaf batch-major, the layers behind."""

    k: jnp.ndarray          # [B, L, G, max_len, d]
    v: jnp.ndarray          # [B, L, G, max_len, d]
    # the convolution tail: of the position before the next one, a layer
    tail_c: jnp.ndarray     # [B, L, (H + G) d]: the pre-convolution latent
    tail_a: jnp.ndarray     # [B, L, (H + G) d]: the first convolution's output
    tail_v: jnp.ndarray     # [B, L, G d / 2]: the value's late half, u Wv'
    pos: jnp.ndarray        # [B] int32: caption tokens held so far
    # [B, L, experts_held + 1] int32: the rows the LAST call put on each held
    # expert of each layer for this row, and (last column) its assignments on
    # all outputs, the no-expert one included
    routed: jnp.ndarray
    # [B, 1, 2] int32: the query-key pairs the LAST call's queries of this
    # row attended in one layer (plain causal attention's, every layer the
    # same), and over all layers the rows whose router chose no expert. The
    # decode loops tally both leaves (obs counters moe.*, attn.*); nothing
    # reads them back into the model
    counted: jnp.ndarray


def rotary_dims(cfg: ModelConfig) -> int:
    return int(cfg.head_dim * cfg.partial_rotary_factor)


def latent_channels(cfg: ModelConfig) -> int:
    """The channels the two convolutions mix: q~ and k~ side by side."""
    return (cfg.num_attention_heads + cfg.num_key_value_heads) * cfg.head_dim


def _around(mean: float, std: float):
    def init(key, shape, dtype):
        return (mean + std * jax.random.normal(key, shape)).astype(dtype)
    return init


def _uniform(bound: float):
    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, minval=-bound,
                                  maxval=bound).astype(dtype)
    return init


def layer_shapes(cfg: ModelConfig) -> dict:
    """One layer's parameters, name -> (initializer, shape, dtype): matrices
    ``N(0, initializer_range)`` in the parameter dtype; the two convolutions
    uniform within ``1 / sqrt(fan_in)`` (a width-2 kernel over one channel,
    or over a head's); the router's three MLP matrices ``N(0, 1 /
    sqrt(router_hidden_size))``, so that a seeded router's choice depends on
    the token more than on its layer's fixed offsets, as a trained, balanced
    one's does (at ``initializer_range`` the two GELUs shrink the token's
    part of the logits under the biases' and nearly every token of a layer
    picks one expert); whatever is an identity at one or at zero drawn
    around it (the scalars a head or a layer in float32), so that leaving it
    out shows."""
    c = cfg
    pd, f32 = jnp.dtype(c.param_dtype), jnp.float32
    h, H, G, d = c.hidden_size, c.num_attention_heads, c.num_key_value_heads, c.head_dim
    C, R, E, m = latent_channels(c), c.router_hidden_size, c.n_routed_experts, \
        c.moe_intermediate_size
    std = c.initializer_range
    w, one = nn.initializers.normal(std), nn.initializers.ones
    scale, offset = _around(1.0, std), _around(0.0, std)
    wide = nn.initializers.normal(1 / math.sqrt(R))
    shapes = {
        "input_layernorm": (one, (h,), pd),
        "q_proj": (w, (h, H * d), pd), "k_proj": (w, (h, G * d), pd),
        "v_proj": (w, (h, G * d // 2), pd),
        "v_shift_proj": (w, (h, G * d // 2), pd),
        "conv0_w": (_uniform(1 / math.sqrt(2)), (2, C), pd),
        "conv0_b": (offset, (C,), pd),
        "conv1_w": (_uniform(1 / math.sqrt(2 * d)), (2, H + G, d, d), pd),
        "conv1_b": (offset, (C,), pd),
        "temp": (scale, (G,), f32),
        "o_proj": (w, (H * d, h), pd),
        "post_attention_layernorm": (one, (h,), pd),
        "router_down": (w, (h, R), pd),
        "router_eda": (_around(0.5, std), (), f32),
        "router_norm": (one, (R,), pd),
        "router_w1": (wide, (R, R), pd), "router_b1": (offset, (R,), f32),
        "router_w2": (wide, (R, R), pd), "router_b2": (offset, (R,), f32),
        "router_w3": (wide, (R, E + 1), pd), "router_b3": (offset, (E + 1,), f32),
        "router_bias": (offset, (E + 1,), f32),
        "experts_gate_proj": (w, (c.experts_held, h, m), pd),
        "experts_up_proj": (w, (c.experts_held, h, m), pd),
        "experts_down_proj": (w, (c.experts_held, m, h), pd),
    }
    for sub in ("attn", "moe"):
        shapes.update({f"{sub}_res_scale": (scale, (h,), pd),
                       f"{sub}_res_bias": (offset, (h,), pd),
                       f"{sub}_out_scale": (scale, (h,), pd),
                       f"{sub}_out_bias": (offset, (h,), pd)})
    return shapes


def shifted(a, first):
    """``a [N, S, ...]`` one position late: entry ``t`` is ``a[t - 1]``, entry
    0 is ``first [N, ...]``."""
    return jnp.concatenate([first[:, None].astype(a.dtype), a[:, :-1]], axis=1)


def merge(p, sub: str, x, f):
    """``(a x + b) + (a' f + b')``: sublayer ``sub``'s residual merge."""
    dt = x.dtype
    return (p[sub + "_res_scale"].astype(dt) * x + p[sub + "_res_bias"].astype(dt)) \
        + (p[sub + "_out_scale"].astype(dt) * f + p[sub + "_out_bias"].astype(dt))


def mixed_qkv(cfg: ModelConfig, p, x, positions, before):
    """The stream x [N, S, h] at ``positions`` [N, S] -> (q [N, S, H, d], k
    [N, S, G, d], v [N, S, G, d], each position's ``(c, a, u Wv')``).
    ``before``: those three of the position before position 0 of each
    sequence, [N, ...] (zeros at a sequence's start, the tail in a step)."""
    c = cfg
    H, G, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    N, S, _ = x.shape
    dt, f32 = x.dtype, jnp.float32
    first_c, first_a, first_v = before
    with jax.named_scope("cca_mix"):
        u = rms_norm(x, p["input_layernorm"], c.rms_norm_eps)
        q0 = u @ p["q_proj"].astype(dt)
        k0 = u @ p["k_proj"].astype(dt)
        late = u @ p["v_shift_proj"].astype(dt)
        v = jnp.concatenate(
            [u @ p["v_proj"].astype(dt), shifted(late, first_v)], axis=-1)
        lat = jnp.concatenate([q0, k0], axis=-1)
        w0 = p["conv0_w"].astype(dt)
        a = w0[0] * shifted(lat, first_c) + w0[1] * lat + p["conv0_b"].astype(dt)
        # both taps of the grouped convolution as one product a head: the
        # position before's channels beside this one's
        heads = lambda t, n: t.reshape(N, S, n, -1)  # noqa: E731
        taps = jnp.concatenate(
            [heads(shifted(a, first_a), H + G), heads(a, H + G)], axis=-1)
        w1 = p["conv1_w"].astype(dt).transpose(1, 0, 2, 3).reshape(H + G, 2 * d, d)
        m = jnp.einsum("nsgi,gio->nsgo", taps, w1,
                       preferred_element_type=f32) \
            + p["conv1_b"].astype(f32).reshape(H + G, d)
        q0f, k0f = heads(q0, H).astype(f32), heads(k0, G).astype(f32)
        q = m[:, :, :H] + (q0f + jnp.repeat(k0f, H // G, axis=2)) / 2
        k = m[:, :, H:] + (q0f.reshape(N, S, G, H // G, d).mean(axis=3) + k0f) / 2
        unit = lambda t: t * math.sqrt(d) * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(t * t, axis=-1, keepdims=True) + NORM_FLOOR)
        rot = rotary_dims(c)
        inv_freq = float(c.rope_theta) ** (
            -jnp.arange(0, rot, 2, dtype=f32) / rot)
        turned = lambda t: jnp.concatenate(  # noqa: E731
            [rope(t[..., :rot], positions, inv_freq), t[..., rot:]], axis=-1)
        q = turned(unit(q)).astype(dt)
        k = turned(unit(k) * p["temp"].astype(f32)[:, None]).astype(dt)
    return q, k, heads(v, G), (lat, a, late)


class CCAMoELayers(nn.Module):
    """Every layer's parameters, each one leaf stacked over the layers."""

    cfg: ModelConfig

    def setup(self):
        L = self.cfg.num_hidden_layers
        self.p = {name: self.param(name, init, (L,) + shape, dtype)
                  for name, (init, shape, dtype) in layer_shapes(self.cfg).items()}


def layer_at(stacked, index):
    """Layer ``index``'s (traced or not) leaves of ``stacked`` but the
    experts', which stay stacked: ``experts.held_experts`` takes an expert's
    where it uses them."""
    return {name: leaf if name.startswith("experts_")
            else jax.lax.dynamic_index_in_dim(leaf, index, 0, keepdims=False)
            for name, leaf in stacked.items()}


def attended(p, x, attn):
    """The stream after the attention sublayer, from the heads' outputs
    ``attn [N, S, H, d]``."""
    attn = attn.reshape(attn.shape[:2] + (-1,))
    return merge(p, "attn", x, attn @ p["o_proj"].astype(attn.dtype))


def moe(cfg: ModelConfig, p, index, x, r_prev, live, differentiable: bool):
    """The expert sublayer: the stream x [N, S, h] and the router's r_prev
    [N S, R] -> (x, r, tally [N S, held + 1], skipped [N S] bool: the live
    rows whose router chose no expert)."""
    c = cfg
    N, S, h = x.shape
    z = rms_norm(x, p["post_attention_layernorm"], c.rms_norm_eps)
    z = z.reshape(N * S, h)
    r, chosen, weight = route_mlp(p, z, r_prev, c.rms_norm_eps)
    out, tally = held_experts(
        z, chosen[:, None], weight[:, None], live, p["experts_gate_proj"],
        p["experts_up_proj"], p["experts_down_proj"],
        c.expert_share_index * c.experts_held, c.n_routed_experts,
        differentiable, layer=index)
    x = merge(p, "moe", x, out.astype(x.dtype).reshape(N, S, h))
    return x, r, tally, live & (chosen == c.n_routed_experts)


class CCAMoEDecoder(nn.Module):
    """Prefix projector, the scanned stack, final norm and the tied head."""

    cfg: ModelConfig

    def setup(self):
        c = self.cfg
        G, H = c.num_key_value_heads, c.num_attention_heads
        if G < 1 or H % G or (G * c.head_dim) % 2 or rotary_dims(c) % 2 \
                or not 0 < rotary_dims(c) <= c.head_dim \
                or c.router_hidden_size < 1 or c.num_hidden_layers < 1:
            raise ValueError(
                "decoder='cca_moe' needs key/value heads that divide "
                "num_attention_heads, an even number of value channels and "
                "of rotary dimensions within head_dim, router_hidden_size "
                ">= 1 and num_hidden_layers >= 1")
        if (c.cca_time0, c.cca_time1) != (2, 2) or c.num_experts_per_tok != 1 \
                or not c.tie_word_embeddings:
            raise ValueError(
                "decoder='cca_moe' is built for convolutions of width 2 "
                "(cca_time0 = cca_time1 = 2: the tail a lane keeps is one "
                "position), num_experts_per_tok 1 and tie_word_embeddings "
                "true")
        check_share(c)
        pd = jnp.dtype(c.param_dtype)
        w = nn.initializers.normal(c.initializer_range)
        self.embed = {name: self.param(f"embed_{name}", w, (dim, c.hidden_size), pd)
                      for name, dim in c.modalities}
        self.embed_tokens = self.param(
            "embed_tokens", w, (c.vocab_size, c.hidden_size), pd)
        self.layers = CCAMoELayers(c, name="layers")
        self.norm = self.param("norm", nn.initializers.ones, (c.hidden_size,), pd)

    def _logits(self, x):
        """``norm(x) E^T`` in float32: the head is the token embedding."""
        x = rms_norm(x, self.norm, self.cfg.rms_norm_eps)
        return jax.lax.dot_general(
            x, self.embed_tokens.astype(x.dtype),
            (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    def _zeros_before(self, N: int):
        c = self.cfg
        dt = jnp.dtype(c.dtype)
        C = latent_channels(c)
        return (jnp.zeros((N, C), dt), jnp.zeros((N, C), dt),
                jnp.zeros((N, c.num_key_value_heads * c.head_dim // 2), dt))

    def _stack(self, x, n, impl: str, differentiable: bool, whole: bool):
        """The layers over whole sequences: x [B, S, h] whose first ``n`` [B]
        positions exist -> (x, keys and values [L, B, G, S, d] head-major,
        each layer's tail ``(c, a, u Wv')`` [L, B, ...] at position ``n -
        1``, routed [B, L, held + 1], skipped [B]). Unless ``whole``, the
        last layer stops at its keys, values and tail."""
        c, stacked = self.cfg, self.layers.p
        L = c.num_hidden_layers
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        live = (positions < n[:, None]).reshape(B * S)
        before = self._zeros_before(B)
        last = jnp.maximum(n - 1, 0)
        at_last = lambda a: jnp.where(  # noqa: E731
            (n > 0)[:, None],
            jnp.take_along_axis(a, last[:, None, None], axis=1)[:, 0], 0)

        def left(k, v, each):
            return (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                    tuple(at_last(a) for a in each))

        def block(state, index):
            x, r = state
            p = layer_at(stacked, index)
            q, k, v, each = mixed_qkv(c, p, x, positions, before)
            x = attended(p, x, wa.cca_prefill(q, k, v, n, impl))
            x, r, tally, skipped = moe(c, p, index, x, r, live, differentiable)
            return (x, r), (left(k, v, each),
                            tally.reshape(B, S, -1).sum(axis=1),
                            skipped.reshape(B, S).sum(axis=1))

        # the router's stream is float32 on every path
        r0 = jnp.zeros((B * S, c.router_hidden_size), jnp.float32)  # graftlint: disable=GL005
        run = L if whole else L - 1
        (x, _), (kept, routed, skipped) = jax.lax.scan(
            block, (x, r0), jnp.arange(run))
        if not whole:
            _, k, v, each = mixed_qkv(c, layer_at(stacked, L - 1), x,
                                      positions, before)
            kept = jax.tree.map(lambda a, b: jnp.concatenate([a, b[None]]),
                                kept, left(k, v, each))
            routed = jnp.pad(routed, ((0, 1), (0, 0), (0, 0)))
        k, v, tail = kept
        return x, (k, v), tail, routed.transpose(1, 0, 2), \
            skipped.sum(axis=0).astype(jnp.int32)

    def prefill(self, feats, masks):
        """-> (bank, n [B], carry): the prefix through the stack. ``bank``
        is ``(keys, values)``, every layer's prefix in the latent, per clip
        and head-major ``[B, L, G, P, d]``; ``carry`` a lane's empty caption
        cache, the tail the clip's last position leaves it and what the
        prefix's queries counted."""
        c = self.cfg
        x, n = compact_prefix(c, self.embed, feats, masks)
        B = x.shape[0]
        _, kv, tail, routed, skipped = self._stack(
            x, n, mixer_impl(), differentiable=False, whole=False)
        keys, values = (a.transpose(1, 0, 2, 3, 4) for a in kv)
        own = jnp.zeros(keys.shape[:3] + (c.max_len, c.head_dim),
                        jnp.dtype(c.dtype))
        pairs = n * (n + 1) // 2        # sum of i + 1 over a clip's positions
        counted = jnp.stack([pairs, skipped], axis=-1).astype(jnp.int32)
        carry = CCAMoECarry(
            own, own, *(a.transpose(1, 0, 2) for a in tail),
            jnp.zeros((B,), jnp.int32), routed.astype(jnp.int32),
            counted[:, None])
        return (keys, values), n, carry

    def step_lanes(self, carry: CCAMoECarry, token, bank, n):
        """One token a lane and clip: carry leaves ``[W, B, ...]``, token
        [W, B]; bank and n [B] once a clip -> (carry, logits [W, B, V]
        float32)."""
        c, stacked = self.cfg, self.layers.p
        keys, values = bank
        x = self.embed_tokens.astype(jnp.dtype(c.dtype))[token]     # [W, B, h]
        W, B, h = x.shape
        N = W * B
        t = carry.pos
        pos = (n[None, :] + t).reshape(N, 1)
        live = jnp.ones((N,), bool)
        layered = lambda a: jnp.moveaxis(a, 2, 0)  # noqa: E731
        heads = lambda a: a.reshape((W, B) + a.shape[1:])  # noqa: E731

        def block(state, xs):
            x, r = state
            index, own_k, own_v, *tail = xs
            p = layer_at(stacked, index)
            q, k, v, each = mixed_qkv(
                c, p, x, pos, tuple(a.reshape(N, -1) for a in tail))
            of = lambda a: jax.lax.dynamic_index_in_dim(  # noqa: E731
                a, index, 1, keepdims=False)
            attn, own_k, own_v = wa.full_step(
                heads(q[:, 0]), heads(k[:, 0]), heads(v[:, 0]), of(keys),
                of(values), n, t, own_k, own_v)
            x = attended(p, x, attn.reshape(N, 1, -1, c.head_dim))
            x, r, tally, skipped = moe(c, p, index, x, r, live, False)
            return (x, r), (own_k, own_v, *(heads(a[:, 0]) for a in each),
                            heads(tally), heads(skipped))

        r0 = jnp.zeros((N, c.router_hidden_size), jnp.float32)  # graftlint: disable=GL005
        (x, _), (own_k, own_v, tail_c, tail_a, tail_v, routed, skipped) = \
            jax.lax.scan(block, (x.reshape(N, 1, h), r0), (
                jnp.arange(c.num_hidden_layers), layered(carry.k),
                layered(carry.v), layered(carry.tail_c), layered(carry.tail_a),
                layered(carry.tail_v)))
        lanes = lambda a: jnp.moveaxis(a, 0, 2)  # noqa: E731
        counted = jnp.stack([n[None, :] + t + 1, skipped.sum(axis=0)], axis=-1)
        carry = CCAMoECarry(
            lanes(own_k), lanes(own_v), lanes(tail_c), lanes(tail_a),
            lanes(tail_v), t + 1, lanes(routed).astype(jnp.int32),
            counted[:, :, None].astype(jnp.int32))
        return carry, self._logits(x.reshape(W, B, h))

    def step(self, carry: CCAMoECarry, token, bank, n):
        """One token a row -> (carry, logits [N, V] float32): one lane of
        :meth:`step_lanes`."""
        carry, logits = self.step_lanes(
            jax.tree.map(lambda a: a[None], carry), token[None], bank, n)
        return jax.tree.map(lambda a: a[0], carry), logits[0]

    def __call__(self, feats, masks, labels):
        """Teacher forcing: ONE forward over each clip's ``n`` prefix
        positions and its shifted caption behind them (token ``t`` written at
        position ``n + t``), through the prefix's own attention in its
        compiled-loop form, which a gradient can pass through -> logits
        [B, T, V] float32; ``logits[:, t]`` predicts ``labels[:, t]``."""
        c = self.cfg
        B, T = labels.shape
        if self.is_initializing():
            # the parameters are all that is wanted: the layers declare
            # theirs and no forward runs (eager, at the published widths)
            self.layers.p
            # logits are float32 on every path
            return jnp.zeros((B, T, c.vocab_size), jnp.float32)  # graftlint: disable=GL005
        x, n = compact_prefix(c, self.embed, feats, masks)
        inputs = jnp.concatenate(
            [jnp.full((B, 1), BOS_ID, labels.dtype), labels[:, :-1]], axis=1)
        at = n[:, None] + jnp.arange(T)[None, :]
        x = jnp.pad(x, ((0, 0), (0, T), (0, 0))).at[
            jnp.arange(B)[:, None], at].set(self.embed_tokens.astype(x.dtype)[inputs])
        x, *_ = self._stack(x, n + T, "xla", differentiable=True, whole=True)
        return self._logits(jnp.take_along_axis(x, at[:, :, None], axis=1))
