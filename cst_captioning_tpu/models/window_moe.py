"""Window/full-attention, routed-expert caption decoder (``ModelConfig.decoder
= "window_moe"``): a pre-norm residual stack behind a long video prefix whose
layers attend one of two ways (ops/window_attention.py) and whose FFN is one
chip's share of a routed-expert layer (models/experts.py).

The fifth decoder kind, reached through the same :class:`~cst_captioning_tpu.
models.captioner.CaptionModel` methods as the other four. The sizes are
fields of ``ModelConfig`` under the key names of the published ``config.json``
they are read from (MiMo-V2.5; benchmark/configs/mimo_v2_5_ep16.json).

- **Prefix.** As the sparse/linear decoder's (models/sparse_linear.py): each
  modality's features through its linear projection (``embed_<name>``, no
  bias), a clip's valid slots moved to the front in their order, slot ``i`` of
  the ``n`` valid ones at position ``i`` and caption token ``t`` at ``n + t``,
  BOS first; nothing from position ``n`` of the prefix on exists.
- **Block.** ``x += Attn(norm(x)); x += FFN(norm(x))``, RMSNorm with float32
  statistics, no biases; after the last block ``norm`` and the untied head,
  logits in float32.
- **Attention**, kind by ``mixer_types``: 64 query heads over 4 (``"full"``)
  or 8 (``"window"``) key/value heads; keys of ``head_dim`` 192, values of
  ``v_head_dim`` 128 scaled by ``attention_value_scale``; rope on the first
  ``int(head_dim * partial_rotary_factor)`` dimensions of q and k, pairs ``(i,
  i + half)``, base ``rope_theta`` (full) or ``swa_rope_theta`` (window). A
  full layer is causal; a window layer sees the last ``sliding_window``
  positions with its own, and one learned sink a query head
  (``attention_sink_bias``) sits in its softmax's denominator.
- **FFN**, by the layer's published index (``first_layer_index`` + its index
  here): dense of ``intermediate_size`` under ``first_k_dense_replace``, else
  the held experts' part of the routed sum, no shared expert.

**Three kinds of state in one beam.** What a clip's lanes share rides in
``EncoderOutput`` and is held once a clip: a full layer's whole prefix keys
and values, a window layer's last ``sliding_window`` prefix positions (a
*window-bounded* leaf: a caption behind the prefix can see no earlier key of
that layer, so no more is kept), both head-major. What a lane owns rides in
:class:`WindowMoECarry`: its caption's keys and values in every layer
(``max_len`` positions; a window layer masks them by position and evicts
none: exact at any length, and no larger than a ring of ``sliding_window``
while ``max_len <= sliding_window``, as at the published 30 under 128).
Every leaf is batch-major, so the decode loops gather it by parent beam like
an LSTM carry.

**A step for all lanes at once** (:meth:`WindowMoEDecoder.step_lanes`): the
beam's ``[lanes, clips]`` tokens go through projections, router and experts
as one list of ``lanes x clips`` rows (the held experts are then one
grouped product a layer over all of them, where a ``vmap`` over lanes would
make it a product a lane, each reading its experts' matrices again), and
attend grouped by clip over the shared keys.
:meth:`step` is the same code with one lane.

The last layer's attention output and FFN over the prefix feed nothing and
are not run: the prefix leaves that layer its keys and values only.
"""

from __future__ import annotations

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp

from cst_captioning_tpu.config.config import BOS_ID, ModelConfig
from cst_captioning_tpu.models.experts import (
    check_share,
    expert_ffn,
    expert_shapes,
    gated,
)
from cst_captioning_tpu.models.latent_moe import rms_norm, rope
from cst_captioning_tpu.models.sparse_linear import (
    FFN_ROWS,
    compact_prefix,
    mixer_impl,
)
from cst_captioning_tpu.ops import window_attention as wa

FULL, WINDOW = "full", "window"


@flax.struct.dataclass
class WindowMoECarry:
    """What one lane owns; every leaf batch-major, a tuple over the layers."""

    k: tuple[jnp.ndarray, ...]      # a layer: [B, G, max_len, head_dim]
    v: tuple[jnp.ndarray, ...]      # [B, G, max_len, v_head_dim]
    pos: jnp.ndarray                # [B] int32: caption tokens held so far
    # [B, expert layers, experts_held + 1] int32: the token-expert
    # assignments the LAST call made for this row on each held expert, and
    # (last column) on all experts
    routed: jnp.ndarray
    # [B, 1, 2] int32: the query-key pairs the LAST call's queries of this
    # row attended in one window layer and in one full layer (every layer of
    # a kind sees the same sets; a full layer's are plain causal attention's).
    # The decode loops tally both leaves (obs counters moe.*, attn.*);
    # nothing reads them back into the model
    counted: jnp.ndarray


def rotary_dims(cfg: ModelConfig) -> int:
    return int(cfg.head_dim * cfg.partial_rotary_factor)


def layer_is_dense(cfg: ModelConfig, index: int) -> bool:
    return cfg.first_layer_index + index < cfg.first_k_dense_replace


class WindowMoELayer(nn.Module):
    """One block: attention of kind ``mixer``, then a dense or a
    routed-expert FFN."""

    cfg: ModelConfig
    mixer: str
    dense: bool

    def setup(self):
        c = self.cfg
        pd = jnp.dtype(c.param_dtype)
        h, H, dk, dv = c.hidden_size, c.num_attention_heads, c.head_dim, c.v_head_dim
        G = self.kv_heads
        w = nn.initializers.normal(c.initializer_range)
        one = nn.initializers.ones
        shapes = {
            "input_layernorm": (one, (h,)),
            "q_proj": (w, (h, H * dk)), "k_proj": (w, (h, G * dk)),
            "v_proj": (w, (h, G * dv)), "o_proj": (w, (H * dv, h)),
            "post_attention_layernorm": (one, (h,)),
        }
        if self.dense:
            m = c.intermediate_size
            shapes.update(gate_proj=(w, (h, m)), up_proj=(w, (h, m)),
                          down_proj=(w, (m, h)))
        else:
            shapes.update(expert_shapes(c, w))
        self.p = {name: self.param(name, init, shape, pd)
                  for name, (init, shape) in shapes.items()}
        # both drawn, not zero: a sink left out of the denominator, or a bias
        # leaking into the experts' weights, shows
        if self.mixer == WINDOW:
            self.sink = self.param("attention_sink_bias", w, (H,), jnp.float32)
        if not self.dense:
            self.bias = self.param(
                "e_score_correction_bias", w, (c.n_routed_experts,), jnp.float32)

    @property
    def kv_heads(self) -> int:
        c = self.cfg
        return c.swa_num_key_value_heads if self.mixer == WINDOW \
            else c.num_key_value_heads

    def qkv(self, x, positions):
        """The stream ``x [..., h]`` -> q [..., H, dk], k [..., G, dk] (the
        first ``rotary_dims`` of each rotated at ``positions``), v [..., G,
        dv] scaled."""
        c, p = self.cfg, self.p
        dt = x.dtype
        y = rms_norm(x, p["input_layernorm"], c.rms_norm_eps)
        heads = lambda a, n: a.reshape(a.shape[:-1] + (n, -1))  # noqa: E731
        rot = rotary_dims(c)
        theta = c.swa_rope_theta if self.mixer == WINDOW else c.rope_theta
        inv_freq = float(theta) ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)

        def turned(a):
            return jnp.concatenate(
                [rope(a[..., :rot], positions, inv_freq), a[..., rot:]], axis=-1)

        q = turned(heads(y @ p["q_proj"].astype(dt), c.num_attention_heads))
        k = turned(heads(y @ p["k_proj"].astype(dt), self.kv_heads))
        v = heads(y @ p["v_proj"].astype(dt), self.kv_heads) \
            * jnp.asarray(c.attention_value_scale, dt)
        return q, k, v

    def mixed(self, attn):
        """The attention branch from its heads' outputs ``attn [..., H, dv]``."""
        attn = attn.reshape(attn.shape[:-2] + (-1,))
        return attn @ self.p["o_proj"].astype(attn.dtype)

    def ffn(self, x, live, differentiable: bool):
        """The stream ``x [N, h]``, live [N] -> (the FFN branch [N, h], tally
        [N, held + 1] or None)."""
        c, p = self.cfg, self.p
        norm = lambda a: rms_norm(  # noqa: E731
            a, p["post_attention_layernorm"], c.rms_norm_eps)
        if not self.dense:
            return expert_ffn(c, p, self.bias, norm(x), live, differentiable)
        rows = lambda a: gated(  # noqa: E731
            norm(a), p["gate_proj"], p["up_proj"], p["down_proj"])
        N, blk = x.shape[0], FFN_ROWS
        if N <= blk or N % blk:
            return rows(x), None
        return jax.lax.map(rows, x.reshape(N // blk, blk, -1)).reshape(x.shape), None


class WindowMoEDecoder(nn.Module):
    """Prefix projector, the stack, final norm and head."""

    cfg: ModelConfig

    def setup(self):
        c = self.cfg
        kinds = c.mixer_types
        if len(kinds) != c.num_hidden_layers or not kinds or any(
                k not in (FULL, WINDOW) for k in kinds):
            raise ValueError(
                f"mixer_types {kinds} must name num_hidden_layers "
                f"{c.num_hidden_layers} mixers, each {FULL!r} or {WINDOW!r}")
        groups = {c.num_key_value_heads if k == FULL else c.swa_num_key_value_heads
                  for k in kinds}
        if any(g < 1 or c.num_attention_heads % g for g in groups) \
                or rotary_dims(c) % 2 or not 0 < rotary_dims(c) <= c.head_dim \
                or c.v_head_dim < 1 or c.sliding_window < 1:
            raise ValueError(
                "decoder='window_moe' needs key/value head counts that divide "
                "num_attention_heads, an even number of rotary dimensions "
                "within head_dim, v_head_dim >= 1 and sliding_window >= 1")
        dense = [layer_is_dense(c, i) for i in range(len(kinds))]
        if not all(dense):
            check_share(c)
        pd = jnp.dtype(c.param_dtype)
        w = nn.initializers.normal(c.initializer_range)
        self.embed = {name: self.param(f"embed_{name}", w, (dim, c.hidden_size), pd)
                      for name, dim in c.modalities}
        self.embed_tokens = self.param(
            "embed_tokens", w, (c.vocab_size, c.hidden_size), pd)
        self.layers = [WindowMoELayer(c, kind, dense[i], name=f"layers_{i}")
                       for i, kind in enumerate(kinds)]
        self.norm = self.param("norm", nn.initializers.ones, (c.hidden_size,), pd)
        self.lm_head = self.param("lm_head", w, (c.hidden_size, c.vocab_size), pd)

    def _logits(self, x):
        x = rms_norm(x, self.norm, self.cfg.rms_norm_eps)
        return jnp.dot(x, self.lm_head.astype(x.dtype),
                       preferred_element_type=jnp.float32)

    def _tallies(self, tallies, lead: tuple[int, ...]):
        """Per-layer tallies (``lead + (held + 1,)`` or None) -> ``lead +
        (expert layers, held + 1)`` int32, zeros for a layer that did not run
        its FFN."""
        c = self.cfg
        zero = jnp.zeros(lead + (c.experts_held + 1,), jnp.int32)
        moe = [zero if t is None else t
               for layer, t in zip(self.layers, tallies) if not layer.dense]
        return (jnp.stack(moe, axis=-2) if moe
                else jnp.zeros(lead + (0, c.experts_held + 1), jnp.int32))

    def _stack(self, x, n, impl: str, differentiable: bool, whole: bool):
        """The layers over whole sequences: x [B, S, h] whose first ``n`` [B]
        positions exist -> (x, a layer's (k, v) [B, S, G, d], tallies
        [B, expert layers, held + 1]). Unless ``whole``, the last layer stops
        at its keys and values."""
        c = self.cfg
        B, S, h = x.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        live = (positions < n[:, None]).reshape(B * S)
        kept, tallies = [], []
        for i, layer in enumerate(self.layers):
            q, k, v = layer.qkv(x, positions)
            kept.append((k, v))
            if not whole and i + 1 == len(self.layers):
                tallies.append(None)
                break
            if layer.mixer == WINDOW:
                attn = wa.window_prefill(q, k, v, layer.sink, n,
                                         c.sliding_window, impl)
            else:
                attn = wa.full_prefill(q, k, v, n, impl)
            x = x + layer.mixed(attn)
            y, tally = layer.ffn(x.reshape(B * S, h), live, differentiable)
            x = x + y.reshape(B, S, h)
            tallies.append(None if tally is None
                           else tally.reshape(B, S, -1).sum(axis=1))
        return x, kept, self._tallies(tallies, (B,))

    def prefill(self, feats, masks):
        """-> (bank, n [B], carry): the prefix through the stack. ``bank`` is
        ``(keys, values, start)``: tuples over the layers of per-clip
        head-major arrays, a full layer's whole prefix ``[B, G, P, d]``, a
        window layer's tail slice ``[B, G, W, d]`` from position ``start``
        [B] on (``ops.window_attention.tail_slice``); ``carry`` a lane's
        empty caption cache and what the prefix's queries counted."""
        c = self.cfg
        x, n = compact_prefix(c, self.embed, feats, masks)
        B, P, _ = x.shape
        _, kept, routed = self._stack(x, n, mixer_impl(), differentiable=False,
                                      whole=False)
        start = wa.tail_start(n, P, c.sliding_window)
        keys, values = [], []
        for layer, (k, v) in zip(self.layers, kept):
            if layer.mixer == WINDOW:
                k, v = (wa.tail_slice(a, start, c.sliding_window) for a in (k, v))
            else:
                k, v = (a.transpose(0, 2, 1, 3) for a in (k, v))
            keys.append(k)
            values.append(v)
        dt = jnp.dtype(c.dtype)
        own = lambda arrays: tuple(  # noqa: E731
            jnp.zeros(a.shape[:2] + (c.max_len, a.shape[-1]), dt) for a in arrays)
        live = jnp.arange(P)[None, :] < n[:, None]
        near, whole = wa.pair_counts(jnp.arange(P), c.sliding_window)
        counted = jnp.stack([jnp.where(live, near[None], 0).sum(-1),
                             jnp.where(live, whole[None], 0).sum(-1)],
                            axis=-1).astype(jnp.int32)
        carry = WindowMoECarry(own(keys), own(values), jnp.zeros((B,), jnp.int32),
                               routed, counted[:, None])
        return (tuple(keys), tuple(values), start), n, carry

    def step_lanes(self, carry: WindowMoECarry, token, bank, n):
        """One token a lane and clip: carry leaves ``[L, B, ...]``, token
        [L, B]; bank and n [B] once a clip -> (carry, logits [L, B, V]
        float32)."""
        c = self.cfg
        keys, values, start = bank
        x = self.embed_tokens.astype(jnp.dtype(c.dtype))[token]     # [L, B, h]
        L, B, h = x.shape
        t = carry.pos
        pos = n[None, :] + t
        own_k, own_v, tallies = [], [], []
        for i, layer in enumerate(self.layers):
            q, k, v = layer.qkv(x, pos)
            if layer.mixer == WINDOW:
                attn, k_own, v_own = wa.window_step(
                    q, k, v, keys[i], values[i], start, n, t, carry.k[i],
                    carry.v[i], layer.sink, c.sliding_window)
            else:
                attn, k_own, v_own = wa.full_step(
                    q, k, v, keys[i], values[i], n, t, carry.k[i], carry.v[i])
            own_k.append(k_own)
            own_v.append(v_own)
            x = x + layer.mixed(attn)
            y, tally = layer.ffn(x.reshape(L * B, h), jnp.ones((L * B,), bool),
                                 differentiable=False)
            x = x + y.reshape(L, B, h)
            tallies.append(None if tally is None else tally.reshape(L, B, -1))
        counted = jnp.stack(wa.pair_counts(pos, c.sliding_window), axis=-1)
        carry = WindowMoECarry(
            tuple(own_k), tuple(own_v), t + 1, self._tallies(tallies, (L, B)),
            counted[:, :, None].astype(jnp.int32))
        return carry, self._logits(x)

    def step(self, carry: WindowMoECarry, token, bank, n):
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
            # the parameters are all that is wanted: every layer declares
            # its own and no forward runs (eager, at the published widths)
            for layer in self.layers:
                layer.p
            # logits are float32 on every path
            return jnp.zeros((B, T, c.vocab_size), jnp.float32)  # graftlint: disable=GL005
        x, n = compact_prefix(c, self.embed, feats, masks)
        inputs = jnp.concatenate(
            [jnp.full((B, 1), BOS_ID, labels.dtype), labels[:, :-1]], axis=1)
        at = n[:, None] + jnp.arange(T)[None, :]
        x = jnp.pad(x, ((0, 0), (0, T), (0, 0))).at[
            jnp.arange(B)[:, None], at].set(self.embed_tokens.astype(x.dtype)[inputs])
        x, _, _ = self._stack(x, n + T, "xla", differentiable=True, whole=True)
        return self._logits(jnp.take_along_axis(x, at[:, :, None], axis=1))
